"""Train loop: seconds of epoch 0 of the measured fit in which nothing was
being built - epoch 0's ``train:epoch`` less the union of its ``jit:trace``,
``jit:lower`` and ``jit:compile`` spans: the steps running, the feed, the loss
fetch, the callbacks."""

from chipbench.trace import build_spans


def read(run):
    return build_spans.run_s()
