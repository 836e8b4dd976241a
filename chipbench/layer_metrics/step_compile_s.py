"""Train loop: seconds of epoch 0 of the measured fit in the backend's compile
or in the persistent compile cache's load in its place - the union of the
program's ``jit:compile`` spans under epoch 0's ``train:epoch``."""

from chipbench.trace import build_spans


def read(run):
    return build_spans.kind_s("jit:compile")
