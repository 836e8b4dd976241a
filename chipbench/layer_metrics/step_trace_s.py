"""Train loop: seconds of epoch 0 of the measured fit that jax spent tracing
Python to jaxprs - the union of the program's ``jit:trace`` spans under epoch
0's ``train:epoch`` (the step program's body, once a build of it; the
functions traced inside it lie inside its span)."""

from chipbench.trace import build_spans


def read(run):
    return build_spans.kind_s("jit:trace")
