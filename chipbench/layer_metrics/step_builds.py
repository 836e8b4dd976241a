"""Train loop: how often epoch 0 of the measured fit lowered its step program
- the ``jit:lower`` spans under epoch 0's ``train:epoch`` that carry the
``fun`` of the step's first build (the longest ``jit:lower`` under
``train:first_dispatch`` or, where the fit compiles its step before that call,
``train:accum`` / ``train:pipeline``). One is the floor: a second is a call
whose argument types differ from the first's."""

from chipbench.trace import build_spans


def read(run):
    return build_spans.step_builds()
