"""Train loop: seconds of epoch 0 of the measured fit that jax spent lowering
jaxprs to MLIR modules - the union of the program's ``jit:lower`` spans under
epoch 0's ``train:epoch``. A Pallas kernel's body is lowered to Mosaic here,
in every run, whether the compile cache then hits or not."""

from chipbench.trace import build_spans


def read(run):
    return build_spans.kind_s("jit:lower")
