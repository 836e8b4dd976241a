"""Kernels: the forward gated-convolution kernel's share of its roofline,
over all the convolution operators held. The seconds of ``rdt_gated_conv_fwd``
(``raydp_tpu/ops/short_conv``'s ``KERNEL_NAMES``) in the traced epochs
against the least a chip could take for the same executions: each kernel
instruction of the step's program runs once over every traced sequence, so
the executions are counted from the trace itself (``trace/executions.py``: a
recomputed layer's second forward counts with its seconds), and one
execution's work is the cell's family's (``flops/<family>.
gated_conv_forward``: ``B``, ``C`` and ``z`` read and the output written
once; bound by memory) at the peaks of ``peaks.json``. A program without the
kernel, or a family that counts none, says nothing. Never clipped."""

from chipbench.trace import executions, roofline

KERNEL = r"^rdt_gated_conv_fwd"


def read(run):
    found = executions.work_of(run, {"conv": KERNEL}, "gated_conv_forward",
                               KERNEL)
    return None if found is None else roofline.share(*found, run["peak"])
