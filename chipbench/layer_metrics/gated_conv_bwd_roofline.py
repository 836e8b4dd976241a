"""Kernels: the backward gated-convolution kernel's share of its roofline,
over all the convolution operators held. The seconds of ``rdt_gated_conv_bwd``
(``raydp_tpu/ops/short_conv``'s ``KERNEL_NAMES``) in the traced epochs
against the least a chip could take for the same executions (counted from
the trace, ``trace/executions.py``), one execution's work the cell's
family's (``flops/<family>.gated_conv_backward``: ``B``, ``C``, ``z`` and the
output's gradient read and the three gradients written once; bound by
memory) at the peaks of ``peaks.json``. A program without the kernel, or a
family that counts none, says nothing. Never clipped."""

from chipbench.trace import executions, roofline

KERNEL = r"^rdt_gated_conv_bwd"


def read(run):
    found = executions.work_of(run, {"conv": KERNEL}, "gated_conv_backward",
                               KERNEL)
    return None if found is None else roofline.share(*found, run["peak"])
