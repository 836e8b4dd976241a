"""Kernels: the forward flash-attention kernels' share of their roofline, over
all the layers held, windowed and full together. The seconds of
``rdt_flash_fwd`` (full causal layers) and ``rdt_flash_win_fwd`` (windowed
ones; ``raydp_tpu/ops/flash_attention``'s ``KERNEL_NAMES`` and
``WINDOW_KERNEL_NAMES``) in the traced epochs against the least a chip could
take for the same executions: each kernel instruction of the step's program
runs once over every traced sequence, so the executions are counted from the
trace itself, by kind (``trace/executions.py``; a recomputed block that ran
its forward kernel a second time would count it with its seconds), and one
execution's work is the cell's family's (``flops/<family>.flash_forward``:
QK^T and PV over the pairs the layer's mask leaves visible, K and V read
once a group of query heads) at the peaks of ``peaks.json``. A program
without these kernels, or a family that counts none, says nothing. Never
clipped."""

from chipbench.trace import executions, roofline

KINDS = {"window": r"^rdt_flash_win_fwd", "full": r"^rdt_flash_fwd"}
KERNEL = r"^rdt_flash(_win)?_fwd"


def read(run):
    found = executions.work_of(run, KINDS, "flash_forward", KERNEL)
    return None if found is None else roofline.share(*found, run["peak"])
