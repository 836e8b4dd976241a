"""Kernels: the backward flash-attention kernels' share of their roofline,
over all the layers held. The seconds of ``rdt_flash_bwd_dkdv`` and
``rdt_flash_bwd_dq`` (full layers) and of ``rdt_flash_win_bwd_dkdv`` and
``rdt_flash_win_bwd_dq`` (windowed) together against the least a chip could
take for the five products the gradient needs over each layer's visible
pairs, K and V read and dK and dV written once a group
(``flops/<family>.flash_backward``: one layer's pair of kernels; the layers
are counted from the trace, one for each ``dkdv`` instruction of a kind:
``trace/executions.py``). The two kernels form the scores and dP twice, which
is recompute and not counted. A program without these kernels, or a family
that counts none, says nothing. Never clipped."""

from chipbench.trace import executions, roofline

LAYERS = {"window": r"^rdt_flash_win_bwd_dkdv", "full": r"^rdt_flash_bwd_dkdv"}
KERNEL = r"^rdt_flash(_win)?_bwd_"


def read(run):
    found = executions.work_of(run, LAYERS, "flash_backward", KERNEL)
    return None if found is None else roofline.share(*found, run["peak"])
