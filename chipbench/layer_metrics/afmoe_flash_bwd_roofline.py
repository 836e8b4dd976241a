"""Kernels: the backward flash-attention kernels' share of their roofline at
the ``afmoe`` sizes. The seconds of ``rdt_flash_bwd_dkdv`` and
``rdt_flash_bwd_dq`` (the full layer) and of ``rdt_flash_win_bwd_dkdv`` and
``rdt_flash_win_bwd_dq`` (the windowed ones), their own events, against the
least a chip could take for the five products the gradient needs over each
layer's visible pairs, K and V read and dK and dV written once a group
(``flops/afmoe_lm.flash_backward``, one layer's pair of kernels; the layers
are counted from the trace, one for each ``dkdv`` instruction of a kind; the
two kernels form the scores and dP twice, which is recompute and not
counted). A program without these kernels, or a run of another
configuration, says nothing. Never clipped."""

from chipbench.trace import executions, kernels, roofline

CONFIG = "trinity-mini"
LAYERS = {"window": r"^rdt_flash_win_bwd_dkdv", "full": r"^rdt_flash_bwd_dkdv"}
KERNEL = r"^rdt_flash(_win)?_bwd_"


def read(run):
    found = executions.work_of(run, CONFIG, LAYERS, "flash_backward")
    if found is None:
        return None
    _, flops, moved = found     # the operations of every layer's pair
    return roofline.share(kernels.seconds_of(run, KERNEL), flops, moved,
                          run["peak"])
