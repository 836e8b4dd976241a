"""Kernels: the backward flash-attention kernels' share of their roofline.
The seconds of ``rdt_flash_bwd_dkdv`` and ``rdt_flash_bwd_dq`` together
against the least a chip could take for the five products the gradient needs
over the causal pairs (``flops/moe_lm.flash_backward``; the two kernels form
the scores and dP twice, which is recompute and not counted). Never
clipped."""

from chipbench.trace import kernels, roofline

CONFIG = "olmoe-1b-7b"
KERNEL = r"^rdt_flash_bwd_"


def read(run):
    seconds = kernels.seconds_of(run, KERNEL)
    sizes = kernels.sizes_of(CONFIG, run) if seconds else None
    if sizes is None:
        return None
    cfg, work = sizes
    seq_len = cfg["max_position_embeddings"]
    flops, moved = work.flash_backward(
        cfg, run["traced_items"] / seq_len / run["chips"], seq_len)
    return roofline.share(seconds, flops, moved, run["peak"])
