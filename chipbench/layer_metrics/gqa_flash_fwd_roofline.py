"""Kernels: the grouped-query forward flash-attention kernels' share of their
roofline, over all the layers held, windowed and full together. The seconds
of ``rdt_flash_fwd`` (the full layers) and ``rdt_flash_win_fwd`` (the windowed
ones; ``raydp_tpu/ops/flash_attention``'s ``KERNEL_NAMES`` and
``WINDOW_KERNEL_NAMES``) in the traced epochs (their own events,
``trace/kernels.py``) against the least a chip could take for the same calls:
QK^T and PV over the pairs each layer's mask leaves visible, a window's at
its own count, K and V read once a group of query heads
(``flops/swa_moe_lm.gqa_flash_forward``, with the sizes of the configuration
whose cell this metric lists) at the peaks of ``peaks.json``. A program
without the windowed kernel, or a run of another configuration, says nothing.
Never clipped."""

from chipbench.trace import kernels, roofline

CONFIG = "smallthinker-21b-a3b"
KERNEL = r"^rdt_flash(_win)?_fwd"


def read(run):
    seconds = kernels.seconds_of(run, KERNEL)
    sizes = kernels.sizes_of(CONFIG, run) if seconds else None
    if sizes is None:
        return None
    cfg, work = sizes
    seq_len = cfg["max_position_embeddings"]
    flops, moved = work.gqa_flash_forward(
        cfg, run["traced_items"] / seq_len / run["chips"], seq_len)
    return roofline.share(seconds, flops, moved, run["peak"])
