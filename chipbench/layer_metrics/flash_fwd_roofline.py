"""Kernels: the forward flash-attention kernel's share of its roofline. The
seconds of ``rdt_flash_fwd`` (``raydp_tpu/ops/flash_attention.KERNEL_NAMES``)
in the traced epochs (its own events, ``trace/kernels.py``) against the least
a chip could take for the same calls: QK^T and PV over the causal pairs of
every traced sequence
(``flops/moe_lm.flash_forward``, with the sizes of the configuration whose
cells this metric lists) at the peaks of ``peaks.json``. Never clipped."""

from chipbench.trace import kernels, roofline

CONFIG = "olmoe-1b-7b"
KERNEL = r"^rdt_flash_fwd"


def read(run):
    seconds = kernels.seconds_of(run, KERNEL)
    sizes = kernels.sizes_of(CONFIG, run) if seconds else None
    if sizes is None:
        return None
    cfg, work = sizes
    seq_len = cfg["max_position_embeddings"]
    flops, moved = work.flash_forward(
        cfg, run["traced_items"] / seq_len / run["chips"], seq_len)
    return roofline.share(seconds, flops, moved, run["peak"])
