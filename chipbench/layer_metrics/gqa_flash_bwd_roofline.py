"""Kernels: the grouped-query backward flash-attention kernels' share of
their roofline, over all the layers held. The seconds of ``rdt_flash_bwd_dkdv``
and ``rdt_flash_bwd_dq`` (full layers) and of ``rdt_flash_win_bwd_dkdv`` and
``rdt_flash_win_bwd_dq`` (windowed) together against the least a chip could
take for the five products the gradient needs over each layer's visible
pairs, K and V read and dK and dV written once a group
(``flops/swa_moe_lm.gqa_flash_backward``; the two kernels form the scores
and dP twice, which is recompute and not counted, and so is a recomputed
block's second forward kernel, whose seconds are the forward metric's).
Never clipped."""

from chipbench.trace import kernels, roofline

CONFIG = "smallthinker-21b-a3b"
KERNEL = r"^rdt_flash(_win)?_bwd_"


def read(run):
    seconds = kernels.seconds_of(run, KERNEL)
    sizes = kernels.sizes_of(CONFIG, run) if seconds else None
    if sizes is None:
        return None
    cfg, work = sizes
    seq_len = cfg["max_position_embeddings"]
    flops, moved = work.gqa_flash_backward(
        cfg, run["traced_items"] / seq_len / run["chips"], seq_len)
    return roofline.share(seconds, flops, moved, run["peak"])
