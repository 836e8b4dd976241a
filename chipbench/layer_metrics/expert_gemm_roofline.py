"""Kernels: the grouped expert products' share of their roofline. The seconds
of the nine ``ragged-dot`` kernels a step (``jax.lax.ragged_dot``, which the
TPU compiler lowers to its grouped-matmul kernel: three forward, six
backward; their small ``ragged-dot-metadata`` ops do not count) against the
least a chip could take for the ``top_k * tokens`` rows of every traced step,
dropless (``flops/moe_lm.expert_gemms``). Never clipped."""

from chipbench.trace import kernels, roofline

CONFIG = "olmoe-1b-7b"
KERNEL = r"^ragged-dot(?!-metadata)"


def read(run):
    seconds = kernels.seconds_of(run, KERNEL)
    sizes = kernels.sizes_of(CONFIG, run) if seconds else None
    if sizes is None:
        return None
    cfg, work = sizes
    flops, moved = work.expert_gemms(cfg, run["traced_items"] / run["chips"])
    return roofline.share(seconds, flops, moved, run["peak"])
