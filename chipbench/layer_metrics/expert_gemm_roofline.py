"""Kernels: the grouped expert products' share of their roofline. The seconds
of the nine ``ragged-dot`` kernels a step (``jax.lax.ragged_dot``, which the
TPU compiler lowers to its grouped-matmul kernel: three forward, six
backward; their small ``ragged-dot-metadata`` ops do not count) against the
least a chip could take for the ``top_k * tokens`` rows of every traced step,
dropless (``flops/<family>.expert_gemms`` of the cell's own sizes; a family
without that count says nothing). Never clipped."""

from chipbench.trace import roofline

KERNEL = r"^ragged-dot(?!-metadata)"


def read(run):
    work = getattr(run["flops"], "expert_gemms", None)
    if not run.get("trace") or work is None:
        return None
    seconds = roofline.seconds_of(run["trace"]["op_seconds"], KERNEL)
    flops, moved = work(run["cfg"], run["traced_items"] / run["chips"])
    return roofline.share(seconds, flops, moved, run["peak"])
