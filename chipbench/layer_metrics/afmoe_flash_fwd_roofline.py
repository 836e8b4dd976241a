"""Kernels: the forward flash-attention kernels' share of their roofline at
the ``afmoe`` sizes (32 query heads on 4 K/V heads of 128, 8,192 positions,
a window of 2048 on four layers and full attention on one). The seconds of
``rdt_flash_fwd`` (the full layer) and ``rdt_flash_win_fwd`` (the windowed
ones) in the traced epochs, their own events (``trace/kernels.py``), against
the least a chip could take for the same executions: each kernel instruction
of the step's program runs once over every traced sequence, so the
executions are counted from the trace itself, by kind
(``trace/executions.py``), and a recomputed
block's second forward kernel counts with its seconds
(``flops/afmoe_lm.flash_forward``: one execution's QK^T and PV over the pairs
the layer's mask leaves visible, K and V read once a group). A program
without these kernels, or a run of another configuration, says nothing.
Never clipped."""

from chipbench.trace import executions, roofline

CONFIG = "trinity-mini"
KINDS = {"window": r"^rdt_flash_win_fwd", "full": r"^rdt_flash_fwd"}


def read(run):
    found = executions.work_of(run, CONFIG, KINDS, "flash_forward")
    if found is None:
        return None
    return roofline.share(*found, run["peak"])
