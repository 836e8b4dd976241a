"""Kernels: the windowed layers' share of the flash kernels' device time.
Seconds of the windowed calls' kernels (``rdt_flash_win_*``, forward and
backward) over the seconds of every flash kernel (``rdt_flash_*``), their
own events. At 16,384 positions three windowed layers of 4096 and one full
layer hold 154.2 of 271.6 MFLOP a token forward: 56.8% if a windowed pair
costs what a full one does; above it, the band's masked edges and its
shorter walks cost. A program without the windowed kernel says nothing."""

from chipbench.trace import kernels

WINDOWED, ALL = r"^rdt_flash_win_", r"^rdt_flash_"


def read(run):
    windowed = kernels.seconds_of(run, WINDOWED)
    every = kernels.seconds_of(run, ALL) if windowed else None
    if not every:
        return None
    return 100.0 * windowed / every
