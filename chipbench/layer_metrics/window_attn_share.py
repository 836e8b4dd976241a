"""Kernels: the windowed layers' share of the flash kernels' device time.
Seconds of the windowed calls' kernels (``rdt_flash_win_*``, forward and
backward) over the seconds of every flash kernel (``rdt_flash_*``). At 16,384
positions three windowed layers of 4096 and one full layer hold 154.2 of
271.6 MFLOP a token forward: 56.8% if a windowed pair costs what a full one
does; above it, the band's masked edges and its shorter walks cost. A program
without the windowed kernel says nothing."""

from chipbench.trace import roofline

WINDOWED, ALL = r"^rdt_flash_win_", r"^rdt_flash_"


def read(run):
    if not run.get("trace"):
        return None
    op_seconds = run["trace"]["op_seconds"]
    windowed = roofline.seconds_of(op_seconds, WINDOWED)
    if not windowed:
        return None
    return 100.0 * windowed / roofline.seconds_of(op_seconds, ALL)
