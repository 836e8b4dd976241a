"""A kernel's share of its roofline, from the trace and the table of peaks.

A reader of one kernel's time is a file under ``layer_metrics/``: it sums the
kernel's events out of ``run["trace"]["op_seconds"]`` (every leaf op's seconds
by name: a kernel holds no op of non-zero length, so these are its own events,
every execution), takes the operations and bytes the algorithm needs for
those calls from the cell's ``flops/<family>.py`` (``run["flops"]``: functions
of ``run["cfg"]`` and ``run["wl"]``, kept with the benchmark) and
``run["peak"]`` from ``peaks.json``, and returns ``share(...)`` under the name
``<kernel>_roofline``, unit ``%``. A share over 100 means the operations or
bytes are counted too high or the time leaves out part of the work: nothing
here clips it.
"""

from __future__ import annotations

import re
from typing import Dict, Optional, Tuple


def seconds_of(op_seconds: Dict[str, float], pattern: str) -> float:
    """Seconds of the ops whose name matches ``pattern`` (a regular
    expression, searched): a kernel's time under its stable name."""
    rx = re.compile(pattern)
    return sum(sec for name, sec in op_seconds.items() if rx.search(name))


def least_seconds(flops: float, bytes_moved: float,
                  peak: dict) -> Tuple[float, str]:
    """The least time a chip could take for the work, and which peak sets
    it: the larger of operations over peak FLOP/s and bytes over peak
    bytes/s."""
    by_compute = flops / peak["bf16_flops_per_s"]
    by_memory = bytes_moved / peak["hbm_bytes_per_s"]
    return ((by_compute, "compute") if by_compute >= by_memory
            else (by_memory, "memory"))


def share(seconds: float, flops: float, bytes_moved: float,
          peak: dict) -> Optional[float]:
    """Percent of the roofline a kernel reached: the least time the chip
    could take over the time it took (both for the same calls on one chip).
    None where the trace holds no time for it."""
    if seconds <= 0:
        return None
    return 100.0 * least_seconds(flops, bytes_moved, peak)[0] / seconds
