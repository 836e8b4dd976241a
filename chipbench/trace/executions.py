"""A kernel's work counted by the executions a trace holds.

Each kernel instruction of a step's program carries its own name in the trace
(``rdt_flash_fwd.2``, ``rdt_flash_fwd.3``: the forward kernel of one layer
and the same layer's recomputed forward), and each runs once over every
sequence the traced steps trained. So the instructions whose names match a
pattern are the executions of that kind a step makes, recomputed ones
included, and a ``<kernel>_roofline`` reader that multiplies one execution's
operations and bytes by them counts executions as executions: what the
program ran, against the seconds it ran it in.
"""

from __future__ import annotations

import re
from typing import Dict, Optional, Tuple

from chipbench.trace import kernels


def work_of(run: dict, config: str, patterns: Dict[str, str],
            one_execution: str) -> Optional[Tuple[float, float, float]]:
    """(seconds, operations, bytes) of the kernel instructions whose names
    match each kind's pattern: ``flops/<family>.<one_execution>(cfg, kind,
    sequences)`` for every instruction found, each of which ran over all the
    traced sequences a chip. None where the run has no trace or no such
    kernel, or is another configuration's (``kernels.sizes_of``)."""
    if not run.get("trace") or not run.get("xplane"):
        return None
    sizes = kernels.sizes_of(config, run)
    if sizes is None:
        return None
    cfg, work = sizes
    sequences = run["traced_items"] / cfg["seq_len"] / run["chips"]
    events = kernels.event_seconds(run["xplane"])
    seconds = flops = moved = 0.0
    for kind, pattern in patterns.items():
        rx = re.compile(pattern)
        found = [sec for name, sec in events.items() if rx.search(name)]
        ops, bytes_moved = getattr(work, one_execution)(cfg, kind, sequences)
        seconds += sum(found)
        flops += len(found) * ops
        moved += len(found) * bytes_moved
    return (seconds, flops, moved) if seconds else None
