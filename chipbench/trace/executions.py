"""A kernel's work counted by the executions a trace holds.

Each kernel instruction of a step's program carries its own name in the trace
(``rdt_flash_fwd.2``, ``rdt_flash_fwd.3``: the forward kernels of two layers,
or of one layer and its recomputed forward), and each runs once over every
sequence the traced steps trained. So the instructions whose names match a
pattern are the executions of that kind a step makes, recomputed ones
included, and a ``<kernel>_roofline`` reader that multiplies one execution's
operations and bytes by them counts executions as executions: what the
program ran, against the seconds it ran it in. No rule about a model's depth
or its recomputation is needed.

One contract serves every family: ``flops/<family>.py`` answers
``<one_execution>(cfg, wl, kind, sequences)`` with the operations and bytes
of ONE execution of the kernels of one layer of ``kind`` (``full`` or
``window``) over ``sequences`` sequences. A family without the function (a
model with no such kernel) has nothing to read.
"""

from __future__ import annotations

import re
from typing import Dict, Optional, Tuple

from chipbench.trace import roofline


def work_of(run: dict, kinds: Dict[str, str], one_execution: str,
            kernel: str) -> Optional[Tuple[float, float, float]]:
    """(seconds, operations, bytes) a chip: the seconds of the ops whose names
    match ``kernel``, and one execution's work (the cell's own ``cfg`` and
    ``wl``, handed over in ``run``) for every instruction whose name matches a
    kind's pattern. None where the run has no trace, no such kernel, or a
    family that does not count it."""
    trace = run.get("trace")
    work = getattr(run["flops"], one_execution, None)
    if not trace or work is None:
        return None
    op_seconds = trace["op_seconds"]
    seconds = roofline.seconds_of(op_seconds, kernel)
    if not seconds:
        return None
    sequences = run["traced_items"] / run["wl"]["seq_len"] / run["chips"]
    flops = moved = 0.0
    for kind, pattern in kinds.items():
        rx = re.compile(pattern)
        executions = sum(1 for name in op_seconds if rx.search(name))
        if executions:
            ops, bytes_moved = work(run["cfg"], run["wl"], kind, sequences)
            flops += executions * ops
            moved += executions * bytes_moved
    return seconds, flops, moved
