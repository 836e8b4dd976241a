"""A kernel's own seconds in a device trace.

``reduce.py`` counts only the ops that hold no other op (a loop's body, not
the loop), which is right for busy time and wrong for a kernel: on the chip
an asynchronous copy's ``-done`` event often falls inside a long custom
call's interval on the same ``XLA Ops`` line, the kernel then "holds" an op,
and ``op_seconds`` leaves that execution out (the backward flash kernels read
5.8, 7.0 and 8.0 ms a step in three traces of one program that way; their own
events: 8.0). A ``<kernel>_roofline`` reader therefore sums the kernel's own
events, every execution, nested ops or not.
"""

from __future__ import annotations

import functools
import math
import re
from typing import Dict, Optional

from chipbench import manifest
from chipbench.trace import reduce as reducer


@functools.lru_cache(maxsize=2)
def event_seconds(xplane_path: str) -> Dict[str, float]:
    """Seconds of every ``XLA Ops`` event by op name, a chip (the mean over
    the chips of the trace)."""
    from jax.profiler import ProfileData

    total: Dict[str, float] = {}
    chips = 0
    for plane in ProfileData.from_file(xplane_path).planes:
        if not reducer.DEVICE_PLANE.match(plane.name):
            continue
        events = reducer._events(plane, reducer.OPS_LINE)
        chips += bool(events)
        for start, end, name in events:
            key = reducer._strip(name)
            total[key] = total.get(key, 0.0) + (end - start) / 1e9
    return {k: v / chips for k, v in total.items()}


def seconds_of(run: dict, pattern: str) -> Optional[float]:
    """Seconds a chip spent in the ops whose name matches ``pattern``, over
    the traced span; None where the run has no trace or no such op."""
    if not run.get("trace") or not run.get("xplane"):
        return None
    rx = re.compile(pattern)
    seconds = sum(sec for name, sec in event_seconds(run["xplane"]).items()
                  if rx.search(name))
    return seconds or None


def sizes_of(config: str, run: dict):
    """(the configuration as its file has it, its family's ``flops`` module):
    where a reader takes the shapes its operations and bytes are counted
    from. A reader is handed the run, not the cell, so it names the
    configuration of the cells its metric lists; ``run["flops_per_item"]``
    is the one thing a run carries of its cell's configuration, and where it
    is not this configuration's own count at its own sequence length the run
    is another configuration's: None, and the reader says nothing rather
    than count a foreign cell with these sizes."""
    cfg = manifest.load_json(manifest.ROOT, "configs", f"{config}.json")
    work = manifest.load_module(manifest.ROOT, "flops",
                                f"{cfg['family']}.py")
    own = work.train_flops_per_item(
        cfg, {"seq_len": cfg["max_position_embeddings"]}, {})
    if not math.isclose(run.get("flops_per_item", 0.0), own, rel_tol=1e-9):
        return None
    return cfg, work
