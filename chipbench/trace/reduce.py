"""Reduce a JAX profiler trace (``.xplane.pb``) to the device numbers the
benchmark reports. Read with nothing but JAX (``jax.profiler.ProfileData``).

A device is a plane named ``/device:TPU:<n>``. Its line ``XLA Ops`` holds one
event per operation the chip executed (a loop's op spans its body's, so only
ops that hold no other op of non-zero length count) and ``XLA Modules`` one
per program execution. Busy time is the union of those op intervals; the
traced span of a chip is taken between two marks the caller gives (on the
trace's own clock) or, without them, from the first op's start to the last
op's end over all chips. Everything here works on intervals and names only,
so the same reduction serves any program.
"""

from __future__ import annotations

import bisect
import glob
import os
import re
from typing import Dict, List, Optional, Tuple

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE, MODULES_LINE = "XLA Ops", "XLA Modules"
COLLECTIVE = re.compile(
    r"(all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute"
    r"|collective-broadcast|ragged-all-to-all)", re.IGNORECASE)
TOP = 10


def find_xplane(trace_dir: str) -> Optional[str]:
    """The newest ``.xplane.pb`` under a ``jax.profiler`` trace directory."""
    found = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    return max(found, key=os.path.getmtime) if found else None


def _events(plane, line_name: str) -> List[Tuple[float, float, str]]:
    out = []
    for line in plane.lines:
        if line.name == line_name:
            out.extend((e.start_ns, e.start_ns + e.duration_ns, e.name)
                       for e in line.events)
    out.sort()
    return out


def leaf_ops(ops):
    """The ops that hold no op of non-zero length. ``XLA Ops`` nests: a
    ``while`` or a ``conditional`` spans the ops of its body, and counting it
    would call the whole loop busy and hide the gaps between its small ops.
    Its own time (loop control on the scalar core) then counts as idle inside
    the program. An event of zero length (on the chip: a ``custom-call`` of
    target ``ConcatBitcast`` at the start of copies, fusions and kernels) is
    neither a leaf nor a reason to drop the op that holds it: the chip was
    running the holder.
    """
    out, stack = [], []         # stack of [op, holds another op]
    for op in sorted(ops, key=lambda o: (o[0], -o[1])):
        if op[1] <= op[0]:
            continue
        while stack and stack[-1][0][1] <= op[0]:
            top, holds = stack.pop()
            if not holds:
                out.append(top)
        if stack and op[1] <= stack[-1][0][1]:
            stack[-1][1] = True
        stack.append([op, False])
    out.extend(op for op, holds in stack if not holds)
    out.sort()
    return out


def held_ops(ops, leaves):
    """What ``leaves = leaf_ops(ops)`` leaves out and the chip still spent
    time under: the ops of non-zero length that hold another such op (a loop,
    a conditional, or, were the chip to write one, a kernel round a copy's
    ``-done``). Look at these by hand in a trace before trusting its busy
    time: ``python3 -m chipbench.trace.reduce <trace>`` lists them."""
    leaves = set(leaves)
    return sorted(op for op in ops if op[1] > op[0] and op not in leaves)


def union_seconds(intervals) -> float:
    """Length of the union of ``(start_ns, end_ns, ...)`` intervals, sorted
    by start, in seconds."""
    total, cur_s, cur_e = 0.0, None, None
    for s, e, *_ in intervals:
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        else:
            cur_e = max(cur_e, e)
    if cur_e is not None:
        total += cur_e - cur_s
    return total / 1e9


def _gaps(ops, modules) -> Dict[str, float]:
    """Idle seconds between consecutive ops, by where the gap sits: inside one
    program execution (the device stalled on itself) or between two (the host
    had not dispatched the next program yet)."""
    starts = [m[0] for m in modules]

    def module_at(t):       # index of the program execution that holds t
        i = bisect.bisect_right(starts, t) - 1
        return i if i >= 0 and t <= modules[i][1] else None

    out: Dict[str, float] = {}
    cur_e = None
    for s, e, _ in ops:
        if cur_e is not None and s > cur_e:
            before, after = module_at(cur_e), module_at(s)
            if before is not None and before == after:
                key = f"inside {modules[before][2]}"
            else:
                name = "unknown" if after is None else modules[after][2]
                key = f"between programs, before {name}"
            out[key] = out.get(key, 0.0) + (s - cur_e) / 1e9
        cur_e = e if cur_e is None else max(cur_e, e)
    return out


def most_first(seconds: Dict[str, float], top: int = TOP) -> List[list]:
    """``[[name, seconds], ...]``, the ``top`` longest: a list of the result
    line's ``breakdown``."""
    return [[k, v] for k, v in sorted(
        seconds.items(), key=lambda kv: -kv[1])[:top]]


def _strip(name: str) -> str:
    """``%fusion.12 = ...`` or ``fusion.12`` -> ``fusion.12``."""
    return name.split(" = ")[0].lstrip("%").strip()


def reduce(xplane_path: str) -> Optional[dict]:
    """Device numbers of one trace, or None when no TPU op is in it."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(xplane_path)
    chips = []
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        if not m:
            continue
        ops = leaf_ops(_events(plane, OPS_LINE))
        if ops:
            chips.append((int(m.group(1)), ops, _events(plane, MODULES_LINE)))
    if not chips:
        return None
    start = min(ops[0][0] for _, ops, _ in chips)
    end = max(max(e for _, e, _ in ops) for _, ops, _ in chips)
    window_s = (end - start) / 1e9
    per_chip, op_time, gap_time = [], {}, {}
    for chip, ops, modules in sorted(chips):
        busy = union_seconds(ops)
        coll = union_seconds([o for o in ops if COLLECTIVE.search(o[2])])
        per_chip.append({"chip": chip, "busy_s": busy, "collective_s": coll,
                         "idle_share": 1.0 - busy / window_s,
                         "ops": len(ops), "programs": len(modules)})
        for s, e, name in ops:
            key = _strip(name)
            op_time[key] = op_time.get(key, 0.0) + (e - s) / 1e9
        for key, sec in _gaps(ops, modules).items():
            gap_time[key] = gap_time.get(key, 0.0) + sec
    n = len(per_chip)
    op_seconds = {k: v / n for k, v in op_time.items()}
    return {
        "window_s": window_s,
        "busy_s": sum(c["busy_s"] for c in per_chip) / n,
        "collective_s": sum(c["collective_s"] for c in per_chip) / n,
        "per_chip": per_chip,
        # every op's seconds by name, averaged over the chips: what a reader
        # of one kernel's time takes (trace/roofline.py); the result line's
        # breakdown carries the longest
        "op_seconds": op_seconds,
        "device_ops": most_first(op_seconds),
        "idle_gaps": most_first({k: v / n for k, v in gap_time.items()}),
    }


def describe(xplane_path: str, head: int = 4) -> None:
    """Print every plane and line of a trace with its event count and first
    event names: look at a trace by hand before trusting a reduction of it."""
    from jax.profiler import ProfileData

    for plane in ProfileData.from_file(xplane_path).planes:
        print(f"plane {plane.name!r}")
        for line in plane.lines:
            events = list(line.events)
            names = [e.name[:60] for e in events[:head]]
            print(f"  line {line.name!r}: {len(events)} events, first {names}")


def dropped(xplane_path: str) -> List[list]:
    """``[[name, executions, seconds, seconds of it under no leaf], ...]``
    of the ops the leaf rule does not count, all chips together, longest
    first: loops and conditionals should be all of it."""
    from jax.profiler import ProfileData

    found: Dict[str, list] = {}
    for plane in ProfileData.from_file(xplane_path).planes:
        if not DEVICE_PLANE.match(plane.name):
            continue
        ops = _events(plane, OPS_LINE)
        leaves = leaf_ops(ops)
        starts = [o[0] for o in leaves]
        for s, e, name in held_ops(ops, leaves):
            inside = leaves[bisect.bisect_left(starts, s):
                            bisect.bisect_left(starts, e)]
            row = found.setdefault(_strip(name), [0, 0.0, 0.0])
            row[0] += 1
            row[1] += (e - s) / 1e9
            row[2] += (e - s) / 1e9 - union_seconds(inside)
    return [[name, *row] for name, row in sorted(
        found.items(), key=lambda kv: -kv[1][1])]


if __name__ == "__main__":
    import json
    import sys

    target = sys.argv[1]
    target = target if target.endswith(".pb") else find_xplane(target)
    describe(target)
    print(json.dumps(reduce(target), indent=1))
    print(json.dumps({"held_ops": dropped(target)}, indent=1))
