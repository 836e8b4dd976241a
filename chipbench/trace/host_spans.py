"""The program's spans on the device trace's clock.

While ``jax.profiler`` captures a trace, the program writes its step spans
(``raydp_tpu.profiler.step``: ``train:feed_wait``, ``train:dispatch``,
``train:epoch_end``, ``feed:decode``, ``feed:h2d``, ``feed:put_wait``) and a
mirror of every phase span into it as annotations. They land in the plane
``/host:CPU``, one line per OS thread, with starts relative to the profiler
session like the device planes' ``XLA Ops`` - one clock, so the time a chip sat
idle can be laid under what the host did meanwhile. Lines are not named after
Python threads (every line is ``python3``), and the OS reuses the ids of ended
threads, so a thread is known by the spans its line carries: the train loop's
is the line with ``train:dispatch``.

A program without these annotations (the parent of the PR that added them)
leaves nothing to find, and everything here then returns nothing.
"""

from __future__ import annotations

import bisect
from typing import Dict, List, Optional, Tuple

from chipbench.trace import reduce as reducer

HOST_PLANE = "/host:CPU"
LOOP_SPANS = ("train:feed_wait", "train:dispatch", "train:epoch_end")
UNATTRIBUTED = "unattributed"

Span = Tuple[float, float, str]         # start_ns, end_ns, name


def annotations(data) -> Dict[str, List[Tuple[float, float, str, Optional[str]]]]:
    """``{thread: [(start_ns, end_ns, name, sid)]}`` of the program's spans in
    a parsed trace, sorted by start; ``sid`` is the ring's span id that a
    mirrored phase span carries, None on a step span."""
    from raydp_tpu.metrics import SPAN_NAMES, SPAN_PREFIXES

    def program_span(name):
        return name in SPAN_NAMES or name.startswith(SPAN_PREFIXES)

    out = {}
    for plane in data.planes:
        if plane.name != HOST_PLANE:
            continue
        for i, line in enumerate(plane.lines):
            found = sorted(
                (e.start_ns, e.start_ns + e.duration_ns, e.name,
                 dict(e.stats).get("sid"))
                for e in line.events if program_span(e.name))
            if found:
                out[f"{line.name}#{i}"] = found
    return out


def host_spans(xplane_path: str) -> Dict[str, List[Span]]:
    """``{thread: [(start_ns, end_ns, name)]}``: the events of the ``/host:CPU``
    plane whose names are in the program's span registry, by thread."""
    from jax.profiler import ProfileData
    return {thread: [s[:3] for s in spans] for thread, spans in annotations(
        ProfileData.from_file(xplane_path)).items()}


def loop_thread(spans_by_thread: dict) -> Optional[list]:
    """The train loop's spans: the line that carries ``train:dispatch``."""
    def dispatches(spans):
        return sum(1 for s in spans if s[2] == "train:dispatch")
    best = max(spans_by_thread.values(), key=dispatches, default=None)
    return best if best and dispatches(best) else None


def attribute(idle_intervals, loop_spans) -> Dict[str, float]:
    """Seconds of the ``(start_ns, end_ns)`` idle intervals under each name of
    ``loop_spans`` (``(start_ns, end_ns, name)``, none overlapping another) and,
    under ``unattributed``, the rest."""
    spans = sorted(loop_spans)
    starts = [s[0] for s in spans]
    out: Dict[str, float] = {}
    total = 0.0
    for a, b in idle_intervals:
        total += b - a
        i = max(bisect.bisect_right(starts, a) - 1, 0)
        while i < len(spans) and spans[i][0] < b:
            s, e, name = spans[i]
            under = min(b, e) - max(a, s)
            if under > 0:
                out[name] = out.get(name, 0.0) + under
            i += 1
    out[UNATTRIBUTED] = total - sum(out.values())
    return {name: ns / 1e9 for name, ns in out.items()}


def device_idle(data) -> List[List[Tuple[float, float]]]:
    """For each chip, the intervals of the traced span in which no operation
    ran on it: the complement of the union of ``reduce.leaf_ops``. The traced
    span is ``reduce.py``'s: first op's start to last op's end over all chips."""
    chips = []
    for plane in data.planes:
        if reducer.DEVICE_PLANE.match(plane.name):
            ops = reducer.leaf_ops(reducer._events(plane, reducer.OPS_LINE))
            if ops:
                chips.append(ops)
    if not chips:
        return []
    start = min(ops[0][0] for ops in chips)
    end = max(max(o[1] for o in ops) for ops in chips)
    out = []
    for ops in chips:
        idle, at = [], start
        for s, e, _ in ops:
            if s > at:
                idle.append((at, s))
            at = max(at, e)
        if end > at:
            idle.append((at, end))
        out.append(idle)
    return out


_seconds: Dict[str, Optional[dict]] = {}    # one parse serves every caller


def idle_seconds(xplane_path: Optional[str]) -> Optional[Dict[str, float]]:
    """Seconds a chip sat idle in the traced span under each of the train
    loop's spans and ``unattributed`` (mean over the chips, as ``reduce.py``
    averages), or None where there is no trace, no device idle time or no
    ``train:dispatch`` in it."""
    if xplane_path is None:
        return None
    if xplane_path not in _seconds:
        from jax.profiler import ProfileData
        data = ProfileData.from_file(xplane_path)
        loop = loop_thread(annotations(data))
        chips = device_idle(data)
        seconds = None
        if loop and chips:
            loop = [s[:3] for s in loop if s[2] in LOOP_SPANS]
            seconds = {}
            for idle in chips:
                for name, sec in attribute(idle, loop).items():
                    seconds[name] = seconds.get(name, 0.0) + sec / len(chips)
            if sum(seconds.values()) <= 0:
                seconds = None
        _seconds[xplane_path] = seconds
    return _seconds[xplane_path]
