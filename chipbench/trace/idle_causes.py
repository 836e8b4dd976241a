"""Every idle gap of a device trace, by what the chip waited for.

A gap on a chip ends when an operation starts. That operation belongs to one
program execution ``k`` (an event of the chip's ``XLA Modules`` line), and the
train loop handed ``k`` over at a known moment ``h_k`` on the same clock: the
end of the ``k``-th ``train:dispatch`` annotation, the step program's
executions paired with the loop's dispatches by order, as
``clock_check.modules_vs_dispatch`` pairs them. For a gap ``(a, b)``:

- both ends inside one execution: **inside a program**, the chip waited on
  itself (a copy, a loop's trip count, a collective's partner);
- otherwise the seconds after ``max(a, h_k)`` are **launch** (the program was
  handed over and had not started), and the seconds from ``a`` to
  ``min(b, h_k)`` are **host late**: nothing was queued. They are laid under
  the innermost step span the loop's thread was in (``train:loss_fetch``,
  ``train:epoch_turn``, ``feed:start`` ...);
- a gap that ends in an execution with no dispatch of its own (an eval pass,
  a metric's ``init``), or in none, is **unmatched**.

The four sum to the idle seconds ``host_spans.device_idle`` finds (the mean
over the chips, as ``reduce.py`` averages). A loop parked in the loss fetch,
or blocked in a full queue, is not blamed for what the chip did to itself.

A program without the once-an-epoch spans (the parent of the PR that added
them) gives nothing, and nothing here raises. By hand, on a run's trace:

    python3 -m chipbench.trace.idle_causes <xplane.pb or trace directory>

prints, besides the four, the host-late seconds by loop span and by what the
feed's threads did meanwhile (``feed:decode``, ``feed:h2d``,
``feed:put_wait``), and for the gaps inside a program the ten operations
after which the chip waited longest, with their ``op_name`` scope
(``trace/scopes.py``), and the seconds that lie UNDER an operation the leaf
rule does not count because it holds another of non-zero length: a loop's or
a conditional's own control (``reduce.held_ops``).
"""

from __future__ import annotations

import bisect
import collections
import functools
from typing import Dict, List, Optional, Sequence, Tuple

from chipbench.trace import host_spans, reduce as reducer

INSIDE, LAUNCH, HOST_LATE, UNMATCHED = ("inside_program", "launch",
                                        "host_late", "unmatched")
KINDS = (INSIDE, LAUNCH, HOST_LATE, UNMATCHED)
# the spans that only a program with the finer once-an-epoch spans writes
FINER_SPANS = ("train:epoch_turn", "train:loss_fetch")
TRACE_START = "(the trace's start)"
# the host's annotations and the device's events are one clock to about a
# millisecond: an epoch's first execution read 0.82-0.85 ms BEFORE the start of
# its own dispatch in three epochs of three (clock_check.py on the chip, PR 35)
CLOCK_SLACK_NS = 2_000_000

Span = Tuple[float, float, str]


def innermost(spans: Sequence[Span]) -> List[Span]:
    """Nested ``(start, end, name)`` spans of one line as segments that do not
    overlap, each under the innermost span that covers it."""
    out: List[Span] = []
    stack: List[Span] = []
    at = None

    def close(upto):
        nonlocal at
        while stack and stack[-1][1] <= upto:
            _, end, name = stack.pop()
            if end > at:
                out.append((at, end, name))
                at = end

    for span in sorted(spans, key=lambda s: (s[0], -s[1])):
        close(span[0])
        if stack and span[0] > at:
            out.append((at, span[0], stack[-1][2]))
        at = span[0] if not stack else max(at, span[0])
        stack.append(span)
    close(float("inf"))
    return out


def step_program(modules: Sequence[Span], dispatches: int) -> Optional[str]:
    """The name of the program the loop's dispatches run: the one whose count
    of executions comes closest to the dispatches', the earlier on a tie (a
    resident fit runs its epoch and its eval program as often)."""
    if not dispatches or not modules:
        return None
    count = collections.Counter(m[2] for m in modules)
    first = {}
    for start, _, name in modules:
        first.setdefault(name, start)
    return min(count, key=lambda n: (abs(count[n] - dispatches), first[n]))


def hand_overs(modules: Sequence[Span],
               dispatches: Sequence[Span]) -> List[Optional[float]]:
    """``h_k`` of each execution: the end of its own ``train:dispatch``, None
    where it has none. The step program's executions and the dispatches pair
    by order where there are as many of the one as of the other (a session
    started and stopped between epochs: the harness's). Where a session
    began or ended inside an epoch they do not: then an execution that began
    before the next unpaired dispatch did (by more than the clocks' slack) was
    handed over before the trace began and has none."""
    step = step_program(modules, len(dispatches))
    mine = [i for i, m in enumerate(modules) if m[2] == step]
    out: List[Optional[float]] = [None] * len(modules)
    if len(mine) == len(dispatches):
        for i, dispatch in zip(mine, dispatches):
            out[i] = dispatch[1]
        return out
    j = 0
    for i in mine:
        if j < len(dispatches) and (dispatches[j][0] - CLOCK_SLACK_NS
                                    <= modules[i][0]):
            out[i] = dispatches[j][1]
            j += 1
    return out


def classify(idle, modules: Sequence[Span], handed) -> Dict[str, list]:
    """The ``(start, end)`` idle intervals of one chip, cut by the rule above:
    ``{kind: [(start, end)]}``."""
    starts = [m[0] for m in modules]

    def execution_at(t):
        i = bisect.bisect_right(starts, t) - 1
        return i if i >= 0 and t <= modules[i][1] else None

    out = {kind: [] for kind in KINDS}
    for a, b in idle:
        before, k = execution_at(a), execution_at(b)
        if k is not None and before == k:
            out[INSIDE].append((a, b))
        elif k is None or handed[k] is None:
            out[UNMATCHED].append((a, b))
        else:
            cut = min(max(handed[k], a), b)
            if cut > a:
                out[HOST_LATE].append((a, cut))
            if b > cut:
                out[LAUNCH].append((cut, b))
    return out


def _seconds(intervals) -> float:
    return sum(b - a for a, b in intervals) / 1e9


def _by(intervals, key) -> Dict[str, float]:
    out: Dict[str, float] = {}
    for a, b in intervals:
        out[key(a)] = out.get(key(a), 0.0) + (b - a) / 1e9
    return out


def _add(total: dict, part: dict, chips: int) -> None:
    for key, sec in part.items():
        total[key] = total.get(key, 0.0) + sec / chips


def _feed_threads(marked, loop, step_names) -> Dict[str, List[Span]]:
    """The step spans of every line but the loop's, lines of one kind (their
    commonest span: a new thread an epoch, one stage) put together."""
    out: Dict[str, List[Span]] = {}
    for spans in marked.values():
        steps = [s[:3] for s in spans if s[2] in step_names]
        if spans is loop or not steps:
            continue
        kind = collections.Counter(s[2] for s in steps).most_common(1)[0][0]
        out.setdefault(kind, []).extend(steps)
    return out


@functools.lru_cache(maxsize=2)
def causes(xplane_path: Optional[str]) -> Optional[dict]:
    """The idle seconds a chip of one trace by cause, with what the by-hand
    listing prints; None where there is no trace, no device idle time, no
    ``train:dispatch`` or none of the once-an-epoch spans in it."""
    if xplane_path is None:
        return None
    from jax.profiler import ProfileData

    from raydp_tpu.metrics import STEP_SPAN_NAMES
    data = ProfileData.from_file(xplane_path)
    marked = host_spans.annotations(data)
    loop = host_spans.loop_thread(marked)
    if not loop or not any(s[2] in FINER_SPANS for s in loop):
        return None
    chips = []
    for plane in data.planes:
        if reducer.DEVICE_PLANE.match(plane.name):
            every = reducer._events(plane, reducer.OPS_LINE)
            ops = reducer.leaf_ops(every)
            if ops:
                chips.append((ops, reducer.held_ops(every, ops),
                              reducer._events(plane, reducer.MODULES_LINE)))
    idle = host_spans.device_idle(data)     # the same planes, in their order
    if not chips or len(idle) != len(chips):
        return None
    dispatches = [s[:3] for s in loop if s[2] == "train:dispatch"]
    loop_steps = innermost([s[:3] for s in loop if s[2] in STEP_SPAN_NAMES])
    feeds = {kind: innermost(spans) for kind, spans in _feed_threads(
        marked, loop, STEP_SPAN_NAMES).items()}
    n = len(chips)
    seconds = {kind: 0.0 for kind in KINDS}
    by_span, after, under, programs = {}, {}, {}, {}
    by_feed = {kind: {} for kind in feeds}
    matched = 0
    for (ops, held, modules), gaps in zip(chips, idle):
        handed = hand_overs(modules, dispatches)
        matched += sum(h is not None for h in handed)
        for (_, _, name), h in zip(modules, handed):
            key = name if h is not None else f"{name} (no dispatch)"
            programs[key] = programs.get(key, 0) + 1
        cut = classify(gaps, modules, handed)
        _add(seconds, {kind: _seconds(cut[kind]) for kind in KINDS}, n)
        _add(by_span, host_spans.attribute(cut[HOST_LATE], loop_steps), n)
        for kind, spans in feeds.items():
            _add(by_feed[kind],
                 host_spans.attribute(cut[HOST_LATE], spans), n)
        ended = {e: reducer._strip(name) for _, e, name in ops}
        _add(after, _by(cut[INSIDE], lambda a: ended.get(a, TRACE_START)), n)
        _add(under, host_spans.attribute(cut[INSIDE], innermost(held)), n)
    total = sum(seconds.values())
    if total <= 0:
        return None
    between = under.pop(host_spans.UNATTRIBUTED, 0.0)
    return {"idle_s": total, "seconds": seconds, "chips": n,
            # of the seconds inside a program: between its operations (a
            # stall), and under an operation that holds another and so is not
            # counted busy (a loop's own control between its body's ops)
            "inside_between_ops_s": between,
            "inside_under_held_op_s": seconds[INSIDE] - between,
            "executions_with_a_dispatch": matched // n,
            "dispatches": len(dispatches), "programs": programs,
            "host_late_by_span": by_span, "host_late_by_feed_thread": by_feed,
            "inside_after_op": after,
            "inside_under_op": {reducer._strip(k): v
                                for k, v in under.items()}}


def shares(xplane_path: Optional[str]) -> Optional[Dict[str, float]]:
    """The four causes as percent of all the idle seconds (they sum to 100)."""
    found = causes(xplane_path)
    if found is None:
        return None
    return {kind: 100.0 * sec / found["idle_s"]
            for kind, sec in found["seconds"].items()}


def share(xplane_path: Optional[str], kind: str) -> Optional[float]:
    """What an ``idle_<cause>_share`` reader returns (it hands over
    ``run["xplane"]``)."""
    found = shares(xplane_path)
    return None if found is None else found[kind]


def history_share(run: dict, field: str) -> Optional[float]:
    """The sum of one once-an-epoch field of the fit's history over the
    untraced window epochs, as percent of their walls; None where an epoch
    lacks the field (a program from before it)."""
    epochs = run["epochs"]
    wall = sum(e["epoch_time_s"] for e in epochs)
    if not wall or not all(field in e for e in epochs):
        return None
    return 100.0 * sum(e[field] for e in epochs) / wall


def listing(xplane_path: str, top: int = reducer.TOP) -> Optional[dict]:
    """``causes`` as the by-hand command prints it: each list longest first,
    the operations with the scope their ``op_name`` gives them."""
    from chipbench.trace import scopes
    found = causes(xplane_path)
    if found is None:
        return None
    scope = scopes.op_names(xplane_path)
    out = dict(found, shares=shares(xplane_path))
    out["host_late_by_span"] = reducer.most_first(found["host_late_by_span"])
    out["host_late_by_feed_thread"] = {
        kind: reducer.most_first(sec)
        for kind, sec in found["host_late_by_feed_thread"].items()}
    for key in ("inside_after_op", "inside_under_op"):
        out[key] = [[op, sec, scope.get(op, "")]
                    for op, sec in reducer.most_first(found[key], top)]
    return out


if __name__ == "__main__":
    import json
    import sys

    target = sys.argv[1]
    target = target if target.endswith(".pb") else reducer.find_xplane(target)
    print(json.dumps(listing(target), indent=1))
