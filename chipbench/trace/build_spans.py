"""Epoch 0 of the measured fit by what built it.

A program that listens to jax's build events (``raydp_tpu.profiler.
watch_jit_builds``) leaves three more phase spans in the ring: ``jit:trace``
(a function traced to a jaxpr), ``jit:lower`` (the jaxpr lowered to an MLIR
module) and ``jit:compile`` (the backend's compile, or the persistent cache's
load in its place: ``args.cache`` is ``hit``, ``miss`` or ``off``), each with
the function's name in ``args.fun`` and, as parent, the span that was active
on its thread. They are recorded after the fact and a function traced inside
another's trace leaves a span inside the outer one's interval, so a reader
takes the UNION of intervals, never a sum.

Read from the last ``fit:run`` as ``fit_spans.py`` reads it, under epoch 0's
``train:epoch``: the ``jit:*`` spans whose chain of parents reaches it, and
those with no parent at all that lie inside its interval on its thread. A
program without the spans leaves no ``jit:*`` span there (a step is always
traced), and every function here then returns None.
"""

from __future__ import annotations

from typing import List, Optional, Tuple

from chipbench.trace import fit_spans, reduce as reducer

KINDS = ("jit:trace", "jit:lower", "jit:compile")
#: the spans that hold the step program's FIRST build: the first call, or the
#: ``lower().compile()`` before it of a fit that accumulates or recomputes
FIRST_BUILD = ("train:first_dispatch", "train:accum", "train:pipeline")


def _ancestors(by_sid: dict, span: dict):
    span = by_sid.get(span.get("par"))
    while span is not None:
        yield span
        span = by_sid.get(span.get("par"))


def fit_builds(spans: List[dict], run: dict) -> List[dict]:
    """Every ``jit:*`` span of the fit, at any depth under its ``fit:run``."""
    by_sid = {s["sid"]: s for s in spans}
    return [s for s in spans if s["name"] in KINDS
            and any(a is run for a in _ancestors(by_sid, s))]


def epoch0_builds(spans: List[dict]) -> Optional[Tuple[dict, List[dict]]]:
    """Epoch 0's ``train:epoch`` of the measured fit and the ``jit:*`` spans
    under it, in the order they started; None where the fit left none."""
    run = fit_spans.measured_fit(spans)
    first = run and fit_spans.epoch0(spans, run)
    if not first:
        return None
    by_sid = {s["sid"]: s for s in spans}
    end = first["ts"] + first["dur"]
    under = [s for s in spans if s["name"] in KINDS and (
        any(a is first for a in _ancestors(by_sid, s))
        or ("par" not in s and s.get("tid") == first.get("tid")
            and first["ts"] <= s["ts"] and s["ts"] + s["dur"] <= end))]
    return (first, sorted(under, key=lambda s: s["ts"])) if under else None


def union_s(spans: List[dict]) -> float:
    return reducer.union_seconds(sorted(
        (1e3 * s["ts"], 1e3 * (s["ts"] + s["dur"])) for s in spans))


def kind_s(kind: str) -> Optional[float]:
    """Seconds epoch 0 spent in builds of one kind (the union)."""
    found = epoch0_builds(fit_spans.ring())
    return found and union_s([s for s in found[1] if s["name"] == kind])


def run_s() -> Optional[float]:
    """Epoch 0 less the union of all its builds: the steps running, the feed,
    the loss fetch, the callbacks."""
    found = epoch0_builds(fit_spans.ring())
    return found and found[0]["dur"] / 1e6 - union_s(found[1])


def step_builds() -> Optional[int]:
    """How often epoch 0 lowered the fit's step program: the ``jit:lower``
    spans under it with the ``fun`` of the longest one under the step's first
    build (an eager op lowered while the step is traced comes first in time).
    One is the floor."""
    spans = fit_spans.ring()
    found = epoch0_builds(spans)
    if not found:
        return None
    by_sid = {s["sid"]: s for s in spans}
    lowered = [s for s in found[1] if s["name"] == "jit:lower"]
    first = [s for s in lowered if any(a["name"] in FIRST_BUILD
                                       for a in _ancestors(by_sid, s))]
    if not first:
        return None
    fun = max(first, key=lambda s: s["dur"]).get("args", {}).get("fun")
    return sum(s.get("args", {}).get("fun") == fun for s in lowered)


def cache_hit_share() -> Optional[float]:
    """Of the measured fit's ``jit:compile`` spans, the share (%) that the
    persistent compile cache served."""
    spans = fit_spans.ring()
    run = fit_spans.measured_fit(spans)
    compiles = [s for s in (fit_builds(spans, run) if run else [])
                if s["name"] == "jit:compile"]
    if not compiles:
        return None
    hits = sum(s.get("args", {}).get("cache") == "hit" for s in compiles)
    return 100.0 * hits / len(compiles)
