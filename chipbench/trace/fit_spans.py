"""The measured fit as the program's own phase spans tell it.

``FlaxEstimator.fit_on_frame`` records a root span ``fit:run`` in the program's
span ring (``raydp_tpu.profiler.spans()``, which outlives ``raydp_tpu.stop()``)
and its phases as children: ``fit:convert``, ``fit:shuffle``, ``fit:feed``,
``fit:init``, ``train:place``, one ``train:epoch`` an epoch (epoch 0 holds
``train:first_dispatch``) and ``ckpt:save`` with ``ckpt:import``, ``ckpt:d2h``
and ``ckpt:write`` under it. The ring lists spans in the order they closed, so
the last ``fit:run`` is the measured fit (a calibration fit leaves an earlier
one). Times in the ring are microseconds of ``time.time_ns()``.

A program without these spans leaves no ``fit:run``, and every function here
then returns None.
"""

from __future__ import annotations

from typing import List, Optional

from chipbench.trace import reduce as reducer

STARTUP_PHASES = ("fit:convert", "fit:shuffle", "fit:feed", "fit:init",
                  "train:place")


def ring() -> List[dict]:
    from raydp_tpu import profiler
    return profiler.spans()


def measured_fit(spans: List[dict]) -> Optional[dict]:
    runs = [s for s in spans if s["name"] == "fit:run"]
    return runs[-1] if runs else None


def children(spans: List[dict], parent: dict, *names: str) -> List[dict]:
    return [s for s in spans
            if s.get("par") == parent["sid"] and s["name"] in names]


def seconds(spans: List[dict]) -> float:
    return sum(s["dur"] for s in spans) / 1e6


def phase_s(*names: str) -> Optional[float]:
    """Seconds in the named phases of the measured fit (its direct children)."""
    spans = ring()
    run = measured_fit(spans)
    return None if run is None else seconds(children(spans, run, *names))


def epoch0(spans: List[dict], run: dict) -> Optional[dict]:
    first = [s for s in children(spans, run, "train:epoch")
             if s.get("args", {}).get("epoch") == "0"]
    return first[-1] if first else None     # the last: the one that succeeded


def epoch0_s() -> Optional[float]:
    spans = ring()
    run = measured_fit(spans)
    first = run and epoch0(spans, run)
    return seconds([first]) if first else None


def unattributed_s() -> Optional[float]:
    """From the start of ``fit:run`` to the end of epoch 0's ``train:epoch``,
    less the union of the start-up phases and that epoch: the fit's start-up
    that no span has named yet."""
    spans = ring()
    run = measured_fit(spans)
    first = run and epoch0(spans, run)
    if not first:
        return None
    end = first["ts"] + first["dur"]
    named = sorted((1e3 * s["ts"], 1e3 * min(s["ts"] + s["dur"], end))
                   for s in children(spans, run, *STARTUP_PHASES) + [first])
    return (end - run["ts"]) / 1e6 - reducer.union_seconds(named)


def save_s(name: str) -> Optional[float]:
    """Seconds in one part (``ckpt:d2h``, ``ckpt:import``, ``ckpt:write``) of
    the measured fit's last checkpoint save."""
    spans = ring()
    run = measured_fit(spans)
    saves = run and children(spans, run, "ckpt:save")
    return seconds(children(spans, saves[-1], name)) if saves else None
