"""Check by hand, on the chip, that the program's spans and the device's ops
are on one clock, before trusting what ``host_spans.py`` joins. Not part of
the command: it makes one traced run of a cell in this process and then reads
the run's trace beside the program's span ring, which only this process has.

    python3 chipbench/trace/clock_check.py --workload <cell> --seed <n> --seconds <s>

It prints one JSON object, and writes it whole to
``chiprun_out/clock_check-<cell>.json``:

- ``modules_vs_dispatch``: the train step's ``XLA Modules`` events of each chip
  matched by order to the ``train:dispatch`` annotations. No module may start
  before its own dispatch does; the lag from dispatch start to module start
  says how far the host runs ahead of the device.
- ``ring_offset_us``: ring time minus trace time at both ends of every phase
  span that is in both (joined by span id; the harness's 5 s of tracing hold
  one whole ``train:epoch``, so its two ends are the points seconds apart).
  The ring is on ``time.time_ns()``, the trace on the profiler session's clock:
  offsets that agree over seconds show that the two do not drift, so a
  ring-only span can be placed by one offset.
- ``step_span_us``: cost of one ``profiler.step`` span with no session active.
- ``epochs``: walls of the window's untraced and traced epochs (what tracing
  costs when it is on), and the measured fit's phases from the ring.
"""

from __future__ import annotations

import collections
import json
import os
import shutil
import statistics
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def _spread(values):
    return {"n": len(values), "min": min(values), "max": max(values),
            "median": statistics.median(values)} if values else {"n": 0}


def modules_vs_dispatch(data, marked) -> dict:
    from chipbench.trace import host_spans, reduce as reducer
    loop = host_spans.loop_thread(marked) or []
    dispatches = [s for s in loop if s[2] == "train:dispatch"]
    out = {"dispatches": len(dispatches), "chips": []}
    for plane in data.planes:
        if not reducer.DEVICE_PLANE.match(plane.name):
            continue
        modules = reducer._events(plane, reducer.MODULES_LINE)
        if not modules:
            continue
        step = collections.Counter(m[2] for m in modules).most_common(1)[0][0]
        modules = [m for m in modules if m[2] == step]
        chip = {"plane": plane.name, "module": step, "modules": len(modules)}
        if len(modules) == len(dispatches):
            lags = [(m[0] - d[0]) / 1e3 for m, d in zip(modules, dispatches)]
            chip["lag_us"] = _spread(lags)
            early = [[i, lag] for i, lag in enumerate(lags) if lag < 0]
            chip["modules_before_their_dispatch"] = len(early)
            chip["earliest"] = sorted(early, key=lambda e: e[1])[:8]
            # where the device had caught up (the start of an epoch) the lag
            # is the dispatch call's own latency
            chip["lag_us_least"] = sorted(lags)[:8]
        out["chips"].append(chip)
    return out


def ring_offsets(ring, marked) -> dict:
    """Ring time minus trace time at the start and at the end of every span
    that is in both: points seconds apart, each on both clocks."""
    by_sid = {s["sid"]: s for s in ring}
    points = []
    for spans in marked.values():
        for start_ns, end_ns, name, sid in spans:
            span = by_sid.get(sid)
            if span is not None:
                points.append({"span": name, "edge": "start",
                               "at_s": start_ns / 1e9,
                               "offset_us": span["ts"] - start_ns / 1e3})
                points.append({"span": name, "edge": "end",
                               "at_s": end_ns / 1e9, "offset_us":
                               span["ts"] + span["dur"] - end_ns / 1e3})
    points.sort(key=lambda f: f["at_s"])
    offsets = [f["offset_us"] for f in points]
    return {"points": points,
            "spread_us": max(offsets) - min(offsets) if offsets else None,
            "apart_s": points[-1]["at_s"] - points[0]["at_s"] if points
            else None}


def step_span_us(n: int = 1_000_000) -> dict:
    from raydp_tpu import profiler
    step = profiler.step

    def loop(body):
        best = float("inf")
        for _ in range(5):
            t0 = time.perf_counter()
            body()
            best = min(best, time.perf_counter() - t0)
        return 1e6 * best / n

    def spans():
        for _ in range(n):
            with step("train:dispatch"):
                pass

    def empty():
        for _ in range(n):
            pass

    with_span, bare = loop(spans), loop(empty)
    return {"per_span": with_span - bare, "loop_alone": bare, "spans": n,
            "best_of": 5}


def main(argv=None) -> int:
    import argparse
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=107)
    ap.add_argument("--seconds", type=float, default=20.0)
    args = ap.parse_args(argv)

    from chipbench import run as command
    rc = command.main(["--workload", args.workload, "--seed", str(args.seed),
                       "--seconds", str(args.seconds), "--trace", "1"])
    if rc:
        return rc
    from jax.profiler import ProfileData

    from chipbench.trace import fit_spans, host_spans, reduce as reducer
    from raydp_tpu import profiler
    path = reducer.find_xplane(os.path.join(
        ROOT, "chipbench", "out", args.workload, "trace"))
    ring = profiler.spans()
    report = {"xplane": path, "step_span_us": step_span_us()}
    out = os.path.join(ROOT, "chiprun_out")
    os.makedirs(out, exist_ok=True)
    if path:
        report["xplane_bytes"] = os.path.getsize(path)
        if report["xplane_bytes"] < 24 << 20:   # to look at by hand afterwards
            shutil.copy(path, os.path.join(
                out, f"clock_check-{args.workload}.xplane.pb"))
        data = ProfileData.from_file(path)
        marked = host_spans.annotations(data)
        report["lines"] = {thread: dict(collections.Counter(
            s[2] for s in spans)) for thread, spans in marked.items()}
        report["modules_vs_dispatch"] = modules_vs_dispatch(data, marked)
        report["ring_offset_us"] = ring_offsets(ring, marked)
        report["idle_seconds"] = host_spans.idle_seconds(path)
    with open(os.path.join(ROOT, "chipbench", "out", args.workload,
                           "detail-trace1.json")) as fh:
        detail = json.load(fh)
    first, last = detail.get("traced_epochs") or [None, None]
    walls = detail["epoch_walls_s"]
    run = fit_spans.measured_fit(ring)
    report["epochs"] = {
        "traced": [first, last],
        "untraced_walls_s": walls[1:first] if first else walls[1:],
        "traced_walls_s": walls[first:last + 1] if first else [],
        "fit_phases_s": [
            [s["name"], (s["ts"] - run["ts"]) / 1e6, s["dur"] / 1e6,
             s.get("args", {})]
            for s in ring if run and s["tr"] == run["tr"]
            and s["name"].split(":")[0] in ("fit", "train", "ckpt")],
        "clock": detail["clock"]}
    with open(os.path.join(out, f"clock_check-{args.workload}.json"),
              "w") as fh:
        json.dump(report, fh, indent=1)
    report.pop("lines", None)
    report["epochs"].pop("fit_phases_s")    # long: in the file
    print("clock_check " + json.dumps(report), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
