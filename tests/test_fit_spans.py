"""The fit on the device trace's clock: the two span classes of the one
registry, the phase spans ``fit_on_frame`` and the checkpoint leave in the
ring, the step spans that exist only in a ``jax.profiler`` trace, and the
counter at the feed's queue."""

import glob
import os
import subprocess
import sys
import tempfile

import numpy as np
import pandas as pd
import pytest

from raydp_tpu import metrics, profiler

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

PHASE_SPANS = ["fit:run", "fit:convert", "fit:shuffle", "fit:feed",
               "fit:init", "train:place", "train:first_dispatch",
               "train:epoch", "ckpt:save", "ckpt:d2h", "ckpt:import",
               "ckpt:write"]
STEP_SPANS = ["train:feed_wait", "train:dispatch", "train:epoch_end",
              "feed:decode", "feed:h2d", "feed:put_wait",
              # once an epoch: what the loop does while the device runs dry
              "train:loss_fetch", "train:report", "train:eval",
              "train:callbacks", "train:epoch_turn", "feed:start",
              "feed:stop"]
EPOCH_END_CHILDREN = ["train:loss_fetch", "train:report", "train:eval",
                      "train:callbacks"]


# ------------------------------------------------------------------ registry
@pytest.mark.parametrize("name,kind", [(n, metrics.PHASE) for n in PHASE_SPANS]
                         + [(n, metrics.STEP) for n in STEP_SPANS])
def test_span_is_registered_with_its_class(name, kind):
    assert metrics.SPANS[name].kind == kind
    assert (name in metrics.STEP_SPAN_NAMES) == (kind == metrics.STEP)
    # the generated table carries the class beside the name
    assert f"| `{name}` | {kind} |" in metrics.generate_table("spans")


def test_queue_counter_is_registered():
    m = metrics.METRICS["feed_pulls_total"]
    assert (m.kind, m.label) == (metrics.COUNTER, "state")


# ------------------------------------------------------------------ mechanism
def test_trace_yields_the_open_span_for_late_args():
    profiler.clear()
    with profiler.trace("ckpt:save", step=3) as span:
        profiler.add_args(span, bytes=788)
    (rec,) = profiler.spans()
    assert rec["args"] == {"step": "3", "bytes": "788"}
    profiler.set_enabled(False)
    try:
        with profiler.trace("ckpt:save") as span:
            profiler.add_args(span, bytes=1)    # harmless on the no-op span
    finally:
        profiler.set_enabled(True)
    assert len(profiler.spans()) == 1


def test_step_span_never_enters_the_ring():
    import jax  # noqa: F401 - so that the annotation is the real one
    profiler.clear()
    with profiler.step("train:dispatch"):
        pass
    assert profiler.spans() == []


def test_profiler_records_a_phase_span_without_importing_jax():
    """ETL executors import the profiler and never load jax."""
    code = ("import sys\n"
            "from raydp_tpu import profiler\n"
            "with profiler.trace('fit:run'):\n"
            "    with profiler.step('train:dispatch'):\n"
            "        pass\n"
            "assert 'jax' not in sys.modules, 'jax was imported'\n"
            "print([s['name'] for s in profiler.spans()])\n")
    proc = subprocess.run([sys.executable, "-c", code], cwd=REPO,
                          capture_output=True, text=True, timeout=120)
    assert proc.returncode == 0, proc.stderr
    assert proc.stdout.strip() == "['fit:run']"


def test_telemetry_files_follow_the_temp_dir(tmp_path, monkeypatch):
    """Without a session nothing goes to a fixed path."""
    monkeypatch.setattr(tempfile, "tempdir", str(tmp_path))
    assert metrics.session_dir() == str(tmp_path / "raydp_tpu")
    path = profiler.collect_chrome_trace(include_actors=False)
    assert path == str(tmp_path / "raydp_tpu" / "traces" / "trace.json")
    assert os.path.exists(path)


# ----------------------------------------------------------------- a real fit
def _estimator(num_epochs, **kw):
    import optax

    from raydp_tpu.models import MLP
    from raydp_tpu.train import FlaxEstimator
    return FlaxEstimator(
        model=MLP(features=(8,), use_batch_norm=False),
        optimizer=optax.adam(1e-2), loss="mse", feature_columns=["x1", "x2"],
        label_column="y", batch_size=64, num_epochs=num_epochs, **kw)


def _frame(session, n=512):
    rng = np.random.RandomState(0)
    x = rng.random_sample((n, 2))
    pdf = pd.DataFrame({"x1": x[:, 0], "x2": x[:, 1],
                        "y": x @ np.array([2.0, -3.0]) + 1.0})
    return session.createDataFrame(pdf, num_partitions=2)


def _by_name(spans):
    out = {}
    for s in spans:
        out.setdefault(s["name"], []).append(s)
    return out


def test_fit_on_frame_leaves_one_root_with_its_phases(session, monkeypatch):
    monkeypatch.setenv("RDT_DEVICE_CACHE", "0")     # stream: the shuffle pass
    metrics.reset()
    profiler.clear()
    _estimator(2, checkpoint_interval=2).fit_on_frame(_frame(session))
    ring = profiler.spans()
    names = _by_name(ring)
    (run,) = names["fit:run"]
    assert run["args"] == {"estimator": "FlaxEstimator", "epochs": "2",
                           "batch": "64"} and "par" not in run
    # the phases are the root's direct children, inside it, and their union
    # (none overlaps another) is no longer than the root
    phases = ["fit:convert", "fit:shuffle", "fit:feed", "fit:init",
              "train:place", "train:epoch", "ckpt:save"]
    # (an eager op that jax compiles between two of them leaves a jit:* child
    # of the root too: tests/test_build_spans.py)
    kids = [s for s in ring if s.get("par") == run["sid"]
            and not s["name"].startswith("jit:")]
    assert {s["name"] for s in kids} == set(phases)
    assert all(s["tr"] == run["tr"] for s in kids)
    end = run["ts"] + run["dur"]
    assert all(run["ts"] <= s["ts"] and s["ts"] + s["dur"] <= end
               for s in kids)
    kids.sort(key=lambda s: s["ts"])
    assert all(a["ts"] + a["dur"] <= b["ts"] for a, b in zip(kids, kids[1:]))
    assert sum(s["dur"] for s in kids) <= run["dur"]
    assert [s["args"] for s in names["fit:feed"]] == [
        {"route": "stream"}, {"what": "first_batch"}]
    assert [s["args"] for s in names["train:epoch"]] == [
        {"epoch": "0", "steps": "8"}, {"epoch": "1", "steps": "8"}]
    # the first call of the step program sits in epoch 0
    (first,) = names["train:first_dispatch"]
    assert first["par"] == names["train:epoch"][0]["sid"]
    # one save (the final one), with its three parts under it
    (save,) = names["ckpt:save"]
    assert save["args"]["step"] == "1" and int(save["args"]["bytes"]) > 0
    for part in ("ckpt:import", "ckpt:d2h", "ckpt:write"):
        (span,) = names[part]
        assert span["par"] == save["sid"]
    # the ETL action of the conversion parents under fit:convert
    (convert,) = names["fit:convert"]
    assert any(s.get("par") == convert["sid"] for s in names["etl:action"])
    # no step span ever enters the ring
    assert not set(names) & metrics.STEP_SPAN_NAMES
    # one pull a batch at the queue the loop pulls from: 2 epochs x 8 steps
    pulls = metrics.snapshot()["counters"]["feed_pulls_total"]
    assert sum(pulls.values()) == 16 and set(pulls) <= {"ready", "empty"}


def test_resident_fit_has_no_shuffle_pass_and_says_so(session):
    profiler.clear()
    _estimator(1).fit_on_frame(_frame(session))
    names = _by_name(profiler.spans())
    assert "fit:shuffle" not in names
    assert names["fit:feed"][0]["args"] == {"route": "resident"}
    assert len(names["train:first_dispatch"]) == 1


@pytest.mark.parametrize("prefetch_to_device", [2, 0])
def test_feed_counts_one_pull_a_batch(session, prefetch_to_device):
    """Counted at the stage the loop pulls from, and at no stage behind it."""
    from raydp_tpu.data import from_frame
    from raydp_tpu.data.feed import DeviceFeed
    ds = from_frame(_frame(session, n=640))
    columns = {"features": (["x1", "x2"], np.float32),
               "label": ("y", np.float32)}
    feed = DeviceFeed(ds, 64, columns, shuffle=False,
                      prefetch_to_device=prefetch_to_device)
    metrics.reset()
    assert len(list(feed)) == 10
    pulls = metrics.snapshot()["counters"]["feed_pulls_total"]
    assert sum(pulls.values()) == 10


def _traced_fit(tmp_path, cache: str, with_eval: bool) -> dict:
    """One fit of one epoch (the OS reuses an ended thread's id) under
    ``profiler.jax_trace``, the operator's way to a device trace with the
    program's spans. Its callback leaves a mark of its own in the trace.
    Gives the program's spans by line (name -> [(start, end)]; the mark too),
    the mirrored spans' ids beside the ring's, and the fit's history."""
    import jax
    from jax.profiler import ProfileData

    import raydp_tpu

    def callback(report):
        with jax.profiler.TraceAnnotation("test:callback"):
            pass

    with pytest.MonkeyPatch.context() as env:
        env.setenv("RDT_DEVICE_CACHE", cache)
        session = raydp_tpu.init("pytest", num_executors=2, executor_cores=1,
                                 executor_memory="512MB")
        try:
            df = _frame(session)
            est = _estimator(1, callbacks=[callback])
            profiler.clear()
            with profiler.jax_trace(str(tmp_path)) as log_dir:
                result = est.fit_on_frame(df, df if with_eval else None)
        finally:
            raydp_tpu.stop()
    (path,) = glob.glob(os.path.join(log_dir, "plugins", "profile", "*",
                                     "*.xplane.pb"))
    lines, sids = [], {}
    for plane in ProfileData.from_file(path).planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            found = {}
            for e in line.events:
                if e.name in metrics.SPAN_NAMES or e.name == "test:callback":
                    found.setdefault(e.name, []).append(
                        (e.start_ns, e.start_ns + e.duration_ns))
                    sid = dict(e.stats).get("sid")
                    if sid:
                        sids[sid] = e.name
            if found:
                lines.append(found)
    return {"lines": lines, "sids": sids, "history": result.history,
            "ring": {s["sid"]: s["name"] for s in profiler.spans()},
            "loop": next(f for f in lines if "train:dispatch" in f)}


@pytest.fixture(scope="module")
def traced_fit(tmp_path_factory):
    """A streaming fit, no eval set: the feed's threads are the only ones."""
    return _traced_fit(tmp_path_factory.mktemp("stream"), "0", False)


@pytest.fixture(scope="module")
def traced_resident_fit(tmp_path_factory):
    """A resident fit with an eval set: one dispatch an epoch, no feed."""
    return _traced_fit(tmp_path_factory.mktemp("resident"), "1", True)


def test_device_trace_carries_the_programs_spans(traced_fit):
    """The step spans by thread, the phase spans mirrored with the ring's
    span id."""
    sids = traced_fit["sids"]
    lines = [{name: len(at) for name, at in found.items()}
             for found in traced_fit["lines"]]

    def line_of(name):
        (line,) = [found for found in lines if name in found]
        return line

    loop = line_of("train:dispatch")
    assert loop["train:dispatch"] == 8 and loop["train:feed_wait"] == 9
    assert loop["train:epoch_end"] == 1
    # the feed's stages each on a thread of their own, neither the loop's
    decode, h2d = line_of("feed:decode"), line_of("feed:h2d")
    assert decode is not h2d and decode is not loop and h2d is not loop
    assert decode["feed:decode"] == 9 and h2d["feed:h2d"] == 8
    assert not set(decode) & {"train:dispatch", "feed:h2d"}
    # the phase spans are mirrored on the loop's line, joined by span id
    # (a jit:* span is recorded after the fact: in the ring, never mirrored)
    ring = traced_fit["ring"]
    assert sids and all(ring[sid] == name for sid, name in sids.items())
    assert not any(name.startswith("jit:") for name in sids.values())
    assert {"fit:run", "train:epoch", "ckpt:save"} <= set(sids.values())
    assert {"fit:run", "train:epoch", "ckpt:write"} <= set(loop)


def _inside(spans, outer):
    return all(any(s <= a and b <= e for s, e in outer) for a, b in spans)


@pytest.mark.parametrize("name", ["train:loss_fetch", "train:report",
                                  "train:callbacks", "train:epoch_turn",
                                  "feed:start", "feed:stop"])
def test_device_trace_carries_the_once_an_epoch_spans(traced_fit, name):
    """Each on the loop's own line, once an epoch, where it belongs."""
    loop = traced_fit["loop"]
    assert all(name not in found for found in traced_fit["lines"]
               if found is not loop)
    pulls = sorted(loop["train:feed_wait"])
    (end,) = loop["train:epoch_end"]
    if name == "train:epoch_turn":
        # the loop's start to the first pull, then the epoch's end to the
        # loop's end, with the final save in it
        first, last = sorted(loop[name])
        assert first[1] <= pulls[0][0] and end[1] <= last[0]
        assert _inside(loop["ckpt:save"], [last])
        return
    (span,) = loop[name]
    outer = {"feed:start": pulls[0], "feed:stop": pulls[-1]}.get(name, end)
    assert _inside([span], [outer])
    assert "train:eval" not in loop     # the fit has no eval set


def test_resident_trace_turns_into_the_epochs_one_dispatch(
        traced_resident_fit):
    loop = traced_resident_fit["loop"]
    (dispatch,), (end,) = loop["train:dispatch"], loop["train:epoch_end"]
    first, last = sorted(loop["train:epoch_turn"])
    assert first[1] <= dispatch[0] and dispatch[1] <= end[0]
    assert end[1] <= last[0]
    assert not {"train:feed_wait", "feed:start", "feed:stop"} & set(loop)
    # the eval pass is a child of the epoch's end, between report and callbacks
    (ev,), (report,) = loop["train:eval"], loop["train:report"]
    (calls,) = loop["train:callbacks"]
    assert _inside([ev], [end]) and report[1] <= ev[0] and ev[1] <= calls[0]
    assert "eval_loss" in traced_resident_fit["history"][0]


@pytest.mark.parametrize("which", ["traced_fit", "traced_resident_fit"])
def test_loss_fetch_ends_before_the_first_callback_is_called(request, which):
    """A profiler session that a callback stops (the benchmark's harness does)
    still holds the epoch's ``train:loss_fetch``: it has closed by then."""
    loop = request.getfixturevalue(which)["loop"]
    (fetch,), (report,) = loop["train:loss_fetch"], loop["train:report"]
    (mark,), (calls,) = loop["test:callback"], loop["train:callbacks"]
    assert fetch[1] <= report[0] and report[1] <= mark[0]
    assert _inside([mark], [calls])
    assert _inside([fetch, report, calls], loop["train:epoch_end"])


@pytest.mark.parametrize("route", ["stream", "resident"])
def test_history_says_how_long_the_device_had_nothing_queued(
        session, monkeypatch, route):
    """``lead_time_s`` (last loss fetch, or the loop's start, to the epoch's
    first program handed over) holds ``first_pull_time_s`` (the epoch's first
    ``next()`` on the feed; a resident epoch pulls nothing) in every epoch,
    traced or not."""
    monkeypatch.setenv("RDT_DEVICE_CACHE", "0" if route == "stream" else "1")
    history = _estimator(3).fit_on_frame(_frame(session)).history
    assert len(history) == 3
    for entry in history:
        assert entry["lead_time_s"] >= entry["first_pull_time_s"] >= 0
        assert entry["lead_time_s"] > 0
    pulls = [e["first_pull_time_s"] for e in history]
    assert all(p > 0 for p in pulls) if route == "stream" else pulls == [0.0] * 3
    # the first pull is one of the epoch's pulls
    assert all(e["first_pull_time_s"] <= e["feed_time_s"] for e in history
               if route == "stream")
