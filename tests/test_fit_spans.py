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
              "feed:decode", "feed:h2d", "feed:put_wait"]


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
    kids = [s for s in ring if s.get("par") == run["sid"]]
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


def test_device_trace_carries_the_programs_spans(session, tmp_path,
                                                 monkeypatch):
    """``profiler.jax_trace`` is the operator's way to a device trace with the
    program's spans: the step spans by thread, the phase spans mirrored with
    the ring's span id."""
    from jax.profiler import ProfileData
    monkeypatch.setenv("RDT_DEVICE_CACHE", "0")
    df = _frame(session)
    est = _estimator(1)     # one epoch: the OS reuses an ended thread's id
    profiler.clear()
    with profiler.jax_trace(str(tmp_path)) as log_dir:
        est.fit_on_frame(df)
    (path,) = glob.glob(os.path.join(log_dir, "plugins", "profile", "*",
                                     "*.xplane.pb"))
    lines, sids = [], {}
    for plane in ProfileData.from_file(path).planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            found = {}
            for e in line.events:
                if e.name in metrics.SPAN_NAMES:
                    found[e.name] = found.get(e.name, 0) + 1
                    sid = dict(e.stats).get("sid")
                    if sid:
                        sids[sid] = e.name
            if found:
                lines.append(found)

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
    ring = {s["sid"]: s["name"] for s in profiler.spans()}
    assert sids and all(ring[sid] == name for sid, name in sids.items())
    assert {"fit:run", "train:epoch", "ckpt:save"} <= set(sids.values())
    assert {"fit:run", "train:epoch", "ckpt:write"} <= set(loop)
