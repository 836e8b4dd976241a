"""The readers of the program's spans: the join of the program's annotations
with the device's idle time on a hand-written trace, each of the eight
per-layer readers on a synthetic ring and counters (and on nothing, as
on a program without the spans), and the cells that read them rehearsed with
them."""

import os

import pytest
from test_chipbench_run import _check_contract, _rehearse, _tiny

from chipbench import manifest
from chipbench.trace import fit_spans, host_spans
from raydp_tpu import profiler

M = manifest.load_manifest()
#: the readers of the fit's spans and of the feed's pulls (the three shares
#: of idle time by where the loop's thread stood were retired in PR 37 for
#: ``idle_causes.py``'s three; the join itself stays, for ``breakdown``)
NEW = ["fit_convert_s", "fit_state_s", "fit_epoch0_s", "fit_unattributed_s",
       "ckpt_d2h_s", "ckpt_import_s", "ckpt_write_s", "feed_starved_share"]
RETIRED = ["idle_feed_wait_share", "idle_dispatch_share",
           "idle_epoch_end_share"]

# Times in us after the lines' common base. Chip 0 runs ops over [0,2] [3,4]
# [6,8] [9,10] and chip 1 over [0,5] [5.5,10]; the traced span is [0,10], so
# chip 0 idles over [2,3] [4,6] [8,9] (4 us) and chip 1 over [5,5.5] (0.5 us).
# The train loop's line: feed_wait [1.5,2.5], dispatch [2.5,4.5], epoch_end
# [5,5.5], inside a mirrored train:epoch [1,9.5] that carries its ring id.
# Under feed_wait: [2,2.5] = 0.5. Under dispatch: [2.5,3] + [4,4.5] = 1.0.
# Under epoch_end: [5,5.5] on both chips = 1.0. Under none: chip 0's [4.5,5]
# [5.5,6] [8,9] = 2.0. A chip, the mean of the two: 0.25, 0.5, 0.5 and 1.0.
HAND_TRACE = """
planes {
  id: 1 name: "/device:TPU:0"
  lines { id: 1 name: "XLA Modules" timestamp_ns: 1000
    events { metadata_id: 10 offset_ps: 3000000 duration_ps: 1000000 } }
  lines { id: 2 name: "XLA Ops" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 2000000 }
    events { metadata_id: 1 offset_ps: 3000000 duration_ps: 1000000 }
    events { metadata_id: 1 offset_ps: 6000000 duration_ps: 2000000 }
    events { metadata_id: 1 offset_ps: 9000000 duration_ps: 1000000 } }
  event_metadata { key: 1 value { id: 1 name: "fusion.1" } }
  event_metadata { key: 10 value { id: 10 name: "jit_train_step(7)" } }
}
planes {
  id: 2 name: "/device:TPU:1"
  lines { id: 2 name: "XLA Ops" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 5000000 }
    events { metadata_id: 1 offset_ps: 5500000 duration_ps: 4500000 } }
  event_metadata { key: 1 value { id: 1 name: "fusion.1" } }
}
planes { id: 3 name: "/host:CPU"
  lines { id: 1 name: "python3" timestamp_ns: 1000
    events { metadata_id: 4 offset_ps: 1000000 duration_ps: 8500000
             stats { metadata_id: 1 str_value: "abcd1234" } }
    events { metadata_id: 1 offset_ps: 1500000 duration_ps: 1000000 }
    events { metadata_id: 2 offset_ps: 2500000 duration_ps: 2000000 }
    events { metadata_id: 3 offset_ps: 5000000 duration_ps: 500000 }
    events { metadata_id: 9 offset_ps: 2600000 duration_ps: 100000 } }
  lines { id: 2 name: "python3" timestamp_ns: 1000
    events { metadata_id: 5 offset_ps: 0 duration_ps: 3000000 } }
  lines { id: 3 name: "tf_XLAEigen/1" timestamp_ns: 1000
    events { metadata_id: 9 offset_ps: 0 duration_ps: 9000000 } }
  event_metadata { key: 1 value { id: 1 name: "train:feed_wait" } }
  event_metadata { key: 2 value { id: 2 name: "train:dispatch" } }
  event_metadata { key: 3 value { id: 3 name: "train:epoch_end" } }
  event_metadata { key: 4 value { id: 4 name: "train:epoch" } }
  event_metadata { key: 5 value { id: 5 name: "feed:decode" } }
  event_metadata { key: 9 value { id: 9 name: "PjRtCpuExecutable::Execute" } }
  stat_metadata { key: 1 value { id: 1 name: "sid" } }
}
"""
BASE = 1000.0


@pytest.fixture
def hand_trace(tmp_path):
    from jax.profiler import ProfileData
    path = tmp_path / "hand.xplane.pb"
    path.write_bytes(ProfileData.text_proto_to_serialized_xspace(HAND_TRACE))
    return str(path)


def test_host_spans_keeps_the_programs_spans_by_thread(hand_trace):
    from jax.profiler import ProfileData
    spans = host_spans.host_spans(hand_trace)
    loop, feed = spans["python3#0"], spans["python3#1"]
    assert len(spans) == 2      # the line with no span of the program is out
    assert loop == [(BASE + 1000, BASE + 9500, "train:epoch"),
                    (BASE + 1500, BASE + 2500, "train:feed_wait"),
                    (BASE + 2500, BASE + 4500, "train:dispatch"),
                    (BASE + 5000, BASE + 5500, "train:epoch_end")]
    assert feed == [(BASE, BASE + 3000, "feed:decode")]
    assert host_spans.loop_thread(spans) is loop
    assert host_spans.loop_thread({"python3#1": feed}) is None
    # a mirrored phase span carries the ring's span id, a step span none
    marked = host_spans.annotations(ProfileData.from_file(hand_trace))
    assert [s[3] for s in marked["python3#0"]] == ["abcd1234", None, None,
                                                   None]


def test_attribute_lays_idle_time_under_the_loops_spans(hand_trace):
    from jax.profiler import ProfileData
    chip0, chip1 = host_spans.device_idle(ProfileData.from_file(hand_trace))
    assert chip0 == [(BASE + 2000, BASE + 3000), (BASE + 4000, BASE + 6000),
                     (BASE + 8000, BASE + 9000)]
    assert chip1 == [(BASE + 5000, BASE + 5500)]
    loop = [s for s in host_spans.host_spans(hand_trace)["python3#0"]
            if s[2] in host_spans.LOOP_SPANS]
    got = host_spans.attribute(chip0, loop)
    assert got == pytest.approx({"train:feed_wait": 0.5e-6,
                                 "train:dispatch": 1.0e-6,
                                 "train:epoch_end": 0.5e-6,
                                 "unattributed": 2.0e-6})
    assert host_spans.attribute(chip1, loop) == pytest.approx(
        {"train:epoch_end": 0.5e-6, "unattributed": 0.0})
    assert host_spans.attribute([], loop) == {"unattributed": 0.0}


def test_a_trace_without_the_loops_spans_gives_no_seconds(tmp_path):
    """The parent of the PR that added the annotations, or a CPU run."""
    from jax.profiler import ProfileData
    cut = HAND_TRACE.replace('name: "train:dispatch"', 'name: "other"')
    path = tmp_path / "cut.xplane.pb"
    path.write_bytes(ProfileData.text_proto_to_serialized_xspace(cut))
    assert host_spans.idle_seconds(str(path)) is None
    host_only = HAND_TRACE[HAND_TRACE.index('planes { id: 3'):]
    path.write_bytes(ProfileData.text_proto_to_serialized_xspace(host_only))
    host_spans._seconds.clear()
    assert host_spans.idle_seconds(str(path)) is None
    assert host_spans.idle_seconds(None) is None


def test_idle_seconds_name_what_the_host_did(hand_trace):
    """What the result line's ``breakdown.idle_gaps`` carries: idle seconds a
    chip (the mean over the two: 4.5 us of idle between them) under the
    loop's span names, and nothing for no trace."""
    got = host_spans.idle_seconds(hand_trace)
    assert got == pytest.approx({
        "train:feed_wait": 0.25e-6, "train:dispatch": 0.5e-6,
        "train:epoch_end": 0.5e-6, "unattributed": 1.0e-6})
    assert host_spans.idle_seconds(None) is None
    assert sum(got.values()) == pytest.approx(4.5e-6 / 2)


def test_clock_check_matches_modules_to_dispatches_and_ring_to_trace(
        hand_trace):
    """The by-hand check of the join (``chipbench/trace/clock_check.py``): the
    one module [3,4] against the one dispatch [2.5,4.5] lags 0.5 us; the ring's
    train:epoch [5,000,001, 5,000,012] us against the trace's [2, 10.5] us is
    4,999,999 off at its start and 5,000,001.5 off at its end."""
    from jax.profiler import ProfileData
    check = manifest.load_module(manifest.ROOT, "trace", "clock_check.py")
    data = ProfileData.from_file(hand_trace)
    marked = host_spans.annotations(data)
    got = check.modules_vs_dispatch(data, marked)
    assert got["dispatches"] == 1
    (chip,) = got["chips"]          # chip 1 has no XLA Modules line
    assert chip["module"] == "jit_train_step(7)"
    assert chip["modules_before_their_dispatch"] == 0
    assert chip["lag_us"]["median"] == pytest.approx(0.5)
    ring = [{"name": "train:epoch", "sid": "abcd1234", "ts": 5_000_001,
             "dur": 11},
            {"name": "fit:run", "sid": "ffff", "ts": 1, "dur": 9}]
    off = check.ring_offsets(ring, marked)
    assert [(f["span"], f["edge"]) for f in off["points"]] == [
        ("train:epoch", "start"), ("train:epoch", "end")]
    assert [f["offset_us"] for f in off["points"]] == pytest.approx(
        [4_999_999.0, 5_000_001.5])
    assert off["spread_us"] == pytest.approx(2.5)
    assert off["apart_s"] == pytest.approx(8.5e-6)


# --------------------------------------------------------------- the readers
def _span(name, ts, dur, sid, par=None, **args):
    span = {"name": name, "ts": ts, "dur": dur, "sid": sid, "tr": "t"}
    if par:
        span["par"] = par
    if args:
        span["args"] = {k: str(v) for k, v in args.items()}
    return span


S = 1_000_000       # the ring counts microseconds
# a calibration fit, then the measured one: 1 s unnamed, then convert 2 s,
# shuffle 1 s, feed 0.25 + 0.25 s, init 3 s, place 0.5 s, 1 s unnamed, epoch 0
# of 4 s (start-up 13 s, 2 s of it under no span), epoch 1, and a save of
# import 12 s + d2h 5 s + write 8 s
RING = [
    _span("train:epoch", 1 * S, 9 * S, "c1", "cal", epoch=0),
    _span("ckpt:d2h", 11 * S, 1 * S, "c3", "c2"),
    _span("ckpt:save", 10 * S, 2 * S, "c2", "cal"),
    _span("fit:run", 0, 20 * S, "cal"),
    _span("etl:action", 101 * S, 2 * S, "m0", "m1"),
    _span("fit:convert", 101 * S, 2 * S, "m1", "run"),
    _span("fit:shuffle", 103 * S, 1 * S, "m2", "run"),
    _span("fit:feed", 104 * S, S // 4, "m3", "run", route="stream"),
    _span("fit:feed", 104 * S + S // 4, S // 4, "m4", "run"),
    _span("fit:init", 104 * S + S // 2, 3 * S, "m5", "run"),
    _span("train:place", 107 * S + S // 2, S // 2, "m6", "run"),
    _span("train:first_dispatch", 109 * S, 2 * S, "m7", "e0"),
    _span("train:epoch", 109 * S, 4 * S, "e0", "run", epoch=0, steps=128),
    _span("train:epoch", 113 * S, 3 * S, "e1", "run", epoch=1, steps=128),
    _span("ckpt:import", 116 * S, 12 * S, "s1", "save"),
    _span("ckpt:d2h", 128 * S, 5 * S, "s2", "save"),
    _span("ckpt:write", 133 * S, 8 * S, "s3", "save"),
    _span("ckpt:save", 116 * S, 25 * S, "save", "run", step=1),
    _span("fit:run", 100 * S, 41 * S, "run"),
    _span("ckpt:d2h", 200 * S, 7 * S, "x1"),     # a save outside any fit
]
COUNTERS = {"feed_pulls_total": {"ready": 950, "empty": 50},
            "feed_staged_tables_total": {"native": 8}}
EXPECTED = {"fit_convert_s": 3.0, "fit_state_s": 4.0, "fit_epoch0_s": 4.0,
            "fit_unattributed_s": 2.0, "ckpt_d2h_s": 5.0,
            "ckpt_import_s": 12.0, "ckpt_write_s": 8.0,
            "feed_starved_share": 5.0}


def _reader(name):
    return manifest.load_module(manifest.ROOT, "layer_metrics", f"{name}.py")


@pytest.mark.parametrize("name", NEW)
def test_reader_on_a_synthetic_run(name, monkeypatch, hand_trace):
    monkeypatch.setattr(fit_spans, "ring", lambda: RING)
    run = {"counters": COUNTERS, "xplane": hand_trace}
    assert _reader(name).read(run) == pytest.approx(EXPECTED[name])


@pytest.mark.parametrize("name", NEW)
def test_reader_that_finds_nothing_says_nothing(name, monkeypatch):
    """What the parent of the PR that added the spans gives: a ring with no
    ``fit:run``, no such counter, no trace with the loop's spans."""
    other = [s for s in RING if s["name"] == "etl:action"]
    monkeypatch.setattr(fit_spans, "ring", lambda: other)
    counters = {"feed_staged_tables_total": {"native": 8}}
    assert _reader(name).read({"counters": counters, "xplane": None}) is None
    assert _reader(name).read({"counters": counters}) is None


def test_start_up_pieces_sum_to_the_start_up():
    """convert + state + epoch 0 + unattributed is fit:run's start to the end
    of epoch 0, whatever the pieces are."""
    run = fit_spans.measured_fit(RING)
    first = fit_spans.epoch0(RING, run)
    assert run["sid"] == "run" and first["sid"] == "e0"
    startup = (first["ts"] + first["dur"] - run["ts"]) / S
    assert startup == sum(EXPECTED[k] for k in NEW[:4]) == 13.0


# -------------------------------------------------------------- the manifest
def _cells_that_read(*names):
    """The cells of the manifest in which every one of these metrics is
    read."""
    return [w["name"] for w in M["workloads"]
            if set(names) <= {m["name"] for m in manifest.metrics_of(
                M["per_layer"], w["name"])}]


def test_the_manifest_holds_the_eight_and_not_the_retired_three():
    """The eight are there, once, each with its reader, read in every cell;
    where they stand in the list is no one's to pin."""
    assert manifest.validate(M) == []
    names = [m["name"] for m in M["per_layer"]]
    assert all(names.count(n) == 1 for n in NEW)
    assert not set(RETIRED) & set(names)
    assert not [n for n in RETIRED if os.path.exists(os.path.join(
        manifest.ROOT, manifest.BENCH_DIR, "layer_metrics", f"{n}.py"))]
    by_name = {m["name"]: m for m in M["per_layer"]}
    for name in NEW:
        assert by_name[name]["better"] == "lower"
        assert os.path.isfile(os.path.join(
            manifest.ROOT, manifest.BENCH_DIR, "layer_metrics", f"{name}.py"))
    # they hold for any cell: none names its cells, and every cell reads them
    assert all("workloads" not in by_name[n] for n in NEW)
    assert _cells_that_read(*NEW) == [w["name"] for w in M["workloads"]]


# ------------------------------------------------------------ the rehearsals
@pytest.mark.parametrize("name", _cells_that_read(*NEW))
def test_traced_rehearsal_carries_the_program_spans(name, tmp_path):
    cell, rehearsal = _tiny(name, tmp_path)
    os.makedirs(tmp_path / ".cache")
    (tmp_path / ".cache" / f"{cell.name}.json").write_text('{"t_e": 0.05}')
    line, detail = _check_contract(_rehearse(cell, rehearsal, True),
                                   cell, trace=True)
    assert line["correct"] is True, detail["found"]
    got = {k: v["value"] for k, v in line["metrics"].items()}
    assert {"fit_convert_s", "fit_state_s", "fit_epoch0_s",
            "fit_unattributed_s", "ckpt_d2h_s", "ckpt_import_s",
            "ckpt_write_s", "feed_starved_share"} <= set(got)
    assert not set(RETIRED) & set(got)
    # the spans tell the benchmark's own clocks from the inside
    startup = sum(got[k] for k in NEW[:4])
    assert startup == pytest.approx(got["fit_startup_s"], abs=0.1)
    assert got["fit_unattributed_s"] >= 0
    # ... but for what the benchmark itself does between its last tick and
    # the end of its callback, which a traced run's final_save_s includes:
    # jax.profiler.stop_trace(). The last train:epoch span holds the callback.
    last = [s for s in profiler.spans() if s["name"] == "train:epoch"][-1]
    in_callback = last["dur"] / 1e6 - detail["epoch_walls_s"][-1]
    save = got["ckpt_d2h_s"] + got["ckpt_import_s"] + got["ckpt_write_s"]
    assert save + in_callback == pytest.approx(got["final_save_s"], abs=0.5)
    assert 0 <= got["feed_starved_share"] <= 100
