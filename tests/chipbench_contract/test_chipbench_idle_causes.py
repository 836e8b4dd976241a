"""One rule for every idle gap (``chipbench/trace/idle_causes.py``) on a
hand-written trace with a gap of each kind, the five readers that stand on it
and on the history's once-an-epoch fields, on a synthetic run and on a program
without them, and the cheapest cell rehearsed with them."""

import os

import pytest
from test_chipbench_run import _check_contract, _rehearse, _tiny

from chipbench import manifest
from chipbench.trace import host_spans, idle_causes

M = manifest.load_manifest()
NEW = ["idle_inside_program_share", "idle_launch_share",
       "idle_host_late_share", "epoch_turn_share", "feed_restart_share"]

# Times in us after the lines' common base; the traced span is [9, 51].
#
# The loop's line: epoch_turn [0,2]; feed_wait [2,6] with feed:start [2.5,5.5]
# in it; dispatch [6,8] (h0 = 8); feed_wait [8,9]; dispatch [9,11] (h1 = 11);
# feed_wait [11,12] with feed:stop [11.2,11.8]; epoch_end [12,31] with
# loss_fetch [12,26], report [26,27], callbacks [27,31]; epoch_turn [31,33];
# feed_wait [33,35] with feed:start [33.2,34.8]; dispatch [35,37] (h2 = 37);
# feed_wait [37,38]; then the LAST epoch_end never closes (the session is
# stopped from a callback inside it): only its loss_fetch [38,46], report
# [46,47] and eval [47,52] are there. A feed thread decodes over [30,34].
#
# Chip 0 runs the step program over [9,20] [22,30] [40,45] and an eval program
# over [48,51]; its ops: [9,12] [14,20] | a while [23,30] that holds [23,26]
# [27,30] | [40,45] | [48,51]. Its gaps:
#   [12,14] inside execution 0, the loop parked in loss_fetch  -> inside 2
#   [20,23] before execution 1, handed over at 11              -> launch 3
#   [26,27] inside execution 1, under the while                -> inside 1
#   [30,40] before execution 2, handed over at 37: straddles   -> host 7,
#                                                                 launch 3
#   [45,48] before the eval program, which no dispatch ran     -> unmatched 3
# Chip 1 runs the step program over [9,20] [20.5,30] [36,45] and the eval
# program over [48,51]; ops [9,20] | [21,30] | [36,45] | [48,50]. Its gaps:
#   [20,21] launch 1; [30,36] execution 2 began before its dispatch ended
#   (37) -> host 6; [45,48] unmatched 3; [50,51] inside the eval program 1.
# A chip, the mean of the two: inside 2, launch 3.5, host late 6.5, unmatched
# 3, of 15 idle us. Host late by the loop's innermost span: callbacks [30,31]
# 1; epoch_turn [31,33] 2; feed_wait [33,33.2] [34.8,35] 0.4; feed:start 1.6;
# dispatch [35,37] 2 on chip 0 and [35,36] 1 on chip 1 -> 1.5.
HAND_TRACE = """
planes {
  id: 1 name: "/device:TPU:0"
  lines { id: 1 name: "XLA Modules" timestamp_ns: 1000
    events { metadata_id: 10 offset_ps: 9000000 duration_ps: 11000000 }
    events { metadata_id: 10 offset_ps: 22000000 duration_ps: 8000000 }
    events { metadata_id: 10 offset_ps: 40000000 duration_ps: 5000000 }
    events { metadata_id: 11 offset_ps: 48000000 duration_ps: 3000000 } }
  lines { id: 2 name: "XLA Ops" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 9000000 duration_ps: 3000000 }
    events { metadata_id: 2 offset_ps: 14000000 duration_ps: 6000000 }
    events { metadata_id: 3 offset_ps: 23000000 duration_ps: 7000000 }
    events { metadata_id: 2 offset_ps: 23000000 duration_ps: 3000000 }
    events { metadata_id: 1 offset_ps: 27000000 duration_ps: 3000000 }
    events { metadata_id: 1 offset_ps: 40000000 duration_ps: 5000000 }
    events { metadata_id: 1 offset_ps: 48000000 duration_ps: 3000000 } }
  event_metadata { key: 1 value { id: 1 name: "%fusion.1 = f32[8]" } }
  event_metadata { key: 2 value { id: 2 name: "fusion.2" } }
  event_metadata { key: 3 value { id: 3 name: "while.3" } }
  event_metadata { key: 10 value { id: 10 name: "jit_train_step(7)" } }
  event_metadata { key: 11 value { id: 11 name: "jit_eval_step(9)" } }
}
planes {
  id: 2 name: "/device:TPU:1"
  lines { id: 1 name: "XLA Modules" timestamp_ns: 1000
    events { metadata_id: 10 offset_ps: 9000000 duration_ps: 11000000 }
    events { metadata_id: 10 offset_ps: 20500000 duration_ps: 9500000 }
    events { metadata_id: 10 offset_ps: 36000000 duration_ps: 9000000 }
    events { metadata_id: 11 offset_ps: 48000000 duration_ps: 3000000 } }
  lines { id: 2 name: "XLA Ops" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 9000000 duration_ps: 11000000 }
    events { metadata_id: 1 offset_ps: 21000000 duration_ps: 9000000 }
    events { metadata_id: 1 offset_ps: 36000000 duration_ps: 9000000 }
    events { metadata_id: 4 offset_ps: 48000000 duration_ps: 2000000 } }
  event_metadata { key: 1 value { id: 1 name: "fusion.1" } }
  event_metadata { key: 4 value { id: 4 name: "fusion.4" } }
  event_metadata { key: 10 value { id: 10 name: "jit_train_step(7)" } }
  event_metadata { key: 11 value { id: 11 name: "jit_eval_step(9)" } }
}
planes { id: 3 name: "/host:CPU"
  lines { id: 1 name: "python3" timestamp_ns: 1000
    events { metadata_id: 5 offset_ps: 0 duration_ps: 2000000 }
    events { metadata_id: 1 offset_ps: 2000000 duration_ps: 4000000 }
    events { metadata_id: 6 offset_ps: 2500000 duration_ps: 3000000 }
    events { metadata_id: 2 offset_ps: 6000000 duration_ps: 2000000 }
    events { metadata_id: 1 offset_ps: 8000000 duration_ps: 1000000 }
    events { metadata_id: 2 offset_ps: 9000000 duration_ps: 2000000 }
    events { metadata_id: 1 offset_ps: 11000000 duration_ps: 1000000 }
    events { metadata_id: 7 offset_ps: 11200000 duration_ps: 600000 }
    events { metadata_id: 3 offset_ps: 12000000 duration_ps: 19000000 }
    events { metadata_id: 8 offset_ps: 12000000 duration_ps: 14000000 }
    events { metadata_id: 12 offset_ps: 26000000 duration_ps: 1000000 }
    events { metadata_id: 13 offset_ps: 27000000 duration_ps: 4000000 }
    events { metadata_id: 5 offset_ps: 31000000 duration_ps: 2000000 }
    events { metadata_id: 1 offset_ps: 33000000 duration_ps: 2000000 }
    events { metadata_id: 6 offset_ps: 33200000 duration_ps: 1600000 }
    events { metadata_id: 2 offset_ps: 35000000 duration_ps: 2000000 }
    events { metadata_id: 1 offset_ps: 37000000 duration_ps: 1000000 }
    events { metadata_id: 8 offset_ps: 38000000 duration_ps: 8000000 }
    events { metadata_id: 12 offset_ps: 46000000 duration_ps: 1000000 }
    events { metadata_id: 14 offset_ps: 47000000 duration_ps: 5000000 } }
  lines { id: 2 name: "python3" timestamp_ns: 1000
    events { metadata_id: 9 offset_ps: 30000000 duration_ps: 4000000 } }
  event_metadata { key: 1 value { id: 1 name: "train:feed_wait" } }
  event_metadata { key: 2 value { id: 2 name: "train:dispatch" } }
  event_metadata { key: 3 value { id: 3 name: "train:epoch_end" } }
  event_metadata { key: 5 value { id: 5 name: "train:epoch_turn" } }
  event_metadata { key: 6 value { id: 6 name: "feed:start" } }
  event_metadata { key: 7 value { id: 7 name: "feed:stop" } }
  event_metadata { key: 8 value { id: 8 name: "train:loss_fetch" } }
  event_metadata { key: 9 value { id: 9 name: "feed:decode" } }
  event_metadata { key: 12 value { id: 12 name: "train:report" } }
  event_metadata { key: 13 value { id: 13 name: "train:callbacks" } }
  event_metadata { key: 14 value { id: 14 name: "train:eval" } }
}
"""
US = 1e-6
SECONDS = {"inside_program": 2.0 * US, "launch": 3.5 * US,
           "host_late": 6.5 * US, "unmatched": 3.0 * US}
HISTORY = [{"epoch_time_s": 1.0, "lead_time_s": 0.04,
            "first_pull_time_s": 0.03},
           {"epoch_time_s": 1.0, "lead_time_s": 0.06,
            "first_pull_time_s": 0.02}]
EXPECTED = {"idle_inside_program_share": 100 * 2.0 / 15,
            "idle_launch_share": 100 * 3.5 / 15,
            "idle_host_late_share": 100 * 6.5 / 15,
            "epoch_turn_share": 5.0, "feed_restart_share": 2.5}


def _write(tmp_path, text, name="hand.xplane.pb"):
    from jax.profiler import ProfileData
    path = tmp_path / name
    path.write_bytes(ProfileData.text_proto_to_serialized_xspace(text))
    return str(path)


@pytest.fixture
def hand_trace(tmp_path):
    return _write(tmp_path, HAND_TRACE)


# --------------------------------------------------------------- the pieces
def test_innermost_cuts_nested_spans_into_segments():
    spans = [(0, 10, "outer"), (2, 4, "a"), (4, 5, "b"), (6, 9, "c"),
             (7, 8, "d"), (12, 13, "next")]
    assert idle_causes.innermost(spans) == [
        (0, 2, "outer"), (2, 4, "a"), (4, 5, "b"), (5, 6, "outer"),
        (6, 7, "c"), (7, 8, "d"), (8, 9, "c"), (9, 10, "outer"),
        (12, 13, "next")]
    assert idle_causes.innermost([]) == []


@pytest.mark.parametrize("modules,dispatches,want", [
    # paired by order, as clock_check pairs them
    ([(3, 4, "step"), (5, 6, "step")], [(1, 2, "d"), (2, 3, "d")], [2, 3]),
    # another program has no dispatch of its own and takes none
    ([(3, 4, "step"), (4, 5, "eval"), (5, 6, "step")],
     [(1, 2, "d"), (2, 3, "d")], [2, None, 3]),
    # a session begun inside an epoch: the first execution started before the
    # trace's first dispatch did, so it was handed over before the trace
    ([(0, 2, "step"), (3, 4, "step")], [(1, 2.5, "d")], [None, 2.5]),
    # ... but not where it is earlier by less than the clocks' slack
    ([(0.9, 2, "step"), (3, 4, "step"), (5, 6, "step")],
     [(1, 2.5, "d"), (2.5, 2.6, "d")], [2.5, 2.6, None]),
    # as many executions as dispatches pair by order whatever the clocks say
    ([(0, 2, "step"), (3, 4, "step")], [(1, 2.5, "d"), (5, 6, "d")],
     [2.5, 6]),
    # a resident fit runs its epoch and its eval program as often: the earlier
    ([(3, 4, "epoch"), (5, 6, "eval"), (8, 9, "epoch"), (10, 11, "eval")],
     [(1, 2, "d"), (7, 7.5, "d")], [2, None, 7.5, None]),
    ([(3, 4, "step")], [], [None]),
])
def test_hand_overs_pair_executions_with_dispatches(modules, dispatches, want):
    ms = 10_000_000.0       # the cases count in units of 10 ms
    def scaled(spans):
        return [(a * ms, b * ms, name) for a, b, name in spans]
    got = idle_causes.hand_overs(scaled(modules), scaled(dispatches))
    assert got == [h if h is None else h * ms for h in want]


@pytest.mark.parametrize("gap,want", [
    ((12, 14), {"inside_program": [(12, 14)]}),
    ((20, 23), {"launch": [(20, 23)]}),
    ((30, 40), {"host_late": [(30, 37)], "launch": [(37, 40)]}),     # split
    ((30, 36), {"host_late": [(30, 36)]}),      # began before h_k: all host
    ((45, 48), {"unmatched": [(45, 48)]}),      # an execution of no dispatch
    ((51, 60), {"unmatched": [(51, 60)]}),      # ends in no execution at all
    ((50, 51), {"inside_program": [(50, 51)]}),
])
def test_classify_cuts_a_gap_at_the_hand_over(gap, want):
    modules = [(9, 20, "step"), (22, 30, "step"), (36, 45, "step"),
               (48, 51, "eval")]
    handed = [8, 11, 37, None]
    got = idle_causes.classify([gap], modules, handed)
    assert {k: v for k, v in got.items() if v} == want
    assert set(got) == set(idle_causes.KINDS)


# ------------------------------------------------------------ the hand trace
def test_every_idle_second_has_one_of_four_causes(hand_trace):
    found = idle_causes.causes(hand_trace)
    assert found["seconds"] == pytest.approx(SECONDS)
    assert found["chips"] == 2 and found["idle_s"] == pytest.approx(15 * US)
    # the same seconds host_spans finds, so the shares are of what
    # device_idle_share counts
    assert found["idle_s"] == pytest.approx(
        sum(host_spans.idle_seconds(hand_trace).values()))
    shares = idle_causes.shares(hand_trace)
    assert sum(shares.values()) == pytest.approx(100.0, abs=1e-9)
    assert found["dispatches"] == 3
    assert found["executions_with_a_dispatch"] == 3
    assert found["programs"] == {"jit_train_step(7)": 6,
                                 "jit_eval_step(9) (no dispatch)": 2}


def test_a_parked_loop_is_not_blamed_for_the_chips_own_stalls(hand_trace):
    """The loop stands in ``train:loss_fetch`` over [12,26] and [38,46], and
    the last ``train:epoch_end`` never closes. ``host_spans`` books what the
    chip did meanwhile to the epoch's end or to nothing; here the gap inside
    execution 0 is the program's, the gaps before a handed-over program are
    launch, and no host-late second lies under the loss fetch."""
    old = host_spans.idle_seconds(hand_trace)
    # chip 0: [12,14] [20,23] [26,27] [30,31]; chip 1: [20,21] [30,31]
    assert old["train:epoch_end"] == pytest.approx((2 + 3 + 1 + 1 + 1 + 1)
                                                   / 2 * US)
    # chip 0 [45,48], chip 1 [45,48] [50,51]: under no closed epoch_end
    assert old["unattributed"] >= 3.5 * US
    found = idle_causes.causes(hand_trace)
    by_span = found["host_late_by_span"]
    assert "train:loss_fetch" not in by_span
    assert "train:epoch_end" not in by_span     # its children cover it
    assert by_span == pytest.approx({
        "train:callbacks": 1.0 * US, "train:epoch_turn": 2.0 * US,
        "train:feed_wait": 0.4 * US, "feed:start": 1.6 * US,
        "train:dispatch": 1.5 * US, "unattributed": 0.0}, abs=1e-12)
    assert sum(by_span.values()) == pytest.approx(SECONDS["host_late"])


def test_by_hand_listing_names_operations_and_feed_threads(hand_trace):
    got = idle_causes.listing(hand_trace)
    # the ops after which the chip waited inside a program, longest first;
    # a hand trace stores no program, so no op_name scope
    assert [row[0] for row in got["inside_after_op"]] == [
        "fusion.1", "fusion.2", "fusion.4"]
    assert [row[1] for row in got["inside_after_op"]] == pytest.approx(
        [1.0 * US, 0.5 * US, 0.5 * US])
    assert all(row[2] == "" for row in got["inside_after_op"])
    # [26,27] lies under the while, which the leaf rule does not count
    assert [row[:2] for row in got["inside_under_op"]] == [
        ["while.3", pytest.approx(0.5 * US)]]
    assert got["inside_under_held_op_s"] == pytest.approx(0.5 * US)
    assert got["inside_between_ops_s"] == pytest.approx(1.5 * US)
    # host-late seconds under what the feed's threads did meanwhile
    (kind,) = got["host_late_by_feed_thread"]
    assert kind == "feed:decode"
    assert dict(got["host_late_by_feed_thread"][kind]) == pytest.approx({
        "feed:decode": 4.0 * US, "unattributed": 2.5 * US})
    assert got["host_late_by_span"][0][0] == "train:epoch_turn"
    assert got["shares"] == idle_causes.shares(hand_trace)


@pytest.mark.parametrize("cut", [
    ['name: "train:epoch_turn"', 'name: "train:loss_fetch"'],  # the parent
    ['name: "train:dispatch"'],
    ['name: "XLA Ops"'],
])
def test_a_trace_without_the_spans_gives_nothing(tmp_path, cut):
    text = HAND_TRACE
    for name in cut:
        text = text.replace(name, 'name: "other"')
    path = _write(tmp_path, text, "cut.xplane.pb")
    assert idle_causes.causes(path) is None
    assert idle_causes.shares(path) is None
    assert idle_causes.share(path, idle_causes.LAUNCH) is None
    assert idle_causes.listing(path) is None
    assert idle_causes.share(None, idle_causes.LAUNCH) is None


# --------------------------------------------------------------- the readers
def _reader(name):
    return manifest.load_module(manifest.ROOT, "layer_metrics", f"{name}.py")


@pytest.mark.parametrize("name", NEW)
def test_reader_on_a_synthetic_run(name, hand_trace):
    run = {"epochs": HISTORY, "xplane": hand_trace}
    assert _reader(name).read(run) == pytest.approx(EXPECTED[name])


@pytest.mark.parametrize("name", NEW)
def test_reader_on_a_program_without_the_spans_says_nothing(name, tmp_path):
    """The parent of the PR that added them: no ``lead_time_s`` in the
    history, no once-an-epoch span in the trace; and a run with no trace."""
    old = [{"epoch_time_s": 1.0, "feed_time_s": 0.1}] * 2
    parent = HAND_TRACE
    for span in idle_causes.FINER_SPANS:
        parent = parent.replace(f'name: "{span}"', 'name: "other"')
    path = _write(tmp_path, parent, "parent.xplane.pb")
    assert _reader(name).read({"epochs": old, "xplane": path}) is None
    assert _reader(name).read({"epochs": old, "xplane": None}) is None
    assert _reader(name).read({"epochs": []}) is None


def test_the_turn_holds_the_restart():
    run = {"epochs": HISTORY}
    assert _reader("epoch_turn_share").read(run) >= _reader(
        "feed_restart_share").read(run)


# -------------------------------------------------------------- the manifest
@pytest.mark.parametrize("name", NEW)
def test_the_entry_is_present_with_its_reader(name):
    """Present, sound, read in every cell: not where in the list it stands
    nor how long the list is, which later PRs change."""
    assert manifest.validate(M) == []
    (entry,) = [m for m in M["per_layer"] if m["name"] == name]
    assert entry["unit"] == "%" and entry["better"] == "lower"
    assert entry["moves"] == "train_throughput"
    assert "workloads" not in entry
    for cell in M["workloads"]:
        assert entry in manifest.metrics_of(M["per_layer"], cell["name"])
    assert callable(_reader(name).read)


# ------------------------------------------------------------- the rehearsal
def test_traced_rehearsal_prints_the_host_clock_shares(tmp_path):
    """Off the chip a trace has no TPU plane, so the three shares of the
    device's idle time say nothing; the two of the history's fields print."""
    cell, rehearsal = _tiny("dlrm_criteo_stream", tmp_path)
    os.makedirs(tmp_path / ".cache")
    (tmp_path / ".cache" / f"{cell.name}.json").write_text('{"t_e": 0.05}')
    line, detail = _check_contract(_rehearse(cell, rehearsal, True),
                                   cell, trace=True)
    assert line["correct"] is True, detail["found"]
    got = {k: v["value"] for k, v in line["metrics"].items()}
    assert not set(NEW[:3]) & set(got)
    assert got["epoch_turn_share"] >= got["feed_restart_share"] > 0
    assert got["feed_restart_share"] <= got["feed_wait_share"]
