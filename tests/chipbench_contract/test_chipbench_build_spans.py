"""The six readers of a fit's build spans (``chipbench/trace/build_spans.py``)
on a hand-made ring - nested and overlapping spans, two builds of the step, a
fit that left no such span, a ring with no fit - their manifest entries, and
one cell rehearsed with them."""

import os

import pytest
from test_chipbench_host_spans import _cells_that_read, _reader, _span
from test_chipbench_run import _check_contract, _rehearse, _tiny

from chipbench import manifest
from chipbench.trace import build_spans, fit_spans

M = manifest.load_manifest()
SIX = ["step_trace_s", "step_lower_s", "step_compile_s", "epoch0_run_s",
       "step_builds", "compile_cache_hit_share"]
S = 1_000_000       # the ring counts microseconds


def _jit(kind, t0, t1, sid, par=None, tid=1, **args):
    span = _span(f"jit:{kind}", int(t0 * S), int((t1 - t0) * S), sid, par,
                 **args)
    span["tid"] = tid
    return span


# The measured fit runs from 100 s; its epoch 0 is [110, 130] on thread 1.
# The step's first build sits under train:accum: traced [110, 114] with a jnp
# function traced inside it [111, 112] and an eager op lowered meanwhile
# [113, 113.5], lowered [114, 116], loaded from the cache [116, 118]. The
# first call [118, 119] builds nothing. The second build hangs from the epoch:
# traced [120, 123], lowered [123, 124], compiled [124, 126]. A compile with
# no parent on the loop's thread [127, 127.5] counts for epoch 0, one on
# another thread does not. So: traced 4 + 3 = 7 s (a sum reads 8), lowered
# 0.5 + 2 + 1 = 3.5, compiled 2 + 2 + 0.5 = 4.5, all three [110, 118] +
# [120, 126] + [127, 127.5] = 14.5 of 20 s, 5.5 left; the parts sum to 20.5
# (the eager op's lowering lies inside the step's trace). Compiles under the
# fit: one in fit:init (hit), the step's two (hit, miss), one in epoch 1
# (miss): 2 of 4. The calibration fit before it is not read.
RING = [
    _jit("lower", 2, 5, "k1", "ce", fun="jit(train_step)"),
    _span("train:first_dispatch", 1 * S, 5 * S, "cd", "ce"),
    _span("train:epoch", 1 * S, 9 * S, "ce", "cal", epoch=0),
    _span("fit:run", 0, 20 * S, "cal"),
    _jit("compile", 105, 106, "j0", "i", fun="jit(init)", cache="hit"),
    _span("fit:init", 104 * S, 3 * S, "i", "run"),
    _jit("trace", 111, 112, "j2", "a", fun="add"),
    _jit("lower", 113, 113.5, "j3", "a", fun="jit(convert_element_type)"),
    _jit("trace", 110, 114, "j1", "a", fun="train_step"),
    _jit("lower", 114, 116, "j4", "a", fun="jit(train_step)"),
    _jit("compile", 116, 118, "j5", "a", fun="jit(train_step)", cache="hit"),
    _span("train:accum", 110 * S, 8 * S, "a", "e0"),
    _span("train:first_dispatch", 118 * S, 1 * S, "d", "e0"),
    _jit("trace", 120, 123, "j6", "e0", fun="train_step"),
    _jit("lower", 123, 124, "j7", "e0", fun="jit(train_step)"),
    _jit("compile", 124, 126, "j8", "e0", fun="jit(train_step)",
         cache="miss"),
    _jit("compile", 127, 127.5, "j9", fun="jit(f)", cache="off"),
    _jit("compile", 128, 129, "j10", tid=2, fun="jit(g)", cache="off"),
    dict(_span("train:epoch", 110 * S, 20 * S, "e0", "run", epoch=0), tid=1),
    _jit("compile", 131, 132, "j11", "e1", fun="jit(train_step)",
         cache="miss"),
    _span("train:epoch", 130 * S, 5 * S, "e1", "run", epoch=1),
    _span("fit:run", 100 * S, 60 * S, "run"),
]
EXPECTED = {"step_trace_s": 7.0, "step_lower_s": 3.5, "step_compile_s": 4.5,
            "epoch0_run_s": 5.5, "step_builds": 2,
            "compile_cache_hit_share": 50.0}
#: what the parent of the PR that added the spans leaves: the fit, no build
NO_BUILDS = [s for s in RING if not s["name"].startswith("jit:")]
NO_FIT = [s for s in RING if s["name"] != "fit:run"]


@pytest.mark.parametrize("name", SIX)
def test_reader_takes_unions_on_a_hand_made_ring(name, monkeypatch):
    monkeypatch.setattr(fit_spans, "ring", lambda: RING)
    assert _reader(name).read({}) == pytest.approx(EXPECTED[name])


@pytest.mark.parametrize("ring", [NO_BUILDS, NO_FIT, []],
                         ids=["no_build_spans", "no_fit", "empty"])
@pytest.mark.parametrize("name", SIX)
def test_reader_that_finds_nothing_says_nothing(name, ring, monkeypatch):
    monkeypatch.setattr(fit_spans, "ring", lambda: ring)
    assert _reader(name).read({}) is None


def test_the_four_parts_cover_epoch_0(monkeypatch):
    """trace + lower + compile + run is epoch 0 and what two kinds share."""
    monkeypatch.setattr(fit_spans, "ring", lambda: RING)
    first, under = build_spans.epoch0_builds(RING)
    assert first["sid"] == "e0"
    assert [s["sid"] for s in under] == ["j1", "j2", "j3", "j4", "j5", "j6",
                                         "j7", "j8", "j9"]
    parts = sum(EXPECTED[k] for k in SIX[:4])
    assert parts == fit_spans.epoch0_s() + 0.5 == 20.5


@pytest.mark.parametrize("drop,builds", [({"j7"}, 1), ({"j3", "j4"}, None),
                                         ({"j3"}, 2)])
def test_step_builds_counts_lowerings_of_the_first_builds_program(
        drop, builds, monkeypatch):
    """One build where the second call lowers nothing; nothing to say where
    the first build left no lowering; the eager op's is never the step's."""
    ring = [s for s in RING if s["sid"] not in drop]
    monkeypatch.setattr(fit_spans, "ring", lambda: ring)
    assert build_spans.step_builds() == builds


def test_the_manifest_holds_the_six():
    assert manifest.validate(M) == []
    by_name = {m["name"]: m for m in M["per_layer"]}
    assert [m["name"] for m in M["per_layer"]].count("step_builds") == 1
    for name in SIX:
        entry = by_name[name]
        assert (entry["source"], entry["moves"], entry["layer"]) == (
            "program_span", "setup_s", "train loop")
        assert entry["better"] == (
            "higher" if name == "compile_cache_hit_share" else "lower")
        assert "workloads" not in entry
        assert os.path.isfile(os.path.join(
            manifest.ROOT, manifest.BENCH_DIR, "layer_metrics", f"{name}.py"))
    assert _cells_that_read(*SIX) == [w["name"] for w in M["workloads"]]


def test_traced_rehearsal_prints_the_six(tmp_path):
    """A cell's result line carries them, and they tell epoch 0 from inside."""
    cell, rehearsal = _tiny("dlrm_criteo_stream", tmp_path)
    os.makedirs(tmp_path / ".cache")
    (tmp_path / ".cache" / f"{cell.name}.json").write_text('{"t_e": 0.05}')
    line, _ = _check_contract(_rehearse(cell, rehearsal, True), cell,
                              trace=True)
    got = {k: v["value"] for k, v in line["metrics"].items()}
    assert set(SIX) <= set(got)
    assert got["step_builds"] == 2
    # (more where two kinds overlap: eager ops compiled while the step is
    # traced lie inside its jit:trace)
    assert sum(got[k] for k in SIX[:4]) >= got["fit_epoch0_s"] - 1e-6
    assert 0 < got["epoch0_run_s"] < got["fit_epoch0_s"]
    assert 0 <= got["compile_cache_hit_share"] <= 100
