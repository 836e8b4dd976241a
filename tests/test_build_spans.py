"""What built a program, and when: jax's build events as phase spans in the
ring (``profiler.watch_jit_builds``), the two counters beside them, and a
fit's epoch 0 read by them - the step program is built once a fit."""

import subprocess
import sys
import time

import numpy as np
import pytest
from test_fit_spans import REPO, _by_name, _estimator, _frame

from raydp_tpu import metrics, profiler
from raydp_tpu.utils import COMPILE_CACHE_ENV

TRACE = "/jax/core/compile/jaxpr_trace_duration"
LOWER = "/jax/core/compile/jaxpr_to_mlir_module_duration"
COMPILE = "/jax/core/compile/backend_compile_duration"
HIT = "/jax/compilation_cache/cache_hits"
MISS = "/jax/compilation_cache/cache_misses"
KINDS = ("jit:trace", "jit:lower", "jit:compile")


@pytest.fixture(scope="module", autouse=True)
def no_compile_cache():
    """This file tells a compile from a cache's load by what jax reports (a
    compile's span says ``cache: off``, a fit's ``cache_hit_share`` is 0): the
    run's compile cache (``conftest.run_compile_cache``) is off while its
    tests run, here and in every process they start."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache
    patch = pytest.MonkeyPatch()
    patch.delenv(COMPILE_CACHE_ENV, raising=False)
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", True)
    compilation_cache.reset_cache()
    patch.undo()


def test_this_files_tests_run_without_the_runs_compile_cache():
    import os

    import jax
    assert COMPILE_CACHE_ENV not in os.environ
    assert not jax.config.jax_enable_compilation_cache


# ------------------------------------------------------------------ registry
@pytest.mark.parametrize("name", KINDS)
def test_build_span_is_a_registered_phase_span(name):
    assert metrics.SPANS[name].kind == metrics.PHASE
    assert name not in metrics.STEP_SPAN_NAMES
    assert f"| `{name}` | {metrics.PHASE} |" in metrics.generate_table("spans")


@pytest.mark.parametrize("name,label", [("jit_lowerings_total", ""),
                                        ("jit_compiles_total", "cache")])
def test_build_counter_is_registered(name, label):
    m = metrics.METRICS[name]
    assert (m.kind, m.label) == (metrics.COUNTER, label)


# ------------------------------------------------------------------ mechanism
@pytest.fixture
def listening():
    """The listener installed (twice: it registers once), ring and counters
    empty."""
    import jax.monitoring as mon
    profiler.watch_jit_builds()
    profiler.watch_jit_builds()
    profiler.clear()
    metrics.reset()
    return mon


def _counters():
    return {k: v for k, v in metrics.snapshot()["counters"].items()
            if k.startswith("jit_")}


def test_record_span_appends_a_closed_span_under_the_active_one():
    profiler.clear()
    with profiler.trace("fit:init") as outer:
        profiler.record_span("jit:lower", 12.5, 12.75, "jit", fun="f")
    late, first = profiler.spans()[0], profiler.spans()[1]
    assert first["name"] == "fit:init" and late["par"] == outer["sid"]
    assert (late["ts"], late["dur"]) == (12_500_000, 250_000)
    assert late["tr"] == outer["tr"] and late["args"] == {"fun": "f"}
    assert late["tid"] == outer["tid"] and late["cat"] == "jit"
    profiler.set_enabled(False)
    try:
        profiler.record_span("jit:lower", 1.0, 2.0)
    finally:
        profiler.set_enabled(True)
    assert len(profiler.spans()) == 2


@pytest.mark.parametrize("event,name,counted", [
    (TRACE, "jit:trace", {}),
    (LOWER, "jit:lower", {"jit_lowerings_total": {"": 1}}),
    (COMPILE, "jit:compile", {"jit_compiles_total": {"off": 1}})])
def test_a_build_event_leaves_one_span_and_counts(listening, event, name,
                                                  counted):
    """One span an event though the installer was called twice, on the ring's
    clock; a lowering and a compile count, a trace never does."""
    t0 = time.time()
    with profiler.trace("train:first_dispatch") as outer:
        listening.record_event_time_span(event, t0, t0 + 0.25, fun_name="f")
    (span,) = [s for s in profiler.spans() if s["name"] in KINDS]
    assert span["name"] == name and span["par"] == outer["sid"]
    assert span["ts"] == int(t0 * 1e6) and abs(span["dur"] - 250_000) <= 1
    assert span["args"]["fun"] == "f"
    assert ("cache" in span["args"]) == (name == "jit:compile")
    assert _counters() == counted


@pytest.mark.parametrize("fired,cache", [([HIT], "hit"), ([MISS], "miss"),
                                         ([], "off")])
def test_a_compile_says_what_the_cache_did(listening, fired, cache):
    """From the cache's event on the compiling thread inside the compile; the
    next compile starts from nothing."""
    for event in fired:
        listening.record_event(event)
    listening.record_event_time_span(COMPILE, 5.0, 6.0, fun_name="jit(f)")
    listening.record_event_time_span(COMPILE, 7.0, 8.0, fun_name="jit(g)")
    first, second = profiler.spans()
    assert first["args"] == {"fun": "jit(f)", "cache": cache}
    assert second["args"] == {"fun": "jit(g)", "cache": "off"} and \
        "par" not in second
    want = {cache: 1, "off": 1} if cache != "off" else {"off": 2}
    assert _counters() == {"jit_compiles_total": want}


@pytest.mark.parametrize("event", [TRACE, LOWER, COMPILE])
def test_an_event_under_the_floor_leaves_no_span(listening, event):
    """... and still counts: the counters are what an operator alerts on."""
    assert 0 < profiler.JIT_SPAN_FLOOR_S <= 0.010
    under = profiler.JIT_SPAN_FLOOR_S * 0.9
    listening.record_event_time_span(event, 3.0, 3.0 + under, fun_name="add")
    assert profiler.spans() == []
    assert sum(sum(v.values()) for v in _counters().values()) == \
        (event != TRACE)
    listening.record_event_time_span(event, 3.0, 3.0 + 2 * under,
                                     fun_name="add")
    assert len(profiler.spans()) == 1


@pytest.mark.parametrize("event", [TRACE, LOWER, COMPILE])
def test_a_disabled_profiler_records_no_build(listening, event):
    profiler.set_enabled(False)
    try:
        listening.record_event(HIT)
        listening.record_event_time_span(event, 1.0, 2.0, fun_name="f")
    finally:
        profiler.set_enabled(True)
    assert profiler.spans() == [] and _counters() == {}
    # and the hit it did not see is not the next compile's
    listening.record_event_time_span(COMPILE, 1.0, 2.0, fun_name="f")
    assert profiler.spans()[0]["args"]["cache"] == "off"


def test_watching_builds_never_imports_jax():
    """ETL executors import the profiler and never load jax: there the
    installer is a no-op."""
    code = ("import sys\n"
            "from raydp_tpu import profiler\n"
            "profiler.watch_jit_builds()\n"
            "profiler.record_span('jit:lower', 1.0, 2.0, fun='f')\n"
            "assert 'jax' not in sys.modules, 'jax was imported'\n"
            "print([s['name'] for s in profiler.spans()])\n")
    proc = subprocess.run([sys.executable, "-c", code], cwd=REPO,
                          capture_output=True, text=True, timeout=120)
    assert proc.returncode == 0, proc.stderr
    assert proc.stdout.strip() == "['jit:lower']"


# ----------------------------------------------------------------- a real fit
class _Events:
    """jax's build events as an independent listener hears them."""

    def __init__(self):
        self.heard = []

    def __call__(self, event, start, end, **kw):
        if event in (TRACE, LOWER, COMPILE):
            self.heard.append((event, end - start))

    def count(self, event, floor=0.0):
        return sum(e == event and d >= floor for e, d in self.heard)


def _ring_of(train, resident=False):
    """``train(frame)`` in a session of its own; gives what it returned, the
    ring, the jit counters and what an independent listener heard meanwhile."""
    import jax.monitoring as mon

    import raydp_tpu

    events = _Events()
    with pytest.MonkeyPatch.context() as env:
        env.setenv("RDT_DEVICE_CACHE", "1" if resident else "0")
        session = raydp_tpu.init("pytest", num_executors=2, executor_cores=1,
                                 executor_memory="512MB")
        try:
            df = _frame(session)
            profiler.clear()
            metrics.reset()
            mon.register_event_time_span_listener(events)
            try:
                result = train(df)
            finally:
                mon.unregister_event_time_span_listener(events)
            counters = _counters()
        finally:
            raydp_tpu.stop()
    ring = profiler.spans()
    return {"ring": ring, "by_sid": {s["sid"]: s for s in ring},
            "names": _by_name(ring), "counters": counters, "events": events,
            "history": result.history}


def _fit(num_epochs, resident=False, evaluate=False, **kw):
    """One fit, streaming unless ``resident``, evaluated on its own rows if
    asked."""
    return _ring_of(lambda df: _estimator(num_epochs, **kw).fit_on_frame(
        df, df if evaluate else None), resident)


@pytest.fixture(scope="module")
def fit():
    """With a user's metric (``Metric.init`` gives weak-typed Python floats)
    and an eval pass: both step programs in one fit."""
    return _fit(3, evaluate=True, metrics=["mae"])


@pytest.fixture(scope="module")
def accumulating_fit():
    """Its step is compiled before the first call, under ``train:accum``."""
    return _fit(1, accum_steps=2)


@pytest.fixture(scope="module")
def resident_fit():
    """The whole epoch, and the whole eval pass, as one scan program each."""
    return _fit(3, resident=True, evaluate=True, metrics=["mae"])


def _reg_table(epoch, rows=128):
    import pyarrow as pa
    x = np.random.RandomState(epoch).random_sample((rows, 2))
    return pa.table({"x1": x[:, 0], "x2": x[:, 1],
                     "y": x @ np.array([2.0, -3.0]) + 1.0})


@pytest.fixture(scope="module")
def online_fit():
    """Two ``partial_fit`` epochs of two steps each."""
    from raydp_tpu import stream

    def train(_):
        pipe = stream.read_stream(
            stream.SyntheticSource(_reg_table, max_epochs=2))
        try:
            return _estimator(1, metrics=["mae"]).partial_fit(pipe)
        finally:
            pipe.close()
    return _ring_of(train)


def _ancestors(fit, span):
    while span.get("par") in fit["by_sid"]:
        span = fit["by_sid"][span["par"]]
        yield span


def _builds(fit):
    return [s for s in fit["ring"] if s["name"] in KINDS]


def test_every_build_of_a_fit_is_a_span_inside_its_parent(fit):
    (run,) = fit["names"]["fit:run"]
    builds = _builds(fit)
    assert {s["name"] for s in builds} == set(KINDS)
    for s in builds:
        parent = fit["by_sid"][s["par"]]
        assert run in list(_ancestors(fit, s)) and s["tr"] == run["tr"]
        assert s["args"]["fun"] and s["cat"] == "jit"
        # recorded on jax's own time.time() pair, the parent on time_ns():
        # one clock, each truncated to a microsecond
        assert parent["ts"] - 1 <= s["ts"]
        assert s["ts"] + s["dur"] <= parent["ts"] + parent["dur"] + 1
        assert s["dur"] >= profiler.JIT_SPAN_FLOOR_S * 1e6 - 1
    # no step span, and nothing else new, entered the ring
    assert not set(fit["names"]) & metrics.STEP_SPAN_NAMES
    assert set(fit["names"]) <= metrics.SPAN_NAMES | {
        n for n in fit["names"] if n.startswith(metrics.SPAN_PREFIXES)}


def _built(fit, fun):
    """The ring's builds of the program ``fun``, in the order they started."""
    return sorted((s for s in _builds(fit) if fun in s["args"]["fun"]),
                  key=lambda s: s["ts"])


@pytest.mark.parametrize("which,under", [
    ("fit", "train:first_dispatch"), ("accumulating_fit", "train:accum")])
def test_the_step_is_built_once_under_its_first_build(request, which, under):
    """Traced, lowered and compiled once a fit: the first call takes
    accumulators of the types the step returns, so the call that takes the
    step's own outputs finds the program built. Nothing named ``train_step``
    is built under epoch 0 outside that one span."""
    fit = request.getfixturevalue(which)
    epoch0 = fit["names"]["train:epoch"][0]
    (first,) = fit["names"]["train:first_dispatch"]
    (build,) = fit["names"][under]
    assert epoch0["args"]["epoch"] == "0"
    assert first["par"] == build["par"] == epoch0["sid"]
    step = _built(fit, "train_step")
    assert [s["name"] for s in step] == list(KINDS)
    assert [s["par"] for s in step] == [build["sid"]] * 3
    assert all(a["ts"] + a["dur"] <= b["ts"] + 1 for a, b in zip(step, step[1:]))


#: by fixture: the program that runs an epoch's steps, and the eval pass's
#: (None: the fit has none)
PROGRAMS = {"fit": ("train_step", "eval_step"),
            "accumulating_fit": ("train_step", None),
            "resident_fit": ("epoch_fn", "epoch_fn"),
            "online_fit": ("train_step", None)}


@pytest.mark.parametrize("which", list(PROGRAMS))
def test_every_step_program_of_a_fit_is_lowered_once(request, which):
    """Over three epochs (two of ``partial_fit``): the streaming step with a
    user's weak-typed metric, the eval step (its second batch once built it
    again), the resident path's two scans (one name: two lowerings), the
    online step."""
    fit = request.getfixturevalue(which)
    train, evaluate = PROGRAMS[which]
    assert len(fit["history"]) == {"accumulating_fit": 1,
                                   "online_fit": 2}.get(which, 3)
    assert all(h["steps"] >= 2 for h in fit["history"])
    want = {train: 1}
    if evaluate:
        assert all("eval_loss" in h for h in fit["history"])
        want[evaluate] = want.get(evaluate, 0) + 1
    for fun, times in want.items():
        lowered = [s for s in _built(fit, fun) if s["name"] == "jit:lower"]
        assert len(lowered) == times, [s["args"] for s in lowered]
    # and the program that makes an epoch's zeros once for each of the two
    zeros = [s for s in _built(fit, "zeros") if s["name"] == "jit:compile"]
    assert len(zeros) <= 1 + bool(evaluate)


#: what the parent of the one-build change printed for the same fits
LOSSES = {
    "fit": ([1.0157625675201416, 0.7355257868766785, 0.5607577562332153],
            [0.8337425589561462, 0.636257529258728, 0.464870810508728],
            [0.8178235292434692, 0.7093298435211182, 0.6268231272697449]),
    "resident_fit": (
        [1.0214362144470215, 0.7455786466598511, 0.567087709903717],
        [0.8363513946533203, 0.6378688812255859, 0.47131332755088806],
        [0.8227283358573914, 0.71086186170578, 0.6299079060554504]),
}


@pytest.mark.parametrize("which", list(LOSSES))
def test_the_epochs_losses_are_the_two_build_fits(request, which):
    """Zeros of another type are the same zeros: the sums the step threads
    through, the eval pass's and a metric's come out as they did."""
    history = request.getfixturevalue(which)["history"]
    got = [[h[key] for h in history]
           for key in ("train_loss", "eval_loss", "train_mae")]
    np.testing.assert_allclose(got, LOSSES[which], rtol=1e-5)


@pytest.mark.parametrize("which", ["fit", "resident_fit"])
def test_no_epoch_after_the_first_builds_anything(request, which):
    fit = request.getfixturevalue(which)
    later = {s["sid"] for s in fit["names"]["train:epoch"]
             if s["args"]["epoch"] != "0"}
    assert len(later) == 2
    for s in _builds(fit):
        assert not later & {a["sid"] for a in _ancestors(fit, s)}


def test_the_counters_match_the_ring_and_the_events(fit):
    """Every lowering and compile counts; those at or over the floor are the
    ring's spans."""
    events, counters = fit["events"], fit["counters"]
    assert counters["jit_lowerings_total"] == {"": events.count(LOWER)}
    assert sum(counters["jit_compiles_total"].values()) == \
        events.count(COMPILE)
    floor = profiler.JIT_SPAN_FLOOR_S
    for event, name in ((TRACE, "jit:trace"), (LOWER, "jit:lower"),
                        (COMPILE, "jit:compile")):
        assert len(fit["names"][name]) == events.count(event, floor)
        assert events.count(event) >= len(fit["names"][name])
    by_cache = {}
    for s in fit["names"]["jit:compile"]:
        by_cache[s["args"]["cache"]] = by_cache.get(s["args"]["cache"], 0) + 1
    assert all(by_cache[c] <= counters["jit_compiles_total"][c]
               for c in by_cache)
    # most trace events are jnp functions inside an outer trace: under the
    # floor, and out of the ring
    assert events.count(TRACE) > 3 * len(fit["names"]["jit:trace"])


def test_an_accumulating_fit_builds_its_step_under_train_accum(
        accumulating_fit):
    fit = accumulating_fit
    (accum,) = fit["names"]["train:accum"]
    (first,) = fit["names"]["train:first_dispatch"]
    epoch0 = fit["names"]["train:epoch"][0]
    assert accum["par"] == first["par"] == epoch0["sid"]
    assert accum["ts"] + accum["dur"] <= first["ts"]
    step = [s for s in _builds(fit) if "train_step" in s["args"]["fun"]]
    assert {s["name"] for s in step if s["par"] == accum["sid"]} == set(KINDS)
    assert {s["par"] for s in step} <= {accum["sid"], first["sid"],
                                        epoch0["sid"]}


@pytest.mark.parametrize("which", ["fit", "accumulating_fit"])
def test_the_benchmarks_readers_read_a_real_ring(request, monkeypatch, which):
    """``chipbench/trace/build_spans.py`` on the ring of a fit: one build of
    the step, and the four parts cover epoch 0."""
    from chipbench.trace import build_spans, fit_spans
    fit = request.getfixturevalue(which)
    monkeypatch.setattr(fit_spans, "ring", lambda: fit["ring"])
    epoch0 = fit["names"]["train:epoch"][0]["dur"] / 1e6
    parts = [build_spans.kind_s(kind) for kind in KINDS]
    assert all(p > 0 for p in parts)
    assert build_spans.step_builds() == 1
    assert 0 < build_spans.run_s() < epoch0
    assert sum(parts) + build_spans.run_s() >= epoch0 - 1e-6
    # nothing here has a compile cache: every compile says so
    assert build_spans.cache_hit_share() == 0.0
