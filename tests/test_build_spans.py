"""What built a program, and when: jax's build events as phase spans in the
ring (``profiler.watch_jit_builds``), the two counters beside them, and a
fit's epoch 0 read by them - the step program built twice shows as two."""

import subprocess
import sys
import time

import pytest
from test_fit_spans import REPO, _by_name, _estimator, _frame

from raydp_tpu import metrics, profiler

TRACE = "/jax/core/compile/jaxpr_trace_duration"
LOWER = "/jax/core/compile/jaxpr_to_mlir_module_duration"
COMPILE = "/jax/core/compile/backend_compile_duration"
HIT = "/jax/compilation_cache/cache_hits"
MISS = "/jax/compilation_cache/cache_misses"
KINDS = ("jit:trace", "jit:lower", "jit:compile")


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


def _fit(num_epochs, **kw):
    """One streaming fit; gives the ring, the jit counters and what an
    independent listener heard meanwhile."""
    import jax.monitoring as mon

    import raydp_tpu

    events = _Events()
    with pytest.MonkeyPatch.context() as env:
        env.setenv("RDT_DEVICE_CACHE", "0")
        session = raydp_tpu.init("pytest", num_executors=2, executor_cores=1,
                                 executor_memory="512MB")
        try:
            df = _frame(session)
            profiler.clear()
            metrics.reset()
            mon.register_event_time_span_listener(events)
            try:
                _estimator(num_epochs, **kw).fit_on_frame(df)
            finally:
                mon.unregister_event_time_span_listener(events)
            counters = _counters()
        finally:
            raydp_tpu.stop()
    ring = profiler.spans()
    return {"ring": ring, "by_sid": {s["sid"]: s for s in ring},
            "names": _by_name(ring), "counters": counters, "events": events}


@pytest.fixture(scope="module")
def fit():
    return _fit(3)


@pytest.fixture(scope="module")
def accumulating_fit():
    """Its step is compiled before the first call, under ``train:accum``."""
    return _fit(1, accum_steps=2)


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


def test_the_step_is_built_twice_and_the_second_build_shows(fit):
    """The first build under ``train:first_dispatch``, the second (the call
    that takes the step's own outputs) under epoch 0's ``train:epoch``."""
    epoch0 = fit["names"]["train:epoch"][0]
    (first,) = fit["names"]["train:first_dispatch"]
    assert epoch0["args"]["epoch"] == "0" and first["par"] == epoch0["sid"]
    step = [s for s in _builds(fit) if "train_step" in s["args"]["fun"]]
    step.sort(key=lambda s: s["ts"])
    assert [s["name"] for s in step] == list(KINDS) * 2
    assert [s["par"] for s in step] == [first["sid"]] * 3 + [epoch0["sid"]] * 3
    assert all(a["ts"] + a["dur"] <= b["ts"] + 1 for a, b in zip(step, step[1:]))
    # every build under epoch 0 hangs from one of the two
    under = [s for s in _builds(fit) if epoch0 in list(_ancestors(fit, s))]
    assert {s["par"] for s in under} <= {first["sid"], epoch0["sid"]}


def test_no_epoch_after_the_first_builds_anything(fit):
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
    """``chipbench/trace/build_spans.py`` on the ring of a fit: two builds of
    the step, and the four parts cover epoch 0."""
    from chipbench.trace import build_spans, fit_spans
    fit = request.getfixturevalue(which)
    monkeypatch.setattr(fit_spans, "ring", lambda: fit["ring"])
    epoch0 = fit["names"]["train:epoch"][0]["dur"] / 1e6
    parts = [build_spans.kind_s(kind) for kind in KINDS]
    assert all(p > 0 for p in parts)
    assert build_spans.step_builds() == 2
    assert 0 < build_spans.run_s() < epoch0
    assert sum(parts) + build_spans.run_s() >= epoch0 - 1e-6
    # nothing here has a compile cache: every compile says so
    assert build_spans.cache_hit_share() == 0.0
