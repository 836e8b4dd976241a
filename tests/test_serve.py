"""Serving-plane tests (ISSUE 11).

Two layers, mirroring how the plane is built:

- **dispatcher units** — :class:`ServingSession`'s micro-batching, demux,
  routing, hedging, and fault re-route driven against in-process fake
  replica handles (no actors, no jax): fast, deterministic, and able to
  script failure shapes no real schedule can time reliably.
- **integration** — a real 2-executor session: estimator fit → export →
  executor-resident replicas, with the coalesced results asserted
  BIT-identical to the estimator's own ``predict`` (the jitted apply is
  row-independent, so batch composition must not leak into results).

The replica-crash chaos leg lives in tests/test_chaos.py with the other
seeded-injection coverage.
"""

import os
import threading
import time
from concurrent.futures import Future

os.environ.setdefault("KERAS_BACKEND", "jax")

import numpy as np
import pandas as pd
import pyarrow as pa
import pytest

from raydp_tpu.runtime.rpc import ConnectionLost, RemoteError
from raydp_tpu.serve import ServingError, ServingSession
from raydp_tpu.serve.session import _as_table


# ---------------------------------------------------------------------------
# fake replica handles: duck-typed ActorHandles serving 2*v in-process
# ---------------------------------------------------------------------------

def _decode_payload(payload: bytes) -> pa.Table:
    return pa.ipc.open_stream(pa.py_buffer(payload)).read_all()


class FakeReplicaHandle:
    """Serves ``2 * v`` per row on a thread after ``delay_s()`` seconds;
    ``fail`` scripts an infrastructure failure per call, ``app_fail`` a
    deterministic application error (a remote ValueError)."""

    def __init__(self, name, delay_s=0.0, fail: bool = False,
                 app_fail: bool = False, fail_delay_s: float = 0.01):
        self.name = name
        self.delay_s = delay_s
        self.fail = fail
        self.app_fail = app_fail
        self.fail_delay_s = fail_delay_s
        self.loads = 0
        self.calls = 0
        self._lock = threading.Lock()

    def call(self, method, *args, timeout=None, **kwargs):
        if method == "serve_load":
            with self._lock:
                self.loads += 1
            return {"replica": args[0]}
        if method == "serve_unload":
            return True
        raise AssertionError(f"unexpected call {method}")

    def submit(self, method, *args, **kwargs):
        fut: Future = Future()
        if method == "serve_load":
            with self._lock:
                self.loads += 1
            fut.set_result({"replica": args[0]})
            return fut
        assert method == "serve_predict"
        _rid, payload = args
        with self._lock:
            self.calls += 1
        threading.Thread(target=self._serve, args=(payload, fut),
                         daemon=True).start()
        return fut

    def _serve(self, payload, fut):
        if self.fail:
            time.sleep(self.fail_delay_s)
            fut.set_exception(ConnectionLost(f"{self.name} is scripted down"))
            return
        if self.app_fail:
            time.sleep(self.fail_delay_s)
            fut.set_exception(RemoteError("ValueError", "bad rows", "<tb>"))
            return
        d = self.delay_s() if callable(self.delay_s) else self.delay_s
        if d:
            time.sleep(d)
        table = _decode_payload(payload)
        v = table.column("v").to_numpy(zero_copy_only=False)
        fut.set_result((v * 2.0).astype(np.float32))


def _serving(replicas, monkeypatch, *, max_batch=1000, timeout_ms=40.0,
             hedge=False, hedge_mult=2.0, hedge_min_ms=50.0,
             grace_s=10.0, inflight=2):
    monkeypatch.setenv("RDT_SERVE_MAX_BATCH", str(max_batch))
    monkeypatch.setenv("RDT_SERVE_BATCH_TIMEOUT_MS", str(timeout_ms))
    monkeypatch.setenv("RDT_SERVE_HEDGE", "1" if hedge else "0")
    monkeypatch.setenv("RDT_SERVE_HEDGE_QUANTILE", "0.5")
    monkeypatch.setenv("RDT_SERVE_HEDGE_MULTIPLIER", str(hedge_mult))
    monkeypatch.setenv("RDT_SERVE_HEDGE_MIN_MS", str(hedge_min_ms))
    monkeypatch.setenv("RDT_SERVE_REROUTE_GRACE_S", str(grace_s))
    monkeypatch.setenv("RDT_SERVE_MAX_INFLIGHT", str(inflight))
    return ServingSession("/nonexistent/bundle", executors=replicas,
                          name="t")


def _rows(*vals):
    return {"v": np.asarray(vals, np.float64)}


def test_as_table_accepts_frames_tables_dicts():
    t = _as_table(pa.table({"v": [1.0]}))
    assert t.num_rows == 1
    t = _as_table(pd.DataFrame({"v": [1.0, 2.0]}))
    assert t.num_rows == 2
    t = _as_table({"v": np.array([3.0])})
    assert t.num_rows == 1
    with pytest.raises(TypeError):
        _as_table([1, 2, 3])


def test_coalescing_batches_and_demuxes(monkeypatch):
    """A burst of single-row requests coalesces into far fewer dispatches,
    and every caller gets exactly its own row back."""
    fakes = [FakeReplicaHandle("a", delay_s=0.02),
             FakeReplicaHandle("b", delay_s=0.02)]
    srv = _serving(fakes, monkeypatch, timeout_ms=40.0)
    try:
        futs = [srv.predict_async(_rows(float(i))) for i in range(64)]
        got = [f.result(timeout=30.0) for f in futs]
        for i, g in enumerate(got):
            assert g.shape == (1,)
            assert g[0] == np.float32(2.0 * i)
        rep = srv.serving_report()
        assert rep["requests"] == 64
        assert rep["batches"] < 64          # coalescing actually happened
        assert rep["rows"] == 64
        assert rep["mean_batch_occupancy"] > 1.0
        assert rep["failed"] == 0
    finally:
        srv.close()


def test_timeout_flushes_a_lone_request(monkeypatch):
    """A single request never waits for a batch to fill: the latency budget
    flushes it."""
    srv = _serving([FakeReplicaHandle("a")], monkeypatch,
                   max_batch=100000, timeout_ms=30.0)
    try:
        t0 = time.monotonic()
        out = srv.predict(_rows(21.0), timeout=30.0)
        wall = time.monotonic() - t0
        assert out[0] == np.float32(42.0)
        assert wall < 5.0
        rep = srv.serving_report()
        assert rep["batches"] == 1 and rep["max_batch_occupancy"] == 1
    finally:
        srv.close()


def test_full_batch_dispatches_before_timeout(monkeypatch):
    """Hitting the row cap flushes immediately — the budget is a ceiling,
    not a tax on full batches."""
    fake = FakeReplicaHandle("a")
    srv = _serving([fake], monkeypatch, max_batch=8, timeout_ms=60_000.0)
    try:
        futs = [srv.predict_async(_rows(float(i))) for i in range(8)]
        t0 = time.monotonic()
        for f in futs:
            f.result(timeout=30.0)
        assert time.monotonic() - t0 < 10.0  # nowhere near the 60s budget
    finally:
        srv.close()


def test_oversized_request_is_its_own_batch(monkeypatch):
    """A request above RDT_SERVE_MAX_BATCH dispatches alone, un-split."""
    srv = _serving([FakeReplicaHandle("a")], monkeypatch, max_batch=4,
                   timeout_ms=10.0)
    try:
        vals = np.arange(10, dtype=np.float64)
        out = srv.predict({"v": vals}, timeout=30.0)
        assert np.array_equal(out, (vals * 2).astype(np.float32))
        rep = srv.serving_report()
        assert rep["max_batch_occupancy"] == 10
    finally:
        srv.close()


def test_demux_ordering_under_interleaved_threads(monkeypatch):
    """Requests issued from many threads each get their own rows, in their
    own order, regardless of how the dispatcher packed them."""
    fakes = [FakeReplicaHandle("a", delay_s=0.01),
             FakeReplicaHandle("b", delay_s=0.01)]
    srv = _serving(fakes, monkeypatch, timeout_ms=20.0)
    errors = []

    def client(base):
        try:
            vals = np.array([base, base + 0.25, base + 0.5])
            out = srv.predict({"v": vals}, timeout=30.0)
            assert np.array_equal(out, (vals * 2).astype(np.float32))
        except Exception as e:  # noqa: BLE001 - surfaced below
            errors.append(e)

    try:
        threads = [threading.Thread(target=client, args=(float(i),))
                   for i in range(16)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=30.0)
        assert not errors
        rep = srv.serving_report()
        assert rep["requests"] == 16 and rep["rows"] == 48
    finally:
        srv.close()


def test_routing_spreads_over_replicas(monkeypatch):
    fakes = [FakeReplicaHandle("a"), FakeReplicaHandle("b")]
    srv = _serving(fakes, monkeypatch, max_batch=1, timeout_ms=0.0)
    try:
        for i in range(10):
            srv.predict(_rows(float(i)), timeout=30.0)
        rep = srv.serving_report()
        per = {r["replica"]: r["batches"] for r in rep["replicas"]}
        assert all(n >= 1 for n in per.values()), per
    finally:
        srv.close()


def test_hedging_wins_and_accounts(monkeypatch):
    """A replica that turns slow after warmup gets hedged: the fast sibling
    answers, the request never waits out the straggler, and the counters
    record the race both ways."""
    slow_after = {"n": 0}

    def a_delay():
        slow_after["n"] += 1
        return 0.0 if slow_after["n"] <= 8 else 1.5

    fakes = [FakeReplicaHandle("a", delay_s=a_delay),
             FakeReplicaHandle("b", delay_s=0.0)]
    srv = _serving(fakes, monkeypatch, max_batch=1, timeout_ms=0.0,
                   hedge=True, hedge_mult=2.0, hedge_min_ms=50.0)
    try:
        # warmup: sequential requests alternate replicas, recording >= 8
        # fast batch latencies (the hedge-eligibility floor)
        for i in range(16):
            srv.predict(_rows(float(i)), timeout=30.0)
        # now replica a is a straggler: every request it receives should
        # hedge onto b and complete far below a's 1.5s delay
        t0 = time.monotonic()
        futs = [srv.predict_async(_rows(100.0 + i)) for i in range(4)]
        got = [f.result(timeout=30.0) for f in futs]
        wall = time.monotonic() - t0
        for i, g in enumerate(got):
            assert g[0] == np.float32(2.0 * (100.0 + i))
        assert wall < 1.4, f"hedging did not cut the straggler tail: {wall}"
        rep = srv.serving_report()
        assert rep["hedged"] >= 1
        assert rep["hedge_won"] >= 1
        assert rep["failed"] == 0
        # the losers land ~1.5s later and are discarded+counted
        deadline = time.time() + 5.0
        while time.time() < deadline:
            rep = srv.serving_report()
            if rep["hedge_lost"] >= 1:
                break
            time.sleep(0.1)
        assert rep["hedge_lost"] >= 1
    finally:
        srv.close()


def test_failed_replica_reroutes_and_reloads(monkeypatch):
    """Every request that lands on the scripted-down replica re-routes to
    the live one; the dead replica's background reload is attempted."""
    down = FakeReplicaHandle("a", fail=True)
    up = FakeReplicaHandle("b")
    srv = _serving([down, up], monkeypatch, max_batch=1, timeout_ms=0.0)
    try:
        for i in range(6):
            out = srv.predict(_rows(float(i)), timeout=30.0)
            assert out[0] == np.float32(2.0 * i)
        rep = srv.serving_report()
        assert rep["failed"] == 0
        assert rep["rerouted"] >= 1          # some requests hit the down one
        assert down.loads >= 2               # initial load + reload attempt
    finally:
        srv.close()


def test_app_error_fails_fast_without_reroute(monkeypatch):
    """A deterministic application error (a remote ValueError) must fail
    the request immediately — replaying it on the sibling replica would
    replay the error, and burning the 30s re-route grace on it is the
    failure mode doc/serving.md's table rules out."""
    srv = _serving([FakeReplicaHandle("a", app_fail=True),
                    FakeReplicaHandle("b", app_fail=True)],
                   monkeypatch, max_batch=1, timeout_ms=0.0, grace_s=30.0)
    try:
        t0 = time.monotonic()
        with pytest.raises(ServingError) as ei:
            srv.predict(_rows(1.0), timeout=30.0)
        assert time.monotonic() - t0 < 5.0
        assert "ValueError" in str(ei.value)
        rep = srv.serving_report()
        assert rep["rerouted"] == 0       # never bounced between replicas
    finally:
        srv.close()


def test_reload_rebinds_replica_off_retired_executor(monkeypatch):
    """Satellite (ISSUE 13): the background reload used to probe a FIXED
    executor identity until the re-route grace expired. With the owning
    session's live-member view available, a replica whose executor was
    retired from the pool re-homes onto a surviving member and reloads
    there — requests keep flowing the whole time."""
    from types import SimpleNamespace

    class RetireableHandle(FakeReplicaHandle):
        def __init__(self, name):
            super().__init__(name)
            self.dead = False

        def call(self, method, *args, timeout=None, **kwargs):
            if self.dead:
                raise ConnectionLost(f"{self.name} was retired")
            return super().call(method, *args, timeout=timeout, **kwargs)

        def submit(self, method, *args, **kwargs):
            if self.dead:
                raise ConnectionLost(f"{self.name} was retired")
            return super().submit(method, *args, **kwargs)

    r0 = RetireableHandle("ex0")
    r1 = FakeReplicaHandle("ex1")
    r2 = FakeReplicaHandle("ex2")
    monkeypatch.setenv("RDT_SERVE_MAX_BATCH", "1000")
    monkeypatch.setenv("RDT_SERVE_BATCH_TIMEOUT_MS", "5")
    monkeypatch.setenv("RDT_SERVE_HEDGE", "0")
    monkeypatch.setenv("RDT_SERVE_REROUTE_GRACE_S", "20")
    # the session's live-member view: ex0 already retired, ex2 a survivor
    # that never hosted a replica
    fake_session = SimpleNamespace(executors=[r1, r2])
    srv = ServingSession("/nonexistent/bundle", session=fake_session,
                         executors=[r0, r1], name="t")
    try:
        r0.dead = True  # the retirement lands after construction
        # first dispatch routes to t-r0 (round-robin start), fails, and
        # re-routes; the reload must re-home t-r0 onto ex2 (least loaded
        # live member), not keep dialing the corpse
        out = srv.predict(_rows(1.0, 2.0), timeout=30.0)
        np.testing.assert_allclose(out, [2.0, 4.0])
        deadline = time.time() + 20
        rep0 = None
        while time.time() < deadline:
            rep0 = next(r for r in srv.serving_report()["replicas"]
                        if r["replica"] == "t-r0")
            if rep0["ready"] and rep0["executor"] == "ex2":
                break
            time.sleep(0.1)
        assert rep0 and rep0["executor"] == "ex2", rep0
        assert rep0["ready"], rep0
        assert r2.loads >= 1, "survivor never loaded the re-homed replica"
        # and the re-homed replica serves again
        out2 = srv.predict(_rows(3.0), timeout=30.0)
        np.testing.assert_allclose(out2, [6.0])
        assert srv.serving_report()["failed"] == 0
    finally:
        srv.close()


def test_mixed_schemas_coalesce_separately(monkeypatch):
    """Requests with different schemas in one batching window dispatch as
    separate batches — a mixed concat would fail and punish well-formed
    requests (and, pre-fix, killed the dispatcher thread outright)."""
    srv = _serving([FakeReplicaHandle("a")], monkeypatch, timeout_ms=40.0)
    try:
        f1 = srv.predict_async({"v": np.array([1.0]),
                                "extra": np.array([9.0])})
        f2 = srv.predict_async(_rows(2.0))
        assert f2.result(timeout=30.0)[0] == np.float32(4.0)
        assert f1.result(timeout=30.0)[0] == np.float32(2.0)
        # the session survives and keeps serving
        assert srv.predict(_rows(3.0), timeout=30.0)[0] == np.float32(6.0)
    finally:
        srv.close()


def test_every_replica_down_fails_within_grace(monkeypatch):
    srv = _serving([FakeReplicaHandle("a", fail=True),
                    FakeReplicaHandle("b", fail=True)],
                   monkeypatch, max_batch=1, timeout_ms=0.0, grace_s=1.0)
    try:
        with pytest.raises(ServingError):
            srv.predict(_rows(1.0), timeout=30.0)
        rep = srv.serving_report()
        assert rep["failed"] >= 1
    finally:
        srv.close()


def test_report_columns(monkeypatch):
    srv = _serving([FakeReplicaHandle("a")], monkeypatch)
    try:
        srv.predict(_rows(1.0), timeout=30.0)
        rep = srv.serving_report()
        for col in ("requests", "batches", "rows", "p50_ms", "p99_ms",
                    "mean_batch_occupancy", "max_batch_occupancy",
                    "queue_depth", "queue_depth_peak", "hedged",
                    "hedge_won", "hedge_lost", "rerouted", "failed",
                    "replicas"):
            assert col in rep, col
        assert rep["p99_ms"] >= rep["p50_ms"] >= 0.0
        r0 = rep["replicas"][0]
        for col in ("replica", "executor", "ready", "requests", "batches",
                    "rows", "hedges", "inflight", "inflight_peak",
                    "reloads"):
            assert col in r0, col
    finally:
        srv.close()


def test_closed_session_refuses_and_empty_request_shortcuts(monkeypatch):
    srv = _serving([FakeReplicaHandle("a")], monkeypatch)
    out = srv.predict(_rows(), timeout=5.0)   # 0 rows: answered inline
    assert out.shape == (0,)
    srv.close()
    with pytest.raises(ServingError):
        srv.predict_async(_rows(1.0))
    # post-close report still answers (snapshot, no dispatcher)
    assert "requests" in srv.serving_report()


# ---------------------------------------------------------------------------
# integration: real executors, real estimator, real bundles
# ---------------------------------------------------------------------------

def _linear_data(n=256):
    rng = np.random.RandomState(3)
    x = rng.random_sample((n, 2))
    y = x @ np.array([2.0, -3.0]) + 1.0
    return pd.DataFrame({"x1": x[:, 0], "x2": x[:, 1], "y": y})


@pytest.fixture(scope="module")
def served_model(tmp_path_factory):
    """One 2-executor session + one trained/exported flax estimator shared
    by the integration tests (executor-side jax import paid once)."""
    import optax

    import raydp_tpu
    from raydp_tpu.models import MLP
    from raydp_tpu.train import FlaxEstimator

    s = raydp_tpu.init("serve_it", num_executors=2, executor_cores=1,
                       executor_memory="512MB")
    try:
        pdf = _linear_data()
        df = s.createDataFrame(pdf, num_partitions=2)
        est = FlaxEstimator(
            model=MLP(features=(8,), use_batch_norm=False),
            optimizer=optax.adam(1e-2), loss="mse",
            feature_columns=["x1", "x2"], label_column="y",
            batch_size=64, num_epochs=1)
        est.fit_on_frame(df)
        export_dir = str(tmp_path_factory.mktemp("servable") / "flax")
        est.export_serving(export_dir)
        yield s, est, export_dir, pdf
    finally:
        raydp_tpu.stop()


def test_flax_servable_roundtrip_matches_predict(served_model):
    """load_servable() in-process reproduces estimator.predict bitwise on
    the same rows."""
    from raydp_tpu.data.dataset import from_frame
    from raydp_tpu.serve import load_servable

    s, est, export_dir, pdf = served_model
    sv = load_servable(export_dir)
    table = pa.table({"x1": pdf["x1"].values, "x2": pdf["x2"].values})
    got = sv.predict_table(table)
    df = s.createDataFrame(pdf, num_partitions=2)
    ref = est.predict(from_frame(df.select("x1", "x2")))
    assert np.array_equal(got, ref)


def test_serving_session_row_identical_to_predict(served_model,
                                                  monkeypatch):
    """The acceptance matrix's core equality: concurrent coalesced serving
    returns, per request, exactly the rows a driver-side predict computes —
    coalescing must be invisible in the bits. Every batch is the same sixteen
    requests whatever the host's load: a batch leaves when it is full, and the
    flush by age is set far beyond a stall."""
    from raydp_tpu.data.dataset import from_frame
    from raydp_tpu.serve import ServingSession

    s, est, export_dir, pdf = served_model
    df = s.createDataFrame(pdf, num_partitions=2)
    ref = est.predict(from_frame(df.select("x1", "x2")))

    monkeypatch.setenv("RDT_SERVE_BATCH_TIMEOUT_MS", "30000")
    monkeypatch.setenv("RDT_SERVE_HEDGE", "0")
    srv = ServingSession(export_dir, session=s, name="it")
    try:
        n = len(pdf)
        futs = [srv.predict_async(
            {"x1": pdf["x1"].values[i:i + 4], "x2": pdf["x2"].values[i:i + 4]})
            for i in range(0, n, 4)]
        got = np.concatenate([f.result(timeout=120.0) for f in futs])
        assert np.array_equal(got, ref)
        rep = srv.serving_report()
        assert rep["requests"] == n // 4
        assert rep["batches"] < rep["requests"]   # coalescing on real RPCs
        assert rep["failed"] == 0
        assert sum(r["batches"] for r in rep["replicas"]) == rep["batches"]
    finally:
        srv.close()


def test_serve_stats_and_unload(served_model, monkeypatch):
    from raydp_tpu.serve import ServingSession

    s, _est, export_dir, pdf = served_model
    monkeypatch.setenv("RDT_SERVE_HEDGE", "0")
    srv = ServingSession(export_dir, session=s, name="stats")
    try:
        srv.predict({"x1": pdf["x1"].values[:8],
                     "x2": pdf["x2"].values[:8]}, timeout=60.0)
        stats = s.executors[0].call("serve_stats")
        mine = [r for r in stats["replicas"]
                if r["replica"].startswith("stats-")]
        assert mine and mine[0]["model_nbytes"] > 0
    finally:
        srv.close()
    # after close(unload=True) the replicas are gone from the registry
    stats = s.executors[0].call("serve_stats")
    assert not any(r["replica"].startswith("stats-")
                   for r in stats["replicas"])


def test_replica_not_loaded_is_typed(served_model):
    s, _est, _export_dir, _pdf = served_model
    with pytest.raises(RemoteError) as ei:
        s.executors[0].call("serve_predict", "no-such-replica", b"")
    assert ei.value.exc_type == "ReplicaNotLoaded"


def test_keras_servable_roundtrip(served_model, tmp_path):
    """Keras export → load_servable reproduces KerasEstimator.predict
    bitwise (architecture from the pickled model, weights from the
    checkpoint). Rides the shared session — init() is a singleton."""
    keras = pytest.importorskip("keras")
    from raydp_tpu.data.dataset import from_frame
    from raydp_tpu.serve import load_servable
    from raydp_tpu.train import KerasEstimator

    s, _est, _export_dir, pdf = served_model
    df = s.createDataFrame(pdf.iloc[:128], num_partitions=1)
    model = keras.Sequential([
        keras.layers.Input((2,)),
        keras.layers.Dense(4, activation="relu"),
        keras.layers.Dense(1),
    ])
    model.compile(optimizer="adam", loss="mse")
    est = KerasEstimator(model=model, feature_columns=["x1", "x2"],
                         label_column="y", batch_size=64, num_epochs=1)
    est.fit_on_frame(df)
    export_dir = str(tmp_path / "keras-bundle")
    est.export_serving(export_dir)
    sv = load_servable(export_dir)
    got = sv.predict_table(
        pa.table({"x1": pdf["x1"].values[:128], "x2": pdf["x2"].values[:128]}))
    ref = est.predict(from_frame(df.select("x1", "x2")))
    assert np.array_equal(got, ref)


def test_export_requires_fit(tmp_path):
    import optax

    from raydp_tpu.models import MLP
    from raydp_tpu.train import FlaxEstimator
    from raydp_tpu.train.gbdt_estimator import GBDTEstimator

    est = FlaxEstimator(model=MLP(features=(4,), use_batch_norm=False),
                        optimizer=optax.adam(1e-2),
                        feature_columns=["a"], label_column="b")
    with pytest.raises(RuntimeError):
        est.export_serving(str(tmp_path / "x"))
    with pytest.raises(NotImplementedError):
        GBDTEstimator(feature_columns=["a"],
                      label_column="b").export_serving(str(tmp_path / "y"))


# ---------------------------------------------------------------------------
# overload shedding (ISSUE 14): typed rejections, dispatcher stays alive
# ---------------------------------------------------------------------------

def test_overload_sheds_typed_and_dispatcher_survives(monkeypatch):
    """Past RDT_SERVE_MAX_QUEUE outstanding requests predict_async fails
    fast with the typed retriable ServingOverloaded; accepted requests
    keep serving byte-correct results, the report shows failed == shed,
    and — the retriable contract — the session accepts again once the
    queue drains."""
    from raydp_tpu.serve import ServingOverloaded

    monkeypatch.setenv("RDT_SERVE_MAX_QUEUE", "4")
    slow = FakeReplicaHandle("a", delay_s=0.25)
    srv = _serving([slow], monkeypatch, max_batch=1, timeout_ms=0.0,
                   inflight=1)
    try:
        futs, sheds = [], 0
        for i in range(12):
            try:
                futs.append((i, srv.predict_async(_rows(float(i)))))
            except ServingOverloaded as e:
                assert isinstance(e, ServingError)  # subclass: one catch
                sheds += 1
        assert sheds >= 1, "queue bound never shed"
        assert len(futs) >= 4  # the bound's worth was accepted
        for i, f in futs:
            got = f.result(timeout=30.0)
            assert got[0] == np.float32(2.0 * i)  # accepted = byte-correct
        rep = srv.serving_report()
        assert rep["shed"] == sheds
        assert rep["failed"] == rep["shed"], rep  # failed == shed ONLY
        assert rep["outstanding"] == 0
        assert rep["max_queue"] == 4
        # retriable: the drained session accepts and serves again
        assert srv.predict(_rows(99.0), timeout=30.0)[0] \
            == np.float32(198.0)
    finally:
        srv.close()


def test_overload_shed_disabled_by_zero(monkeypatch):
    monkeypatch.setenv("RDT_SERVE_MAX_QUEUE", "0")
    srv = _serving([FakeReplicaHandle("a", delay_s=0.05)], monkeypatch,
                   max_batch=1, timeout_ms=0.0, inflight=1)
    try:
        futs = [srv.predict_async(_rows(float(i))) for i in range(32)]
        for i, f in enumerate(futs):
            assert f.result(timeout=30.0)[0] == np.float32(2.0 * i)
        rep = srv.serving_report()
        assert rep["shed"] == 0 and rep["failed"] == 0
    finally:
        srv.close()


def test_hedging_suppressed_while_shedding(monkeypatch):
    """A saturated session must not hedge: the duplicate dispatch would
    amplify the very overload being shed. The same straggler that hedges
    under an uncontended queue rides out its full delay when the
    outstanding queue sits at the bound."""
    for max_queue, expect_hedge in (("100", True), ("1", False)):
        monkeypatch.setenv("RDT_SERVE_MAX_QUEUE", max_queue)
        slow_after = {"n": 0}

        def a_delay():
            slow_after["n"] += 1
            return 0.0 if slow_after["n"] <= 8 else 1.0

        fakes = [FakeReplicaHandle("a", delay_s=a_delay),
                 FakeReplicaHandle("b", delay_s=0.0)]
        srv = _serving(fakes, monkeypatch, max_batch=1, timeout_ms=0.0,
                       hedge=True, hedge_mult=2.0, hedge_min_ms=50.0)
        try:
            for i in range(16):  # warmup: record the fast latency floor
                srv.predict(_rows(float(i)), timeout=30.0)
            # one straggler dispatch; with max_queue=1 the lone
            # outstanding request saturates the session
            t0 = time.monotonic()
            while True:  # land a request on the (now slow) replica a
                got = srv.predict(_rows(123.0), timeout=30.0)
                if slow_after["n"] > 9:
                    break
            wall = time.monotonic() - t0
            assert got[0] == np.float32(246.0)
            rep = srv.serving_report()
            if expect_hedge:
                assert rep["hedged"] >= 1, (max_queue, rep)
            else:
                assert rep["hedged"] == 0, (max_queue, rep)
                assert wall >= 0.9, "suppressed hedge still cut the tail?"
        finally:
            srv.close()


# ---------------------------------------------------------------------------
# hot swap (ISSUE 15): versioned servables under live traffic
# ---------------------------------------------------------------------------

class VersionedFakeReplica(FakeReplicaHandle):
    """A fake whose answer depends on the LOADED bundle: a replica id
    loaded from ``.../vN`` answers ``(N + 1) * v`` — so every response
    names the exact servable version that computed it."""

    def __init__(self, name, delay_s=0.0):
        super().__init__(name, delay_s=delay_s)
        self.dirs: dict = {}        # rid -> export dir
        self.unloaded: list = []

    def call(self, method, *args, timeout=None, **kwargs):
        if method == "serve_unload":
            with self._lock:
                self.dirs.pop(args[0], None)
                self.unloaded.append(args[0])
            return True
        return super().call(method, *args, timeout=timeout, **kwargs)

    def submit(self, method, *args, **kwargs):
        if method == "serve_load":
            rid, export_dir = args
            with self._lock:
                self.dirs[rid] = export_dir
                self.loads += 1
            fut = Future()
            fut.set_result({"replica": rid})
            return fut
        assert method == "serve_predict"
        rid, payload = args
        with self._lock:
            self.calls += 1
            mult = int(self.dirs[rid].rsplit("v", 1)[1]) + 1
        fut = Future()
        threading.Thread(target=self._serve_versioned,
                         args=(payload, fut, mult), daemon=True).start()
        return fut

    def _serve_versioned(self, payload, fut, mult):
        d = self.delay_s() if callable(self.delay_s) else self.delay_s
        if d:
            time.sleep(d)
        table = _decode_payload(payload)
        v = table.column("v").to_numpy(zero_copy_only=False)
        fut.set_result((v * float(mult)).astype(np.float32))


def test_hot_swap_shifts_traffic_and_reports_active_version(monkeypatch):
    monkeypatch.setenv("RDT_SERVE_BATCH_TIMEOUT_MS", "5")
    monkeypatch.setenv("RDT_SERVE_HEDGE", "0")
    monkeypatch.setenv("RDT_SERVE_SWAP_DRAIN_S", "5")
    reps = [VersionedFakeReplica("a"), VersionedFakeReplica("b")]
    srv = ServingSession("/bundles/v1", executors=reps, name="hs")
    try:
        assert np.array_equal(srv.predict(_rows(1.0, 2.0)), [2.0, 4.0])
        rep = srv.serving_report()
        assert rep["servable"] == {"version": 1,
                                   "export_dir": "/bundles/v1",
                                   "tag": None}
        info = srv.hot_swap("/bundles/v2", tag="epoch-9")
        assert info["version"] == 2
        assert info["replicas"] == ["hs-v2-r0", "hs-v2-r1"]
        # every post-swap dispatch answers from v2
        assert np.array_equal(srv.predict(_rows(1.0, 2.0)), [3.0, 6.0])
        rep = srv.serving_report()
        assert rep["servable"] == {"version": 2,
                                   "export_dir": "/bundles/v2",
                                   "tag": "epoch-9"}
        assert rep["hot_swaps"] == 1
        # the old version retires (drained: nothing in flight on it)
        deadline = time.monotonic() + 5
        while time.monotonic() < deadline \
                and not all(h.unloaded for h in reps):
            time.sleep(0.02)
        assert [u for h in reps for u in h.unloaded] \
            == ["hs-r0", "hs-r1"]
    finally:
        srv.close()


def test_hot_swap_racing_predict_burst_zero_dropped(monkeypatch):
    """The ISSUE 15 race leg at unit precision: a predict burst straddles
    two hot-swaps while the outgoing version still holds in-flight work
    (a scripted apply delay) — zero dropped requests, and every response
    is the output of exactly ONE servable version (2v, 3v or 4v — never a
    mix within one request, never a value from no version)."""
    monkeypatch.setenv("RDT_SERVE_BATCH_TIMEOUT_MS", "2")
    monkeypatch.setenv("RDT_SERVE_HEDGE", "0")
    monkeypatch.setenv("RDT_SERVE_SWAP_DRAIN_S", "3")
    reps = [VersionedFakeReplica("a", delay_s=0.01),
            VersionedFakeReplica("b", delay_s=0.01)]
    srv = ServingSession("/bundles/v1", executors=reps, name="race")
    try:
        stop = threading.Event()
        futs, errors = [], []
        # the burst waits for answers before it runs 256 ahead of them: on a
        # loaded host a swap can take longer than RDT_SERVE_MAX_QUEUE
        # (1024) requests at one a millisecond, and shedding is not the
        # race this test is about. An idle host's whole burst is ~150
        # requests (three sleeps of 50 ms), so the bound binds only there
        ahead = threading.Semaphore(256)

        def fire():
            i = 0
            while not stop.is_set():
                if ahead.acquire(timeout=0.01):
                    try:
                        fut = srv.predict_async(_rows(float(i)))
                        fut.add_done_callback(lambda _: ahead.release())
                        futs.append((float(i), fut))
                    except Exception as e:  # noqa: BLE001 - counted
                        ahead.release()
                        errors.append(repr(e))
                    i += 1
                time.sleep(0.001)

        t = threading.Thread(target=fire)
        t.start()
        time.sleep(0.05)
        srv.hot_swap("/bundles/v2", tag="epoch-2")
        time.sleep(0.05)
        srv.hot_swap("/bundles/v3", tag="epoch-4")
        time.sleep(0.05)
        stop.set()
        t.join(timeout=30)
        assert not errors, errors
        assert len(futs) > 20
        versions = set()
        for v, f in futs:
            got = f.result(timeout=30.0)
            assert got.shape == (1,)
            if v == 0.0:
                continue  # 0 is version-blind
            mult = got[0] / v
            # exactly one version answered: the multiplier is one of the
            # three loaded servables', bit-exact
            assert mult in (2.0, 3.0, 4.0), (v, got)
            versions.add(mult)
        assert len(versions) >= 2, "burst never straddled a swap"
        rep = srv.serving_report()
        assert rep["hot_swaps"] == 2
        assert rep["failed"] == 0 and rep["shed"] == 0
        assert rep["servable"]["version"] == 3
        assert rep["servable"]["tag"] == "epoch-4"
    finally:
        srv.close()


def test_hot_swap_drain_waits_for_inflight_then_unloads(monkeypatch):
    """Retirement semantics: the outgoing version's in-flight dispatch
    completes (no drop), and its replicas unload only after the drain."""
    monkeypatch.setenv("RDT_SERVE_BATCH_TIMEOUT_MS", "2")
    monkeypatch.setenv("RDT_SERVE_HEDGE", "0")
    monkeypatch.setenv("RDT_SERVE_SWAP_DRAIN_S", "10")
    slow = VersionedFakeReplica("slow", delay_s=0.3)
    srv = ServingSession("/bundles/v1", executors=[slow], name="drain")
    try:
        f = srv.predict_async(_rows(5.0))   # in flight on v1, 300ms apply
        time.sleep(0.05)
        srv.hot_swap("/bundles/v2")
        assert not slow.unloaded            # v1 still busy: not retired yet
        assert np.array_equal(f.result(timeout=30.0), [10.0])  # v1 answered
        deadline = time.monotonic() + 5
        while time.monotonic() < deadline and not slow.unloaded:
            time.sleep(0.02)
        assert slow.unloaded == ["drain-r0"]
        assert np.array_equal(srv.predict(_rows(5.0)), [15.0])  # v2 now
    finally:
        srv.close()


# ---------------------------------------------------------------------------
# guarded rollouts (ISSUE 18): weighted versions, judgment, autoscale
# ---------------------------------------------------------------------------

def _mult_of(got, vals):
    """The single servable multiplier a whole response came from — raises
    if the rows disagree (a response mixing versions is the bug)."""
    mults = {round(float(g) / float(v), 6) for g, v in zip(got, vals) if v}
    assert len(mults) == 1, f"response mixed versions: {mults}"
    return mults.pop()


def test_weighted_routing_splits_deterministically(monkeypatch):
    """Smooth WRR at weights 1.0 : 0.5 gives an exact 2:1 dispatch split —
    no RNG, so the counts are pinned, not statistical."""
    monkeypatch.setenv("RDT_SERVE_HEDGE", "0")
    reps = [VersionedFakeReplica("a"), VersionedFakeReplica("b")]
    srv = ServingSession("/bundles/v1", executors=reps, name="w")
    try:
        srv.load_version("/bundles/v2", weight=0.5, tag="canary")
        counts = {2.0: 0, 3.0: 0}
        for i in range(1, 31):  # sequential: one dispatch per request
            got = srv.predict(_rows(float(i)), timeout=30.0)
            counts[_mult_of(got, [float(i)])] += 1
        assert counts == {2.0: 20, 3.0: 10}, counts
        rep = srv.serving_report()
        rows = {v["version"]: v for v in rep["versions"]}
        assert rows[1]["primary"] and not rows[2]["primary"]
        assert rows[1]["weight"] == 1.0 and rows[2]["weight"] == 0.5
        assert rows[1]["requests"] == 20 and rows[2]["requests"] == 10
        assert rows[2]["tag"] == "canary"
        assert rows[1]["lat_n"] == 20 and rows[2]["lat_n"] == 10
        # primary view (back-compat surfaces) unchanged by a live canary
        assert rep["servable"]["version"] == 1
    finally:
        srv.close()


def test_multi_row_requests_never_split_across_versions(monkeypatch):
    """A coalesced batch (and therefore every response demuxed from it)
    is computed by exactly one version, even at a 50/50 split."""
    monkeypatch.setenv("RDT_SERVE_BATCH_TIMEOUT_MS", "10")
    monkeypatch.setenv("RDT_SERVE_HEDGE", "0")
    reps = [VersionedFakeReplica("a", delay_s=0.005),
            VersionedFakeReplica("b", delay_s=0.005)]
    srv = ServingSession("/bundles/v1", executors=reps, name="nosplit")
    try:
        srv.load_version("/bundles/v2", weight=1.0)
        futs = []
        for i in range(1, 25):
            vals = [float(i), float(i) + 0.25, float(i) + 0.5]
            futs.append((vals, srv.predict_async({"v": np.array(vals)})))
        seen = set()
        for vals, f in futs:
            got = f.result(timeout=30.0)
            seen.add(_mult_of(got, vals))  # raises on any within-row mix
        assert seen == {2.0, 3.0}, seen    # both versions took traffic
        rep = srv.serving_report()
        assert rep["failed"] == 0
    finally:
        srv.close()


def test_weight_zero_parks_version_out_of_new_traffic(monkeypatch):
    monkeypatch.setenv("RDT_SERVE_HEDGE", "0")
    reps = [VersionedFakeReplica("a")]
    srv = ServingSession("/bundles/v1", executors=reps, name="wz")
    try:
        srv.load_version("/bundles/v2", weight=1.0)
        srv.set_weight(2, 0.0)
        for i in range(1, 9):
            got = srv.predict(_rows(float(i)), timeout=30.0)
            assert _mult_of(got, [float(i)]) == 2.0  # primary only
        # still live (not unloaded), just weightless
        assert {v["version"] for v in srv.serving_report()["versions"]} \
            == {1, 2}
        with pytest.raises(ServingError):
            srv.set_weight(99, 0.5)
    finally:
        srv.close()


def test_hedge_requires_sibling_within_version(monkeypatch):
    """Hedges are version-local: two single-replica versions hold two
    replicas total, but neither version has a sibling, so a straggler must
    NOT hedge across versions (a canary answering a baseline request is
    the contamination this pins)."""
    slow_after = {"n": 0}

    def a_delay():
        slow_after["n"] += 1
        return 0.0 if slow_after["n"] <= 10 else 0.4

    rep = VersionedFakeReplica("a", delay_s=a_delay)
    monkeypatch.setenv("RDT_SERVE_MAX_BATCH", "1")
    monkeypatch.setenv("RDT_SERVE_BATCH_TIMEOUT_MS", "0")
    monkeypatch.setenv("RDT_SERVE_HEDGE", "1")
    monkeypatch.setenv("RDT_SERVE_HEDGE_QUANTILE", "0.5")
    monkeypatch.setenv("RDT_SERVE_HEDGE_MULTIPLIER", "2.0")
    monkeypatch.setenv("RDT_SERVE_HEDGE_MIN_MS", "50")
    srv = ServingSession("/bundles/v1", executors=[rep], name="hl")
    try:
        srv.load_version("/bundles/v2", weight=1.0)
        for i in range(1, 11):  # warm the latency window
            srv.predict(_rows(float(i)), timeout=30.0)
        got = srv.predict(_rows(7.0), timeout=30.0)  # the straggler
        assert _mult_of(got, [7.0]) in (2.0, 3.0)
        assert srv.serving_report()["hedged"] == 0
    finally:
        srv.close()


def test_hedged_canary_stays_canary(monkeypatch):
    """With the canary at full weight and a straggling canary replica, the
    hedge races the canary's OWN sibling — the answer keeps the canary's
    multiplier bit-exact."""
    slow_after = {"n": 0}

    def a_delay():
        slow_after["n"] += 1
        return 0.0 if slow_after["n"] <= 12 else 1.0

    reps = [VersionedFakeReplica("a", delay_s=a_delay),
            VersionedFakeReplica("b")]
    monkeypatch.setenv("RDT_SERVE_MAX_BATCH", "1")
    monkeypatch.setenv("RDT_SERVE_BATCH_TIMEOUT_MS", "0")
    monkeypatch.setenv("RDT_SERVE_HEDGE", "1")
    monkeypatch.setenv("RDT_SERVE_HEDGE_QUANTILE", "0.5")
    monkeypatch.setenv("RDT_SERVE_HEDGE_MULTIPLIER", "2.0")
    monkeypatch.setenv("RDT_SERVE_HEDGE_MIN_MS", "50")
    srv = ServingSession("/bundles/v1", executors=reps, name="hc")
    try:
        srv.load_version("/bundles/v2", weight=1.0)
        srv.set_weight(1, 0.0)  # all traffic to the canary
        deadline = time.monotonic() + 20
        while time.monotonic() < deadline:
            got = srv.predict(_rows(3.0), timeout=30.0)
            assert _mult_of(got, [3.0]) == 3.0  # never the baseline's 2.0
            if srv.serving_report()["hedged"] >= 1:
                break
        rep = srv.serving_report()
        assert rep["hedged"] >= 1, "straggler never hedged"
        assert rep["failed"] == 0
    finally:
        srv.close()


def test_promote_version_retires_old_primary(monkeypatch):
    monkeypatch.setenv("RDT_SERVE_HEDGE", "0")
    monkeypatch.setenv("RDT_SERVE_SWAP_DRAIN_S", "5")
    reps = [VersionedFakeReplica("a"), VersionedFakeReplica("b")]
    srv = ServingSession("/bundles/v1", executors=reps, name="pr")
    try:
        srv.load_version("/bundles/v2", weight=0.25, tag="canary")
        info = srv.promote_version(2)
        assert info["retired"] == 1
        rep = srv.serving_report()
        assert rep["servable"] == {"version": 2,
                                   "export_dir": "/bundles/v2",
                                   "tag": "canary"}
        assert rep["hot_swaps"] == 1  # rides the swap counter contract
        assert [v["version"] for v in rep["versions"]] == [2]
        for i in range(1, 6):
            got = srv.predict(_rows(float(i)), timeout=30.0)
            assert _mult_of(got, [float(i)]) == 3.0
        deadline = time.monotonic() + 5
        while time.monotonic() < deadline \
                and not all(h.unloaded for h in reps):
            time.sleep(0.02)
        assert [u for h in reps for u in h.unloaded] == ["pr-r0", "pr-r1"]
    finally:
        srv.close()


def test_drop_version_unloads_canary_and_rehomes_nothing(monkeypatch):
    monkeypatch.setenv("RDT_SERVE_HEDGE", "0")
    monkeypatch.setenv("RDT_SERVE_SWAP_DRAIN_S", "5")
    reps = [VersionedFakeReplica("a")]
    srv = ServingSession("/bundles/v1", executors=reps, name="dr")
    try:
        srv.load_version("/bundles/v2", weight=0.5)
        with pytest.raises(ServingError):
            srv.drop_version(1)  # the primary is not droppable
        srv.drop_version(2)
        for i in range(1, 7):
            got = srv.predict(_rows(float(i)), timeout=30.0)
            assert _mult_of(got, [float(i)]) == 2.0  # primary serves on
        deadline = time.monotonic() + 5
        while time.monotonic() < deadline and not reps[0].unloaded:
            time.sleep(0.02)
        assert reps[0].unloaded == ["dr-v2-r0"]
        assert [v["version"]
                for v in srv.serving_report()["versions"]] == [1]
    finally:
        srv.close()


class FailingVersionReplica(VersionedFakeReplica):
    """Replica ids matching ``fail_substr`` answer with the chaos plane's
    transient InjectedFault (re-routable) — every replica of that version
    refuses, so its dispatches exhaust the version-local re-route and
    fail, exactly the error-rate shape a regressing canary produces."""

    def __init__(self, name, fail_substr):
        super().__init__(name)
        self.fail_substr = fail_substr

    def submit(self, method, *args, **kwargs):
        if method == "serve_predict" and self.fail_substr in args[0]:
            with self._lock:
                self.calls += 1
            fut = Future()

            def _fail():
                time.sleep(0.005)
                fut.set_exception(
                    RemoteError("InjectedFault", "scripted canary fault",
                                "<tb>"))

            threading.Thread(target=_fail, daemon=True).start()
            return fut
        return super().submit(method, *args, **kwargs)


def _traffic(srv, stop, errors, period_s=0.004):
    """Open-loop background load for rollout tests; ServingError is the
    expected casualty of a scripted-to-fail canary, anything else isn't."""
    i = 0
    while not stop.is_set():
        try:
            srv.predict_async(_rows(float(i % 50 + 1)))
        except ServingError:
            pass
        except Exception as e:  # noqa: BLE001 - surfaced by the test
            errors.append(repr(e))
        i += 1
        time.sleep(period_s)


def test_rollout_promotes_healthy_canary_under_traffic(monkeypatch):
    monkeypatch.setenv("RDT_SERVE_BATCH_TIMEOUT_MS", "2")
    monkeypatch.setenv("RDT_SERVE_HEDGE", "0")
    monkeypatch.setenv("RDT_SERVE_SWAP_DRAIN_S", "3")
    reps = [VersionedFakeReplica("a"), VersionedFakeReplica("b")]
    srv = ServingSession("/bundles/v1", executors=reps, name="ro")
    stop, errors = threading.Event(), []
    t = threading.Thread(target=_traffic, args=(srv, stop, errors))
    t.start()
    try:
        out = srv.rollout("/bundles/v2", tag="epoch-1",
                          initial_weight=0.5, steps=[1.0], step_s=5.0,
                          min_samples=8)
        assert out["outcome"] == "promoted", out
        assert out["version"] == 2
        assert any(s["verdict"] == "healthy" for s in out["steps"])
        rep = srv.serving_report()
        assert rep["servable"]["version"] == 2
        assert rep["servable"]["tag"] == "epoch-1"
        assert rep["hot_swaps"] == 1
    finally:
        stop.set()
        t.join(timeout=30)
        srv.close()
    assert not errors, errors


def test_rollout_rolls_back_on_canary_error_rate(monkeypatch):
    """The canary's replicas fail every dispatch (transient InjectedFault:
    re-routed version-locally, exhausted, counted per-version) — the
    judgment sees its error rate, rolls back, and the baseline keeps
    serving untouched; run() RETURNS the outcome rather than raising."""
    monkeypatch.setenv("RDT_SERVE_BATCH_TIMEOUT_MS", "2")
    monkeypatch.setenv("RDT_SERVE_HEDGE", "0")
    monkeypatch.setenv("RDT_SERVE_SWAP_DRAIN_S", "3")
    monkeypatch.setenv("RDT_SERVE_REROUTE_GRACE_S", "0.4")
    reps = [FailingVersionReplica("a", "-v2-"),
            FailingVersionReplica("b", "-v2-")]
    srv = ServingSession("/bundles/v1", executors=reps, name="rb")
    stop, errors = threading.Event(), []
    t = threading.Thread(target=_traffic, args=(srv, stop, errors))
    t.start()
    try:
        out = srv.rollout("/bundles/v2", initial_weight=0.5,
                          steps=[1.0], step_s=15.0, min_samples=6,
                          err_tol=0.05)
        assert out["outcome"] == "rolled_back", out
        assert "error rate" in out["reason"]
        rep = srv.serving_report()
        assert rep["servable"]["version"] == 1   # baseline untouched
        assert [v["version"] for v in rep["versions"]] == [1]
        assert rep["hot_swaps"] == 0
        deadline = time.monotonic() + 5
        want = {"rb-v2-r0", "rb-v2-r1"}
        while time.monotonic() < deadline:
            got = {u for h in reps for u in h.unloaded}
            if want <= got:
                break
            time.sleep(0.02)
        assert want <= {u for h in reps for u in h.unloaded}
        from raydp_tpu import metrics
        assert any(e["kind"] == "rollout_rollback"
                   for e in metrics.events())
    finally:
        stop.set()
        t.join(timeout=30)
        srv.close()
    assert not errors, errors
    # post-rollback: the baseline still answers bit-correct
    # (session closed above, so assert on the collected report instead)
    assert rep["versions"][0]["failed"] == 0


def test_rollout_advances_without_traffic(monkeypatch):
    """An idle session must still deploy: a step whose judgment window
    never fills advances vacuously (insufficient traffic is no evidence
    of regression)."""
    monkeypatch.setenv("RDT_SERVE_HEDGE", "0")
    monkeypatch.setenv("RDT_SERVE_SWAP_DRAIN_S", "3")
    reps = [VersionedFakeReplica("a")]
    srv = ServingSession("/bundles/v1", executors=reps, name="idle")
    try:
        out = srv.rollout("/bundles/v2", initial_weight=0.25,
                          steps=[1.0], step_s=0.15, min_samples=1000)
        assert out["outcome"] == "promoted", out
        assert all(s["verdict"] == "insufficient" for s in out["steps"])
        assert srv.serving_report()["servable"]["version"] == 2
    finally:
        srv.close()


def test_rollout_judgment_suspended_while_shedding():
    """The false-positive the design must not have: identical (terrible)
    canary numbers are 'unhealthy' under normal load but 'suspended' while
    the shedding gate is active — saturation inflates both versions, so no
    verdict is allowed."""
    from raydp_tpu.serve.rollout import RolloutController

    ctl = RolloutController.__new__(RolloutController)
    ctl.min_samples = 4
    ctl.err_tol = 0.02
    ctl.p99_factor = 2.0
    base0 = {"requests": 0, "failed": 0, "p99_ms": 5.0, "lat_n": 50}
    can0 = {"requests": 0, "failed": 0, "p99_ms": 50.0, "lat_n": 50}
    base1 = {"requests": 100, "failed": 0, "p99_ms": 5.0, "lat_n": 50}
    can1 = {"requests": 2, "failed": 20, "p99_ms": 50.0, "lat_n": 50}
    assert ctl._judge(base0, can0, base1, can1,
                      shedding=False)["verdict"] == "unhealthy"
    assert ctl._judge(base0, can0, base1, can1,
                      shedding=True)["verdict"] == "suspended"
    # and the latency arm alone also kills it once windows are full
    can_lat = {"requests": 100, "failed": 0, "p99_ms": 50.0, "lat_n": 50}
    v = ctl._judge(base0, can0, base1, can_lat, shedding=False)
    assert v["verdict"] == "unhealthy" and "p99" in v["reason"]
    # below the min-sample floor: no verdict either way
    tiny = {"requests": 2, "failed": 1, "p99_ms": 50.0, "lat_n": 2}
    assert ctl._judge(base0, can0, base1, tiny,
                      shedding=False)["verdict"] == "insufficient"


def test_scale_replicas_grows_and_shrinks_every_version(monkeypatch):
    monkeypatch.setenv("RDT_SERVE_HEDGE", "0")
    monkeypatch.setenv("RDT_SERVE_SWAP_DRAIN_S", "2")
    reps = [VersionedFakeReplica("a"), VersionedFakeReplica("b")]
    srv = ServingSession("/bundles/v1", executors=reps, name="sc")
    try:
        srv.load_version("/bundles/v2", weight=0.5)
        out = srv.scale_replicas(3)
        assert out["replicas"] == 3
        rep = srv.serving_report()
        assert all(v["replicas"] == 3 for v in rep["versions"]), rep
        rids = {r["replica"] for r in rep["replicas"]}
        assert {"sc-v1-r2", "sc-v2-r2"} <= rids  # scale-up id namespace
        for i in range(1, 13):  # the grown fleet serves, both versions
            got = srv.predict(_rows(float(i)), timeout=30.0)
            assert _mult_of(got, [float(i)]) in (2.0, 3.0)
        srv.scale_replicas(1)
        rep = srv.serving_report()
        assert all(v["replicas"] == 1 for v in rep["versions"]), rep
        deadline = time.monotonic() + 5  # drained victims unload
        while time.monotonic() < deadline \
                and sum(len(h.unloaded) for h in reps) < 4:
            time.sleep(0.02)
        assert sum(len(h.unloaded) for h in reps) == 4
        assert srv.predict(_rows(2.0), timeout=30.0).shape == (1,)
    finally:
        srv.close()


def test_serving_autoscaler_grows_on_pressure_then_drains(monkeypatch):
    """The PR 13 controller shape on serving signals: sustained queue
    pressure grows every version's replica count before the shed bound,
    sustained idleness drains back to the floor, cooldown between."""
    from raydp_tpu.serve import ServingAutoscaler

    monkeypatch.setenv("RDT_SERVE_MAX_BATCH", "1")
    monkeypatch.setenv("RDT_SERVE_BATCH_TIMEOUT_MS", "0")
    monkeypatch.setenv("RDT_SERVE_HEDGE", "0")
    monkeypatch.setenv("RDT_SERVE_MAX_INFLIGHT", "1")
    monkeypatch.setenv("RDT_SERVE_SCALE_INTERVAL_S", "0.05")
    monkeypatch.setenv("RDT_SERVE_SCALE_UP_S", "0.1")
    monkeypatch.setenv("RDT_SERVE_SCALE_IDLE_S", "0.4")
    monkeypatch.setenv("RDT_SERVE_SCALE_COOLDOWN_S", "0.1")
    monkeypatch.setenv("RDT_SERVE_SWAP_DRAIN_S", "2")

    class SerialVersionedReplica(VersionedFakeReplica):
        """A real replica serves its loop serially — the fake must too, or
        a 60-dispatch burst drains in one delay and no pressure sustains."""

        _serial = threading.Lock()

        def _serve_versioned(self, payload, fut, mult):
            with self._serial:
                super()._serve_versioned(payload, fut, mult)

    rep = SerialVersionedReplica("a", delay_s=0.03)
    srv = ServingSession("/bundles/v1", executors=[rep], name="as")
    scaler = ServingAutoscaler(srv, min_replicas=1, max_replicas=3).start()
    try:
        futs = [srv.predict_async(_rows(float(i + 1))) for i in range(60)]
        deadline = time.monotonic() + 20
        while time.monotonic() < deadline:
            if any(e["direction"] == "up" for e in scaler.events):
                break
            time.sleep(0.02)
        assert any(e["direction"] == "up" for e in scaler.events), \
            scaler.events
        for i, f in enumerate(futs):  # burst fully served, bit-correct
            assert f.result(timeout=30.0)[0] == np.float32(2.0 * (i + 1))
        deadline = time.monotonic() + 20  # idle: drain back to the floor
        while time.monotonic() < deadline:
            vrow = srv.serving_report()["versions"][0]
            if vrow["replicas"] == 1:
                break
            time.sleep(0.05)
        assert srv.serving_report()["versions"][0]["replicas"] == 1, \
            scaler.events
        assert any(e["direction"] == "down" for e in scaler.events)
    finally:
        scaler.stop()
        srv.close()


def test_hot_swap_racing_overload_shed(monkeypatch):
    """ISSUE 18 satellite: a swap during a saturated burst. Accepted
    requests all complete from exactly one version, sheds stay typed
    (failed == shed), and the outgoing version's replicas unload within
    the drain bound — no replica leak behind the shed wall."""
    monkeypatch.setenv("RDT_SERVE_MAX_QUEUE", "6")
    monkeypatch.setenv("RDT_SERVE_MAX_BATCH", "1")
    monkeypatch.setenv("RDT_SERVE_BATCH_TIMEOUT_MS", "0")
    monkeypatch.setenv("RDT_SERVE_HEDGE", "0")
    monkeypatch.setenv("RDT_SERVE_MAX_INFLIGHT", "1")
    monkeypatch.setenv("RDT_SERVE_SWAP_DRAIN_S", "2")
    from raydp_tpu.serve import ServingOverloaded

    reps = [VersionedFakeReplica("a", delay_s=0.02)]
    srv = ServingSession("/bundles/v1", executors=reps, name="swsh")
    try:
        accepted, sheds, errors = [], [0], []
        stop = threading.Event()

        def flood():
            i = 0
            while not stop.is_set():
                try:
                    accepted.append((float(i % 40 + 1), srv.predict_async(
                        _rows(float(i % 40 + 1)))))
                except ServingOverloaded:
                    sheds[0] += 1
                except Exception as e:  # noqa: BLE001 - counted
                    errors.append(repr(e))
                i += 1
                time.sleep(0.001)

        t = threading.Thread(target=flood)
        t.start()
        time.sleep(0.1)
        srv.hot_swap("/bundles/v2", tag="mid-burst")  # racing saturation
        time.sleep(0.1)
        stop.set()
        t.join(timeout=30)
        assert not errors, errors
        assert sheds[0] >= 1, "burst never saturated the queue"
        for v, f in accepted:  # zero dropped accepted requests
            got = f.result(timeout=30.0)
            assert _mult_of(got, [v]) in (2.0, 3.0)
        deadline = time.monotonic() + 8  # v1 must not leak past the drain
        while time.monotonic() < deadline \
                and "swsh-r0" not in reps[0].unloaded:
            time.sleep(0.02)
        assert "swsh-r0" in reps[0].unloaded
        rep = srv.serving_report()
        assert rep["failed"] == rep["shed"] >= 1
        assert rep["servable"]["version"] == 2
        assert rep["retiring_replicas"] == 0
    finally:
        srv.close()


class RestartingUnloadReplica(VersionedFakeReplica):
    """serve_unload refuses (ConnectionLost) for the first ``refuse`` calls
    per rid — the executor-mid-restart shape the retry path exists for."""

    def __init__(self, name, refuse=2):
        super().__init__(name)
        self.refuse = refuse
        self.unload_attempts: dict = {}

    def call(self, method, *args, timeout=None, **kwargs):
        if method == "serve_unload":
            rid = args[0]
            with self._lock:
                n = self.unload_attempts.get(rid, 0) + 1
                self.unload_attempts[rid] = n
            if n <= self.refuse:
                raise ConnectionLost(f"{self.name} restarting")
        return super().call(method, *args, timeout=timeout, **kwargs)


def test_retired_unload_retries_through_restart(monkeypatch):
    """ISSUE 18 satellite: retirement unloads RETRY through the probe
    path — an executor that refuses twice mid-restart still gets its
    registry entry dropped, with no unload_failed leak recorded."""
    from raydp_tpu import metrics
    monkeypatch.setenv("RDT_SERVE_HEDGE", "0")
    monkeypatch.setenv("RDT_SERVE_SWAP_DRAIN_S", "1")
    rep = RestartingUnloadReplica("a", refuse=2)
    srv = ServingSession("/bundles/v1", executors=[rep], name="ur")
    try:
        base_failed = metrics.snapshot()["counters"].get(
            "serve_unload_failed_total", {}).get("", 0)
        srv.predict(_rows(1.0), timeout=30.0)
        srv.hot_swap("/bundles/v2")
        deadline = time.monotonic() + 10
        while time.monotonic() < deadline and "ur-r0" not in rep.unloaded:
            time.sleep(0.05)
        assert "ur-r0" in rep.unloaded          # landed on the 3rd attempt
        assert rep.unload_attempts["ur-r0"] == 3
        now_failed = metrics.snapshot()["counters"].get(
            "serve_unload_failed_total", {}).get("", 0)
        assert now_failed == base_failed        # retried ≠ leaked
    finally:
        srv.close()


def test_unload_exhaustion_counts_loudly(monkeypatch):
    """A replica that refuses unload through the whole window is a LOUD
    leak: counter + unload_failed event, never silence."""
    from raydp_tpu import metrics
    monkeypatch.setenv("RDT_SERVE_HEDGE", "0")
    monkeypatch.setenv("RDT_SERVE_SWAP_DRAIN_S", "0.5")
    monkeypatch.setenv("RDT_SERVE_REROUTE_GRACE_S", "1")
    rep = RestartingUnloadReplica("a", refuse=10_000)
    srv = ServingSession("/bundles/v1", executors=[rep], name="ulk")
    try:
        base = metrics.snapshot()["counters"].get(
            "serve_unload_failed_total", {}).get("", 0)
        srv.hot_swap("/bundles/v2")
        deadline = time.monotonic() + 10
        while time.monotonic() < deadline:
            now = metrics.snapshot()["counters"].get(
                "serve_unload_failed_total", {}).get("", 0)
            if now > base:
                break
            time.sleep(0.05)
        assert now == base + 1
        ev = [e for e in metrics.events() if e["kind"] == "unload_failed"]
        assert ev and ev[-1]["replica"] == "ulk-r0"
    finally:
        srv.close()
