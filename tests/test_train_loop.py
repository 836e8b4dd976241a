"""The one epoch loop and the one feed plan (``raydp_tpu/train/loop.py``),
held once for both estimators: each test of a fit runs a small MLP through
``FlaxEstimator`` and through ``KerasEstimator`` on the CPU mesh, and the feed
plan is tested as the function it is."""

import glob
import os

import numpy as np
import pandas as pd
import pytest

os.environ.setdefault("KERAS_BACKEND", "jax")

from raydp_tpu import faults, metrics, profiler  # noqa: E402

KINDS = ["flax", "keras"]
#: the keys of a report that are the loop's own, whichever estimator
TIMING_KEYS = {"epoch", "steps", "samples_per_s", "epoch_time_s",
               "feed_time_s", "decode_time_s", "h2d_time_s",
               "dispatch_time_s", "sync_time_s", "lead_time_s",
               "first_pull_time_s"}
LOSS_KEY = {"flax": "train_loss", "keras": "loss"}


def _estimator(kind, num_epochs, **kw):
    common = dict(feature_columns=["x1", "x2"], label_column="y",
                  batch_size=64, num_epochs=num_epochs, seed=0, **kw)
    if kind == "flax":
        import optax

        from raydp_tpu.models import MLP
        from raydp_tpu.train import FlaxEstimator
        return FlaxEstimator(model=MLP(features=(8,), use_batch_norm=False),
                             optimizer=optax.adam(1e-2), loss="mse", **common)
    import keras

    from raydp_tpu.train import KerasEstimator
    model = keras.Sequential([keras.layers.Input(shape=(2,)),
                              keras.layers.Dense(8, activation="relu"),
                              keras.layers.Dense(1)])
    return KerasEstimator(model=model, optimizer="adam", loss="mse", **common)


def _frame(session, n=512, seed=0):
    x = np.random.RandomState(seed).random_sample((n, 2)).astype(np.float32)
    pdf = pd.DataFrame({"x1": x[:, 0], "x2": x[:, 1],
                        "y": x @ np.array([2.0, -3.0], np.float32) + 1.0})
    return session.createDataFrame(pdf, num_partitions=2)


@pytest.fixture
def streaming(monkeypatch):
    monkeypatch.setenv("RDT_DEVICE_CACHE", "0")


@pytest.fixture
def epoch_fault():
    """``arm(epoch)``: the ``estimator.epoch`` site raises once, at that
    epoch's start."""
    faults.clear()
    yield lambda epoch: faults.inject("estimator.epoch", "raise",
                                      match=str(epoch), times=1)
    faults.clear()


# ------------------------------------------------------------ spans and keys
@pytest.mark.parametrize("kind", KINDS)
def test_a_fits_epochs_are_spans_with_their_steps(shared_session, streaming,
                                                  kind):
    profiler.clear()
    _estimator(kind, 2).fit_on_frame(_frame(shared_session))
    ring = profiler.spans()
    epochs = [s for s in ring if s["name"] == "train:epoch"]
    assert [s["args"] for s in epochs] == [
        {"epoch": "0", "steps": "8"}, {"epoch": "1", "steps": "8"}]
    # the plan says which way the batches come, the step's first call sits
    # in epoch 0, and no step span ever enters the ring
    feeds = [s["args"] for s in ring if s["name"] == "fit:feed"]
    assert feeds == [{"route": "stream"}, {"what": "first_batch"}]
    (first,) = [s for s in ring if s["name"] == "train:first_dispatch"]
    assert first["par"] == epochs[0]["sid"]
    assert not {s["name"] for s in ring} & metrics.STEP_SPAN_NAMES


@pytest.mark.parametrize("kind", KINDS)
def test_an_epochs_span_holds_its_pulls_and_dispatches(shared_session,
                                                       streaming, tmp_path,
                                                       kind):
    """In a device trace (the step spans exist nowhere else): on the loop's
    line, every ``train:dispatch`` and ``train:feed_wait`` inside the one
    ``train:epoch``, a pull more than the steps, the epoch's end after the
    last."""
    from jax.profiler import ProfileData

    est = _estimator(kind, 1)
    df = _frame(shared_session)
    with profiler.jax_trace(str(tmp_path)) as log_dir:
        history = est.fit_on_frame(df).history
    (path,) = glob.glob(os.path.join(log_dir, "plugins", "profile", "*",
                                     "*.xplane.pb"))
    lines = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            found = {}
            for e in line.events:
                if e.name in metrics.SPAN_NAMES:
                    found.setdefault(e.name, []).append(
                        (e.start_ns, e.start_ns + e.duration_ns))
            if "train:dispatch" in found:
                lines.append(found)
    (found,) = lines
    (epoch,) = found["train:epoch"]
    steps = history[0]["steps"]
    assert steps == 8 and len(found["train:dispatch"]) == steps
    assert len(found["train:feed_wait"]) == steps + 1
    for name in ("train:dispatch", "train:feed_wait", "train:epoch_end",
                 "train:loss_fetch", "train:report"):
        assert all(epoch[0] <= a and b <= epoch[1] for a, b in found[name])
    (end,) = found["train:epoch_end"]
    assert max(b for _, b in found["train:dispatch"]) <= end[0]
    assert len(found["train:epoch_turn"]) == 2


@pytest.mark.parametrize("route", ["stream", "resident"])
@pytest.mark.parametrize("kind", KINDS)
def test_a_report_carries_the_loops_timing_keys(shared_session, monkeypatch,
                                                kind, route):
    monkeypatch.setenv("RDT_DEVICE_CACHE", "0" if route == "stream" else "1")
    df = _frame(shared_session)
    history = _estimator(kind, 2).fit_on_frame(df, df).history
    assert [h["epoch"] for h in history] == [0, 1]
    eval_key = {"flax": "eval_loss", "keras": "val_loss"}[kind]
    for h in history:
        assert TIMING_KEYS | {LOSS_KEY[kind], eval_key} <= set(h)
        assert h["steps"] == 8 and np.isfinite(h[LOSS_KEY[kind]])
        assert np.isfinite(h[eval_key])
        assert h["lead_time_s"] >= h["first_pull_time_s"] >= 0
        assert h["lead_time_s"] > 0 and h["dispatch_time_s"] > 0
        assert (h["first_pull_time_s"] > 0) == (route == "stream")
        assert h["first_pull_time_s"] <= h["feed_time_s"]
    assert history[1][LOSS_KEY[kind]] < history[0][LOSS_KEY[kind]]


class _Lowerings:
    """jax's lowerings by the function lowered, as a listener hears them."""

    EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"

    def __init__(self):
        self.funs = []

    def __call__(self, event, start, end, fun_name="", **kw):
        if event == self.EVENT:
            self.funs.append(fun_name)


@pytest.mark.parametrize("kind", KINDS)
def test_the_step_is_lowered_once_a_fit(shared_session, streaming, kind):
    """Every epoch starts from accumulators typed and placed as the step
    returns them (the estimator's zeros program), on the eight-device mesh
    too: three epochs, one build."""
    import jax.monitoring as mon
    heard = _Lowerings()
    mon.register_event_time_span_listener(heard)
    try:
        history = _estimator(kind, 3).fit_on_frame(
            _frame(shared_session)).history
    finally:
        mon.unregister_event_time_span_listener(heard)
    assert len(history) == 3
    assert sum("train_step" in f for f in heard.funs) == 1, heard.funs


# ------------------------------------------------------------------ the retry
@pytest.mark.parametrize("kind", KINDS)
def test_an_epoch_fault_is_retried_and_the_history_is_whole(
        shared_session, streaming, tmp_path, epoch_fault, kind):
    """Epoch 1 dies at the ``estimator.epoch`` site; the retry adopts this
    run's own save of epoch 0 and the fit ends where an unbroken one does."""
    df = _frame(shared_session)

    def fit(name, **kw):
        return _estimator(kind, 3, checkpoint_dir=str(tmp_path / name)
                          ).fit_on_frame(df, **kw).history

    clean = fit("clean")
    rule = epoch_fault(1)
    with pytest.raises(RuntimeError):
        fit("unretried")
    assert rule.fires == 1
    rule = epoch_fault(1)
    retried = fit("retried", max_retries=1)
    assert rule.fires == 1, "the fault site never fired"
    assert [h["epoch"] for h in retried] == [0, 1, 2]
    np.testing.assert_allclose([h[LOSS_KEY[kind]] for h in retried],
                               [h[LOSS_KEY[kind]] for h in clean], rtol=1e-5)


@pytest.mark.parametrize("kind", KINDS)
def test_a_retry_never_adopts_an_earlier_runs_checkpoint(
        shared_session, streaming, tmp_path, epoch_fault, kind):
    """A reused ``checkpoint_dir`` holds run A's checkpoint of epoch 3 (for
    Flax a HIGHER-numbered step than any this run writes, for Keras a
    ``model.keras`` + ``state.json``): a retry before this run's first save
    starts afresh, and one after it adopts this run's own save."""
    df = _frame(shared_session)
    ck = str(tmp_path / "ck")
    _estimator(kind, 4, checkpoint_dir=ck).fit_on_frame(df)
    held = set(os.listdir(ck))
    assert {"flax": "step_3", "keras": "state.json"}[kind] in held

    rule = epoch_fault(1)
    history = _estimator(kind, 2, checkpoint_dir=ck, checkpoint_interval=10
                         ).fit_on_frame(df, max_retries=1).history
    assert rule.fires == 1
    # adopted, run A's four epochs would come back
    assert [h["epoch"] for h in history] == [0, 1]

    rule = epoch_fault(1)
    history = _estimator(kind, 2, checkpoint_dir=ck
                         ).fit_on_frame(df, max_retries=1).history
    assert rule.fires == 1
    assert [h["epoch"] for h in history] == [0, 1]


@pytest.mark.parametrize("kind", KINDS)
def test_a_retry_before_the_first_save_starts_afresh(
        shared_session, streaming, epoch_fault, kind):
    """Nothing of this run's to restore, and the failed carry's buffers are
    donated away: the state is rebuilt as a fit builds it, from the seed."""
    df = _frame(shared_session)
    clean = _estimator(kind, 2, checkpoint_interval=10).fit_on_frame(
        df).history
    rule = epoch_fault(1)
    retried = _estimator(kind, 2, checkpoint_interval=10).fit_on_frame(
        df, max_retries=1).history
    assert rule.fires == 1
    assert [h["epoch"] for h in retried] == [0, 1]
    np.testing.assert_allclose([h[LOSS_KEY[kind]] for h in retried],
                               [h[LOSS_KEY[kind]] for h in clean], rtol=1e-5)


@pytest.mark.parametrize("resume,last_written,asked", [
    (True, None, [None]), (True, 2, [None]), (False, 2, [2]),
    (False, 0, [0]), (False, None, [])])
def test_the_rule_on_which_checkpoint_a_retry_may_adopt(resume, last_written,
                                                        asked):
    from raydp_tpu.train import loop
    calls = []

    def restore(carry, max_step):
        calls.append(max_step)
        return carry, 7, None

    got = loop._restore_for_retry(restore, "carry", resume, last_written)
    assert calls == asked
    assert got == (("carry", 7, None) if asked else None)


# -------------------------------------------------------------- the feed plan
COLUMNS = {"features": (["x1", "x2"], np.float32), "label": ("y", np.float32)}


def _plan(train_ds, eval_ds=None, devices=1, may_pad=True, batch_size=64):
    import jax

    from raydp_tpu.parallel import make_mesh
    from raydp_tpu.train import loop
    mesh = make_mesh(devices=jax.devices()[:devices])
    return loop.plan_feeds(train_ds, eval_ds, COLUMNS, mesh, batch_size,
                           shuffle=True, seed=0, drop_last=True,
                           prefetch_to_device=None, may_pad=may_pad, seq=False)


def _datasets(session, rows=(512, 100)):
    from raydp_tpu.data import from_frame
    return [from_frame(_frame(session, n, seed=i))
            for i, n in enumerate(rows)]


#: 512 rows of three f32 columns are 6,144 bytes, the eval set's 100 are 1,200
@pytest.mark.parametrize("env,train,evaluate", [
    ({}, "resident", "resident"),
    ({"RDT_DEVICE_CACHE": "0"}, "stream", "stream"),
    # the train set fits the cap alone, not together with the eval set
    ({"RDT_DEVICE_CACHE_MB": str(7000 / (1 << 20))}, "resident", "stream"),
    ({"RDT_DEVICE_CACHE_MB": str(6000 / (1 << 20))}, "stream", "stream"),
])
def test_the_plan_goes_resident_where_the_budget_allows(
        shared_session, monkeypatch, env, train, evaluate):
    for name, value in env.items():
        monkeypatch.setenv(name, value)
    train_ds, eval_ds = _datasets(shared_session)
    profiler.clear()
    plan = _plan(train_ds, eval_ds)
    assert (plan.cache is not None, plan.feed is None) == \
        (train == "resident",) * 2
    assert (plan.eval_cache is not None, plan.eval_feed is None) == \
        (evaluate == "resident",) * 2
    (span,) = profiler.spans()
    assert span["name"] == "fit:feed" and span["args"] == {"route": train}
    # and without an eval set there is neither
    plan = _plan(train_ds)
    assert plan.eval_cache is None and plan.eval_feed is None
    assert not plan.eval_tail_ok and plan.eval_tail(64) is None


@pytest.mark.parametrize("devices,may_pad,ok,pad", [
    (1, True, True, False), (1, False, True, False),
    (2, True, True, True), (2, False, False, False)])
@pytest.mark.parametrize("route", ["resident", "stream"])
def test_the_plans_rule_for_the_ragged_eval_tail(
        shared_session, monkeypatch, route, devices, may_pad, ok, pad):
    """100 eval rows in batches of 64: the 36 travel as they are on one data
    shard, padded and masked over two where the step can mask, and are
    dropped where it cannot."""
    from raydp_tpu.data.feed import MASK_KEY
    monkeypatch.setenv("RDT_DEVICE_CACHE", "0" if route == "stream" else "1")
    plan = _plan(*_datasets(shared_session), devices=devices,
                 may_pad=may_pad)
    assert (plan.eval_tail_ok, plan.eval_tail_pad) == (ok, pad)
    if route == "stream":
        host = plan.eval_feed.host_iter
        assert (host.drop_remainder, host.pad_remainder) == (not ok, pad)
        assert plan.eval_tail(64) is None
        # the train feed drops its tail (drop_last) whatever the rule
        assert plan.feed.host_iter.drop_remainder
        assert not plan.feed.host_iter.pad_remainder
        return
    tail = plan.eval_tail(64)
    if not ok:
        assert tail is None
    elif pad:
        assert tail["features"].shape == (64, 2)
        np.testing.assert_array_equal(
            np.asarray(tail[MASK_KEY]), np.arange(64) < 36)
        assert not np.asarray(tail["features"])[36:].any()
    else:
        assert tail["features"].shape == (36, 2) and MASK_KEY not in tail
