"""Estimator tests (parity: reference test_torch.py — synthetic linear data,
object-store vs parquet conversion paths, shape-only model assertions)."""

import numpy as np
import pandas as pd
import pytest

from raydp_tpu.etl.expressions import col
from raydp_tpu.models import MLP
from raydp_tpu.train import FlaxEstimator


def _linear_df(session, n=2048):
    rng = np.random.RandomState(0)
    x = rng.random_sample((n, 2)).astype(np.float64)
    y = x @ np.array([2.0, -3.0]) + 1.0 + rng.normal(0, 0.01, n)
    pdf = pd.DataFrame({"x1": x[:, 0], "x2": x[:, 1], "y": y})
    return session.createDataFrame(pdf, num_partitions=4)


@pytest.mark.parametrize("use_fs_directory", [False, True])
def test_estimator_fit_on_frame(shared_session, tmp_path, use_fs_directory):
    import optax

    df = _linear_df(shared_session)
    train_df, test_df = df.randomSplit([0.75, 0.25], seed=1)
    est = FlaxEstimator(
        model=MLP(features=(16,), use_batch_norm=False),
        optimizer=optax.adam(1e-2),
        loss="mse",
        feature_columns=["x1", "x2"],
        label_column="y",
        batch_size=64,
        num_epochs=3,
        metrics=["mae", "mse"],
    )
    kwargs = {"fs_directory": str(tmp_path / "spill")} if use_fs_directory else {}
    result = est.fit_on_frame(train_df, test_df, **kwargs)
    assert len(result.history) == 3
    last = result.history[-1]
    assert last["train_loss"] < result.history[0]["train_loss"]
    assert "eval_mae" in last and "train_mse" in last

    model = est.get_model()
    kernel = model["params"]["Dense_0"]["kernel"]
    assert kernel.shape == (2, 16)


def test_estimator_predict(shared_session):
    """predict() runs the trained model over a dataset's feature columns,
    covers the full row count (ragged final batch included), and matches a
    manual model.apply on the same rows."""
    import jax
    import optax

    from raydp_tpu.data.dataset import from_frame

    # 1000 % 64 != 0: exercises the tail
    df = _linear_df(shared_session, n=1000)
    est = FlaxEstimator(
        model=MLP(features=(16,), use_batch_norm=False),
        optimizer=optax.adam(1e-2),
        loss="mse",
        feature_columns=["x1", "x2"],
        label_column="y",
        batch_size=64,
        num_epochs=2,
    )
    ds = from_frame(df)
    est.fit(ds)

    preds = est.predict(ds)
    assert preds.shape == (1000,)
    assert np.isfinite(preds).all()

    table = ds.to_arrow()
    x = np.stack([table.column("x1").to_numpy(),
                  table.column("x2").to_numpy()], axis=1).astype(np.float32)
    manual = MLP(features=(16,), use_batch_norm=False).apply(
        {"params": jax.tree.map(np.asarray, est.get_model()["params"])}, x)
    np.testing.assert_allclose(preds, np.asarray(manual).squeeze(-1),
                               rtol=1e-5, atol=1e-6)
    # rough sanity: a fitted linear model correlates with the labels
    y = table.column("y").to_numpy()
    assert np.corrcoef(preds, y)[0, 1] > 0.5


def test_estimator_batchnorm_model(shared_session):
    import optax

    from raydp_tpu.models import NYCTaxiModel

    df = _linear_df(shared_session, n=1024)
    est = FlaxEstimator(
        model=NYCTaxiModel(),
        optimizer=optax.adam(1e-3),
        loss="smooth_l1",
        feature_columns=["x1", "x2"],
        label_column="y",
        batch_size=128,
        num_epochs=2,
    )
    result = est.fit_on_frame(df)
    assert len(result.history) == 2
    model = est.get_model()
    assert "batch_stats" in model


def test_estimator_creators_and_retry(shared_session):
    """Creator callables (parity torch/estimator.py:177-220) + checkpoint resume."""
    import optax

    df = _linear_df(shared_session, n=512)
    est = FlaxEstimator(
        model_creator=lambda: MLP(features=(8,), use_batch_norm=False),
        optimizer_creator=lambda: optax.sgd(1e-2),
        loss="mse",
        feature_columns=["x1", "x2"],
        label_column="y",
        batch_size=64,
        num_epochs=2,
    )
    result = est.fit_on_frame(df, max_retries=1)
    assert len(result.history) == 2
    assert result.checkpoint_dir is not None
    import os
    assert any(d.startswith("step_") for d in os.listdir(result.checkpoint_dir))


def test_estimator_sharded_batch(shared_session):
    """Batch lands sharded over the 8-device data axis; loss still converges."""
    import jax
    import optax

    from raydp_tpu.parallel import MeshSpec, make_mesh

    assert len(jax.devices()) == 8
    mesh = make_mesh(MeshSpec(data=8))
    df = _linear_df(shared_session, n=2048)
    est = FlaxEstimator(
        model=MLP(features=(16,), use_batch_norm=False),
        optimizer=optax.adam(1e-2),
        loss="mse",
        feature_columns=["x1", "x2"],
        label_column="y",
        batch_size=256,
        num_epochs=2,
        mesh=mesh,
    )
    result = est.fit_on_frame(df)
    assert result.history[-1]["train_loss"] < result.history[0]["train_loss"]


def test_streaming_ragged_tail(shared_session):
    """drop_last=False on the streaming feed: the smaller epoch-tail batch
    travels as a step of its own, so training sees every row. A ragged batch
    only shards on a size-1 data axis (same rule the eval feed applies), so
    this runs on a single-device mesh."""
    import jax
    import optax

    from raydp_tpu.data import from_frame
    from raydp_tpu.parallel import MeshSpec, make_mesh

    # 21 full batches of 64 + a 6-row tail
    df = _linear_df(shared_session, n=1350)
    ds = from_frame(df)
    est = FlaxEstimator(
        model=MLP(features=(8,), use_batch_norm=False),
        optimizer=optax.adam(1e-2),
        loss="mse",
        feature_columns=["x1", "x2"],
        label_column="y",
        batch_size=64,
        num_epochs=2,
        shuffle=False,
        drop_last=False,
        mesh=make_mesh(MeshSpec(data=1), devices=jax.devices()[:1]),
    )
    result = est.fit(ds)
    assert [r["steps"] for r in result.history] == [22, 22]
    assert np.isfinite(result.history[-1]["train_loss"])


def test_device_cache_parity_and_fallback(shared_session, monkeypatch):
    """The device-resident epoch path (whole epoch = one jitted scan over
    HBM-pinned arrays) must produce exactly the streaming feed's update
    sequence at shuffle=False — same batches, same order — and the
    ``RDT_DEVICE_CACHE`` / budget knobs must force the streaming fallback."""
    import optax

    from raydp_tpu.data import from_frame

    df = _linear_df(shared_session, n=1344)
    ds = from_frame(df)
    # pin the knobs: ambient RDT_DEVICE_CACHE*=... (e.g. exported while
    # debugging the streaming path) must not flip the first run
    monkeypatch.setenv("RDT_DEVICE_CACHE", "1")
    monkeypatch.delenv("RDT_DEVICE_CACHE_MB", raising=False)

    # ragged vs batch 64
    eval_ds = from_frame(_linear_df(shared_session, n=333))

    def run():
        est = FlaxEstimator(
            model=MLP(features=(8,), use_batch_norm=True),
            optimizer=optax.adam(1e-2),
            loss="mse",
            feature_columns=["x1", "x2"],
            label_column="y",
            batch_size=64,
            num_epochs=2,
            shuffle=False,
            seed=0,
            metrics=["mae"],
        )
        return est.fit(ds, eval_ds)

    resident = run()
    # the resident path does no host-side feeding at all
    assert all(r["feed_time_s"] == 0.0 for r in resident.history)

    monkeypatch.setenv("RDT_DEVICE_CACHE", "0")
    streamed = run()
    assert any(r["feed_time_s"] > 0.0 for r in streamed.history)

    assert [r["steps"] for r in resident.history] == \
        [r["steps"] for r in streamed.history]
    for a, b in zip(resident.history, streamed.history):
        np.testing.assert_allclose(a["train_loss"], b["train_loss"],
                                   rtol=1e-5, atol=1e-6)
        # the resident EVAL scan (+ tail rule) must match the streaming
        # eval pass exactly too
        np.testing.assert_allclose(a["eval_loss"], b["eval_loss"],
                                   rtol=1e-5, atol=1e-6)
        np.testing.assert_allclose(a["eval_mae"], b["eval_mae"],
                                   rtol=1e-5, atol=1e-6)

    # a zero budget must also fall back (estimate > cap)
    monkeypatch.setenv("RDT_DEVICE_CACHE", "1")
    monkeypatch.setenv("RDT_DEVICE_CACHE_MB", "0")
    capped = run()
    assert any(r["feed_time_s"] > 0.0 for r in capped.history)


def _short_set_estimator(**kw):
    import optax

    return FlaxEstimator(
        model=MLP(features=(8,)), optimizer=optax.adam(1e-2), loss="mse",
        feature_columns=["x1", "x2"], label_column="y", batch_size=64,
        num_epochs=1, shuffle=False, seed=0, metrics=["mae"], **kw)


def test_resident_eval_set_under_one_batch_is_the_tail(shared_session,
                                                       monkeypatch):
    """An evaluation set of fewer rows than one batch rides beside a resident
    training set: its scan has no step to trace and the tail call serves
    every row — the streaming pass's numbers."""
    from raydp_tpu.data import from_frame

    ds = from_frame(_linear_df(shared_session, n=640))
    eval_ds = from_frame(_linear_df(shared_session, n=40))
    monkeypatch.delenv("RDT_DEVICE_CACHE_MB", raising=False)
    reports = {}
    for cache in ("1", "0"):
        monkeypatch.setenv("RDT_DEVICE_CACHE", cache)
        reports[cache] = _short_set_estimator().fit(ds, eval_ds).history[-1]
    assert reports["1"]["feed_time_s"] == 0.0 < reports["0"]["feed_time_s"]
    for key in ("eval_loss", "eval_mae"):
        np.testing.assert_allclose(reports["1"][key], reports["0"][key],
                                   rtol=1e-5, atol=1e-6)


def test_training_set_under_one_batch_is_refused_by_name(shared_session):
    """drop_last leaves such a set no step: the fit says so, with both
    numbers, before a program is traced; drop_last=False trains on it."""
    from raydp_tpu.data import from_frame

    ds = from_frame(_linear_df(shared_session, n=40))
    with pytest.raises(ValueError, match=r"40 rows.*batch of 64"):
        _short_set_estimator().fit(ds)
    result = _short_set_estimator(drop_last=False).fit(ds)
    assert [r["steps"] for r in result.history] == [1]
    assert np.isfinite(result.history[-1]["train_loss"])


def test_device_cache_shuffled_training_converges(shared_session, monkeypatch):
    """With shuffle=True the resident path shuffles via an on-device
    permutation per epoch: training must still converge on the linear task
    and walk a different batch order every epoch (loss histories differ from
    an unshuffled run)."""
    import optax

    from raydp_tpu.data import from_frame

    df = _linear_df(shared_session, n=1344)
    ds = from_frame(df)
    monkeypatch.setenv("RDT_DEVICE_CACHE", "1")
    monkeypatch.delenv("RDT_DEVICE_CACHE_MB", raising=False)

    def run(shuffle):
        est = FlaxEstimator(
            model=MLP(features=(16,), use_batch_norm=False),
            optimizer=optax.adam(1e-2),
            loss="mse",
            feature_columns=["x1", "x2"],
            label_column="y",
            batch_size=64,
            num_epochs=4,
            shuffle=shuffle,
            seed=0,
        )
        return est.fit(ds)

    result = run(True)
    assert all(r["feed_time_s"] == 0.0 for r in result.history)
    assert result.history[-1]["train_loss"] < result.history[0]["train_loss"]

    # the permutation must actually reorder rows: an unshuffled twin walks a
    # different batch sequence, so its loss history cannot coincide
    unshuffled = run(False)
    assert any(
        abs(a["train_loss"] - b["train_loss"]) > 1e-9
        for a, b in zip(result.history, unshuffled.history))


def test_checkpoint_interval(shared_session, tmp_path):
    """checkpoint_interval=N saves every N-th epoch plus always the final one
    (per-epoch checkpointing is reference parity and stays the default; the
    knob exists because a resident epoch can be cheaper than its save)."""
    import os

    import optax

    df = _linear_df(shared_session, n=512)
    est = FlaxEstimator(
        model=MLP(features=(8,), use_batch_norm=False),
        optimizer=optax.adam(1e-2),
        loss="mse",
        feature_columns=["x1", "x2"],
        label_column="y",
        batch_size=64,
        num_epochs=5,
        checkpoint_dir=str(tmp_path / "ck"),
        checkpoint_interval=3,
    )
    est.fit_on_frame(df)
    steps = sorted(d for d in os.listdir(tmp_path / "ck")
                   if d.startswith("step_"))
    # epochs 0..4: saves at epoch 2 (3rd) and epoch 4 (final)
    assert steps == ["step_2", "step_4"]


def test_retry_before_first_interval_save_rebuilds(shared_session):
    """A failure before the first interval checkpoint has nothing to
    restore; the retry must rebuild the state from scratch (the failed
    state's buffers may be donated away), not continue on dead buffers."""
    import optax

    calls = {"n": 0}

    def boom(report):
        if calls["n"] == 0:
            calls["n"] += 1
            raise RuntimeError("transient failure injected at epoch 0")

    df = _linear_df(shared_session, n=512)
    est = FlaxEstimator(
        model=MLP(features=(8,), use_batch_norm=False),
        optimizer=optax.adam(1e-2),
        loss="mse",
        feature_columns=["x1", "x2"],
        label_column="y",
        batch_size=64,
        num_epochs=2,
        checkpoint_interval=10,  # no save before the injected failure
        callbacks=[boom],
    )
    result = est.fit_on_frame(df, max_retries=1)
    assert len(result.history) == 2
    assert np.isfinite(result.history[-1]["train_loss"])


def test_retry_ignores_stale_checkpoint_dir(shared_session, tmp_path):
    """A fresh fit reusing a checkpoint_dir from an EARLIER run must not
    adopt that run's checkpoint on retry — only checkpoints this run wrote
    (or an explicit resume) may restore; otherwise the retry silently
    returns the old model and history."""
    import optax

    df = _linear_df(shared_session, n=512)
    ck = str(tmp_path / "ck")

    def make(**kw):
        return FlaxEstimator(
            model=MLP(features=(8,), use_batch_norm=False),
            optimizer=optax.adam(1e-2),
            loss="mse",
            feature_columns=["x1", "x2"],
            label_column="y",
            batch_size=64,
            checkpoint_dir=ck,
            **kw,
        )

    make(num_epochs=4).fit_on_frame(df)  # run A leaves step_3 behind

    calls = {"n": 0}

    def boom(report):
        if calls["n"] == 0:
            calls["n"] += 1
            raise RuntimeError("transient")

    result = make(num_epochs=2, checkpoint_interval=10,
                  callbacks=[boom]).fit_on_frame(df, max_retries=1)
    # adopted-stale would return run A's 4-epoch history; fresh rebuild
    # trains exactly this run's 2 epochs
    assert len(result.history) == 2

    # the harder mixed case: run C saves step_0, then fails — the retry
    # must restore run C's OWN step_0 (and retention must not have pruned
    # it in favor of run A's higher-numbered stale steps, which latest-step
    # selection would otherwise adopt)
    calls2 = {"n": 0}

    def boom_epoch1(report):
        if report["epoch"] == 1 and calls2["n"] == 0:
            calls2["n"] += 1
            raise RuntimeError("transient at epoch 1")

    result_c = make(num_epochs=2, checkpoint_interval=1,
                    callbacks=[boom_epoch1]).fit_on_frame(df, max_retries=1)
    # run C resumed from its own epoch-0 save: exactly 2 epoch reports,
    # not run A's 4
    assert len(result_c.history) == 2
