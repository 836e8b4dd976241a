"""GBDT model + estimator tests (parity model: reference test_xgboost.py:31-57
— synthetic frames through fit_on_spark, prediction-shape checks; plus direct
algorithm quality assertions the reference leaves to xgboost upstream)."""

import numpy as np
import pandas as pd
import pytest

from raydp_tpu.models.gbdt import apply_bins, fit_gbdt, make_bins


def test_binning_roundtrip():
    rng = np.random.RandomState(0)
    X = rng.randn(1000, 3).astype(np.float32)
    edges = make_bins(X, num_bins=16)
    assert edges.shape == (3, 15)
    Xb = apply_bins(X, edges)
    assert Xb.min() >= 0 and Xb.max() <= 15
    # quantile bins are roughly balanced
    counts = np.bincount(Xb[:, 0], minlength=16)
    assert counts.min() > 20


def test_regression_quality():
    rng = np.random.RandomState(1)
    X = rng.rand(4000, 6).astype(np.float32)
    y = (2 * X[:, 0] - X[:, 1] ** 2 + np.sin(4 * X[:, 2])
         + 0.05 * rng.randn(4000)).astype(np.float32)
    model, _, _ = fit_gbdt(X, y, num_trees=40, max_depth=5, num_bins=64,
                        learning_rate=0.2)
    rmse = float(np.sqrt(np.mean((model.predict(X) - y) ** 2)))
    base = float(y.std())
    assert rmse < 0.2 * base, (rmse, base)


def test_classification_quality():
    rng = np.random.RandomState(2)
    X = rng.rand(3000, 4).astype(np.float32)
    y = (X[:, 0] + X[:, 1] > 1.0).astype(np.float32)
    model, _, _ = fit_gbdt(X, y, num_trees=30, max_depth=4, num_bins=64,
                        learning_rate=0.3, objective="binary:logistic")
    p = model.predict(X)
    assert ((p > 0.5) == (y > 0.5)).mean() > 0.97
    # probabilities, not margins
    assert 0.0 <= p.min() and p.max() <= 1.0
    margins = model.predict(X, output_margin=True)
    assert margins.min() < 0 or margins.max() > 1.0


def test_unsupported_objective():
    with pytest.raises(ValueError, match="objective"):
        fit_gbdt(np.zeros((10, 2), np.float32), np.zeros(10, np.float32),
                 objective="rank:pairwise")


def test_estimator_fit_on_frame(shared_session):
    from raydp_tpu.train import GBDTEstimator

    rng = np.random.RandomState(3)
    x = rng.rand(600, 3).astype(np.float32)
    y = (x[:, 0] * 4 + x[:, 1] + 0.01 * rng.randn(600)).astype(np.float32)
    df = shared_session.createDataFrame(
        pd.DataFrame({"f0": x[:, 0], "f1": x[:, 1], "f2": x[:, 2], "y": y}),
        num_partitions=2)
    train_df, eval_df = df.randomSplit([0.8, 0.2], seed=0)

    est = GBDTEstimator(
        params={"objective": "reg:squarederror", "max_depth": 4, "eta": 0.3,
                "max_bin": 64},
        feature_columns=["f0", "f1", "f2"], label_column="y",
        num_boost_round=30)
    result = est.fit_on_frame(train_df, eval_df)
    report = result.history[0]
    assert report["num_trees"] == 30
    assert report["train_rmse"] < 0.3
    assert "eval_rmse" in report

    model = est.get_model()
    preds = model.predict(x[:5])
    assert preds.shape == (5,)

    # checkpoint reload parity (per-iteration checkpoint keeping 1,
    # xgboost/estimator.py:60-68)
    loaded = GBDTEstimator.load_model(result.checkpoint_dir)
    np.testing.assert_allclose(loaded.predict(x[:5]), preds, rtol=1e-6)

    # estimator-level batched inference over a dataset (mirrors
    # FlaxEstimator.predict)
    from raydp_tpu.data import from_frame

    eval_ds = from_frame(eval_df)
    ds_preds = est.predict(eval_ds)
    assert ds_preds.shape == (eval_ds.count(),)
    exp = model.predict(np.stack(
        [eval_ds.to_arrow().column(c).to_numpy().astype(np.float32)
         for c in ["f0", "f1", "f2"]], axis=1))
    np.testing.assert_allclose(ds_preds, exp, rtol=1e-6)


def test_multiclass_matches_sklearn_quality():
    """multi:softprob on 4-class blobs: accuracy within 3 points of sklearn's
    GradientBoostingClassifier on the same data (VERDICT #8 done-bar)."""
    from sklearn.datasets import make_blobs
    from sklearn.ensemble import GradientBoostingClassifier

    X, y = make_blobs(n_samples=3000, centers=4, n_features=5,
                      cluster_std=3.0, random_state=3)
    X = X.astype(np.float32)
    cut = 2400
    model, _, _ = fit_gbdt(X[:cut], y[:cut].astype(np.float32),
                           num_trees=40, max_depth=4, num_bins=64,
                           learning_rate=0.2, objective="multi:softprob")
    probs = model.predict(X[cut:])
    assert probs.shape == (600, 4)
    np.testing.assert_allclose(probs.sum(axis=1), 1.0, rtol=1e-5)
    acc = float((probs.argmax(axis=1) == y[cut:]).mean())

    sk = GradientBoostingClassifier(n_estimators=40, max_depth=4,
                                    learning_rate=0.2, random_state=0)
    sk.fit(X[:cut], y[:cut])
    sk_acc = float(sk.score(X[cut:], y[cut:]))
    assert acc >= sk_acc - 0.03, (acc, sk_acc)

    # multi:softmax returns class ids directly
    model2, _, _ = fit_gbdt(X[:cut], y[:cut].astype(np.float32),
                            num_trees=10, max_depth=4, num_bins=64,
                            objective="multi:softmax")
    pred = model2.predict(X[cut:])
    assert set(np.unique(pred)).issubset({0.0, 1.0, 2.0, 3.0})


def test_per_round_eval_and_early_stopping():
    rng = np.random.RandomState(5)
    X = rng.rand(2000, 5).astype(np.float32)
    y = (X[:, 0] + 0.3 * rng.randn(2000)).astype(np.float32)  # noisy target
    cut = 1000
    model, _, evals = fit_gbdt(
        X[:cut], y[:cut], num_trees=200, max_depth=6, num_bins=64,
        learning_rate=0.5, evals=(X[cut:], y[cut:]),
        early_stopping_rounds=5)
    history = evals["eval_rmse"]
    # stopped early: deep greedy trees at lr=0.5 overfit noise quickly
    assert len(history) < 200
    assert model.best_iteration == int(np.argmin(history))
    # the forest is truncated to the best iteration
    assert model.num_trees == model.best_iteration + 1
    # per-round reporting really is per round
    assert len(history) == model.best_iteration + 1 + 5


def test_instance_weights_shift_the_fit():
    """Weighting duplicates: weight-2 fit == duplicated-row fit."""
    rng = np.random.RandomState(7)
    X = rng.rand(600, 3).astype(np.float32)
    y = (X[:, 0] > 0.5).astype(np.float32)
    w = np.where(y > 0, 2.0, 1.0).astype(np.float32)

    edges = make_bins(X, 32)
    m_w, _, _ = fit_gbdt(X, y, num_trees=10, max_depth=3, num_bins=32,
                         objective="binary:logistic", sample_weight=w,
                         bin_edges=edges)
    Xd = np.concatenate([X, X[y > 0]], axis=0)
    yd = np.concatenate([y, y[y > 0]], axis=0)
    m_d, _, _ = fit_gbdt(Xd, yd, num_trees=10, max_depth=3, num_bins=32,
                         objective="binary:logistic", bin_edges=edges)
    np.testing.assert_allclose(m_w.predict(X), m_d.predict(X),
                               rtol=1e-4, atol=1e-5)


def test_estimator_multiclass_early_stop(shared_session):
    from raydp_tpu.train import GBDTEstimator

    rng = np.random.RandomState(11)
    n = 1500
    X = rng.rand(n, 4)
    label = (X[:, 0] * 3).astype(np.int64).clip(0, 2)
    pdf = pd.DataFrame({f"f{i}": X[:, i] for i in range(4)})
    pdf["y"] = label.astype(np.float64)
    df = shared_session.createDataFrame(pdf, num_partitions=3)
    train_df, eval_df = df.randomSplit([0.8, 0.2], seed=0)

    est = GBDTEstimator(
        params={"objective": "multi:softprob", "num_class": 3,
                "max_depth": 3, "eta": 0.3},
        feature_columns=[f"f{i}" for i in range(4)],
        label_column="y", num_boost_round=60, early_stopping_rounds=8)
    result = est.fit_on_frame(train_df, eval_df)
    report = result.history[-1]
    assert report["eval_merror"] < 0.1
    assert "eval_mlogloss" in est.evals_result
    assert len(est.evals_result["eval_mlogloss"]) <= 60


def test_row_sharded_fit_matches_single_device():
    """mesh-sharded rows: XLA reduces the per-device partial histograms (the
    Rabit-allreduce slot); results must match the unsharded fit."""
    import jax

    from raydp_tpu.parallel import make_mesh

    rng = np.random.RandomState(9)
    n = 3001  # deliberately not divisible by 8: exercises zero-weight padding
    X = rng.rand(n, 5).astype(np.float32)
    y = (X[:, 0] - 2 * X[:, 1] + 0.1 * rng.randn(n)).astype(np.float32)

    edges = make_bins(X, 64)
    plain, pred_plain, _ = fit_gbdt(X, y, num_trees=12, max_depth=4,
                                    num_bins=64, bin_edges=edges)
    mesh = make_mesh()
    assert int(np.prod(list(mesh.shape.values()))) == 8
    shard, pred_shard, _ = fit_gbdt(X, y, num_trees=12, max_depth=4,
                                    num_bins=64, bin_edges=edges, mesh=mesh)
    assert pred_shard.shape == (n,)
    # reduction order can flip an argmax at a near-tied split, so require
    # near-identical structure (not bit-exact) plus matching predictions
    diff = np.mean(shard.split_feature != plain.split_feature)
    assert diff < 0.05, f"{diff:.1%} of split nodes differ"
    np.testing.assert_allclose(pred_shard, pred_plain, rtol=1e-3, atol=1e-4)


def test_fused_eval_scan_matches_host_loop():
    """The fused on-device train+eval scan (no early stopping: one dispatch
    for the whole history) must reproduce the host per-round loop's eval
    history and forest — the loop is the reference-semantics oracle (xgboost
    per-round eval reports, reference xgboost/estimator.py:54-81)."""
    rng = np.random.RandomState(3)
    X = rng.rand(2000, 5).astype(np.float32)
    y = (X[:, 0] - 2 * X[:, 1] + 0.1 * rng.randn(2000)).astype(np.float32)
    eX = rng.rand(400, 5).astype(np.float32)
    ey = (eX[:, 0] - 2 * eX[:, 1] + 0.1 * rng.randn(400)).astype(np.float32)

    kw = dict(num_trees=8, max_depth=4, num_bins=32, learning_rate=0.3,
              evals=(eX, ey))
    fused_model, fused_pred, fused_hist = fit_gbdt(X, y, **kw)
    # early_stopping_rounds > num_trees never fires: the host loop runs all
    # rounds and its history is the oracle trajectory
    host_model, host_pred, host_hist = fit_gbdt(
        X, y, early_stopping_rounds=kw["num_trees"] + 1, **kw)

    np.testing.assert_allclose(fused_hist["eval_rmse"],
                               host_hist["eval_rmse"][:8], rtol=1e-5)
    np.testing.assert_array_equal(fused_model.split_feature,
                                  host_model.split_feature)
    np.testing.assert_array_equal(fused_model.split_bin,
                                  host_model.split_bin)
    np.testing.assert_allclose(fused_model.leaf_value,
                               host_model.leaf_value, rtol=1e-5)
    np.testing.assert_allclose(fused_pred, host_pred, rtol=1e-4, atol=1e-5)


def test_fused_eval_scan_matches_host_loop_multiclass():
    """Multiclass twin of the fused-eval parity test: the vmapped K-tree
    round and the [K, nodes] eval routing must also match the host loop."""
    rng = np.random.RandomState(5)
    X = rng.rand(1500, 4).astype(np.float32)
    y = (X[:, 0] + X[:, 1] > 1.0).astype(np.float32) \
        + (X[:, 2] > 0.66).astype(np.float32)  # 3 classes
    eX = rng.rand(300, 4).astype(np.float32)
    ey = (eX[:, 0] + eX[:, 1] > 1.0).astype(np.float32) \
        + (eX[:, 2] > 0.66).astype(np.float32)

    kw = dict(num_trees=6, max_depth=3, num_bins=32, learning_rate=0.4,
              objective="multi:softmax", num_class=3, evals=(eX, ey))
    fused_model, _, fused_hist = fit_gbdt(X, y, **kw)
    host_model, _, host_hist = fit_gbdt(
        X, y, early_stopping_rounds=kw["num_trees"] + 1, **kw)

    np.testing.assert_allclose(fused_hist["eval_mlogloss"],
                               host_hist["eval_mlogloss"][:6], rtol=1e-5)
    np.testing.assert_array_equal(fused_model.split_feature,
                                  host_model.split_feature)
    np.testing.assert_allclose(fused_model.leaf_value,
                               host_model.leaf_value, rtol=1e-5)
