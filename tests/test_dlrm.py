"""DLRM on a dp×expert mesh: embedding tables sharded, end-to-end fit
(parity target: examples/pytorch_dlrm.ipynb pipeline on Ray Train)."""

import numpy as np
import pandas as pd
import pytest


NUM_DENSE = 4
CAT_SIZES = [40, 16, 24, 8, 32, 48]  # 6 tables (downscaled Criteo shape)


def _criteo_like(session, n=2048):
    rng = np.random.RandomState(0)
    data = {"_c0": rng.randint(0, 2, n).astype(np.float64)}
    for i in range(1, NUM_DENSE + 1):
        data[f"_c{i}"] = rng.random_sample(n)
    for j, vocab in enumerate(CAT_SIZES):
        data[f"_c{NUM_DENSE + 1 + j}"] = rng.randint(0, vocab, n)
    return session.createDataFrame(pd.DataFrame(data), num_partitions=4)


def test_dlrm_model_shapes():
    import jax
    import jax.numpy as jnp

    from raydp_tpu.models import DLRM

    model = DLRM(categorical_sizes=CAT_SIZES, num_dense=NUM_DENSE,
                 embedding_dim=8, bottom_mlp=(16, 8), top_mlp=(16, 1))
    batch = {"dense": jnp.ones((32, NUM_DENSE)),
             "sparse": jnp.zeros((32, len(CAT_SIZES)), jnp.int32)}
    variables = model.init(jax.random.PRNGKey(0), batch)
    out = model.apply(variables, batch)
    assert out.shape == (32, 1)
    assert variables["params"]["embedding_0"]["embedding"].shape == (40, 8)


def test_dlrm_fit_sharded_embeddings(shared_session):
    import optax

    from raydp_tpu.models import DLRM, criteo_batch_preprocessor, dlrm_param_rules
    from raydp_tpu.parallel import MeshSpec, make_mesh
    from raydp_tpu.train import FlaxEstimator

    mesh = make_mesh(MeshSpec(data=2, expert=4))
    df = _criteo_like(shared_session)
    features = [f"_c{i}" for i in range(1, NUM_DENSE + 1 + len(CAT_SIZES))]

    est = FlaxEstimator(
        model=DLRM(categorical_sizes=CAT_SIZES, num_dense=NUM_DENSE,
                   embedding_dim=8, bottom_mlp=(16, 8), top_mlp=(32, 16, 1)),
        optimizer=optax.sgd(0.05),
        loss="bce_with_logits",
        feature_columns=features,
        label_column="_c0",
        feature_dtype=np.float64,
        batch_size=128,
        num_epochs=2,
        mesh=mesh,
        param_rules=dlrm_param_rules("expert"),
        batch_preprocessor=criteo_batch_preprocessor(NUM_DENSE),
        metrics=["accuracy"],
    )
    result = est.fit_on_frame(df)
    assert len(result.history) == 2
    # embedding tables actually sharded over the expert axis
    emb = result.state.params["embedding_0"]["embedding"]
    shard_rows = emb.sharding.shard_shape(emb.shape)[0]
    assert shard_rows == emb.shape[0] // 4

    # predict() works for batch_preprocessor models: the same column spec
    # decodes, the preprocessor splits, the label is read and discarded —
    # and the output matches a manual get_model() apply on the first rows
    from raydp_tpu.data import from_frame

    ds = from_frame(df)
    preds = est.predict(ds, batch_size=128)
    assert preds.shape == (2048,) and preds.dtype == np.float32

    # the normal inference frame has NO label column: predict synthesizes
    # the spec's label entry as zeros (discarded) and returns the same preds
    ds_nolabel = from_frame(df.drop("_c0"))
    np.testing.assert_array_equal(est.predict(ds_nolabel, batch_size=128),
                                  preds)

    import jax.numpy as jnp
    table = ds.get_block(0)
    feats = np.stack([table.column(c).to_numpy(zero_copy_only=False)
                      .astype(np.float64) for c in features], axis=1)
    inputs, _ = est.batch_preprocessor(
        {"features": jnp.asarray(feats),
         "label": jnp.zeros((len(feats),), jnp.float32)})
    manual = est._build_model().apply(est.get_model(), inputs)
    np.testing.assert_allclose(preds[:len(feats)],
                               np.asarray(manual).squeeze(-1),
                               rtol=2e-4, atol=2e-5)


@pytest.mark.slow
def test_predict_synthesizes_nonstandard_label_key(shared_session):
    """ADVICE r5 #1: a columns_spec may key its label entry anything (the
    batch_preprocessor consumes arbitrary keys) — predict() must synthesize
    zeros for ANY spec entry whose columns the inference frame lacks, not
    just the entry literally keyed "label"."""
    import optax

    from raydp_tpu.data import from_frame
    from raydp_tpu.models import MLP
    from raydp_tpu.train import FlaxEstimator

    n = 512
    rng = np.random.RandomState(0)
    pdf = pd.DataFrame({"x1": rng.rand(n), "x2": rng.rand(n),
                        "target": rng.rand(n)})
    df = shared_session.createDataFrame(pdf, num_partitions=2)

    est = FlaxEstimator(
        model=MLP(features=(8,), use_batch_norm=False),
        optimizer=optax.adam(1e-2),
        loss="mse",
        batch_size=64,
        num_epochs=2,
        columns_spec={"features": (["x1", "x2"], np.float32),
                      "target": ("target", np.float32)},
        batch_preprocessor=lambda b: (b["features"], b["target"]),
    )
    est.fit_on_frame(df)

    preds = est.predict(from_frame(df))
    assert preds.shape == (n,) and np.isfinite(preds).all()

    # the inference frame lacks "target": the entry is synthesized as zeros
    # (its value is discarded by the preprocessor's label output anyway),
    # so predictions are identical
    preds_nolabel = est.predict(from_frame(df.drop("target")))
    np.testing.assert_array_equal(preds_nolabel, preds)

    # but a PARTIALLY-missing entry is a schema mismatch, not a label-less
    # frame: synthesizing zeros for half a feature matrix would silently
    # produce garbage predictions — it must raise instead
    with pytest.raises(ValueError, match="partially"):
        est.predict(from_frame(df.drop("x2")))
