"""End-to-end pipeline-parallel training (ISSUE 20): stage-stacked
estimator placement, unified microbatching, and per-role remat.

The contract under test: ``FlaxEstimator.fit`` on a mesh with ``stage > 1``
places a :class:`PipelineModel`'s layer stack across the ``stage`` axis and
runs the GPipe schedule as ONE compiled SPMD program — the ``accum_steps``
microbatches double as the pipeline microbatches, so a staged run must
reproduce the unstaged losses to tolerance (sharding is a layout, not a
math change). Misconfigurations (layers that do not divide over stages, a
monolithic model on a staged mesh, microbatches that do not divide the
batch, an unknown remat role/mode) must fail loudly BEFORE compile. The
chaos leg proves the staged state checkpoints and resumes bit-identically
through an injected epoch crash.

All legs run on the conftest 8-device CPU mesh (tier-1 safe).
"""

import flax.linen as nn
import numpy as np
import pandas as pd
import pytest

from raydp_tpu import faults, metrics
from raydp_tpu.parallel import make_mesh
from raydp_tpu.train import FlaxEstimator, PipelineModel

DIM = 8
FEATURES = [f"f{i}" for i in range(DIM)]


class Block(nn.Module):
    """Residual tanh block: cheap, yet deep enough to stack into stages."""

    @nn.compact
    def __call__(self, x):
        return x + nn.tanh(nn.Dense(DIM)(x))


def _model(n_layers=4):
    return PipelineModel(layers=[Block() for _ in range(n_layers)],
                        head=nn.Dense(1))


def _linear_ds(session, n=256, parts=4):
    from raydp_tpu.data.dataset import from_frame

    rng = np.random.RandomState(0)
    x = rng.normal(size=(n, DIM))
    w = rng.normal(size=(DIM,))
    pdf = pd.DataFrame({f"f{i}": x[:, i] for i in range(DIM)})
    pdf["label"] = x @ w + 0.1 * rng.normal(size=n)
    return from_frame(session.createDataFrame(pdf, num_partitions=parts))


def _est(**kw):
    kw.setdefault("model", _model())
    kw.setdefault("num_epochs", 3)
    return FlaxEstimator(loss="mse", feature_columns=FEATURES,
                         label_column="label", batch_size=64, seed=0,
                         shuffle=False, **kw)


def _losses(result):
    return [h["train_loss"] for h in result.history]


def _gauge(name):
    return metrics.snapshot()["gauges"].get(name, {}).get("")


def test_stage2_matches_stage1_losses_and_params(shared_session):
    """The tentpole equivalence: a 2-stage pipelined fit (4 microbatches
    marching through the GPipe scan) reproduces the unstaged per-epoch
    losses AND the final parameters — the stage axis changes where layers
    live, never what they compute."""
    ds = _linear_ds(shared_session)
    r1 = _est(mesh=make_mesh(dict(stage=1, data=8)), accum_steps=4).fit(ds)
    r2 = _est(mesh=make_mesh(dict(stage=2, data=4)), accum_steps=4).fit(ds)
    np.testing.assert_allclose(_losses(r2), _losses(r1), rtol=5e-4)
    import jax

    a = jax.tree_util.tree_leaves(r1.state.params)
    b = jax.tree_util.tree_leaves(r2.state.params)
    assert len(a) == len(b) and len(a) > 0
    for la, lb in zip(a, b):
        np.testing.assert_allclose(np.asarray(la), np.asarray(lb),
                                   atol=1e-5)


def test_unified_microbatching_accum_is_pipeline_microbatch(shared_session):
    """accum_steps IS the pipeline microbatch count: different accum
    values at stage=2 land the same losses (row-weighted masked stats keep
    microbatch size out of the math), and the estimator reports the staged
    geometry through the train_pipeline_stages / train_accum_steps
    gauges."""
    ds = _linear_ds(shared_session)
    base = _losses(_est(mesh=make_mesh(dict(stage=1, data=8))).fit(ds))
    for accum in (2, 4):
        r = _est(mesh=make_mesh(dict(stage=2, data=4)),
                 accum_steps=accum).fit(ds)
        np.testing.assert_allclose(_losses(r), base, rtol=5e-4,
                                   err_msg=f"accum={accum}")
        assert _gauge("train_pipeline_stages") == 2
        assert _gauge("train_accum_steps") == accum


def test_per_role_remat_policy_trains_to_same_loss(shared_session):
    """A role→mode remat policy is a schedule hint, not a math change:
    checkpointing kernels at ``dots`` and everything else at ``full``
    lands the same losses as no remat at all."""
    ds = _linear_ds(shared_session)
    base = _losses(_est(mesh=make_mesh(dict(stage=2, data=4)),
                        accum_steps=4).fit(ds))
    r = _est(mesh=make_mesh(dict(stage=2, data=4)), accum_steps=4,
             remat="embedding=none,kernel=dots,default=full").fit(ds)
    np.testing.assert_allclose(_losses(r), base, rtol=5e-4)


def test_remat_policy_validates_before_compile(shared_session):
    """Unknown remat modes and roles fail eagerly with the offending
    token named — not as a shape error three layers into tracing."""
    ds = _linear_ds(shared_session, n=64, parts=2)
    mesh = make_mesh(dict(stage=2, data=4))
    with pytest.raises(ValueError, match="unknown remat mode 'huge'"):
        _est(mesh=mesh, remat="kernel=huge").fit(ds)
    with pytest.raises(ValueError, match="unknown remat role 'attention'"):
        _est(mesh=mesh, remat="attention=dots").fit(ds)


def test_misplacement_fails_loud(shared_session):
    """Placement misconfigurations raise actionable errors before any
    compile: layers must divide over stages, a staged mesh needs the
    layer-list model description, and the microbatch count must divide
    the batch."""
    from raydp_tpu.models import MLP

    ds = _linear_ds(shared_session, n=64, parts=2)
    mesh = make_mesh(dict(stage=2, data=4))
    with pytest.raises(ValueError, match="stage=2 must divide"):
        _est(model=_model(3), mesh=mesh).fit(ds)
    with pytest.raises(ValueError, match="not a PipelineModel"):
        _est(model=MLP(features=(8,), use_batch_norm=False),
             mesh=mesh).fit(ds)
    with pytest.raises(ValueError, match="accum_steps=5"):
        _est(mesh=mesh, accum_steps=5).fit(ds)


def test_pipeline_model_description_contract():
    """PipelineModel is a description, not a module: empty layer lists and
    mutable collections (batch_stats) are rejected at init."""
    import jax

    with pytest.raises(ValueError, match="at least one layer"):
        PipelineModel(layers=[])

    class Stateful(nn.Module):
        @nn.compact
        def __call__(self, x):
            return nn.BatchNorm(use_running_average=False)(x)

    with pytest.raises(ValueError, match="mutable"):
        PipelineModel(layers=[Stateful(), Stateful()]).init(
            jax.random.PRNGKey(0), np.zeros((4, DIM), np.float32))


def test_pipeline_chaos_epoch_crash_resumes_identically(shared_session,
                                                        tmp_path):
    """Chaos leg: an injected crash at ``estimator.epoch`` mid-fit on the
    staged mesh restores the epoch-0 checkpoint (stage-stacked params save
    and restore under their placed shardings) and replays to weights
    bit-identical to an uninterrupted staged fit."""
    ds = _linear_ds(shared_session)

    def make(ckpt):
        return _est(mesh=make_mesh(dict(stage=2, data=4)), accum_steps=4,
                    checkpoint_dir=str(tmp_path / ckpt))

    clean = make("clean").fit(ds)
    assert len(clean.history) == 3

    faults.clear()
    try:
        rule = faults.inject("estimator.epoch", "raise", match="1", times=1)
        faulted = make("faulted").fit(ds, max_retries=1)
    finally:
        faults.clear()
    assert rule.fires == 1, "epoch fault never fired"
    assert len(faulted.history) == 3
    np.testing.assert_allclose(_losses(faulted), _losses(clean), rtol=5e-4)

    import jax

    a = jax.tree_util.tree_leaves(clean.state.params)
    b = jax.tree_util.tree_leaves(faulted.state.params)
    assert len(a) == len(b) and len(a) > 0
    for la, lb in zip(a, b):
        np.testing.assert_array_equal(np.asarray(la), np.asarray(lb))
