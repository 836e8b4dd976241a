"""Gang training with parameters sharded ACROSS processes.

The reference's Ray Train path only replicates (DDP, torch/estimator.py:243);
sharding model state over the gang (fsdp/expert axes spanning hosts) is the
TPU-native capability that makes pod-scale DLRM embeddings possible
(SURVEY.md §7 step 5 / BASELINE.json "Criteo DLRM pod-scale" config). These
tests run a real 2-process ``jax.distributed`` gang where no single process
ever holds the full state on device, exercising:

- the sharded multi-writer checkpoint format (train/checkpoint.py),
- batch-row derivation from the actual batch sharding
  (``process_local_batch_rows``): proper slices under a >1 data axis,
  full-batch replication under a size-1 data axis (pure fsdp/expert),
- ``process_allgather`` assembly of the trained model.
"""

import numpy as np
import pandas as pd

from raydp_tpu.models import MLP
from raydp_tpu.parallel import MeshSpec
from raydp_tpu.train import FlaxEstimator

NUM_DENSE = 4
CAT_SIZES = [32, 16, 48, 64]


def _linear_df(session, n=1536, parts=4):
    rng = np.random.RandomState(0)
    x = rng.random_sample((n, 2))
    y = x @ np.array([2.0, -3.0]) + 1.0 + rng.normal(0, 0.01, n)
    pdf = pd.DataFrame({"x1": x[:, 0], "x2": x[:, 1], "y": y})
    return session.createDataFrame(pdf, num_partitions=parts)


def _mlp_estimator(mesh_spec=None, num_epochs=3, ckpt_dir=None, **kw):
    import optax

    return FlaxEstimator(
        model=MLP(features=(32, 16), use_batch_norm=False),
        optimizer=optax.sgd(5e-2),
        loss="mse",
        feature_columns=["x1", "x2"],
        label_column="y",
        batch_size=64,
        num_epochs=num_epochs,
        mesh_spec=mesh_spec,
        shuffle=False,
        checkpoint_dir=ckpt_dir,
        **kw,
    )


def _single_device_mesh():
    """A 1-device mesh: the unsharded ground truth every mesh shape must
    reproduce (SPMD sharding is a layout, not a math change)."""
    import jax

    from raydp_tpu.parallel import make_mesh

    return make_mesh(MeshSpec(), devices=jax.devices()[:1])


def test_process_local_batch_rows_single_process():
    from raydp_tpu.data.feed import process_local_batch_rows
    from raydp_tpu.parallel import batch_sharding, make_mesh

    # every device is local → the full range, whatever the mesh shape
    for spec in (MeshSpec(), MeshSpec(fsdp=8), MeshSpec(expert=8),
                 MeshSpec(data=2, fsdp=4)):
        mesh = make_mesh(spec)
        assert process_local_batch_rows(batch_sharding(mesh), 64) == (0, 64)


def test_gang_iterator_explicit_row_range():
    """row_range=(0, B) on every rank = full-batch replication semantics."""
    import pyarrow as pa

    from raydp_tpu.data.feed import GangShardIterator

    rows = np.arange(32, dtype=np.float64)

    class _Ds:
        def block_sizes(self):
            return [32]

        def get_block(self, i, zero_copy=False):
            return pa.table({"x": rows})

    for rank in (0, 1):
        it = GangShardIterator(_Ds(), global_batch=16, world_size=2, rank=rank,
                               columns={"x": ("x", np.float64)},
                               row_range=(0, 16))
        batches = list(it)
        assert [b["x"].shape for b in batches] == [(16,), (16,)]
        np.testing.assert_array_equal(batches[0]["x"], rows[:16])


def test_gang_fsdp_params_sharded_across_processes(shared_session, tmp_path):
    """fsdp=16 over 2 processes × 8 devices: every weight matrix is sharded
    across the process boundary; losses must still match the single-process
    run (SPMD sharding changes nothing about the math)."""
    from raydp_tpu.data.dataset import from_frame

    df = _linear_df(shared_session)
    ds = from_frame(df)

    single = _mlp_estimator(ckpt_dir=str(tmp_path / "single"))
    r1 = single.fit(ds)

    gang = _mlp_estimator(mesh_spec=MeshSpec(fsdp=16),
                          ckpt_dir=str(tmp_path / "gang"))
    r2 = gang.fit_gang(ds, num_workers=2, run_timeout=900.0)

    np.testing.assert_allclose(
        [h["train_loss"] for h in r2.history],
        [h["train_loss"] for h in r1.history], rtol=2e-4)
    # the allgathered model matches the single-process weights
    k1 = np.asarray(single.get_model()["params"]["Dense_0"]["kernel"])
    k2 = np.asarray(gang.get_model()["params"]["Dense_0"]["kernel"])
    assert k2.shape == k1.shape  # full (unsharded) host copy came back
    np.testing.assert_allclose(k2, k1, rtol=1e-3, atol=1e-4)


def test_gang_sharded_checkpoint_resume(shared_session, tmp_path):
    """A second gang over the same checkpoint dir resumes from the sharded
    multi-writer checkpoint instead of retraining."""
    from raydp_tpu.data.dataset import from_frame
    import raydp_tpu.train.checkpoint as ckpt

    df = _linear_df(shared_session, n=1024)
    ds = from_frame(df)
    ckpt_dir = str(tmp_path / "ck")

    first = _mlp_estimator(mesh_spec=MeshSpec(fsdp=16), num_epochs=2,
                           ckpt_dir=ckpt_dir)
    r1 = first.fit_gang(ds, num_workers=2, run_timeout=900.0)
    assert [h["epoch"] for h in r1.history] == [0, 1]
    # the sharded format is on disk: per-process manifests + COMPLETE marker
    import glob as _glob
    import os
    steps = [p for p in _glob.glob(os.path.join(ckpt_dir, "step_*"))]
    assert steps
    latest = sorted(steps, key=lambda p: int(p.rsplit("_", 1)[1]))[-1]
    assert len(_glob.glob(os.path.join(latest, "manifest_*.json"))) == 2
    assert os.path.exists(os.path.join(latest, "COMPLETE"))

    second = _mlp_estimator(mesh_spec=MeshSpec(fsdp=16), num_epochs=4,
                            ckpt_dir=ckpt_dir)
    r2 = second.fit_gang(ds, num_workers=2, run_timeout=900.0)
    # epochs 0-1 came from the restored sidecar; 2-3 were trained
    assert [h["epoch"] for h in r2.history] == [0, 1, 2, 3]
    assert r2.history[-1]["train_loss"] < r1.history[-1]["train_loss"]
    assert ckpt.restore_extra(ckpt_dir)["history"]


def test_gang_expert_sharded_dlrm(shared_session, tmp_path):
    """expert=16 (data axis size 1) over 2 processes: embedding tables sharded
    across the process boundary, batch REPLICATED on every process — the
    row-range derivation must feed the full global batch from each rank."""
    import optax

    from raydp_tpu.data.dataset import from_frame
    from raydp_tpu.models import DLRM, criteo_batch_preprocessor, \
        dlrm_param_rules

    rng = np.random.RandomState(0)
    n = 1024
    data = {"label": rng.randint(0, 2, n).astype(np.float64)}
    for i in range(NUM_DENSE):
        data[f"d{i}"] = rng.random_sample(n)
    for j, vocab in enumerate(CAT_SIZES):
        data[f"c{j}"] = rng.randint(0, vocab, n)
    df = shared_session.createDataFrame(pd.DataFrame(data), num_partitions=4)
    ds = from_frame(df)
    features = [f"d{i}" for i in range(NUM_DENSE)] + \
        [f"c{j}" for j in range(len(CAT_SIZES))]

    def make_est(mesh_spec, ckpt_dir):
        return FlaxEstimator(
            model=DLRM(categorical_sizes=CAT_SIZES, num_dense=NUM_DENSE,
                       embedding_dim=8, bottom_mlp=(16, 8),
                       top_mlp=(32, 16, 1)),
            optimizer=optax.sgd(0.05),
            loss="bce_with_logits",
            feature_columns=features,
            label_column="label",
            feature_dtype=np.float64,
            batch_size=128,
            num_epochs=2,
            mesh_spec=mesh_spec,
            shuffle=False,
            param_rules=dlrm_param_rules("expert"),
            batch_preprocessor=criteo_batch_preprocessor(NUM_DENSE),
            checkpoint_dir=ckpt_dir,
        )

    single = make_est(MeshSpec(expert=8), str(tmp_path / "single"))
    r1 = single.fit(ds)

    gang = make_est(MeshSpec(expert=16), str(tmp_path / "gang"))
    r2 = gang.fit_gang(ds, num_workers=2, run_timeout=900.0)

    np.testing.assert_allclose(
        [h["train_loss"] for h in r2.history],
        [h["train_loss"] for h in r1.history], rtol=5e-4)
    emb1 = np.asarray(single.get_model()["params"]["embedding_0"]["embedding"])
    emb2 = np.asarray(gang.get_model()["params"]["embedding_0"]["embedding"])
    assert emb2.shape == emb1.shape
    np.testing.assert_allclose(emb2, emb1, rtol=1e-3, atol=1e-4)


# ---- single-process mesh matrix (8 virtual devices, PR 16) ------------------
# The role policy + pad-and-mask feed path, exercised where the container
# can run them: one process, 8 virtual CPU devices. The 2-process tests
# above cover the cross-process variants of the same machinery.


def test_role_policy_classify_and_specs():
    """The SpecLayout-style role classifier: path+shape → role → spec."""
    from jax.sharding import PartitionSpec as P

    from raydp_tpu.parallel import make_mesh
    from raydp_tpu.parallel.roles import classify_param, role_partition_spec

    assert classify_param("params/embedding_0/embedding", (32, 8)) \
        == "embedding"
    assert classify_param("params/Dense_0/kernel", (16, 8)) == "kernel"
    assert classify_param("params/Dense_0/bias", (8,)) == "replicated"
    # optimizer-state mirrors classify like the parameter itself
    assert classify_param("opt_state/0/mu/Dense_0/kernel", (16, 8)) \
        == "kernel"

    mesh = make_mesh(dict(fsdp=4, tensor=2))
    # embedding rows span fsdp×tensor when the product divides the vocab
    assert role_partition_spec(mesh, "params/embed/embedding", (32, 8)) \
        == P(("fsdp", "tensor"), None)
    # kernels: tensor on the output dim, fsdp on the largest remaining
    assert role_partition_spec(mesh, "params/Dense_0/kernel", (16, 8)) \
        == P("fsdp", "tensor")
    # ≤1-D replicates; indivisible dims degrade axis by axis, never raise
    assert role_partition_spec(mesh, "params/Dense_0/bias", (8,)) == P()
    assert role_partition_spec(mesh, "params/Dense_0/kernel", (3, 5)) \
        == P(None, None)
    # tensor-only fit on the vocab when fsdp does not divide
    mesh2 = make_mesh(dict(fsdp=4, tensor=2))
    assert role_partition_spec(mesh2, "params/embed/embedding", (6, 4)) \
        == P("tensor", None)


def test_optimizer_state_inherits_param_specs():
    """Adam moments mirror the parameter paths/shapes, so the role policy
    shards them identically — the FSDP memory win covers the optimizer."""
    import jax
    import jax.numpy as jnp
    import optax
    from flax.training import train_state

    from raydp_tpu.parallel import make_mesh, param_sharding_rules

    mesh = make_mesh(dict(fsdp=4, tensor=2))
    model = MLP(features=(32, 16), use_batch_norm=False)
    variables = model.init(jax.random.PRNGKey(0), jnp.zeros((1, 2)))
    state = train_state.TrainState.create(
        apply_fn=model.apply, params=variables["params"],
        tx=optax.adam(1e-3))
    sh = param_sharding_rules(mesh, None)(state)
    mu = sh.opt_state[0].mu
    p_leaves = jax.tree.leaves(sh.params)
    m_leaves = jax.tree.leaves(mu)
    assert len(p_leaves) == len(m_leaves)
    for p_s, m_s in zip(p_leaves, m_leaves):
        assert p_s.spec == m_s.spec
    # at least one kernel actually sharded (the policy is not a no-op here)
    assert any(tuple(s.spec) for s in p_leaves)


def test_a_leaf_no_rule_names_is_placed_by_its_role():
    """``param_rules`` win where they match; every other leaf — a kernel, a
    bias a legacy largest-dim rule would have cut over fsdp, a step counter
    with no shape — takes the role policy's spec. There is one fallback."""
    from jax.sharding import PartitionSpec as P

    from raydp_tpu.parallel import make_mesh, param_sharding_rules
    from raydp_tpu.parallel.roles import role_partition_spec

    mesh = make_mesh(dict(fsdp=4, tensor=2))
    tree = {"step": np.zeros((), np.int32),
            "params": {"Dense_0": {"kernel": np.zeros((16, 8), np.float32),
                                   "bias": np.zeros((8,), np.float32)},
                       "embed": {"embedding": np.zeros((32, 8), np.float32)}}}
    sh = param_sharding_rules(mesh, [("embed", (None, "tensor"))])(tree)
    assert sh["params"]["embed"]["embedding"].spec == P(None, "tensor")
    assert sh["params"]["Dense_0"]["kernel"].spec == P("fsdp", "tensor") \
        == role_partition_spec(mesh, "params/Dense_0/kernel", (16, 8))
    assert sh["params"]["Dense_0"]["bias"].spec == P()
    assert sh["step"].spec == P()


def test_mesh_equivalence_matrix(shared_session):
    """dp / fsdp / fsdp×tp from mesh_spec alone (no param_rules): per-epoch
    losses match the single-device run — sharding changes the layout, not
    the math. Also the dict-valued mesh_spec path."""
    from raydp_tpu.data.dataset import from_frame

    ds = from_frame(_linear_df(shared_session))
    base = _mlp_estimator(mesh=_single_device_mesh())
    losses0 = [h["train_loss"] for h in base.fit(ds).history]

    for spec in (MeshSpec(), MeshSpec(fsdp=8), dict(fsdp=4, tensor=2)):
        est = _mlp_estimator(mesh_spec=spec)
        r = est.fit(ds)
        np.testing.assert_allclose(
            [h["train_loss"] for h in r.history], losses0, rtol=5e-4,
            err_msg=f"mesh_spec={spec}")

    # the last (fsdp=4 × tensor=2) state is really sharded by role:
    # Dense_1 kernel (32, 16) → fsdp on the input dim, tensor on the output
    from jax.sharding import PartitionSpec as P

    k = est.get_state().params["Dense_1"]["kernel"]
    assert k.sharding.spec == P("fsdp", "tensor")


def test_train_ragged_tail_pad_parity(shared_session):
    """drop_last=False with a 28-row tail (1500 = 23×64 + 28): under an
    8-way data extent the tail pads-and-masks to a full batch — same step
    count and same per-epoch losses as the single-device run that consumes
    the ragged batch natively. Before PR 16 this config could not even
    place the tail (28 rows do not divide over 8 devices)."""
    from raydp_tpu.data.dataset import from_frame

    ds = from_frame(_linear_df(shared_session, n=1500))

    base = _mlp_estimator(mesh=_single_device_mesh(), drop_last=False)
    r0 = base.fit(ds)
    assert [h["steps"] for h in r0.history] == [24, 24, 24]

    sharded = _mlp_estimator(mesh_spec=MeshSpec(fsdp=8), drop_last=False)
    r1 = sharded.fit(ds)
    assert [h["steps"] for h in r1.history] == [24, 24, 24]
    np.testing.assert_allclose(
        [h["train_loss"] for h in r1.history],
        [h["train_loss"] for h in r0.history], rtol=5e-4)


def test_eval_ragged_tail_pad_parity(shared_session, monkeypatch):
    """The eval tail (300 = 4×64 + 44) is padded-and-masked instead of
    dropped under a >1 data extent, on BOTH eval paths: the device-resident
    scan (tail padded in-jit) and the streaming feed (tail padded on the
    host). eval_loss must match the single-device run exactly because the
    mask keeps padded rows out of the loss AND the row count."""
    from raydp_tpu.data.dataset import from_frame

    train = from_frame(_linear_df(shared_session, n=1024))
    ev = from_frame(_linear_df(shared_session, n=300, parts=2))

    base = _mlp_estimator(mesh=_single_device_mesh(), metrics=["mae"])
    e0 = base.fit(train, ev).history[-1]

    # device-resident eval cache: the ragged tail pads inside the jit
    cached = _mlp_estimator(mesh_spec=MeshSpec(fsdp=8), metrics=["mae"])
    e1 = cached.fit(train, ev).history[-1]
    np.testing.assert_allclose(e1["eval_loss"], e0["eval_loss"], rtol=5e-4)
    np.testing.assert_allclose(e1["eval_mae"], e0["eval_mae"], rtol=5e-4)

    # streaming eval feed: pad_batch on the host side of the prefetcher
    monkeypatch.setenv("RDT_DEVICE_CACHE", "0")
    streamed = _mlp_estimator(mesh_spec=MeshSpec(fsdp=8), metrics=["mae"])
    e2 = streamed.fit(train, ev).history[-1]
    np.testing.assert_allclose(e2["eval_loss"], e0["eval_loss"], rtol=5e-4)
    np.testing.assert_allclose(e2["eval_mae"], e0["eval_mae"], rtol=5e-4)


def test_pad_tail_knob_restores_drop(shared_session, monkeypatch):
    """RDT_TRAIN_PAD_TAIL=0 is the escape hatch back to the pre-PR-16 drop:
    a 40-row online epoch under fsdp=8 (batch 64) then yields no step at
    all, where padding turns it into one masked step."""
    from raydp_tpu.data.dataset import from_frame

    ds = from_frame(_linear_df(shared_session, n=40, parts=2))

    est = _mlp_estimator(mesh_spec=MeshSpec(fsdp=8))
    r1 = est._partial_fit_epoch(ds, 0)
    assert r1["steps"] == 1
    assert np.isfinite(r1["train_loss"])

    monkeypatch.setenv("RDT_TRAIN_PAD_TAIL", "0")
    est2 = _mlp_estimator(mesh_spec=MeshSpec(fsdp=8))
    r2 = est2._partial_fit_epoch(ds, 0)
    assert r2["steps"] == 0


def test_checkpoint_roundtrip_across_mesh_shapes(shared_session, tmp_path):
    """Train under fsdp=2, restore the checkpoint into a dp-only mesh:
    restore_placed reassembles full values under the NEW shardings — a
    topology change between save and restore is routine (autoscale)."""
    import jax

    from raydp_tpu.data.dataset import from_frame
    from raydp_tpu.parallel import make_mesh, param_sharding_rules
    from raydp_tpu.train import checkpoint as ckpt

    ds = from_frame(_linear_df(shared_session, n=1024))
    ckpt_dir = str(tmp_path / "ck")
    est = _mlp_estimator(mesh_spec=dict(fsdp=2), num_epochs=2,
                         ckpt_dir=ckpt_dir)
    est.fit(ds)
    trained = est.get_state()

    dp_mesh = make_mesh(MeshSpec())  # data=8: every param replicated
    shardings = param_sharding_rules(dp_mesh, None)(trained)
    restored, step = ckpt.restore_placed(ckpt_dir, trained, shardings)
    assert step == 1
    for a, b in zip(jax.tree.leaves(trained), jax.tree.leaves(restored)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # and the restored tree really lives under the dp mesh's shardings
    from jax.sharding import PartitionSpec as P

    k = restored.params["Dense_1"]["kernel"]
    assert k.sharding.mesh.shape["fsdp"] == 1
    assert k.sharding.spec == P()


def test_sharded_export_serve_bitwise_matches_predict(shared_session,
                                                      tmp_path):
    """export_serving off an fsdp×tp-trained state → load_servable →
    predict_table is bit-identical to the estimator's own predict: the
    export gathered exactly the trained weights."""
    import pyarrow as pa

    from raydp_tpu.data.dataset import from_frame
    from raydp_tpu.serve.servable import load_servable

    rng = np.random.RandomState(0)
    x = rng.random_sample((512, 2))
    y = x @ np.array([2.0, -3.0]) + 1.0
    pdf = pd.DataFrame({"x1": x[:, 0], "x2": x[:, 1], "y": y})
    df = shared_session.createDataFrame(pdf, num_partitions=2)
    ds = from_frame(df)

    est = _mlp_estimator(mesh_spec=dict(fsdp=4, tensor=2), num_epochs=2)
    est.fit(ds)
    ref = est.predict(from_frame(df.select("x1", "x2")))

    sv = load_servable(est.export_serving(str(tmp_path / "bundle")))
    got = sv.predict_table(pa.table({"x1": pdf["x1"].values,
                                     "x2": pdf["x2"].values}))
    assert np.array_equal(got, ref)


# ---- activation-side parallelism (PR 17): accum × remat × seq ---------------
# Gradient accumulation, role-driven rematerialization and seq-axis
# activation sharding are residency/layout levers — every test here is a
# parity contract against the unaccumulated / unsharded run.


def test_accum_parity_across_meshes(shared_session):
    """accum=4 reproduces the accum=1 per-epoch loss trajectory on dp,
    fsdp and fsdp×tp meshes: row-weighted microbatch accumulation is the
    same math as the full-batch step, whatever the param layout."""
    from raydp_tpu.data.dataset import from_frame

    ds = from_frame(_linear_df(shared_session))
    losses0 = [h["train_loss"]
               for h in _mlp_estimator(mesh_spec=MeshSpec()).fit(ds).history]

    for spec in (MeshSpec(), MeshSpec(fsdp=8), dict(fsdp=4, tensor=2)):
        r = _mlp_estimator(mesh_spec=spec, accum_steps=4).fit(ds)
        np.testing.assert_allclose(
            [h["train_loss"] for h in r.history], losses0, rtol=5e-4,
            err_msg=f"accum=4 diverged on mesh_spec={spec}")

    # the engaged plane publishes its telemetry: the accumulation factor
    # and the compiled step's peak temp bytes (XLA memory_analysis)
    from raydp_tpu import metrics

    snap = metrics.snapshot()["gauges"]
    assert snap["train_accum_steps"][""] == 4
    assert snap["train_activation_bytes_per_process"][""] > 0


def test_accum_knob_matches_constructor(shared_session, monkeypatch):
    """RDT_TRAIN_ACCUM_STEPS=4 builds the identical step program as
    accum_steps=4 — same losses bitwise — and an accum that does not
    divide the batch fails loudly, not by silently truncating rows."""
    import pytest

    from raydp_tpu.data.dataset import from_frame

    ds = from_frame(_linear_df(shared_session, n=1024))
    r1 = _mlp_estimator(mesh_spec=MeshSpec(), accum_steps=4).fit(ds)
    monkeypatch.setenv("RDT_TRAIN_ACCUM_STEPS", "4")
    r2 = _mlp_estimator(mesh_spec=MeshSpec()).fit(ds)
    monkeypatch.delenv("RDT_TRAIN_ACCUM_STEPS")
    np.testing.assert_array_equal(
        [h["train_loss"] for h in r2.history],
        [h["train_loss"] for h in r1.history])

    with pytest.raises(ValueError, match="divide"):
        _mlp_estimator(mesh_spec=MeshSpec(), accum_steps=5).fit(ds)


def test_remat_modes_identical_losses(shared_session):
    """jax.checkpoint placement (none/dots/full) recomputes, never
    approximates: loss trajectories agree to float-summation noise (the
    recompute can re-associate reductions, nothing more) across remat
    modes, with accumulation and an fsdp mesh engaged."""
    from raydp_tpu.data.dataset import from_frame

    ds = from_frame(_linear_df(shared_session, n=1024))
    ref = _mlp_estimator(mesh_spec=MeshSpec(fsdp=8), accum_steps=4,
                         remat="none").fit(ds)
    for mode in ("dots", "full"):
        r = _mlp_estimator(mesh_spec=MeshSpec(fsdp=8), accum_steps=4,
                           remat=mode).fit(ds)
        np.testing.assert_allclose(
            [h["train_loss"] for h in r.history],
            [h["train_loss"] for h in ref.history], rtol=1e-6,
            err_msg=f"remat={mode} changed the math")


def test_seq_sharded_parity(shared_session):
    """data=4 × seq=2: feature dims shard over the seq axis on top of the
    batch dim — a pure layout change, so per-epoch losses match the
    seq-less dp run and per-row predictions agree tightly."""
    from raydp_tpu.data.dataset import from_frame

    df = _linear_df(shared_session)
    ds = from_frame(df)
    base = _mlp_estimator(mesh_spec=MeshSpec())
    r0 = base.fit(ds)

    seq = _mlp_estimator(mesh_spec=dict(data=4, seq=2))
    r1 = seq.fit(ds)
    np.testing.assert_allclose(
        [h["train_loss"] for h in r1.history],
        [h["train_loss"] for h in r0.history], rtol=5e-4)

    feats = from_frame(df.select("x1", "x2"))
    np.testing.assert_allclose(seq.predict(feats), base.predict(feats),
                               rtol=1e-4, atol=1e-6)


def test_seq_sharded_with_accum_and_remat(shared_session):
    """The full activation plane at once — accum=4 × remat=full ×
    data=4/seq=2 — still lands the plain single-mesh trajectory."""
    from raydp_tpu.data.dataset import from_frame

    ds = from_frame(_linear_df(shared_session))
    losses0 = [h["train_loss"]
               for h in _mlp_estimator(mesh_spec=MeshSpec()).fit(ds).history]
    r = _mlp_estimator(mesh_spec=dict(data=4, seq=2), accum_steps=4,
                       remat="full").fit(ds)
    np.testing.assert_allclose(
        [h["train_loss"] for h in r.history], losses0, rtol=5e-4)


def test_accum_ragged_tail_partial_fit(shared_session):
    """40 rows, batch 64, accum=4 under fsdp=8: the padded tail splits
    into microbatches where the LAST is all padding — its rows-weight is
    zero, so the masked online step still matches the unaccumulated one."""
    from raydp_tpu.data.dataset import from_frame

    ds = from_frame(_linear_df(shared_session, n=40, parts=2))

    plain = _mlp_estimator(mesh_spec=MeshSpec(fsdp=8))._partial_fit_epoch(
        ds, 0)
    accum = _mlp_estimator(
        mesh_spec=MeshSpec(fsdp=8), accum_steps=4)._partial_fit_epoch(ds, 0)
    assert accum["steps"] == plain["steps"] == 1
    np.testing.assert_allclose(accum["train_loss"], plain["train_loss"],
                               rtol=5e-4)


def test_accum_checkpoint_roundtrip(shared_session, tmp_path):
    """Accumulation holds no state across optimizer steps: a checkpoint
    written by an accum=4 fit restores bit-identically to the live state,
    and a longer accum=4 run resumes from it epoch-for-epoch."""
    import jax

    from raydp_tpu.data.dataset import from_frame
    from raydp_tpu.parallel import param_sharding_rules
    from raydp_tpu.train import checkpoint as ckpt

    ds = from_frame(_linear_df(shared_session, n=1024))
    ckpt_dir = str(tmp_path / "ck")
    est = _mlp_estimator(mesh_spec=MeshSpec(fsdp=8), num_epochs=2,
                         ckpt_dir=ckpt_dir, accum_steps=4)
    r1 = est.fit(ds)
    trained = est.get_state()
    shardings = param_sharding_rules(trained.params["Dense_0"]["kernel"]
                                     .sharding.mesh, None)(trained)
    restored, step = ckpt.restore_placed(ckpt_dir, trained, shardings)
    assert step == 1
    for a, b in zip(jax.tree.leaves(trained), jax.tree.leaves(restored)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    resumed = _mlp_estimator(mesh_spec=MeshSpec(fsdp=8), num_epochs=4,
                             ckpt_dir=ckpt_dir, accum_steps=4)
    r2 = resumed.fit(ds)
    assert [h["epoch"] for h in r2.history] == [0, 1, 2, 3]
    np.testing.assert_allclose(
        [h["train_loss"] for h in r2.history[:2]],
        [h["train_loss"] for h in r1.history], rtol=1e-6)
    assert r2.history[-1]["train_loss"] < r1.history[-1]["train_loss"]
