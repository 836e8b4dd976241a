"""OLMoE-style sparse-expert LM: the program's model, loss and train step
against the plain reference (``chipbench/reference/olmoe-1b-7b.py``: float32
``jax.numpy``, dense attention, every expert on every token), at a tiny size
on the CPU, seeded random weights. Widths are small here, and only here.
"""

import functools

import numpy as np
import pytest

from tests import lm_testing
from tests.lm_testing import (F32_TOL, close as _close,
                              estimator as _estimator, leaves as _leaves,
                              token_frame as _token_frame, tokens as _tokens,
                              variables as _variables)

CONFIG = "olmoe-1b-7b"

TINY = {"hidden_size": 32, "num_attention_heads": 4, "intermediate_size": 16,
        "num_experts": 8, "num_experts_per_tok": 2, "vocab_size": 64,
        "max_position_embeddings": 16, "layers": 2, "compared_positions": 4,
        "compute_dtype": "float32", "attention": "dense", "init_std": 0.3}


_files = functools.partial(lm_testing.files, CONFIG, TINY)


def _skewed(params):
    """A constant feature in the embedding and a router row that reads it,
    so that expert 0 is in every token's top-2 and experts 5-7 are in
    nobody's: one expert holds half of all slots, three groups are empty."""
    params["embed"]["embedding"][:, 0] = 25.0
    for name in (n for n in params if n.startswith("block_")):
        router = params[name]["moe"]["router"]
        router[0] = [6.0, 0, 0, 0, 0, -6.0, -6.0, -6.0]
    return params


def _mean_weights(tokens):
    """The mean's weights: what the train step hands the model's loss
    without a mask."""
    return np.full(len(tokens), 1.0 / len(tokens), np.float32)


# bfloat16 activations, float32 router and loss: 4 ulps of bfloat16 on the
# relative RMS error of the logits (the chip's check (a) and its TOLERANCE)
BF16_TOL = 4 * 2.0 ** -8


@pytest.mark.parametrize("dtype,tol", [("float32", F32_TOL),
                                       ("bfloat16", BF16_TOL)])
def test_forward_logits_match_the_reference(dtype, tol):
    from chipbench.harness import relative_rms_error
    cfg, pipeline, _ = _files(compute_dtype=dtype)
    model = pipeline.build_model(cfg)
    tokens = _tokens(cfg, 3)
    params, _ = _variables(model, tokens)
    got = pipeline.compared(lm_testing.logits(model, {"params": params},
                                              tokens), cfg)
    want = lm_testing.reference_program(CONFIG, cfg, "forward")(
        {"params": params}, tokens)
    assert got.shape == want.shape == (3, 4, cfg["vocab_size"])
    assert relative_rms_error(got, want) <= tol
    if dtype == "bfloat16":     # and the tolerance does separate precisions
        assert relative_rms_error(got, want) > F32_TOL


@pytest.mark.parametrize("routing", ["uniform", "skewed"])
def test_loss_and_every_gradient_leaf_match_the_reference(routing):
    """Dropless under imbalance: with the skewed router one expert holds
    half of all slots and three hold none, and every slot still contributes
    (the gradients of the full experts, the empty experts' zeros and the
    router's all match a reference that computes every expert densely)."""
    cfg, pipeline, _ = _files()
    model = pipeline.build_model(cfg)
    tokens = _tokens(cfg, 4, seed=1)
    params, _ = _variables(model, tokens)
    if routing == "skewed":
        _skewed(params)

    (loss, counts), grads = lm_testing.loss_and_grads(
        model, params, None, tokens, _mean_weights(tokens))
    want_loss, want_grads = lm_testing.reference_program(
        CONFIG, cfg, "loss", grad=True)(params, tokens)
    assert abs(float(loss) - float(want_loss)) <= F32_TOL * float(want_loss)
    _close(grads, want_grads)
    got = _leaves(grads)

    slots = tokens.size * cfg["num_experts_per_tok"] * cfg["layers"]
    assert float(counts[1]) == slots
    ids = np.stack(lm_testing.reference_program(CONFIG, cfg, "top_k_ids")(
        params, tokens))
    per_expert = np.stack([np.bincount(layer.ravel(), minlength=8)
                           for layer in ids])
    assert float(counts[0]) == per_expert.max(axis=1).sum()
    if routing == "skewed":
        assert (per_expert[:, 0] == tokens.size).all()      # half of all
        assert (per_expert[:, 5:] == 0).all()               # empty groups
        gate = got["block_0/moe/experts_gate"]
        assert np.abs(gate[0]).max() > 0 and np.abs(gate[5:]).max() == 0
    else:
        assert (per_expert > 0).all()


def _steps_counted():
    from raydp_tpu import metrics as registry
    return registry.snapshot()["counters"].get(
        "train_head_loss_total", {}).get("forward_grad", 0)


def _dense(cfg):
    """The same files, no experts: the head's loss with no auxiliary term."""
    cfg["num_experts"], cfg["model_type"] = 0, "dense"
    return cfg


def test_the_fused_loss_is_lm_loss_on_materialised_logits():
    from raydp_tpu.models.transformer import lm_loss
    cfg, pipeline, _ = _files()
    model = pipeline.build_model(_dense(cfg))
    tokens = _tokens(cfg, 3, seed=2)
    params, _ = _variables(model, tokens)
    (loss, counts), _ = lm_testing.loss_and_grads(
        model, params, None, tokens, _mean_weights(tokens))
    want = lm_loss(lm_testing.logits(model, {"params": params}, tokens),
                   tokens)
    np.testing.assert_allclose(float(loss), float(want), rtol=1e-6)
    assert counts.shape == (0,) and model.loss_counters == ()


@pytest.mark.parametrize("weights", [[0.5, 0.25, 0.25, 0.0],
                                     [0.5, 0.0, 0.5, 0.0],
                                     [0.0, 0.0, 0.0, 0.0]])
def test_the_model_loss_weighs_rows_and_a_masked_row_carries_no_gradient(
        weights):
    """``loss_rows`` under the rows' weights is the weighted sum of the
    reference's loss a row plus ``sum(weights)`` times the batch's auxiliary
    terms (what the mean over real rows gave); a zero-weight row adds nothing
    to the cross entropy's gradients, and an all-pad microbatch is 0 with
    zero gradients."""
    import jax
    cfg, pipeline, reference = _files()
    model = pipeline.build_model(cfg)
    tokens = _tokens(cfg, 4, seed=3)
    params, _ = _variables(model, tokens)
    w = np.asarray(weights, np.float32)

    def want_fn(p):
        # a row's cross entropy alone: the reference on that row, less its
        # auxiliary terms; the batch's: those of the reference on the batch
        dense = dict(cfg, aux_loss=dict(cfg["aux_loss"], balance_weight=0.0,
                                        z_weight=0.0))
        rows = [reference.loss(p, tokens[i:i + 1], dense) for i in range(4)]
        aux = reference.loss(p, tokens, cfg) - reference.loss(p, tokens, dense)
        return sum(wi * r for wi, r in zip(w, rows)) + w.sum() * aux

    (loss, _), grads = lm_testing.loss_and_grads(model, params, None,
                                                 tokens, w)
    want, want_grads = jax.jit(jax.value_and_grad(want_fn))(params)
    assert abs(float(loss) - float(want)) <= F32_TOL * max(float(want), 1.0)
    _close(grads, want_grads)
    got = _leaves(grads)
    if not w.any():
        assert float(loss) == 0.0
        assert all(not g.any() for g in got.values())


def test_a_masked_rows_tokens_do_not_reach_a_dense_models_gradients():
    cfg, pipeline, _ = _files()
    model = pipeline.build_model(_dense(cfg))
    tokens = _tokens(cfg, 4, seed=4)
    params, _ = _variables(model, tokens)
    w = np.asarray([0.5, 0.5, 0.0, 0.0], np.float32)
    other = tokens.copy()
    other[2:] = _tokens(cfg, 2, seed=9)
    program = lm_testing.loss_program(model)
    grad = lambda t, w: _leaves(program(params, None, t, w)[1])  # noqa: E731
    a, b = grad(tokens, w), grad(other, w)
    halves = grad(tokens[:2], _mean_weights(tokens[:2]))
    for name in a:
        np.testing.assert_array_equal(a[name], b[name], err_msg=name)
        scale = max(np.abs(halves[name]).max(), 1e-3)
        assert np.abs(a[name] - halves[name]).max() <= 10 * F32_TOL * scale


@pytest.mark.parametrize("accum", [1, 2])
def test_fit_on_frame_reproduces_an_optax_loop_over_the_reference(
        session, tmp_path, accum):
    """Token rows from the ETL plane through ``fit_on_frame`` (list column,
    feed, the model's own loss, AdamW with clipping) against a hand-written
    loop: ``jax.grad`` of the reference's loss, the same optimizer. With
    ``accum_steps`` 2 each half batch has its own auxiliary losses (they are
    statistics of the tokens routed together), and the halves' gradients are
    averaged."""
    import jax
    import optax
    from raydp_tpu import metrics as registry
    from raydp_tpu.parallel import make_mesh

    cfg, pipeline, _ = _files()
    df, info, table = _token_frame(session, tmp_path, cfg, pipeline, 8, 5)
    mesh = make_mesh(None, devices=jax.devices()[:1])
    before = registry.snapshot()["counters"]
    est = _estimator(cfg, pipeline, info, mesh, num_epochs=2, batch_size=4,
                     accum_steps=accum)
    history = est.fit_on_frame(df).history

    tokens = pipeline.reference_inputs(table, info)
    assert tokens.shape == (8, 16) and tokens.dtype == np.int32
    tx = pipeline.build_optimizer(cfg)
    params, _ = _variables(est._build_model(), tokens)  # as the fit's are
    opt_state = tx.init(params)
    grad = lm_testing.reference_program(CONFIG, cfg, "loss", grad=True)
    want = []
    for _ in range(2):
        losses = []
        for at in (0, 4):
            halves = np.split(tokens[at:at + 4], accum)
            pairs = [grad(params, h) for h in halves]
            losses.append(np.mean([float(v) for v, _ in pairs]))
            g = jax.tree.map(lambda *gs: sum(gs) / accum,
                             *[g for _, g in pairs])
            updates, opt_state = tx.update(g, opt_state, params)
            params = optax.apply_updates(params, updates)
        want.append(np.mean(losses))
    got = [e["train_loss"] for e in history]
    # float32 both sides; two epochs of AdamW steps amplify summation order
    np.testing.assert_allclose(got, want, rtol=2e-4)
    assert got[-1] < got[0]

    # (f) no lookups declared: the row-wise table update does not engage for
    # the LM's embedding (AdamW decays untouched rows: not row-wise), and
    # the routing counters arrived with the epochs' losses
    assert not hasattr(est._build_model(), "lookups")
    after = registry.snapshot()["counters"]
    assert after.get("train_table_updates_total", {}) == before.get(
        "train_table_updates_total", {})
    moved = {k: v - before.get("moe_slots_total", {}).get(k, 0)
             for k, v in after["moe_slots_total"].items()}
    assert moved["all"] == 2 * 8 * 16 * 2 * 2   # epochs x tokens x top-2 x layers
    assert moved["all"] / 8 <= moved["max_expert"] <= moved["all"]
    staged = after["feed_staged_tables_total"]
    assert staged.get("native", 0) > before.get(
        "feed_staged_tables_total", {}).get("native", 0)
    assert staged.get("numpy", 0) == before.get(
        "feed_staged_tables_total", {}).get("numpy", 0)


def test_an_expert_sharded_fit_gives_the_single_device_losses(
        session, tmp_path):
    """``expert`` 4 over four virtual devices: the stacked expert kernels
    (and their AdamW mirrors) split on dim 0 by the role policy, same
    losses."""
    import jax
    from raydp_tpu.parallel import make_mesh

    cfg, pipeline, _ = _files()
    df, info, _ = _token_frame(session, tmp_path, cfg, pipeline, 8, 6)
    losses = {}
    for name, devices, spec in (("one", 1, None), ("four", 4, {"expert": 4})):
        mesh = make_mesh(spec, devices=jax.devices()[:devices])
        est = _estimator(cfg, pipeline, info, mesh, num_epochs=2,
                         batch_size=4)
        losses[name] = [e["train_loss"]
                        for e in est.fit_on_frame(df).history]
        if name == "four":
            state = est.get_state()
            gate = state.params["block_0"]["moe"]["experts_gate"]
            assert gate.sharding.spec[0] == "expert"
            assert {s.data.shape[0] for s in gate.addressable_shards} == {2}
            mu = state.opt_state[1][0].mu["block_0"]["moe"]["experts_gate"]
            assert mu.sharding.spec[0] == "expert"
    np.testing.assert_allclose(losses["four"], losses["one"], rtol=1e-5)


@pytest.mark.parametrize("model_name,counted", [("lm", 1), ("dlrm", 0)])
def test_the_head_loss_counter_counts_a_built_step_of_a_model_with_a_loss(
        model_name, counted):
    """``train_head_loss_total{forward_grad}``: once a built train step whose
    model was handed the rows' weights; a model without ``loss_rows`` takes
    the estimator's loss and is not counted."""
    from raydp_tpu.models import DLRM, criteo_batch_preprocessor
    from raydp_tpu.train.flax_estimator import (_make_apply, _make_train_step,
                                                _resolve_loss)
    if model_name == "lm":
        cfg, pipeline, _ = _files()
        model, split = pipeline.build_model(cfg), (
            lambda b: (b["tokens"], b["tokens"]))
    else:
        model, split = DLRM(categorical_sizes=[16, 8], num_dense=4,
                            embedding_dim=8, bottom_mlp=(16, 8),
                            top_mlp=(16, 1)), criteo_batch_preprocessor(4)
    before = _steps_counted()
    apply_fn = _make_apply(model, False, split, None)
    assert apply_fn.model_loss == (model_name == "lm")
    _make_train_step(apply_fn, _resolve_loss("bce"), [], 1, "none")
    assert _steps_counted() - before == counted


def _plain_rows(model, params, tokens):
    """Each row's mean next-token cross entropy from the model's plain
    path: materialised float32 logits, no fused loss."""
    import optax
    logits = model.apply({"params": params}, tokens)
    return optax.softmax_cross_entropy_with_integer_labels(
        logits[:, :-1], tokens[:, 1:]).mean(axis=1)


@pytest.mark.parametrize("accum", [1, 2])
def test_a_pad_and_mask_tail_fit_is_the_mean_over_real_rows(
        session, tmp_path, accum):
    """Six rows in batches of four over ``data`` 2, ``drop_last=False``: the
    second batch is two real rows and two pad rows under a mask. The step
    hands the model ``mask / sum(mask)`` and differentiates the scalar it
    gets back; losses, eval loss and parameters are those of a hand-written
    loop over the plain loss's mean over the REAL rows (what the step's
    ``_masked_mean`` of the rows gave before), with ``accum_steps`` 2 too
    (the tail's second microbatch is all pad), and the step is counted
    once."""
    import jax
    import optax
    from raydp_tpu.parallel import make_mesh

    cfg, pipeline, _ = _files()
    _dense(cfg)
    df, info, table = _token_frame(session, tmp_path, cfg, pipeline, 6, 7)
    mesh = make_mesh({"data": 2}, devices=jax.devices()[:2])
    before = _steps_counted()
    est = _estimator(cfg, pipeline, info, mesh, num_epochs=2, batch_size=4,
                     accum_steps=accum, drop_last=False)
    history = est.fit_on_frame(df, evaluate_df=df).history
    assert _steps_counted() == before + 1
    assert [e["steps"] for e in history] == [2, 2]

    tokens = pipeline.reference_inputs(table, info)
    model = est._build_model()
    tx = pipeline.build_optimizer(cfg)
    params, _ = _variables(model, tokens)               # as the fit's are
    opt_state = tx.init(params)
    plain = jax.jit(lambda p, t: _plain_rows(model, p, t).mean())
    grad = jax.jit(jax.value_and_grad(plain))
    want, want_eval = [], []
    for _ in range(2):
        losses = []
        for batch in (tokens[:4], tokens[4:]):
            loss, g = grad(params, batch)
            losses.append(float(loss))
            updates, opt_state = tx.update(g, opt_state, params)
            params = optax.apply_updates(params, updates)
        want.append(np.mean(losses))
        want_eval.append(float(plain(params, tokens)))
    np.testing.assert_allclose([e["train_loss"] for e in history], want,
                               rtol=2e-4)
    np.testing.assert_allclose([e["eval_loss"] for e in history], want_eval,
                               rtol=2e-4)
    got = _leaves(jax.tree.map(np.asarray, est.get_state().params))
    for name, leaf in _leaves(params).items():
        np.testing.assert_allclose(got[name], leaf, rtol=2e-3, atol=2e-5,
                                   err_msg=name)


@pytest.mark.parametrize("mesh_spec,path,shape,role,spec", [
    # the stacked expert kernels and their AdamW mirrors: dim 0 over expert
    (dict(expert=4), "params/block_0/moe/experts_gate", (64, 32, 16),
     "expert", ("expert",)),
    (dict(expert=4), "opt_state/1/0/mu/block_0/moe/experts_down",
     (64, 16, 32), "expert", ("expert",)),
    (dict(expert=4), "opt_state/1/0/nu/block_0/moe/experts_up",
     (8, 32, 16), "expert", ("expert",)),
    # the axis does not divide the stack: replicated, never an error
    (dict(expert=4), "params/block_0/moe/experts_gate", (6, 32, 16),
     "expert", ()),
    # no expert axis on the mesh: each expert's kernel takes a kernel's spec
    (dict(fsdp=4, tensor=2), "params/block_0/moe/experts_gate",
     (64, 32, 16), "expert", (None, "fsdp", "tensor")),
    (dict(expert=2, tensor=2), "params/block_0/moe/experts_down",
     (64, 16, 32), "expert", ("expert", None, "tensor")),
    # the router is 2-D: a kernel; a 3-D leaf that names no expert too
    (dict(expert=4), "params/block_0/moe/router", (32, 64), "kernel", ()),
    (dict(expert=4), "params/block_0/attn/q/kernel", (32, 4, 8), "kernel",
     ()),
])
def test_the_expert_role(mesh_spec, path, shape, role, spec):
    import jax
    from jax.sharding import PartitionSpec as P

    from raydp_tpu.parallel import make_mesh
    from raydp_tpu.parallel.roles import classify_param, role_partition_spec

    size = int(np.prod(list(mesh_spec.values())))
    mesh = make_mesh(dict(mesh_spec, data=1), devices=jax.devices()[:size])
    assert classify_param(path, shape) == role
    assert role_partition_spec(mesh, path, shape) == P(*spec)


def test_a_remat_policy_may_name_the_expert_role():
    from raydp_tpu.parallel.roles import (parse_remat_policy,
                                          remat_mode_for_role, segment_role)
    policy = parse_remat_policy("expert=dots,default=none")
    assert remat_mode_for_role(policy, "expert") == "dots"
    assert remat_mode_for_role(policy, "kernel") == "none"
    # a block whose bytes are mostly stacked expert kernels is an expert
    # segment: that role's mode is what its forward runs under
    tree = {"block_0": {"moe": {"experts_gate": np.zeros((8, 32, 16)),
                                "router": np.zeros((32, 8))},
                        "attn": {"q": {"kernel": np.zeros((32, 4, 8))}}}}
    assert segment_role(tree) == "expert"


@pytest.mark.parametrize("source,dtype", [("int32", np.int32),
                                          ("int64", np.int32),
                                          ("float32", np.float32),
                                          ("float64", np.int32)])
def test_a_token_column_is_staged_in_one_flat_pass(source, dtype):
    """One fixed-size-list column -> ``[rows, list_size]``: natively where
    the dtype pair is eligible, by numpy where it is not (float -> int is
    declined), the same array either way, over chunks and a sliced offset."""
    import pyarrow as pa
    from raydp_tpu import metrics as registry
    from raydp_tpu.data.feed import _as_numpy
    from raydp_tpu.native.stage import native_stage_available

    flat = np.arange(7 * 5, dtype=source)
    whole = pa.FixedSizeListArray.from_arrays(pa.array(flat), 5)
    col = pa.chunked_array([whole.slice(1, 2), whole.slice(3, 4)])
    table = pa.table({"tokens": col})
    before = registry.snapshot()["counters"].get(
        "feed_staged_tables_total", {})
    got = _as_numpy(table, ("tokens",), dtype)
    assert got.dtype == dtype and got.shape == (6, 5)
    np.testing.assert_array_equal(got, flat.reshape(7, 5)[1:].astype(dtype))
    after = registry.snapshot()["counters"]["feed_staged_tables_total"]
    native = native_stage_available() and source != "float64"
    path = "native" if native else "numpy"
    assert after.get(path, 0) == before.get(path, 0) + 1
    holed = pa.table({"tokens": pa.array([[1, 2], None],
                                         pa.list_(pa.int32(), 2))})
    with pytest.raises(ValueError, match="nulls"):
        _as_numpy(holed, ("tokens",), np.int32)
