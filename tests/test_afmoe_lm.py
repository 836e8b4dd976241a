"""Trinity-style LM (``afmoe``: sigmoid routing picked by score + a balancing
bias that no gradient moves, a shared expert, a leading dense layer, gated
attention with a norm a head, four norms a block, scaled embeddings; one chip
holds a share of the experts): the routing, the expert layer's shares, the
bias through the train step (accumulated, recomputed, saved and restored),
the whole model and a fit, against the plain reference
(``chipbench/reference/trinity-mini.py``: float32 ``jax.numpy``, attention by
blocks of queries, every held expert on every token), at small sizes on the
CPU, seeded random weights. Widths are small here, and only here (the fit at
the CPU cut keeps them).
"""

import functools
import os

import numpy as np
import pytest

from tests import lm_testing
from tests.lm_testing import (F32_TOL, ROOT, close as _close,
                              leaves as _leaves, tokens as _tokens,
                              train_step as _train_step,
                              variables as _variables)

CONFIG = "trinity-mini"

# 8 query heads on 1 K/V head (eight a group, as published), the dense layer
# and the period of four expert layers, 16 experts of which experts 2-3 are
# held, 4 a token, 64 of 512 vocabulary rows, 32 positions with a window of 8
TINY = {"hidden_size": 32, "head_dim": 8, "num_attention_heads": 8,
        "num_key_value_heads": 1, "intermediate_size": 48,
        "moe_intermediate_size": 16, "num_experts": 16, "first_expert": 2,
        "experts_held": 2, "num_experts_per_tok": 4, "vocab_size": 512,
        "vocab_rows_held": 64, "seq_len": 32, "sliding_window": 8,
        "compared_positions": 8, "compute_dtype": "float32",
        "attention": "dense", "init_std": 0.3, "remat_blocks": False}
_files = functools.partial(lm_testing.files, CONFIG, TINY)


def _f32(tree):
    import jax
    return jax.tree.map(lambda a: np.asarray(a, np.float32), tree)


# ------------------------------------------------------- (a) the routing
def test_sigmoid_routing_picks_by_score_plus_bias_and_weighs_by_score():
    """A bias large enough to change the pick changes which experts are
    weighed and leaves the weights' formula alone: the bare scores of the
    chosen, over their sum, times the scale; and no gradient reaches it."""
    import jax
    import jax.numpy as jnp
    from raydp_tpu.models.moe import route

    rng = np.random.default_rng(0)
    logits = jnp.asarray(rng.normal(size=(64, 16)), jnp.float32)
    scores = np.asarray(jax.nn.sigmoid(logits))
    plain = route(logits, 4, True, "sigmoid", None, 2.826)
    np.testing.assert_array_equal(plain[0], scores)
    np.testing.assert_array_equal(
        np.sort(plain[1], -1), np.sort(np.argsort(-scores, -1)[:, :4], -1))
    # a bias that lifts the four lowest experts of every token's scores
    # over all the others: they are picked, and weighed by their own scores
    bias = np.zeros(16, np.float32)
    bias[[3, 7, 11, 12]] = 5.0
    _, ids, weights = route(logits, 4, True, "sigmoid", jnp.asarray(bias),
                            2.826)
    assert set(np.unique(ids)) == {3, 7, 11, 12}
    assert not np.array_equal(np.sort(ids, -1), np.sort(plain[1], -1))
    chosen = np.take_along_axis(scores, np.asarray(ids), -1)
    np.testing.assert_allclose(
        weights, 2.826 * chosen / (chosen.sum(-1, keepdims=True) + 1e-20),
        rtol=1e-6)
    np.testing.assert_allclose(weights.sum(-1), 2.826, rtol=1e-5)
    # not renormalised: the bare scores times the scale
    np.testing.assert_allclose(
        route(logits, 4, False, "sigmoid", jnp.asarray(bias), 1.5)[2],
        1.5 * chosen, rtol=1e-6)
    grad = jax.grad(lambda b: jnp.sum(route(
        logits, 4, True, "sigmoid", b, 2.826)[2] ** 2))(jnp.asarray(bias))
    assert not np.any(np.asarray(grad))
    with pytest.raises(ValueError, match="softmax"):
        route(logits, 4, kind="tanh")


def test_softmax_routing_is_the_routing_as_it_was():
    """The two older language models' call: the lowered text of ``route``
    with the defaults is what the softmax-only function lowered to."""
    import jax
    import jax.numpy as jnp
    from raydp_tpu.models.moe import route

    def before(logits, top_k, normalize):
        probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
        weights, ids = jax.lax.top_k(probs, top_k)
        if normalize:
            weights = weights / jnp.sum(weights, axis=-1, keepdims=True)
        return probs, ids, weights

    logits = jnp.zeros((8, 16), jnp.float32)
    for normalize in (False, True):
        assert str(jax.make_jaxpr(lambda x: route(x, 4, normalize))(
            logits)) == str(jax.make_jaxpr(lambda x: before(
                x, 4, normalize))(logits))


# ------------------------------------- (b) the shares of one expert layer
def _expert_layer(seed=0, shared_dim=16, gated=True):
    """An uncut layer's seeded weights (router, 16 experts, the shared
    expert of width ``shared_dim``; without a gate's kernels where the
    experts are of two matrices), a bias that changes picks, and tokens."""
    rng = np.random.default_rng(seed)
    d, f, e, n, s = 32, 16, 16, 48, shared_dim
    full = {"router": rng.normal(0, 0.3, (d, e)),
            "experts_gate": rng.normal(0, 0.3, (e, d, f)),
            "experts_up": rng.normal(0, 0.3, (e, d, f)),
            "experts_down": rng.normal(0, 0.3, (e, f, d)),
            "shared_gate": {"kernel": rng.normal(0, 0.3, (d, s))},
            "shared_up": {"kernel": rng.normal(0, 0.3, (d, s))},
            "shared_down": {"kernel": rng.normal(0, 0.3, (s, d))}}
    if not gated:
        del full["experts_gate"], full["shared_gate"]
    return (_f32(full), _f32(rng.normal(0, 0.2, (e,))),
            _f32(rng.normal(size=(n, d))))


LAYER_CFG = {"num_experts": 16, "num_experts_per_tok": 4, "route_norm": True,
             "route_scale": 2.826, "num_shared_experts": 1,
             "first_expert": 0, "experts_held": 16}


def _share_of(full, first, held, shared):
    return {k: v[first:first + held] if k.startswith("experts_") else v
            for k, v in full.items()
            if shared or not k.startswith("shared_")}


# a second family's expert layer of the same kind, in its own file's keys
# (kanana-2-30b-a3b: 6 a token, two shared experts as one MLP, times 2.448)
KANANA_LAYER_CFG = {"n_routed_experts": 16, "num_experts_per_tok": 6,
                    "norm_topk_prob": True, "routed_scaling_factor": 2.448,
                    "n_shared_experts": 2, "first_expert": 0,
                    "experts_held": 16}


# a third family's: experts of TWO matrices with relu(.)^2, 6 a token, one
# shared expert twice an expert's width, times 2.5 (nemotron-3-nano-30b-a3b)
NEMOTRON_LAYER_CFG = {"n_routed_experts": 16, "num_experts_per_tok": 6,
                      "norm_topk_prob": True, "routed_scaling_factor": 2.5,
                      "n_shared_experts": 1, "first_expert": 0,
                      "experts_held": 16}
NEMOTRON = {"gated": False, "activation": "relu2"}


def _program_layer(params, bias, m, first, held, shared, mutable=False,
                   top_k=4, scale=2.826, shared_dim=16, gated=True,
                   activation="silu"):
    from raydp_tpu.models.moe import STATE, MoE
    layer = MoE(16, top_k, 16, first_expert=first, experts_held=held,
                normalize_top_k=True, routing="sigmoid", route_scale=scale,
                shared_dim=shared_dim if shared else 0, gated=gated,
                activation=activation)
    variables = {"params": params, STATE: {
        "bias": bias, "counts": np.zeros(16, np.float32)}}
    if mutable:
        return layer.apply(variables, m, mutable=[STATE])
    return layer.apply(variables, m)


@pytest.mark.parametrize("config,layer_cfg,none_shared,form,held", [
    (CONFIG, LAYER_CFG, "num_shared_experts", {}, 2),
    ("kanana-2-30b-a3b", KANANA_LAYER_CFG, "n_shared_experts", {}, 2),
    ("nemotron-3-nano-30b-a3b", NEMOTRON_LAYER_CFG, "n_shared_experts",
     NEMOTRON, 1)], ids=["trinity", "kanana", "nemotron_sixteen_shares"])
def test_the_eight_shares_of_an_expert_layer_add_up_to_the_uncut_layer(
        config, layer_cfg, none_shared, form, held):
    """Experts 0-1, 2-3, ... 14-15 of 16, 4 a token (6 in the second family,
    beside a shared MLP two experts wide; in the third SIXTEEN shares of one
    expert each, 6 a token, experts of two matrices with ``relu(.)^2``): each
    chip routes over all sixteen (by score + bias, the weights over all the
    choices) and computes its own experts' part; the routed parts and the
    shared expert, counted once, sum to the family's own reference's uncut
    layer, and the held slots to all slots. A share that holds the shared
    expert carries it whole."""
    from chipbench import manifest
    reference = manifest.load_module(ROOT, "reference", f"{config}.py")
    top_k = layer_cfg["num_experts_per_tok"]
    sizes = {"top_k": top_k, "scale": layer_cfg.get(
        "route_scale", layer_cfg.get("routed_scaling_factor")),
        "shared_dim": 16 * layer_cfg[none_shared] * (1 if form == {} else 2)}
    layer_of = functools.partial(_program_layer, **sizes, **form)
    full, bias, m = _expert_layer(shared_dim=sizes["shared_dim"],
                                  gated=form.get("gated", True))
    assert ("experts_gate" in full) == form.get("gated", True)
    want = np.asarray(reference.expert_layer(full, m, bias, layer_cfg))
    shared = np.asarray(reference.expert_layer(
        _share_of(full, 0, 0, True), m, bias,
        dict(layer_cfg, experts_held=0)))
    parts, held_slots = [], 0.0
    for first in range(0, 16, held):
        y, aux = layer_of(_share_of(full, first, held, False), bias, m,
                          first, held, shared=False)
        one = dict(layer_cfg, first_expert=first, experts_held=held,
                   **{none_shared: 0})
        np.testing.assert_allclose(
            y, reference.expert_layer(_share_of(full, first, held, False), m,
                                      bias, one), rtol=1e-4, atol=1e-5)
        with_shared, _ = layer_of(_share_of(full, first, held, True), bias,
                                  m, first, held, shared=True)
        np.testing.assert_allclose(with_shared, np.asarray(y) + shared,
                                   rtol=1e-4, atol=1e-5)
        parts.append(np.asarray(y))
        held_slots += float(aux["slots_held"])
        assert float(aux["slots_all"]) == top_k * 48
        assert float(aux["bias_spread"]) == pytest.approx(
            bias.max() - bias.min())
    np.testing.assert_allclose(sum(parts) + shared, want, rtol=1e-4,
                               atol=1e-5)
    assert held_slots == top_k * 48
    assert np.abs(shared).max() > 0.1 and np.abs(sum(parts)).max() > 0.1
    # the uncut program layer is the same sum, and counts no share
    y, aux = layer_of(full, bias, m, 0, 16, shared=True)
    np.testing.assert_allclose(y, want, rtol=1e-4, atol=1e-5)
    assert "slots_held" not in aux
    # the bias moved picks: without it the layer is another
    assert np.abs(np.asarray(reference.expert_layer(
        full, m, np.zeros(16, np.float32), layer_cfg)) - want).max() > 0.01


@pytest.mark.parametrize("form", ["gated", "two_matrices"])
@pytest.mark.parametrize("first,held", [(0, 2), (6, 2), (14, 2), (0, 16)])
def test_a_shares_gradients_and_counts_match_the_references(first, held,
                                                            form):
    """Of the router, the held kernels, the shared expert and the input; the
    bias gets none. Under a mutable collection the forward adds the slots of
    ALL sixteen experts to the counts and leaves the bias alone; called
    plainly it writes nothing. With gated experts (the walk's three grouped
    products a trip) and with experts of two matrices and ``relu(.)^2`` (two
    a trip), each against its family's dense reference: the held share's
    walk where two are held, the single-shot path where all sixteen are."""
    import jax
    import jax.numpy as jnp
    from chipbench import manifest
    from raydp_tpu.models.moe import STATE
    gated = form == "gated"
    reference = _files()[2] if gated else manifest.load_module(
        ROOT, "reference", "nemotron-3-nano-30b-a3b.py")
    sizes = {} if gated else dict(NEMOTRON, top_k=6, scale=2.5)
    full, bias, m = _expert_layer(seed=first + 1, gated=gated)
    params = _share_of(full, first, held, True)
    cfg = dict(LAYER_CFG if gated else NEMOTRON_LAYER_CFG,
               first_expert=first, experts_held=held)
    w = np.random.default_rng(9).normal(size=m.shape).astype(np.float32)
    _program_layer = functools.partial(globals()["_program_layer"], **sizes)
    got = jax.jit(jax.grad(lambda p, b, m: jnp.sum(_program_layer(
        p, b, m, first, held, True)[0] * w), (0, 1, 2)))(params, bias, m)
    want = jax.jit(jax.grad(lambda p, m: jnp.sum(reference.expert_layer(
        p, m, bias, cfg) * w), (0, 1)))(params, m)
    _close((got[0], got[2]), want)
    assert not np.any(np.asarray(got[1]))
    assert np.abs(np.asarray(want[0]["router"])).max() > 1e-3
    (_, aux), updates = _program_layer(params, bias, m, first, held, True,
                                       mutable=True)
    ids = np.asarray(reference._experts(params, jnp.asarray(m), bias, cfg)[1])
    np.testing.assert_array_equal(updates[STATE]["counts"],
                                  np.bincount(ids.ravel(), minlength=16))
    np.testing.assert_array_equal(updates[STATE]["bias"], bias)
    assert float(aux["slots_max"]) == np.bincount(ids.ravel()).max()


# ------------------------------------------------ (c) the flash op's sizes
@pytest.mark.parametrize("heads", [16, 32],
                         ids=["group_of_8", "group_of_16"])
@pytest.mark.parametrize("widths", [(16, 16), (192, 128)],
                         ids=["one_width", "keys_192_values_128"])
@pytest.mark.parametrize("interpret", [False, True], ids=["jnp", "kernel"])
@pytest.mark.parametrize("t,window", [(64, 16), (64, None), (32, 48)])
def test_flash_with_a_group_of_eight_matches_dense_masked_attention(
        t, window, interpret, widths, heads):
    """8 query heads a K/V head (Trinity's group; SmallThinker's is seven),
    and 16 (Nemotron-3-Nano's 32 on 2; no rotation is applied here or there),
    through the op's jnp path and its kernels in interpret mode, with a
    window a quarter of the sequence (as 2048 is of 8,192), without one, and
    with one longer than the sequence: forward and all three gradients; at
    one width, and with keys of 192 beside values of 128 (latent attention's
    widths, which its own model runs with neither a group nor a window)."""
    import jax
    import jax.numpy as jnp
    from raydp_tpu.ops.flash_attention import flash_attention
    from raydp_tpu.ops.ring_attention import dense_attention

    rng = np.random.default_rng(t + (window or 0))
    d, d_v = widths
    q = jnp.asarray(rng.normal(size=(1, t, heads, d)), jnp.float32)
    k, v = (jnp.asarray(rng.normal(size=(1, t, 2, n)), jnp.float32)
            for n in widths)
    w = jnp.asarray(rng.normal(size=(1, t, heads, d_v)), jnp.float32)
    flash = lambda q, k, v: flash_attention(  # noqa: E731
        q, k, v, causal=True, window=window, block_q=16, block_k=16,
        interpret=interpret)
    dense = lambda q, k, v: dense_attention(  # noqa: E731
        q, k, v, causal=True, window=window)
    np.testing.assert_allclose(flash(q, k, v), dense(q, k, v), rtol=2e-4,
                               atol=2e-5)
    got = jax.grad(lambda *a: jnp.sum(flash(*a) * w), (0, 1, 2))(q, k, v)
    want = jax.grad(lambda *a: jnp.sum(dense(*a) * w), (0, 1, 2))(q, k, v)
    for g, x in zip(got, want):
        np.testing.assert_allclose(g, x, rtol=2e-3, atol=2e-4)


# ----------------------------------------------------- (d) the whole model
def test_the_parameter_tree_is_the_published_layers():
    """Layer 0 dense (SwiGLU of the dense width), then four expert layers
    with router, held experts and the shared expert; the gate and a norm a
    head in attention, four norms a block, no bias; the routing bias and its
    counts in the estimator's collection, outside the parameters."""
    from raydp_tpu.models.moe import STATE
    cfg, pipeline, _ = _files()
    model = pipeline.build_model(cfg)
    params, state = _variables(model, _tokens(cfg, 1))
    shapes = {k: v.shape for k, v in _leaves(params).items()}
    attn = {"attn/q/kernel": (32, 8, 8), "attn/k/kernel": (32, 1, 8),
            "attn/v/kernel": (32, 1, 8), "attn/o/kernel": (8, 8, 32),
            "attn/gate/kernel": (32, 8, 8), "attn/q_norm/scale": (8,),
            "attn/k_norm/scale": (8,), "ln1/scale": (32,),
            "ln1_post/scale": (32,), "ln2/scale": (32,),
            "ln2_post/scale": (32,)}
    want = {f"block_0/{k}": v for k, v in attn.items()}
    want.update({"block_0/gate/kernel": (32, 48),
                 "block_0/up/kernel": (32, 48),
                 "block_0/down/kernel": (48, 32)})
    assert {k: v for k, v in shapes.items() if k.startswith("block_0/")} \
        == want
    want = {f"block_4/{k}": v for k, v in attn.items()}
    want.update({"block_4/moe/router": (32, 16),
                 "block_4/moe/experts_gate": (2, 32, 16),
                 "block_4/moe/experts_up": (2, 32, 16),
                 "block_4/moe/experts_down": (2, 16, 32),
                 "block_4/moe/shared_gate/kernel": (32, 16),
                 "block_4/moe/shared_up/kernel": (32, 16),
                 "block_4/moe/shared_down/kernel": (16, 32)})
    assert {k: v for k, v in shapes.items() if k.startswith("block_4/")} \
        == want
    assert shapes["embed/embedding"] == (64, 32)
    assert shapes["lm_head/kernel"] == (32, 64)
    assert {k: v.shape for k, v in _leaves(state).items()} == {
        f"block_{i}/moe/{name}": (16,) for i in (1, 2, 3, 4)
        for name in ("bias", "counts")}
    assert model.attention_layers == {"window": 4, "full": 1}
    assert [model._windowed(i) for i in range(5)] == [1, 1, 1, 1, 0]
    assert [model._rope(i) for i in range(5)] == [1, 1, 1, 1, 0]
    assert [model._sparse(i) for i in range(5)] == [0, 1, 1, 1, 1]
    assert model.loss_counters == (
        ("moe_slots_total", "max_expert"), ("moe_slots_total", "all"),
        ("moe_slots_total", "held"), ("moe_slots_total", "moved"),
        ("moe_router_bias_spread", ""))
    assert STATE == "batch_stats"


@pytest.mark.parametrize("dtype,attention,tol", [
    ("float32", "dense", 10 * F32_TOL), ("float32", "flash", 10 * F32_TOL),
    ("bfloat16", "flash", 0.15)])
def test_forward_logits_match_the_reference(dtype, attention, tol):
    """What check (a) compares, with biases that move picks: float32 to
    rounding on both attention paths; bfloat16 inside what near-tied picks
    cost under four norms a block (every sub-layer's output is normed to
    unit size, so a flipped expert is not small beside the stream)."""
    from chipbench.harness import relative_rms_error
    cfg, pipeline, _ = _files(compute_dtype=dtype,
                                      attention=attention)
    model = pipeline.build_model(cfg)
    tokens = _tokens(cfg, 2, seed=5)
    params, state = _variables(model, tokens, bias_std=0.1)
    variables = {"params": params, "batch_stats": state}
    got = pipeline.compared(lm_testing.logits(model, variables, tokens), cfg)
    forward = lm_testing.reference_program(CONFIG, cfg, "forward")
    want = forward(variables, tokens)
    assert got.shape == want.shape == (2, 8, 64)
    assert relative_rms_error(np.asarray(got, np.float32), want) <= tol
    # the biases matter to the outputs compared
    zero = forward({"params": params}, tokens)
    assert relative_rms_error(zero, want) > 100 * F32_TOL


def test_the_reference_by_blocks_of_queries_is_the_reference():
    import jax
    cfg, pipeline, reference = _files()
    model = pipeline.build_model(cfg)
    tokens = _tokens(cfg, 1, seed=2)
    params, _ = _variables(model, tokens)
    forward = lambda: jax.jit(lambda p: reference.forward(  # noqa: E731
        {"params": p}, tokens, cfg))(params)
    whole = forward()
    block, reference.QUERY_BLOCK = reference.QUERY_BLOCK, 8
    try:
        blocked = forward()
    finally:
        reference.QUERY_BLOCK = block
    np.testing.assert_allclose(blocked, whole, rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("remat,accum,attention,forward", [
    (False, 1, "dense", "once"), (True, 1, "dense", "twice"),
    (True, 2, "dense", "twice"), (False, 1, "flash", "once"),
    (True, 1, "flash", "once"), (True, 2, "flash", "once")],
    ids=["kept", "recomputed", "recomputed_accum2", "kept_flash",
         "recomputed_flash", "recomputed_flash_accum2"])
def test_loss_gradients_and_the_bias_after_three_steps_match_the_reference(
        remat, accum, attention, forward, forward_flash_kernels):
    """The model's own loss (fused head over the rows held, no auxiliary
    loss) and the gradient of every leaf, with seeded biases; then three
    optimizer steps of the estimator's train step: after each, every layer's
    bias is the reference's ``next_bias`` of the slots ALL experts were
    picked for in the step's tokens (both micro-batches together under
    ``accum_steps`` 2: one update a step), the counts are empty again, and
    the forward of a step used the bias the step before left. With the flash
    kernels (interpret mode) a recomputed block keeps the kernel's output and
    row sums: the built step's program holds one forward kernel a layer and
    micro-batch and counts its five layers ``once``; recomputed dense
    attention counts them ``twice``."""
    import jax
    import optax
    cfg, pipeline, reference = _files(remat_blocks=remat, attention=attention)
    model = pipeline.build_model(cfg)
    assert model.attention_forward == {forward: 5}
    tokens = _tokens(cfg, 4, seed=1)
    params, state = _variables(model, tokens, bias_std=0.1)
    w = np.full(4, 0.25, np.float32)
    (loss, counts), grads = lm_testing.loss_and_grads(model, params, state,
                                                      tokens, w)
    want_loss, want_grads = lm_testing.reference_program(
        CONFIG, cfg, "loss", grad=True)(params, state, tokens)
    assert abs(float(loss) - float(want_loss)) <= F32_TOL * float(want_loss)
    _close(grads, want_grads)
    counts_of = lm_testing.reference_program(CONFIG, cfg, "slot_counts")
    picked = np.stack(counts_of(params, state, tokens))
    assert float(counts[1]) == tokens.size * 4 * 4      # top-4, four layers
    assert float(counts[0]) == picked.max(axis=1).sum()
    assert float(counts[2]) == picked[:, 2:4].sum() < float(counts[1])
    spread = max(float(b["moe"]["bias"].max() - b["moe"]["bias"].min())
                 for b in state.values())
    assert float(counts[4]) == pytest.approx(spread)

    before = lm_testing.counters()
    step, create, arguments = _train_step(model, optax.sgd(0.05), accum)
    assert lm_testing.moved(before, "train_attention_forward_total") == {
        forward: 5}
    now = create(params, state)
    traced = jax.jit(step).trace(*arguments(now, tokens))
    # a scan over the micro-batches holds its body once
    assert forward_flash_kernels(traced.jaxpr) == (
        5 if attention == "flash" else 0)
    run = traced.lower().compile()      # the one trace, run three times
    bias = {name: b["moe"]["bias"] for name, b in state.items()}
    for i in range(3):
        batch = _tokens(cfg, 4, seed=10 + i)
        before = jax.tree.map(np.asarray, (now.params, now.batch_stats))
        now, _, stats = run(*arguments(now, batch))
        want_counts = counts_of(*before, batch)
        for (name, b), c in zip(sorted(bias.items()), want_counts):
            assert float(np.sum(c)) == batch.size * 4
            bias[name] = np.asarray(reference.next_bias(b, c, cfg))
            got = now.batch_stats[name]["moe"]
            np.testing.assert_allclose(got["bias"], bias[name], rtol=0,
                                       atol=1e-7)
            assert not np.any(np.asarray(got["counts"]))
            # delta - mean(delta): steps of +-0.001, centred
            moved = np.asarray(got["bias"]) - before[1][name]["moe"]["bias"]
            assert abs(moved.sum()) < 1e-6
            assert np.abs(moved).max() <= 2 * cfg["load_balance_coeff"]
        assert stats[0][1] == batch.size * 4 * 4
    assert any(np.abs(bias[n] - state[n]["moe"]["bias"]).max() > 1e-3
               for n in bias)


@pytest.mark.parametrize("attention", ["dense", "flash"])
def test_a_recomputed_block_keeps_what_its_second_norms_read(
        attention, forward_flash_kernels, grouped_products,
        policy_without_sublayer_out):
    """The two-layer cut (the dense layer, one expert layer). A norm's
    backward reads its input, so under ``sandwich_norms`` a recomputed block
    that kept its flash kernel's pair alone had to rebuild both sub-layers'
    outputs: the held experts' whole forward walk once more. It keeps the
    feed-forward's (``SUBLAYER_OUT``): the built step holds 11 grouped
    products an expert layer (3 forward, 2 the backward re-forms, 6 in the
    backward) as with nothing recomputed, 14 with the name left out of the
    policy; the step counts two outputs ``kept`` and the attention's two
    ``rebuilt``; loss and gradients are the unrecomputed model's."""
    import jax
    import optax

    def built(remat):
        cfg, pipeline, _ = _files(layers=2, layers_held=[0, 7],
                                  remat_blocks=remat, attention=attention)
        return cfg, pipeline.build_model(cfg)

    cfg, plain = built(False)
    _, recomputed = built(True)
    assert (plain.sublayer_out, recomputed.sublayer_out) == (
        {}, {"kept": 2, "rebuilt": 2})
    tokens = _tokens(cfg, 4, seed=2)
    params, state = _variables(plain, tokens, bias_std=0.1)
    w = np.full(4, 0.25, np.float32)
    (loss, _), grads = lm_testing.loss_and_grads(recomputed, params, state,
                                                 tokens, w)
    (want_loss, _), want_grads = lm_testing.loss_and_grads(
        plain, params, state, tokens, w)
    assert abs(float(loss) - float(want_loss)) <= F32_TOL * float(want_loss)
    _close(grads, want_grads)

    def products(model):
        before = lm_testing.counters()
        step, create, arguments = _train_step(model, optax.sgd(0.05))
        moved = lm_testing.moved(before, "train_sublayer_out_total")
        return grouped_products(jax.make_jaxpr(jax.jit(step))(
            *arguments(create(params, state), tokens))), moved

    assert products(plain) == (11, {})
    assert products(recomputed) == (11, {"kept": 2, "rebuilt": 2})
    policy_without_sublayer_out()
    assert products(recomputed)[0] == 14


def test_the_bias_has_no_gradient_no_decay_and_no_moments():
    """The optimizer's state holds moments for the parameters and nothing
    for the bias; AdamW with decay on the matrices moves every parameter and
    the bias moves by the balancing step alone, whatever the learning rate."""
    import jax
    cfg, pipeline, _ = _files()
    model = pipeline.build_model(cfg)
    tokens = _tokens(cfg, 4, seed=3)
    params, state = _variables(model, tokens, bias_std=0.1)
    step, create, arguments = _train_step(model, pipeline.build_optimizer(
        dict(cfg, optimizer=dict(cfg["optimizer"], warmup_steps=1,
                                 learning_rate=0.1))))
    run = jax.jit(step)
    now = create(params, state)
    n_params = sum(v.size for v in _leaves(params).values())
    moments = [v for v in jax.tree.leaves(now.opt_state) if np.ndim(v) > 0]
    assert sum(v.size for v in moments) == 2 * n_params     # mu and nu
    now, _, _ = run(*arguments(now, tokens))    # the first step's rate is 0
    now, _, _ = run(*arguments(now, tokens))
    for name, leaf in _leaves(now.params).items():
        assert np.abs(leaf - _leaves(params)[name]).max() > 1e-4, name
    # decay on matrices only: with no gradient at all, a matrix shrinks and
    # a norm's weight stays
    tx = pipeline.build_optimizer(dict(cfg, optimizer=dict(
        cfg["optimizer"], warmup_steps=1)))
    zero, opt = jax.tree.map(np.zeros_like, params), tx.init(params)
    _, opt = tx.update(zero, opt, params)       # the rate is 0 here
    updates, _ = tx.update(zero, opt, params)
    for name, leaf in _leaves(updates).items():
        assert np.any(leaf) == (leaf.ndim >= 2), name
    for name, block in now.batch_stats.items():
        moved = np.asarray(block["moe"]["bias"]) - state[name]["moe"]["bias"]
        assert np.abs(moved).max() <= 2 * 2 * cfg["load_balance_coeff"] + 1e-7


def test_the_bias_survives_save_restore_and_one_more_step_bit_for_bit(
        tmp_path):
    """``train/checkpoint.py`` writes the collection beside the parameters
    and the optimizer's state; a step from the restored state is the step
    from the saved one, in every leaf."""
    import jax
    import optax
    from raydp_tpu.train import checkpoint as ckpt
    cfg, pipeline, _ = _files(remat_blocks=True)
    model = pipeline.build_model(cfg)
    tokens = _tokens(cfg, 4, seed=4)
    params, state = _variables(model, tokens)
    step, create, arguments = _train_step(model, optax.adam(1e-2), 2)
    run = jax.jit(step)
    now = create(params, state)
    for i in range(2):
        now, _, _ = run(*arguments(now, _tokens(cfg, 4, seed=20 + i)))
    assert any(np.any(b["moe"]["bias"]) for b in now.batch_stats.values())
    ckpt.save(str(tmp_path), now, 2)
    restored, at = ckpt.restore(str(tmp_path), now)
    assert at == 2
    for name, leaf in _leaves(restored.batch_stats).items():
        np.testing.assert_array_equal(leaf, _leaves(now.batch_stats)[name])
    more = _tokens(cfg, 4, seed=30)
    a, loss_a, _ = run(*arguments(now, more))
    b, loss_b, _ = run(*arguments(now.replace(
        params=restored.params, opt_state=restored.opt_state,
        batch_stats=restored.batch_stats, step=restored.step), more))
    assert float(loss_a) == float(loss_b)
    for got, want in ((b.params, a.params), (b.batch_stats, a.batch_stats),
                      (b.opt_state, a.opt_state)):
        got, want = _leaves(got), _leaves(want)
        assert set(got) == set(want)
        for name in want:
            np.testing.assert_array_equal(got[name], want[name], name)


def test_older_models_have_no_state_and_the_options_default_off():
    """A model without sigmoid routing carries no collection, brings no
    ``after_step`` work and counts no gauge; the new options are off by
    default, so the two older language models build the blocks they built."""
    import jax
    from raydp_tpu.models import TransformerLM
    model = TransformerLM(vocab_size=32, dim=16, num_heads=2, num_layers=2,
                          num_experts=4, experts_per_token=2, ffn_dim=8,
                          qk_norm=True)
    tokens = np.zeros((1, 8), np.int32)
    variables = jax.eval_shape(model.init, jax.random.PRNGKey(0), tokens)
    assert set(variables) == {"params"}
    assert set(variables["params"]["block_0"]) == {"ln1", "ln2", "attn",
                                                   "moe"}
    assert set(variables["params"]["block_0"]["attn"]) == {
        "q", "k", "v", "o", "q_norm", "k_norm"}
    assert variables["params"]["block_0"]["attn"]["q_norm"]["scale"].shape \
        == (16,)
    assert model.after_step(None) is None
    assert model.after_step({"x": 1}) == {"x": 1}
    assert model.loss_counters == (("moe_slots_total", "max_expert"),
                                   ("moe_slots_total", "all"))


# -------------------------------------------------------------- (e) a fit
def test_fit_on_frame_at_the_cpu_cut_learns_counts_and_balances(session,
                                                                tmp_path):
    """The cell's own CPU cut (the dense layer and one expert layer, 16
    experts of which 2 held, 8 a token, 512 of 4096 vocabulary rows, 256
    positions with a window of 64, no width cut) through ``fit_on_frame``
    with ``accum_steps`` 2: the loss falls, the held slots are some and not
    all of the slots, the bias has moved by whole steps and is in the state
    the fit hands back, the gauge reads its spread."""
    import jax
    import pyarrow.parquet as pq
    from chipbench import manifest
    from raydp_tpu import metrics as registry
    from raydp_tpu.parallel import make_mesh

    cfg = manifest.load_json(ROOT, "configs", f"{CONFIG}.json")
    pipeline = manifest.load_module(ROOT, "pipelines", f"{CONFIG}.py")
    wl = {"seq_len": cfg["seq_len"], "batch_per_replica": 2}
    pipeline.cpu_cut(cfg, wl, 1)
    rows = 4                # two micro-batches of one row, two steps an epoch
    assert (cfg["hidden_size"], cfg["head_dim"], cfg["intermediate_size"],
            cfg["moe_intermediate_size"]) == (2048, 128, 6144, 1024)
    cfg["compute_dtype"] = "float32"
    path = str(tmp_path / "tokens")
    os.makedirs(path)
    pq.write_table(pipeline.generate(rows, 3, cfg),
                   os.path.join(path, "part-0.parquet"))
    df, info = pipeline.etl(session.read.parquet(path), cfg, wl)
    mesh = make_mesh(None, devices=jax.devices()[:1])
    counters = registry.snapshot()["counters"]
    before = counters.get("moe_slots_total", {})
    forward = dict(counters.get("train_attention_forward_total", {}))
    outputs = dict(counters.get("train_sublayer_out_total", {}))
    est = lm_testing.estimator(cfg, pipeline, info, mesh, num_epochs=2,
                               batch_size=2, accum_steps=2,
                               checkpoint_interval=2)
    history = est.fit_on_frame(df.persist()).history
    losses = [e["train_loss"] for e in history]
    assert np.all(np.isfinite(losses)) and losses[-1] < losses[0]
    snapshot = registry.snapshot()
    slots = {k: v - before.get(k, 0)
             for k, v in snapshot["counters"]["moe_slots_total"].items()}
    steps = 2 * rows // 2
    assert slots["all"] == 2 * rows * 256 * 8       # epochs, tokens, top-8, one layer
    # the cut's two blocks are recomputed and keep their flash op's pair
    assert (cfg["remat_blocks"], cfg["attention"]) == (True, "flash")
    after = snapshot["counters"]["train_attention_forward_total"]
    assert after["once"] - forward.get("once", 0) == 2
    assert after.get("twice", 0) == forward.get("twice", 0)
    # and of the four sub-layer outputs their second norms read, the
    # feed-forwards'
    assert snapshot["counters"]["train_sublayer_out_total"] == {
        kind: outputs.get(kind, 0) + 2 for kind in ("kept", "rebuilt")}
    assert 0 < slots["held"] <= slots["moved"] < slots["all"]
    state = est.get_model()["batch_stats"]
    spreads = []
    for block in state.values():
        bias = np.asarray(block["moe"]["bias"])
        assert not np.any(np.asarray(block["moe"]["counts"]))
        assert abs(bias.sum()) < 1e-5 and np.any(bias)
        # one update an optimizer step: at most ``steps`` steps of 0.001
        # (and the centring's) from zero
        assert np.abs(bias).max() <= 2 * steps * cfg["load_balance_coeff"]
        spreads.append(bias.max() - bias.min())
    # the gauge: the widest layer's spread as the last step's forward read it
    gauge = snapshot["gauges"]["moe_router_bias_spread"][""]
    assert 0 < gauge <= max(spreads) + 1e-6
