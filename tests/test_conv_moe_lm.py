"""LFM2-style LM (``lfm2_moe``: every layer a pair whose operator is a gated
short convolution, three layers in four, or attention with a norm a head;
sigmoid routing picked by score + a balancing bias and renormalised with the
family's 1e-6; one array for embedding and head; one chip holds a share of
the experts): the whole model, its loss, every gradient leaf, the slots and
the bias through the train step, the four shares of an expert layer and a
fit, against the plain reference (``chipbench/reference/lfm2-8b-a1b.py``:
float32 ``jax.numpy``, the convolution as three shifted products, every held
expert on every token), at small sizes on the CPU, seeded random weights.
Widths are small here, and only here.
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

CONFIG = "lfm2-8b-a1b"

# 8 query heads on 2 K/V heads (four a group, as published), the seven layers
# held in the published pattern (C A C C C A C, the first one dense), 8
# experts of which experts 2-3 are held, 4 a token, 64 of 256 vocabulary
# rows, 32 positions
TINY = {"hidden_size": 32, "head_dim": 8, "num_attention_heads": 8,
        "num_key_value_heads": 2, "intermediate_size": 48,
        "moe_intermediate_size": 16, "num_experts": 8, "first_expert": 2,
        "experts_held": 2, "vocab_size": 256, "vocab_rows_held": 64,
        "seq_len": 32, "compared_positions": 8, "compute_dtype": "float32",
        "attention": "dense", "init_std": 0.3, "remat_blocks": False}
# the same, wide enough for the gated convolution's kernels (whole 128-lane
# tiles): three layers, one of each kind
KERNEL_SIZED = {"hidden_size": 128, "head_dim": 16, "layers": 3,
                "layers_held": [0, 2, 3], "layer_pattern_held": "CAC"}
_files = functools.partial(lm_testing.files, CONFIG, TINY)


@pytest.fixture
def interpreted_conv_kernels(monkeypatch):
    """A model's ``C`` layers run their Pallas kernels here, interpreted, in
    row tiles of 16 (off the chip the op would take its jnp path)."""
    from raydp_tpu.ops import short_conv as sc

    monkeypatch.setattr(sc, "gated_conv", functools.partial(
        sc.gated_conv, interpret=True, rows=16))


# ----------------------------------------------------- (a) the whole model
def test_the_parameter_tree_is_the_published_layers():
    """Layer 0 a convolution operator over the dense SwiGLU, then attention
    and convolution operators over expert layers with router and held
    experts; two norms a layer, a norm a head in attention, no bias, no
    shared expert, NO ``lm_head``: the embedding is the head; the routing
    bias and its counts in the estimator's collection."""
    cfg, pipeline, _ = _files()
    model = pipeline.build_model(cfg)
    assert model.layer_kinds == "CBCCCBC" and model.tie_embeddings
    params, state = _variables(model, _tokens(cfg, 1))
    shapes = {k: v.shape for k, v in _leaves(params).items()}
    conv = {"short_conv/in_proj/kernel": (32, 96), "short_conv/conv": (3, 32),
            "short_conv/out_proj/kernel": (32, 32), "ln1/scale": (32,),
            "ln2/scale": (32,)}
    attn = {"attn/q/kernel": (32, 8, 8), "attn/k/kernel": (32, 2, 8),
            "attn/v/kernel": (32, 2, 8), "attn/o/kernel": (8, 8, 32),
            "attn/q_norm/scale": (8,), "attn/k_norm/scale": (8,),
            "ln1/scale": (32,), "ln2/scale": (32,)}
    experts = {"moe/router": (32, 8), "moe/experts_gate": (2, 32, 16),
               "moe/experts_up": (2, 32, 16), "moe/experts_down": (2, 16, 32)}
    want = {f"block_0/{k}": v for k, v in conv.items()}
    want.update({"block_0/gate/kernel": (32, 48),
                 "block_0/up/kernel": (32, 48),
                 "block_0/down/kernel": (48, 32)})
    for i, operator in enumerate("ACCCAC", start=1):
        want.update({f"block_{i}/{k}": v for k, v in {
            **(conv if operator == "C" else attn), **experts}.items()})
    want.update({"embed/embedding": (64, 32), "ln_f/scale": (32,)})
    assert shapes == want
    assert {k: v.shape for k, v in _leaves(state).items()} == {
        f"block_{i}/moe/{name}": (8,) for i in range(1, 7)
        for name in ("bias", "counts")}
    assert model.conv_layers == {"plain": 5}
    assert model.attention_layers == {"window": 0, "full": 2}
    assert model.attention_forward == {"once": 2}
    assert model.sublayer_out == {} and model.ssm_layers == {"plain": 0}
    assert [model._sparse(i) for i in range(7)] == [0, 1, 1, 1, 1, 1, 1]
    assert model.loss_counters == (
        ("moe_slots_total", "max_expert"), ("moe_slots_total", "all"),
        ("moe_slots_total", "held"), ("moe_slots_total", "moved"),
        ("moe_router_bias_spread", ""))
    # the published count, at the published widths, from the tree itself
    import jax
    from chipbench import manifest
    full = manifest.load_json(ROOT, "configs", f"{CONFIG}.json")
    published = jax.eval_shape(
        pipeline.build_model(full).init, jax.random.PRNGKey(0),
        np.zeros((1, 8), np.int32))["params"]
    assert sum(int(np.prod(v.shape))
               for v in jax.tree.leaves(published)) == 711_389_440


@pytest.mark.parametrize("dtype,attention,sized,tol", [
    ("float32", "dense", {}, 10 * F32_TOL),
    ("float32", "flash", {}, 10 * F32_TOL),
    ("float32", "dense", KERNEL_SIZED, 10 * F32_TOL),
    ("bfloat16", "flash", {}, 0.1)],
    ids=["float32", "float32_flash", "float32_conv_kernels", "bfloat16"])
def test_forward_logits_match_the_reference(dtype, attention, sized, tol,
                                            interpreted_conv_kernels):
    """What check (a) compares, with biases that move picks: float32 to
    rounding on both attention paths and with the gated convolution through
    its kernels (interpreted); bfloat16 inside what near-tied picks cost."""
    from chipbench.harness import relative_rms_error
    cfg, pipeline, _ = _files(compute_dtype=dtype, attention=attention,
                              **sized)
    model = pipeline.build_model(cfg)
    tokens = _tokens(cfg, 2, seed=5)
    params, state = _variables(model, tokens, bias_std=0.1)
    variables = {"params": params, "batch_stats": state}
    before = lm_testing.counters()
    got = pipeline.compared(lm_testing.logits(model, variables, tokens), cfg)
    path = "kernel" if sized else "jnp"
    moved = lm_testing.moved(before, "short_conv_total")
    assert set(moved) <= {path}         # (a program met before counts none)
    forward = lm_testing.reference_program(CONFIG, cfg, "forward")
    want = forward(variables, tokens)
    assert got.shape == want.shape == (2, 8, 64)
    assert relative_rms_error(np.asarray(got, np.float32), want) <= tol
    if not sized:       # the biases matter to the outputs compared
        zero = forward({"params": params}, tokens)
        assert relative_rms_error(zero, want) > 100 * F32_TOL


@pytest.mark.parametrize("remat,sized", [
    (False, {}), (True, KERNEL_SIZED)],
    ids=["kept", "recomputed_conv_kernels"])
def test_loss_gradients_slots_and_the_bias_after_a_step_match_the_reference(
        remat, sized, interpreted_conv_kernels):
    """The model's own loss (fused head over the rows held, the embedding as
    it lies for the head's kernel, no auxiliary loss) and the gradient of
    every leaf, with seeded biases, against ``jax.grad`` of the reference's
    loss (one matrix: the gather's gradient plus the head's); the slots all
    experts were picked for; then one optimizer step of the estimator's
    train step: every layer's bias is the reference's ``next_bias`` and the
    counts are empty again. A recomputed model is the same model; with the
    gated convolution through its kernels (interpreted) too."""
    import jax
    import optax
    cfg, pipeline, reference = _files(remat_blocks=remat, **sized)
    model = pipeline.build_model(cfg)
    layers = cfg["layers"]
    convs = cfg["layer_pattern_held"].count("C")
    assert model.conv_layers == {"recomputed" if remat else "plain": convs}
    tokens = _tokens(cfg, 4, seed=1)
    params, state = _variables(model, tokens, bias_std=0.1)
    w = np.full(4, 0.25, np.float32)
    (loss, counts), grads = lm_testing.loss_and_grads(model, params, state,
                                                      tokens, w)
    want_loss, want_grads = lm_testing.reference_program(
        CONFIG, cfg, "loss", grad=True)(params, state, tokens)
    assert abs(float(loss) - float(want_loss)) <= F32_TOL * float(want_loss)
    _close(grads, want_grads)
    assert "lm_head" not in grads
    assert np.abs(_leaves(grads)["block_0/short_conv/conv"]).max() > 1e-4
    counts_of = lm_testing.reference_program(CONFIG, cfg, "slot_counts")
    picked = np.stack(counts_of(params, state, tokens))
    assert picked.shape == (layers - 1, 8)
    assert float(counts[1]) == tokens.size * 4 * (layers - 1)   # top-4
    assert float(counts[0]) == picked.max(axis=1).sum()
    assert float(counts[2]) == picked[:, 2:4].sum() < float(counts[1])

    before = lm_testing.counters()
    step, create, arguments = _train_step(model, optax.sgd(0.05))
    assert lm_testing.moved(before, "train_conv_layers_total") == {
        "recomputed" if remat else "plain": convs}
    now, _, stats = jax.jit(step)(*arguments(create(params, state), tokens))
    for (name, block), c in zip(sorted(state.items()), picked):
        got = now.batch_stats[name]["moe"]
        np.testing.assert_allclose(
            got["bias"], reference.next_bias(block["moe"]["bias"], c, cfg),
            rtol=0, atol=1e-7)
        assert not np.any(np.asarray(got["counts"]))
    assert stats[0][1] == tokens.size * 4 * (layers - 1)
    # the step moved the one array by the sum of both gradients
    moved = np.asarray(now.params["embed"]["embedding"]) \
        - params["embed"]["embedding"]
    np.testing.assert_allclose(
        moved, -0.05 * np.asarray(want_grads["embed"]["embedding"]),
        rtol=1e-3, atol=1e-6)


# ------------------------------------- (b) the shares of one expert layer
def test_the_four_shares_of_an_expert_layer_add_up_to_the_uncut_layer():
    """Experts 0-1, 2-3, 4-5, 6-7 of 8, 4 a token: each chip routes over all
    eight (by score + bias, the weights over all four choices plus the
    family's 1e-6) and computes its own experts' part; the parts sum to the
    reference's uncut layer, and the held slots to all slots. The epsilon is
    the family's: with the default 1e-20 the layer is another."""
    from chipbench import manifest
    from raydp_tpu.models.moe import STATE, MoE
    reference = manifest.load_module(ROOT, "reference", f"{CONFIG}.py")
    rng = np.random.default_rng(0)
    d, f, e, n = 32, 16, 8, 48
    f32 = lambda a: np.asarray(a, np.float32)  # noqa: E731
    full = {"router": f32(rng.normal(0, 0.3, (d, e))),
            "experts_gate": f32(rng.normal(0, 0.3, (e, d, f))),
            "experts_up": f32(rng.normal(0, 0.3, (e, d, f))),
            "experts_down": f32(rng.normal(0, 0.3, (e, f, d)))}
    bias, m = f32(rng.normal(0, 0.2, (e,))), f32(rng.normal(size=(n, d)))
    layer_cfg = {"num_experts": e, "num_experts_per_tok": 4,
                 "norm_topk_prob": True, "routed_scaling_factor": 1,
                 "first_expert": 0, "experts_held": e}

    def share(first, held, eps=1e-6):
        params = {k: v[first:first + held] if k.startswith("experts_") else v
                  for k, v in full.items()}
        layer = MoE(e, 4, f, first_expert=first, experts_held=held,
                    normalize_top_k=True, routing="sigmoid",
                    normalize_eps=eps)
        y, aux = layer.apply({"params": params, STATE: {
            "bias": bias, "counts": np.zeros(e, np.float32)}}, m)
        return np.asarray(y), aux, params

    want = np.asarray(reference.expert_layer(full, m, bias, layer_cfg))
    parts, held_slots = [], 0.0
    for first in range(0, e, 2):
        y, aux, params = share(first, 2)
        np.testing.assert_allclose(y, reference.expert_layer(
            params, m, bias, dict(layer_cfg, first_expert=first,
                                  experts_held=2)), rtol=1e-4, atol=1e-5)
        parts.append(y)
        held_slots += float(aux["slots_held"])
        assert float(aux["slots_all"]) == 4 * n
    np.testing.assert_allclose(sum(parts), want, rtol=1e-4, atol=1e-5)
    assert held_slots == 4 * n and np.abs(want).max() > 0.1
    whole, aux, _ = share(0, e)
    np.testing.assert_allclose(whole, want, rtol=1e-4, atol=1e-5)
    assert "slots_held" not in aux
    # a sum of four sigmoids is of order 1: the epsilon shows at 1e-6 of it
    # and 0.5 makes another layer
    other, _, _ = share(0, e, eps=0.5)
    assert np.abs(other - want).max() > 0.01
    assert reference.ROUTE_EPS == 1e-6


def test_the_routing_epsilon_is_a_field_and_the_default_is_what_it_was():
    """``route(..., eps=)``: 1e-20 by default (the older families' lowered
    text holds that constant), the family's 1e-6 from ``TransformerLM(
    route_norm_eps=)`` down to the routing function."""
    import jax
    import jax.numpy as jnp
    from raydp_tpu.models.moe import route

    logits = jnp.asarray(np.random.default_rng(0).normal(size=(16, 8)),
                         jnp.float32)
    scores = np.asarray(jax.nn.sigmoid(logits))
    _, ids, weights = route(logits, 4, True, "sigmoid", None, 1.0, 1e-6)
    chosen = np.take_along_axis(scores, np.asarray(ids), -1)
    np.testing.assert_allclose(
        weights, chosen / (chosen.sum(-1, keepdims=True) + 1e-6), rtol=1e-6)
    text = lambda **kw: str(jax.make_jaxpr(lambda x: route(  # noqa: E731
        x, 4, True, "sigmoid", **kw))(logits))
    assert text() == text(eps=1e-20) != text(eps=1e-6)
    cfg, pipeline, _ = _files()
    model = pipeline.build_model(cfg)
    assert model.route_norm_eps == 1e-6
    assert model.clone(route_norm_eps=1e-20) != model


# ------------------------------------------- (d) the letters and the mesh
def test_a_model_of_b_and_c_letters_under_dense_layers():
    """``C`` and ``B`` mixed layer by layer, the leading ``dense_layers``
    dense whatever their operator; a ``C`` layer counts under no attention
    kind; the letter is refused where it cannot stand (a looped model), the
    message names it, and a ``seq`` mesh axis raises as a state-space layer's
    does."""
    import jax
    from raydp_tpu.models import TransformerLM
    from raydp_tpu.models.transformer import ShortConv
    from raydp_tpu.parallel import make_mesh
    model = TransformerLM(
        vocab_size=32, dim=16, num_heads=2, num_layers=4, ffn_dim=8,
        dense_ffn_dim=24, num_experts=4, experts_per_token=2,
        layer_kinds="BCCB", dense_layers=2, attention="dense",
        sandwich_norms=True, remat_blocks=True, conv_taps=4)
    tokens = np.zeros((1, 8), np.int32)
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0),
                            tokens)["params"]
    assert set(shapes["block_0"]) == {"ln1", "ln1_post", "ln2", "ln2_post",
                                      "attn", "gate", "up", "down"}
    assert set(shapes["block_1"]) == {"ln1", "ln1_post", "ln2", "ln2_post",
                                      "short_conv", "gate", "up", "down"}
    assert set(shapes["block_2"]) == {"ln1", "ln1_post", "ln2", "ln2_post",
                                      "short_conv", "moe"}
    assert "attn" in shapes["block_3"] and "moe" in shapes["block_3"]
    assert shapes["block_1"]["short_conv"]["conv"].shape == (4, 16)
    assert shapes["block_1"]["gate"]["kernel"].shape == (16, 24)
    assert "lm_head" in shapes
    assert model.conv_layers == {"recomputed": 2}
    assert model.attention_layers == {"window": 0, "full": 2}
    assert model.sublayer_out == {"kept": 4, "rebuilt": 4}
    assert [model._sparse(i) for i in range(4)] == [0, 0, 1, 1]
    with pytest.raises(ValueError, match="'B', 'C', 'K', 'M'"):
        model.clone(layer_kinds="BCXB")._kind(0)
    with pytest.raises(ValueError, match="dense blocks alone"):
        jax.eval_shape(TransformerLM(
            vocab_size=32, dim=16, num_heads=2, num_layers=2,
            layer_kinds="CB", total_ut_steps=2).init,
            jax.random.PRNGKey(0), tokens)
    mesh = make_mesh({"seq": 2}, devices=jax.devices()[:2])
    with pytest.raises(NotImplementedError, match="seq axis"):
        jax.eval_shape(ShortConv(mesh=mesh).init, jax.random.PRNGKey(0),
                       np.zeros((1, 8, 16), np.float32))
    # the default model is what it was: no conv layer, a head of its own
    plain = TransformerLM(vocab_size=32, dim=16, num_heads=2, num_layers=2)
    assert plain.conv_layers == {"plain": 0} and not plain.tie_embeddings
    assert plain.route_norm_eps == 1e-20


# -------------------------------------------------------------- (e) a fit
def test_fit_on_frame_trains_the_pairs_and_saves_one_array(session,
                                                           tmp_path):
    """The tiny cut through ETL -> ``fit_on_frame`` with recomputed layers:
    the loss falls, the step counted its five convolution pairs
    ``recomputed`` and the calls' path, the held slots are some and not all,
    the bias has moved, and the model the fit hands back has no ``lm_head``
    (one array is embedding and head)."""
    import jax
    import pyarrow.parquet as pq
    from raydp_tpu.parallel import make_mesh

    cfg, pipeline, _ = _files(remat_blocks=True)
    wl = {"seq_len": cfg["seq_len"]}
    path = str(tmp_path / "tokens")
    os.makedirs(path)
    cfg["input"]["eos_id"] = 63
    pq.write_table(pipeline.generate(8, 3, cfg),
                   os.path.join(path, "part-0.parquet"))
    df, info = pipeline.etl(session.read.parquet(path), cfg, wl)
    mesh = make_mesh(None, devices=jax.devices()[:1])
    before = lm_testing.counters()
    est = lm_testing.estimator(cfg, pipeline, info, mesh, num_epochs=3,
                               batch_size=4, checkpoint_interval=3)
    history = est.fit_on_frame(df.persist()).history
    losses = [e["train_loss"] for e in history]
    assert np.all(np.isfinite(losses)) and losses[-1] < losses[0]
    assert lm_testing.moved(before, "train_conv_layers_total") == {
        "recomputed": 5}
    assert set(lm_testing.moved(before, "short_conv_total")) == {"jnp"}
    assert lm_testing.moved(before, "train_attention_layers_total") == {
        "full": 2}
    slots = lm_testing.moved(before, "moe_slots_total")
    assert slots["all"] == 3 * 8 * 32 * 4 * 6   # epochs, tokens, top-4, layers
    assert 0 < slots["held"] <= slots["moved"] < slots["all"]
    fitted = est.get_model()
    assert "lm_head" not in fitted["params"]
    assert fitted["params"]["embed"]["embedding"].shape == (64, 32)
    assert any(np.any(np.asarray(b["moe"]["bias"]))
               for b in fitted["batch_stats"].values())
