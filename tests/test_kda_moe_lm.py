"""Kimi-Linear-style LM (``kimi_linear``: every layer a pair whose operator is
Kimi Delta Attention, three layers in four, or latent attention without
positions; sigmoid routing picked by score + a balancing bias, a shared
expert; one chip holds a share of the experts): the whole model, its loss,
every gradient leaf, the slots and the bias through the train step, the
shares of an expert layer, attention without positions and a fit, against the
plain reference (``chipbench/reference/kimi-linear-48b-a3b.py``: float32
``jax.numpy``, the delta rule's state a position at a time, every held expert
on every token), at small sizes on the CPU, seeded random weights. Widths are
small here, and only here.
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

CONFIG = "kimi-linear-48b-a3b"

# 2 heads of 8 in the delta-rule operator (gates of rank 8, chunks of 16: three
# a row), 2 heads of 8 + 4 / 8 over a latent of 16 in attention, the five
# layers held in the published pattern (K K K A K, the first one dense), 16
# experts of which experts 4-7 are held, 4 a token, a shared expert, 64 of 256
# vocabulary rows, 48 positions
TINY = {"hidden_size": 32, "num_attention_heads": 2,
        "linear_attn_config": {"num_heads": 2, "head_dim": 8},
        "kda_chunk": 16, "kv_lora_rank": 16,
        "qk_nope_head_dim": 8, "qk_rope_head_dim": 4, "v_head_dim": 8,
        "intermediate_size": 48, "moe_intermediate_size": 16,
        "num_experts": 16, "num_experts_per_token": 4, "first_expert": 4,
        "experts_held": 4, "vocab_size": 256, "vocab_rows_held": 64,
        "seq_len": 48, "compared_positions": 8, "compute_dtype": "float32",
        "attention": "dense", "init_std": 0.3, "remat_blocks": False}
_files = functools.partial(lm_testing.files, CONFIG, TINY)


def _seeded(model, tokens, bias_std=0.1):
    """Seeded parameters with the head norm's weight moved off 1."""
    params, state = _variables(model, tokens, bias_std=bias_std)
    rng = np.random.default_rng(2)
    for block in params.values():
        if "kda" in block:
            block["kda"]["norm"] = (block["kda"]["norm"] + rng.normal(
                0, 0.2, block["kda"]["norm"].shape)).astype(np.float32)
    return params, state


# ----------------------------------------------------- (a) the whole model
def test_the_parameter_tree_is_the_published_layers():
    """Layer 1 a delta-rule operator over the dense SwiGLU, then delta-rule
    and attention operators over expert layers with router, shared expert
    and held experts; ONE fused q | k | v matrix and one array of taps; no
    bias but ``dt_bias``; a head of its own; the routing bias and its counts
    in the estimator's collection."""
    cfg, pipeline, _ = _files()
    model = pipeline.build_model(cfg)
    assert model.layer_kinds == "KKKBK" and model.rope_layers == (0,)
    assert (model.kda.num_heads, model.kda.head_dim, model.kda.conv_taps,
            model.kda.gate_rank, model.kda.chunk) == (2, 8, 4, 8, 16)
    params, state = _variables(model, _tokens(cfg, 1))
    shapes = {k: v.shape for k, v in _leaves(params).items()}
    kda = {"kda/in_proj/kernel": (32, 48), "kda/conv": (4, 48),
           "kda/gate_a": (32, 8), "kda/gate_b": (8, 16), "kda/A_log": (2,),
           "kda/dt_bias": (16,), "kda/beta": (32, 2),
           "kda/out_gate_a": (32, 8), "kda/out_gate_b": (8, 16),
           "kda/norm": (8,), "kda/out_proj/kernel": (16, 32),
           "ln1/scale": (32,), "ln2/scale": (32,)}
    attn = {"attn/q/kernel": (32, 2, 12), "attn/kv_a/kernel": (32, 20),
            "attn/kv_norm/scale": (16,), "attn/kv_b/kernel": (16, 2, 16),
            "attn/o/kernel": (2, 8, 32), "ln1/scale": (32,),
            "ln2/scale": (32,)}
    experts = {"moe/router": (32, 16), "moe/experts_gate": (4, 32, 16),
               "moe/experts_up": (4, 32, 16), "moe/experts_down": (4, 16, 32),
               "moe/shared_gate/kernel": (32, 16),
               "moe/shared_up/kernel": (32, 16),
               "moe/shared_down/kernel": (16, 32)}
    want = {f"block_0/{k}": v for k, v in kda.items()}
    want.update({"block_0/gate/kernel": (32, 48),
                 "block_0/up/kernel": (32, 48),
                 "block_0/down/kernel": (48, 32)})
    for i, operator in enumerate("KKAK", start=1):
        want.update({f"block_{i}/{k}": v for k, v in {
            **(kda if operator == "K" else attn), **experts}.items()})
    want.update({"embed/embedding": (64, 32), "lm_head/kernel": (32, 64),
                 "ln_f/scale": (32,)})
    assert shapes == want
    assert {k: v.shape for k, v in _leaves(state).items()} == {
        f"block_{i}/moe/{name}": (16,) for i in range(1, 5)
        for name in ("bias", "counts")}
    assert model.kda_layers == {"plain": 4}
    assert model.attention_layers == {"window": 0, "full": 1, "latent": 1}
    assert model.conv_layers == {"plain": 0}
    assert model.sublayer_out == {} and model.ssm_layers == {"plain": 0}
    assert [model._sparse(i) for i in range(5)] == [0, 1, 1, 1, 1]
    a_log = np.exp(params["block_0"]["kda"]["A_log"])
    assert np.all((a_log >= 1) & (a_log <= 16))
    # the published count, at the published widths, from the tree itself
    import jax
    from chipbench import manifest
    full = manifest.load_json(ROOT, "configs", f"{CONFIG}.json")
    published = jax.eval_shape(
        pipeline.build_model(full).init, jax.random.PRNGKey(0),
        np.zeros((1, 8), np.int32))["params"]
    assert published["block_0"]["kda"]["in_proj"]["kernel"].shape == (
        2304, 12288)
    assert sum(int(np.prod(v.shape))
               for v in jax.tree.leaves(published)) == 602_433_408
    flops = manifest.load_module(ROOT, "flops", "kda_moe_lm.py")
    assert sum(flops.parameters(full).values()) == 602_433_408


@pytest.mark.parametrize("dtype,attention,tol", [
    ("float32", "dense", 10 * F32_TOL), ("float32", "flash", 10 * F32_TOL),
    ("bfloat16", "flash", 0.2)],
    ids=["float32", "float32_flash", "bfloat16"])
def test_forward_logits_match_the_reference(dtype, attention, tol):
    """What check (a) compares, with biases that move picks: float32 to
    rounding (the chunked scan with its triangular solve against the state a
    position at a time) on both attention paths; bfloat16 inside what
    near-tied picks cost (at these sizes and weights the reference with its
    own operands rounded to bfloat16 reads 0.153 against itself, the program
    0.155)."""
    from chipbench.harness import relative_rms_error
    cfg, pipeline, _ = _files(compute_dtype=dtype, attention=attention)
    model = pipeline.build_model(cfg)
    tokens = _tokens(cfg, 2, seed=5)
    params, state = _seeded(model, tokens)
    variables = {"params": params, "batch_stats": state}
    got = pipeline.compared(lm_testing.logits(model, variables, tokens), cfg)
    forward = lm_testing.reference_program(CONFIG, cfg, "forward")
    want = forward(variables, tokens)
    assert got.shape == want.shape == (2, 8, 64)
    assert relative_rms_error(np.asarray(got, np.float32), want) <= tol
    if dtype == "float32" and attention == "dense":
        # the biases matter to the outputs compared
        zero = forward({"params": params}, tokens)
        assert relative_rms_error(zero, want) > 100 * F32_TOL


@pytest.mark.parametrize("remat", [False, True], ids=["kept", "recomputed"])
def test_loss_gradients_slots_and_the_bias_after_a_step_match_the_reference(
        remat):
    """The model's own loss (fused head over the rows held, no auxiliary
    loss) and the gradient of every leaf (``A_log``, ``dt_bias``, the taps,
    both low-rank pairs, ``beta`` and the head norm's weight among them),
    with seeded biases, against ``jax.grad`` of the reference's loss; the
    slots all experts were picked for; then one optimizer step of the
    estimator's train step: every layer's bias is the reference's
    ``next_bias`` and the counts are empty again. A recomputed model is the
    same model."""
    import jax
    import optax
    cfg, pipeline, reference = _files(remat_blocks=remat)
    model = pipeline.build_model(cfg)
    assert model.kda_layers == {"rescanned" if remat else "plain": 4}
    tokens = _tokens(cfg, 2, seed=1)
    params, state = _seeded(model, tokens)
    w = np.full(2, 0.5, np.float32)
    (loss, counts), grads = lm_testing.loss_and_grads(model, params, state,
                                                      tokens, w)
    want_loss, want_grads = lm_testing.reference_program(
        CONFIG, cfg, "loss", grad=True)(params, state, tokens)
    assert abs(float(loss) - float(want_loss)) <= F32_TOL * float(want_loss)
    _close(grads, want_grads)
    for name in ("A_log", "dt_bias", "conv", "gate_b", "beta", "out_gate_a",
                 "norm"):
        assert np.abs(_leaves(grads)[f"block_1/kda/{name}"]).max() > 1e-6, \
            name
    counts_of = lm_testing.reference_program(CONFIG, cfg, "slot_counts")
    picked = np.stack(counts_of(params, state, tokens))
    assert picked.shape == (4, 16)
    assert float(counts[1]) == tokens.size * 4 * 4          # top-4, 4 layers
    assert float(counts[0]) == picked.max(axis=1).sum()
    assert float(counts[2]) == picked[:, 4:8].sum() < float(counts[1])

    before = lm_testing.counters()
    step, create, arguments = _train_step(model, optax.sgd(0.05))
    assert lm_testing.moved(before, "train_kda_layers_total") == {
        "rescanned" if remat else "plain": 4}
    now, _, stats = jax.jit(step)(*arguments(create(params, state), tokens))
    assert lm_testing.moved(before, "kda_scan_total").get("jnp", 0) >= 4
    for (name, block), c in zip(sorted(state.items()), picked):
        got = now.batch_stats[name]["moe"]
        np.testing.assert_allclose(
            got["bias"], reference.next_bias(block["moe"]["bias"], c, cfg),
            rtol=0, atol=1e-7)
        assert not np.any(np.asarray(got["counts"]))
    assert stats[0][1] == tokens.size * 4 * 4


# ------------------------------------- (b) the shares of one expert layer
def test_the_shares_of_an_expert_layer_add_up_to_the_uncut_layer():
    """32 experts over 4 shares of 8 (the deployment's 256 over 32), 8 a
    token: each chip routes over all 32 (by score + bias, the weights over
    all eight choices times 2.446) and computes its own experts' part; the
    parts and the shared expert, counted ONCE, sum to the reference's uncut
    layer, and the held slots to all slots."""
    from chipbench import manifest
    from raydp_tpu.models.moe import STATE, MoE
    reference = manifest.load_module(ROOT, "reference", f"{CONFIG}.py")
    rng = np.random.default_rng(0)
    d, f, e, n, k = 32, 16, 32, 48, 8
    f32 = lambda a: np.asarray(a, np.float32)  # noqa: E731
    full = {"router": f32(rng.normal(0, 0.3, (d, e))),
            "experts_gate": f32(rng.normal(0, 0.3, (e, d, f))),
            "experts_up": f32(rng.normal(0, 0.3, (e, d, f))),
            "experts_down": f32(rng.normal(0, 0.3, (e, f, d))),
            **{f"shared_{name}": {"kernel": f32(rng.normal(0, 0.3, shape))}
               for name, shape in (("gate", (d, f)), ("up", (d, f)),
                                   ("down", (f, d)))}}
    bias, m = f32(rng.normal(0, 0.2, (e,))), f32(rng.normal(size=(n, d)))
    layer_cfg = {"num_experts": e, "num_experts_per_token": k,
                 "moe_renormalize": True, "routed_scaling_factor": 2.446,
                 "num_shared_experts": 1, "first_expert": 0,
                 "experts_held": e}

    def share(first, held):
        params = {key: v[first:first + held] if key.startswith("experts_")
                  else v for key, v in full.items()}
        layer = MoE(e, k, f, first_expert=first, experts_held=held,
                    normalize_top_k=True, routing="sigmoid",
                    route_scale=2.446, shared_dim=f)
        y, aux = layer.apply({"params": params, STATE: {
            "bias": bias, "counts": np.zeros(e, np.float32)}}, m)
        return np.asarray(y), aux, params

    want = np.asarray(reference.expert_layer(full, m, bias, layer_cfg))
    shared = want - np.asarray(reference.expert_layer(
        full, m, bias, layer_cfg, False))
    assert np.abs(shared).max() > 0.1
    parts, held_slots = [], 0.0
    for first in range(0, e, 8):
        y, aux, params = share(first, 8)
        np.testing.assert_allclose(y, reference.expert_layer(
            params, m, bias, dict(layer_cfg, first_expert=first,
                                  experts_held=8)), rtol=1e-4, atol=1e-5)
        parts.append(y - shared)        # a chip's routed part alone
        held_slots += float(aux["slots_held"])
        assert float(aux["slots_all"]) == k * n
    np.testing.assert_allclose(sum(parts) + shared, want, rtol=1e-4,
                               atol=2e-5)
    assert held_slots == k * n and np.abs(want - shared).max() > 0.1
    whole, aux, _ = share(0, e)
    np.testing.assert_allclose(whole, want, rtol=1e-4, atol=2e-5)
    assert "slots_held" not in aux
    assert reference.ROUTE_EPS == 1e-20


# ----------------------------- (c) latent attention without positions
def test_latent_attention_without_positions_is_the_references():
    """``LatentAttention(rope=False)``: the shared key and the queries' last
    dimensions kept and not rotated, the widths and the parameters those of
    the rotating layer; the rotating layer (the default) is another
    function of the same parameters."""
    import jax
    from raydp_tpu.models.transformer import LatentAttention
    cfg, _, reference = _files()
    layer = LatentAttention(2, 16, 8, 4, 8, attention="dense",
                            rms_norm_eps=cfg["rms_norm_eps"])
    assert layer.rope is True
    u = np.random.default_rng(3).normal(size=(2, 24, 32)).astype(np.float32)
    params = jax.jit(layer.init)(jax.random.PRNGKey(1), u)["params"]
    plain = layer.clone(rope=False)
    assert jax.tree.map(np.shape, jax.eval_shape(
        plain.init, jax.random.PRNGKey(1), u)["params"]) == jax.tree.map(
            np.shape, params)
    got = np.asarray(jax.jit(plain.apply)({"params": params}, u))
    want = np.asarray(reference.latent_attention(params, u, cfg))
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-5)
    turned = np.asarray(jax.jit(layer.apply)({"params": params}, u))
    assert np.abs(turned - want).max() > 1e-3
    # position 0 is rotated by nothing: the two agree there
    np.testing.assert_allclose(turned[:, 0], want[:, 0], rtol=1e-4,
                               atol=1e-5)


# ------------------------------------------- (d) the letters and the mesh
def test_a_model_of_k_and_b_letters_under_dense_layers():
    """``K`` and ``B`` mixed layer by layer, the leading ``dense_layers``
    dense whatever their operator; a ``K`` layer counts under no attention
    kind; ``rope_layers`` reaches a latent layer; the letter is refused where
    it cannot stand (a looped model, a model without ``kda``), the message
    names it, and a ``seq`` mesh axis raises as a state-space layer's
    does."""
    import jax
    from raydp_tpu.models import TransformerLM
    from raydp_tpu.models.transformer import KDASpec, KimiDeltaAttention
    from raydp_tpu.parallel import make_mesh
    spec = KDASpec(2, 8, 4, 4, 16)
    model = TransformerLM(
        vocab_size=32, dim=16, num_heads=2, num_layers=4, ffn_dim=8,
        dense_ffn_dim=24, num_experts=4, experts_per_token=2,
        layer_kinds="BKKB", dense_layers=2, attention="dense",
        sandwich_norms=True, remat_blocks=True, kda=spec, kv_lora_rank=8,
        qk_nope_head_dim=4, qk_rope_head_dim=4, v_head_dim=4,
        rope_layers=(1, 0))
    tokens = np.zeros((1, 8), np.int32)
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0),
                            tokens)["params"]
    assert set(shapes["block_0"]) == {"ln1", "ln1_post", "ln2", "ln2_post",
                                      "attn", "gate", "up", "down"}
    assert set(shapes["block_1"]) == {"ln1", "ln1_post", "ln2", "ln2_post",
                                      "kda", "gate", "up", "down"}
    assert set(shapes["block_2"]) == {"ln1", "ln1_post", "ln2", "ln2_post",
                                      "kda", "moe"}
    assert "attn" in shapes["block_3"] and "moe" in shapes["block_3"]
    assert shapes["block_1"]["kda"]["conv"].shape == (4, 48)
    assert shapes["block_1"]["gate"]["kernel"].shape == (16, 24)
    assert [model._rope(i) for i in range(4)] == [True, False, True, False]
    assert model.kda_layers == {"rescanned": 2}
    assert model.attention_layers == {"window": 0, "full": 2, "latent": 2}
    assert model.sublayer_out == {"kept": 4, "rebuilt": 4}
    assert [model._sparse(i) for i in range(4)] == [0, 0, 1, 1]
    assert set(model.attention_inputs) == {"rebuilt"}
    with pytest.raises(ValueError, match="'B', 'C', 'K', 'M'"):
        model.clone(layer_kinds="BKXB")._kind(0)
    with pytest.raises(ValueError, match="kda=KDASpec"):
        jax.eval_shape(model.clone(kda=None).init, jax.random.PRNGKey(0),
                       tokens)
    with pytest.raises(ValueError, match="dense blocks alone"):
        jax.eval_shape(TransformerLM(
            vocab_size=32, dim=16, num_heads=2, num_layers=2,
            layer_kinds="KB", total_ut_steps=2, kda=spec).init,
            jax.random.PRNGKey(0), tokens)
    mesh = make_mesh({"seq": 2}, devices=jax.devices()[:2])
    with pytest.raises(NotImplementedError, match="seq axis"):
        jax.eval_shape(KimiDeltaAttention(spec, mesh=mesh).init,
                       jax.random.PRNGKey(0),
                       np.zeros((1, 8, 16), np.float32))
    # the default model is what it was: no such layer, every layer rotates
    plain = TransformerLM(vocab_size=32, dim=16, num_heads=2, num_layers=2)
    assert plain.kda_layers == {"plain": 0} and plain.kda is None
    assert plain._rope(0) and plain._rope(1)


def test_the_pipeline_refuses_layers_that_are_not_the_pattern():
    """A ``layers_held`` whose letters by the published ``kda_layers`` /
    ``full_attn_layers`` (counted from 1) are not ``layer_pattern_held``, and
    a ``dense_layers`` that is not the held layers at or under
    ``first_k_dense_replace``, as ``lfm2``'s pipeline refuses its pattern."""
    from chipbench import manifest
    cfg = manifest.load_json(ROOT, "configs", f"{CONFIG}.json")
    kinds = manifest.load_module(ROOT, "pipelines",
                                 f"{CONFIG}.py").layer_kinds
    assert kinds(cfg) == "KKKBK"
    assert kinds(dict(cfg, layers=4, layers_held=[1, 2, 3, 4],
                      layer_pattern_held="KKKA")) == "KKKB"
    with pytest.raises(ValueError, match="layer_pattern_held"):
        kinds(dict(cfg, layers_held=[1, 2, 3, 5, 6]))
    with pytest.raises(ValueError, match="layer_pattern_held"):
        kinds(dict(cfg, layers_held=[0, 1, 2, 3, 4]))    # counted from 1
    with pytest.raises(ValueError, match="layer_pattern_held"):
        kinds(dict(cfg, layers=6))
    with pytest.raises(ValueError, match="dense_layers"):
        kinds(dict(cfg, dense_layers=2))
    with pytest.raises(ValueError, match="dense_layers"):
        kinds(dict(cfg, layers_held=[2, 3, 4, 5, 6],
                   layer_pattern_held="KKAKK"))
    with pytest.raises(ValueError, match="dense_layers"):    # not leading
        kinds(dict(cfg, layers_held=[2, 1, 3, 4, 5]))


# -------------------------------------------------------------- (e) a fit
def test_fit_on_frame_trains_the_pairs(session, tmp_path):
    """The tiny cut through ETL -> ``fit_on_frame`` with recomputed layers:
    the loss falls, the step counted its four delta-rule pairs ``rescanned``
    and its one latent attention layer, the scans' path and chunks, the held
    slots are some and not all, and the bias has moved."""
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
    assert lm_testing.moved(before, "train_kda_layers_total") == {
        "rescanned": 4}
    assert set(lm_testing.moved(before, "kda_scan_total")) == {"jnp"}
    chunks = lm_testing.moved(before, "kda_chunks_total")
    assert chunks["forward"] >= chunks["backward"] >= 4 * 4 * 2 * 3
    assert lm_testing.moved(before, "train_attention_layers_total") == {
        "full": 1, "latent": 1}
    slots = lm_testing.moved(before, "moe_slots_total")
    assert slots["all"] == 3 * 8 * 48 * 4 * 4   # epochs, tokens, top-4, layers
    assert 0 < slots["held"] <= slots["moved"] < slots["all"]
    fitted = est.get_model()
    assert fitted["params"]["lm_head"]["kernel"].shape == (32, 64)
    assert fitted["params"]["block_0"]["kda"]["in_proj"]["kernel"].shape == (
        32, 48)
    assert any(np.any(np.asarray(b["moe"]["bias"]))
               for b in fitted["batch_stats"].values())
