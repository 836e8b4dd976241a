"""Latent-attention sparse-expert LM (``deepseek_v3``: kanana-2-30b-a3b): the
flash op at two widths (keys of 192 beside values of 128), RoPE over
interleaved pairs, ``LatentAttention`` alone, the parameter tree, the whole
model's logits, loss, gradients and balancing bias through the estimator's
train step, a recomputed latent block, and the older families' programs left
as they were; against the plain reference
(``chipbench/reference/kanana-2-30b-a3b.py``: float32 ``jax.numpy``, scores as
the sum of two products, the rotation written out pair by pair), at small
sizes on the CPU, seeded random weights. Widths are small here, and only
here (``tests/chipbench_contract/test_chipbench_kanana_2.py`` keeps them).
"""

import functools
import hashlib

import numpy as np
import pytest

from tests import lm_testing
from tests.lm_testing import (F32_TOL, ROOT, close as _close,
                              leaves as _leaves, step_text as _step_text,
                              tokens as _tokens, train_step as _train_step,
                              variables as _variables)

CONFIG = "kanana-2-30b-a3b"

# 4 heads of 16 + 8 beside 16 over a K/V latent of 24, the dense layer and
# two expert layers, 16 experts of which experts 2-3 are held, 6 a token (as
# published), two shared experts, 64 of 512 vocabulary rows, 32 positions
TINY = {"hidden_size": 32, "num_attention_heads": 4, "num_key_value_heads": 4,
        "kv_lora_rank": 24, "qk_nope_head_dim": 16, "qk_rope_head_dim": 8,
        "qk_head_dim": 24, "head_dim": 8, "v_head_dim": 16,
        "intermediate_size": 48, "moe_intermediate_size": 16,
        "n_routed_experts": 16, "first_expert": 2, "experts_held": 2,
        "vocab_size": 512, "vocab_rows_held": 64, "seq_len": 32,
        "layers": 3, "layers_held": [0, 1, 2],
        "compared_positions": 8, "compute_dtype": "float32",
        "attention": "dense", "init_std": 0.3, "remat_blocks": False}
_files = functools.partial(lm_testing.files, CONFIG, TINY)


# --------------------------------------------- (b) RoPE over interleaved pairs
def test_interleaved_rope_turns_pairs_2i_and_2i_plus_1():
    """Against the rotation written out pair by pair; and the scores of
    rotated queries against rotated keys do not change when the pairs of q
    and k alike are first moved to the half-split layout and turned there
    (what the family's own code does)."""
    import jax.numpy as jnp
    from raydp_tpu.models.transformer import rotary_embedding

    rng = np.random.default_rng(0)
    x = rng.normal(size=(2, 12, 3, 8)).astype(np.float32)
    positions = jnp.arange(12)
    got = np.asarray(rotary_embedding(jnp.asarray(x), positions, 1e4,
                                      interleaved=True))
    want = np.zeros_like(x)
    for p in range(12):
        for i in range(4):
            angle = p * 1e4 ** (-i / 4)
            a, b = x[:, p, :, 2 * i], x[:, p, :, 2 * i + 1]
            want[:, p, :, 2 * i] = a * np.cos(angle) - b * np.sin(angle)
            want[:, p, :, 2 * i + 1] = a * np.sin(angle) + b * np.cos(angle)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
    # off by default: the halves, as before
    halves = np.asarray(rotary_embedding(jnp.asarray(x), positions, 1e4))
    assert np.abs(halves - got).max() > 0.1
    k = rng.normal(size=(2, 12, 3, 8)).astype(np.float32)
    perm = np.r_[0:8:2, 1:8:2]          # interleaved -> half-split
    turn = lambda a, inter: np.asarray(rotary_embedding(  # noqa: E731
        jnp.asarray(a), positions, 1e4, interleaved=inter))
    scores = np.einsum("bqhd,bkhd->bhqk", got, turn(k, True))
    moved = np.einsum("bqhd,bkhd->bhqk", turn(x[..., perm], False),
                      turn(k[..., perm], False))
    np.testing.assert_allclose(moved, scores, rtol=1e-4, atol=1e-4)


# ------------------------------------------------ (c) the sub-layer alone
@pytest.mark.parametrize("q_rank", [None, 12], ids=["full_q", "q_latent"])
@pytest.mark.parametrize("dtype,attention,tol", [
    ("float32", "dense", 10 * F32_TOL), ("bfloat16", "flash", 0.03)])
def test_latent_attention_matches_the_references(
        dtype, attention, tol, q_rank, forward_flash_kernels):
    import jax
    import jax.numpy as jnp
    from chipbench.harness import relative_rms_error
    from raydp_tpu.models.transformer import LatentAttention

    cfg, _, reference = _files(q_lora_rank=q_rank)
    layer = LatentAttention(
        4, 24, 16, 8, 16, q_rank, attention, None, jnp.dtype(dtype),
        float(cfg["rope_theta"]), True, cfg["rms_norm_eps"], 0.3)
    u = np.random.default_rng(1).normal(size=(2, 32, 32)).astype(np.float32)
    variables = jax.jit(layer.init)(jax.random.PRNGKey(1), u)
    params = jax.tree.map(np.asarray, variables["params"])
    params["kv_norm"]["scale"] = np.random.default_rng(2).uniform(
        0.5, 1.5, 24).astype(np.float32)
    names = {"kv_a", "kv_norm", "kv_b", "o"} | (
        {"q"} if q_rank is None else {"q_a", "q_a_norm", "q_b"})
    assert set(params) == names
    got = jax.jit(layer.apply)({"params": params},
                               jnp.asarray(u, jnp.dtype(dtype)))
    assert got.dtype == jnp.dtype(dtype) and got.shape == u.shape
    latent = jax.jit(lambda p: reference.latent_attention(p, u, cfg))
    want = latent(params)
    assert relative_rms_error(np.asarray(got, np.float32), want) <= tol
    # the latent's norm and the one shared rotary key are in the result
    plain = dict(params, kv_norm={"scale": np.ones(24, np.float32)})
    assert relative_rms_error(latent(plain), want) > 0.01


def test_latent_attention_takes_no_seq_axis_and_no_window():
    import jax
    import jax.numpy as jnp
    from raydp_tpu.models import TransformerLM
    from raydp_tpu.models.transformer import LatentAttention
    from raydp_tpu.parallel import make_mesh

    mesh = make_mesh({"seq": 2}, devices=jax.devices()[:2])
    u = jnp.zeros((1, 8, 16))
    layer = LatentAttention(2, 8, 8, 8, 8, mesh=mesh)
    with pytest.raises(NotImplementedError, match="seq axis"):
        layer.init(jax.random.PRNGKey(0), u)
    windowed = TransformerLM(
        vocab_size=16, dim=16, num_heads=2, num_layers=1, kv_lora_rank=8,
        qk_nope_head_dim=8, qk_rope_head_dim=8, v_head_dim=8,
        sliding_window=4, window_layers=(1,))
    with pytest.raises(ValueError, match="no window"):
        windowed.init(jax.random.PRNGKey(0), np.zeros((1, 8), np.int32))


# ----------------------------------------------------- (d) the whole model
def test_the_parameter_tree_is_the_published_layers():
    """Names and shapes at the tiny widths; and at the PUBLISHED widths, by
    ``jax.eval_shape`` (nothing is allocated), a layer's attention is
    26,345,984 parameters and its two norms 4,096."""
    import jax
    cfg, pipeline, _ = _files()
    model = pipeline.build_model(cfg)
    params, state = _variables(model, _tokens(cfg, 1))
    shapes = {k: v.shape for k, v in _leaves(params).items()}
    attn = {"attn/q/kernel": (32, 4, 24), "attn/kv_a/kernel": (32, 32),
            "attn/kv_norm/scale": (24,), "attn/kv_b/kernel": (24, 4, 32),
            "attn/o/kernel": (4, 16, 32), "ln1/scale": (32,),
            "ln2/scale": (32,)}
    want = {f"block_0/{k}": v for k, v in attn.items()}
    want.update({"block_0/gate/kernel": (32, 48),
                 "block_0/up/kernel": (32, 48),
                 "block_0/down/kernel": (48, 32)})
    assert {k: v for k, v in shapes.items() if k.startswith("block_0/")} \
        == want
    want = {f"block_2/{k}": v for k, v in attn.items()}
    want.update({"block_2/moe/router": (32, 16),
                 "block_2/moe/experts_gate": (2, 32, 16),
                 "block_2/moe/experts_up": (2, 32, 16),
                 "block_2/moe/experts_down": (2, 16, 32),
                 # two shared experts as one MLP of width 2 x 16
                 "block_2/moe/shared_gate/kernel": (32, 32),
                 "block_2/moe/shared_up/kernel": (32, 32),
                 "block_2/moe/shared_down/kernel": (32, 32)})
    assert {k: v for k, v in shapes.items() if k.startswith("block_2/")} \
        == want
    assert {k: v.shape for k, v in _leaves(state).items()} == {
        f"block_{i}/moe/{name}": (16,) for i in (1, 2)
        for name in ("bias", "counts")}
    assert model.attention_layers == {"window": 0, "full": 3, "latent": 3}
    assert [model._sparse(i) for i in range(3)] == [0, 1, 1]

    from chipbench import manifest
    published = manifest.load_json(ROOT, "configs", f"{CONFIG}.json")
    big = pipeline.build_model(dict(published, layers=2, layers_held=[0, 1],
                                    vocab_rows_held=8))
    tree = jax.eval_shape(lambda: big.init(
        jax.random.PRNGKey(0), np.zeros((1, 8), np.int32)))["params"]
    count = lambda t: sum(int(np.prod(x.shape))  # noqa: E731
                          for x in jax.tree.leaves(t))
    block = tree["block_1"]
    assert count(block["attn"]) == 26345984
    assert count(block["ln1"]) + count(block["ln2"]) == 4096
    assert block["attn"]["q"]["kernel"].shape == (2048, 32, 192)
    assert block["attn"]["kv_a"]["kernel"].shape == (2048, 576)
    assert block["attn"]["kv_b"]["kernel"].shape == (512, 32, 256)
    assert block["attn"]["o"]["kernel"].shape == (32, 128, 2048)
    assert count(block["moe"]) == 262144 + 9437184 + 16 * 4718592
    assert count(tree["block_0"]) == 64098816


@pytest.mark.parametrize("dtype,attention,tol", [
    ("float32", "dense", 10 * F32_TOL), ("float32", "flash", 10 * F32_TOL),
    ("bfloat16", "flash", 0.1)])
def test_forward_logits_match_the_reference(dtype, attention, tol,
                                            forward_flash_kernels):
    """What check (a) compares, with biases that move picks: float32 to
    rounding on the dense path and through the kernels (interpreted, keys of
    24 beside values of 16); bfloat16 inside what near-tied picks cost."""
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


@pytest.mark.parametrize("remat,attention,forward", [
    (False, "dense", "once"), (True, "dense", "twice"),
    (True, "flash", "once")],
    ids=["kept", "recomputed", "recomputed_flash"])
def test_loss_gradients_and_the_bias_after_three_steps_match_the_reference(
        remat, attention, forward, forward_flash_kernels):
    """The model's own loss (fused head over the rows held, no auxiliary
    loss) and the gradient of every leaf, with seeded biases; then three
    optimizer steps of the estimator's train step: after each, every expert
    layer's bias is the reference's ``next_bias`` of the slots ALL experts
    were picked for in the step's tokens, and the counts are empty again."""
    import jax
    import optax
    cfg, pipeline, reference = _files(remat_blocks=remat, attention=attention)
    model = pipeline.build_model(cfg)
    assert model.attention_forward == {forward: 3}
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
    assert float(counts[1]) == tokens.size * 6 * 2      # top-6, two layers
    assert float(counts[0]) == picked.max(axis=1).sum()
    assert float(counts[2]) == picked[:, 2:4].sum() < float(counts[1])

    step, create, arguments = _train_step(model, optax.sgd(0.05))
    run = jax.jit(step)
    now = create(params, state)
    bias = {name: b["moe"]["bias"] for name, b in state.items()}
    for i in range(3):
        batch = _tokens(cfg, 4, seed=10 + i)
        before = jax.tree.map(np.asarray, (now.params, now.batch_stats))
        now, _, _ = run(*arguments(now, batch))
        for (name, b), c in zip(sorted(bias.items()),
                                counts_of(*before, batch)):
            assert float(np.sum(c)) == batch.size * 6
            bias[name] = np.asarray(reference.next_bias(b, c, cfg))
            got = now.batch_stats[name]["moe"]
            np.testing.assert_allclose(got["bias"], bias[name], rtol=0,
                                       atol=1e-7)
            assert not np.any(np.asarray(got["counts"]))
    assert any(np.abs(bias[n] - state[n]["moe"]["bias"]).max() > 1e-3
               for n in bias)


# --------------------------------------------- (g) a recomputed latent block
def test_a_recomputed_latent_block_keeps_its_kernels_pair(
        forward_flash_kernels):
    """Loss and gradients are the unrecomputed model's; the built step holds
    ONE forward kernel a layer (q, k and v are formed again from the normed
    input, the kernel's output and row sums are kept), its operands keys of
    24 beside values of 16 with nothing padded, and counts its layers
    ``once`` and as ``latent``."""
    import jax
    import optax

    def built(remat):
        cfg, pipeline, _ = _files(remat_blocks=remat, attention="flash")
        return cfg, pipeline.build_model(cfg)

    cfg, plain = built(False)
    _, recomputed = built(True)
    tokens = _tokens(cfg, 2, seed=2)
    params, state = _variables(plain, tokens, bias_std=0.1)
    w = np.full(2, 0.5, np.float32)
    (loss, _), grads = lm_testing.loss_and_grads(recomputed, params, state,
                                                 tokens, w)
    (want_loss, _), want_grads = lm_testing.loss_and_grads(
        plain, params, state, tokens, w)
    assert abs(float(loss) - float(want_loss)) <= F32_TOL * float(want_loss)
    _close(grads, want_grads)

    before = lm_testing.counters()
    step, create, arguments = _train_step(recomputed, optax.sgd(0.05))
    assert [lm_testing.moved(before, name) for name in (
        "train_attention_layers_total", "train_attention_forward_total")] \
        == [{"full": 3, "latent": 3}, {"once": 3}]
    program = str(jax.make_jaxpr(step)(*arguments(create(params, state),
                                                  tokens)))
    assert forward_flash_kernels(program) == 3
    # q and k [B * H, T, 24], v [B * H, T, 16] -> the output at 16
    assert "f32[8,32,24]" in program and "f32[8,32,16]" in program
    assert "f32[8,32,32]" not in program


# ------------------------------------------------------- (h) older models
# sha256 of the estimator's train step as jax lowers it (the StableHLO text,
# no source locations; the flash kernels interpreted in blocks of 16, so
# their bodies are in it) for the three older families' CPU cuts: with the
# backward as the pair of kernels, computed on the commit before latent
# attention (74af897) with ``_step_text``; with the backward as one kernel,
# on the commit that built it (PR 43). A PR that means to change one of
# these programs replaces its line (PR 58 the four of the models that hold
# a share of their experts: its held rows come back to token order in runs;
# PR 62 the four of the two recomputed models, which keep their attention's
# inputs: the two of ``olmoe``, which is not recomputed, are the parent's).
PARENT_STEP = {
    ("olmoe-1b-7b", "split"):
        "fc12cbdf792919544024982de7e9d345a78459791d3896cc61eac78be79ff455",
    ("smallthinker-21b-a3b", "split"):
        "8f009551937607b21c9003d18c667001ed179ee8e4809b5f3c6d0efcbf729be6",
    ("trinity-mini", "split"):
        "4f0029dd34cdc1fb95a7a1f8a3dce768c34226ad1747bb1d35b8e13ad8c73676",
    ("olmoe-1b-7b", "fused"):
        "8f37bf58d0cd1236e4ebe06e3aaa2d7ba11414a3ba5e485f65347853ae8aadec",
    ("smallthinker-21b-a3b", "fused"):
        "5532bc0ad438c3cf87f4ac2fb00df7db4a9c9dc2ef26e05fe280a3a5e22a8b3c",
    ("trinity-mini", "fused"):
        "eda453dbe06d3815de71073821a63860678bce1bd18a786b4d2abfeba1523ecf",
}


@pytest.mark.parametrize("config,cell", [
    ("olmoe-1b-7b", "olmoe_1b7b_train"),
    ("smallthinker-21b-a3b", "smallthinker_21ba3b_16k_train"),
    ("trinity-mini", "trinity_mini_8k_train")])
@pytest.mark.parametrize("backward", ["fused", "split"])
def test_an_older_familys_step_is_the_parents_text(config, cell, backward,
                                                   forward_flash_kernels,
                                                   monkeypatch):
    """Every latent option is off by default, the blocks build ``Attention``
    as it was, and a flash call with one width lowers to the text it lowered
    to before the kernels took two; where the backward takes its pair of
    kernels (the shape rule set aside), to the text of before the one kernel:
    the pair is as it was."""
    from raydp_tpu.ops import flash_attention as fa
    if backward == "split":
        monkeypatch.setattr(fa, "_fused_backward_fits", lambda *a: False)
    model, text, params = _step_text(config, cell)
    attn = params["block_0"]["attn"]
    assert (model.kv_lora_rank, model.q_lora_rank, model.qk_nope_head_dim,
            model.qk_rope_head_dim, model.v_head_dim,
            model.rope_interleave) == (None,) * 5 + (False,)
    assert "latent" not in model.attention_layers
    assert {"q", "k", "v", "o"} <= set(attn) and "kv_a" not in attn
    assert model.attention_inputs == (
        {} if config == "olmoe-1b-7b" else {"kept": model.num_layers})
    assert hashlib.sha256(text.encode()).hexdigest() == PARENT_STEP[
        config, backward]
