"""The ``blockdiff_moe_lm`` family (SDAR-30B-A3B-Chat) on the CPU at tiny
sizes: the block-diffusion mask through the flash kernels against a
brute-force table, the sampler, the per-position head loss, the program
against ``chipbench/reference/sdar-30b-a3b-chat.py`` (forward and the loss's
gradients on seeded weights and an explicit noised copy), the clean half's
independence of the noise, the eight shares of an expert layer, the train
step's key, and the older families' lowered steps.
"""

from __future__ import annotations

import copy
import functools
import hashlib
import os

import numpy as np
import pytest

from tests import lm_testing
from tests.lm_testing import (F32_TOL, ROOT, close as _close,
                              leaves as _leaves, step_text as _step_text,
                              tokens as _tokens, train_step as _train_step)

CONFIG = "sdar-30b-a3b-chat"

# 8 query heads on 1 K/V head (eight a group, as published), two layers, 16
# experts of which experts 2-3 are held, 4 a token, 64 of 512 vocabulary rows
# (the mask id 62, the end-of-text id 63), 32 tokens a row in blocks of 4
TINY = {"hidden_size": 32, "head_dim": 8, "num_attention_heads": 8,
        "num_key_value_heads": 1, "moe_intermediate_size": 16,
        "num_experts": 16, "first_expert": 2, "experts_held": 2,
        "num_experts_per_tok": 4, "vocab_size": 512, "vocab_rows_held": 64,
        "layers": 2, "seq_len": 32, "compared_positions": 8,
        "compute_dtype": "float32", "attention": "dense", "init_std": 0.3,
        "remat_blocks": False, "diffusion": {"mask_id": 62}}
_files = functools.partial(lm_testing.files, CONFIG, TINY)


def _noised(cfg, pipeline, tokens):
    """The noised copy and levels a plain call of the program draws."""
    import jax
    from raydp_tpu.models.transformer import block_diffusion_noise
    spec = pipeline.diffusion_spec(cfg["diffusion"])
    noised, level, masked = block_diffusion_noise(
        jax.random.PRNGKey(spec.eval_seed), jax.numpy.asarray(tokens), spec)
    return np.asarray(noised), np.asarray(level), np.asarray(masked)


def _moved_norms(model, tokens, seed=0):
    """Seeded weights; norms' weights moved off 1 so that they count."""
    params, _ = lm_testing.variables(model, tokens[:, :8], seed)
    rng = np.random.default_rng(seed)
    for name, block in params.items():
        for norm in ([block] if name == "ln_f" else
                     [block[k] for k in ("ln1", "ln2")] + [
                         block["attn"]["q_norm"], block["attn"]["k_norm"]]
                     if name.startswith("block_") else []):
            norm["scale"] = (1 + rng.normal(0, 0.2, norm["scale"].shape)
                             ).astype(np.float32)
    return {"params": params}


# ------------------------------------------------------------ (a) the mask
def _table(length, block):
    """Brute force, pair by pair: query i of [x0 ; x_t] sees key j."""
    def sees(i, j):
        bi, bj = (i % length) // block, (j % length) // block
        if i < length:
            return j < length and bj <= bi
        return bj < bi if j < length else bj == bi
    return np.array([[sees(i, j) for j in range(2 * length)]
                     for i in range(2 * length)])


@pytest.mark.parametrize("length,block,tile,backward,heads", [
    (64, 4, 32, "fused", (2, 1)),   # whole blocks a half, no tiles: edges
    # masked
    (48, 4, 32, "split", (4, 2)),   # whole; a half is no whole number of
    # blocks: every pair looked at, visible ones masked whole
    (512, 32, 256, "fused", (4, 2)),    # the compact walk in tiles of 128
    (256, 1, 256, "split", (2, 1)),     # one block a half
], ids=lambda v: str(v))
def test_the_mask_through_the_kernels_is_the_brute_force_table(
        length, block, tile, backward, heads, monkeypatch):
    """Bd in {1, 4, 32}, L a multiple of the kernels' block and not, query
    heads on grouped K/V heads (two on one, four on two): the op's visibility
    function, its jnp path and its Pallas kernels (interpret mode: forward,
    the one-kernel backward and the dK/dV + dQ pair) all give dense attention
    under the brute-force ``[2L, 2L]`` table, output and all three gradients;
    and the tiles the kernels count are the table's."""
    import jax
    import jax.numpy as jnp
    from raydp_tpu import metrics
    from raydp_tpu.ops import flash_attention as fa
    from raydp_tpu.ops.ring_attention import dense_attention

    if backward == "split":
        monkeypatch.setattr(fa, "FUSED_BWD_RESIDENT_BYTES", 0)
    t, (h, hk), d = 2 * length, heads, 8
    seen = _table(length, block)
    at = jnp.arange(t)
    np.testing.assert_array_equal(
        fa.blockdiff_visible(at[:, None], at[None, :], block, length), seen)
    rng = np.random.default_rng(0)
    q, w = (jnp.asarray(rng.normal(size=(1, t, h, d)), jnp.float32)
            for _ in range(2))
    k, v = (jnp.asarray(rng.normal(size=(1, t, hk, d)), jnp.float32)
            for _ in range(2))

    def brute(q, k, v):
        kk, vv = (jnp.repeat(a, h // hk, axis=2) for a in (k, v))
        s = jnp.einsum("bqhd,bkhd->bhqk", q, kk) / np.sqrt(d)
        s = jnp.where(seen[None, None], s, -jnp.inf)
        return jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, -1), vv)

    want = jax.jit(jax.value_and_grad(lambda *a: jnp.sum(brute(*a) * w),
                                      (0, 1, 2)))(q, k, v)
    for name, op in (
            ("dense", lambda *a: dense_attention(*a, blockdiff=block)),
            ("jnp", lambda *a: fa.flash_attention(
                *a, block_q=tile, block_k=tile, blockdiff=block)),
            ("kernel", lambda *a: fa.flash_attention(
                *a, block_q=tile, block_k=tile, interpret=True,
                blockdiff=block))):
        run = jax.value_and_grad(lambda *a: jnp.sum(op(*a) * w), (0, 1, 2))
        if name == "kernel":    # eagerly: a counter counts a call lowered
            before = metrics.snapshot()["counters"].get(
                "flash_blocks_total", {})
        else:
            run = jax.jit(run)
        got = run(q, k, v)
        for g, x in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
            assert float(jnp.max(jnp.abs(g - x))) < 2e-4, name
    # the block pairs the kernels computed hold every visible pair, and where
    # the walk is compact no pair without one
    after = metrics.snapshot()["counters"]["flash_blocks_total"]
    blk = fa._fit_block(t, tile)
    n = t // blk
    kernels = 2 if backward == "fused" else 3       # forward + backward
    computed = (after["computed"] - before.get("computed", 0)) / (kernels * h)
    with_pairs = sum(bool(seen[a * blk:(a + 1) * blk,
                               c * blk:(c + 1) * blk].any())
                     for a in range(n) for c in range(n))
    assert computed >= with_pairs
    if fa._bd_compact(length, blk, blk, block):
        assert computed == with_pairs
        assert (after["skipped_blockdiff"]
                - before.get("skipped_blockdiff", 0)) / (kernels * h) \
            == n * n - with_pairs


def test_the_mask_at_the_published_size_is_80_tile_pairs_of_256():
    """8,192 tokens in blocks of 4 under 1024-blocks: 36 + 36 + 8 block pairs
    hold a visible pair, 24 of them cut by an edge; in half-block tiles that
    is 288 tiles for 256 tiles' worth of visible pairs."""
    from raydp_tpu import metrics
    from raydp_tpu.ops import flash_attention as fa

    before = copy.deepcopy(metrics.snapshot()["counters"])
    fa._count_blocks(1, 1, 16384, 1024, 1024, None, True, 4)
    after = metrics.snapshot()["counters"]
    got = {name: {k: v - before.get(name, {}).get(k, 0)
                  for k, v in after[name].items()}
           for name in ("flash_blocks_total", "flash_tiles_total")}
    assert got["flash_blocks_total"]["computed"] == 80
    assert got["flash_blocks_total"]["skipped_blockdiff"] == 176
    tiles = got["flash_tiles_total"]
    assert tiles["masked"] == 2 * 24
    assert tiles["unmasked"] == 4 * 56 + 16
    assert tiles["skipped"] == 16 + 2 * 8
    assert tiles["masked"] + tiles["unmasked"] == 288
    assert fa._band_steps(16384, 1024, 1024, None, 4) == (9, 16)
    with pytest.raises(ValueError, match="whole blocks"):
        fa._check_blockdiff(30, 4, True, None)
    with pytest.raises(ValueError, match="no\n? *window|window"):
        fa._check_blockdiff(32, 4, True, 8)


# --------------------------------------------------------- (b) the sampler
@pytest.mark.parametrize("t_min", [1e-3, 0.5])
def test_the_samplers_masked_share_a_block_follows_t(t_min):
    """Over many blocks the share of a block's tokens masked is its t (the
    linear schedule), t lies in (t_min, 1] (the configuration's clip and a
    rehearsal's), a masked token holds the mask id and no other token moves;
    a key reproduces the draw and another key does not."""
    import jax
    from raydp_tpu.models.transformer import (BlockDiffusionSpec,
                                              block_diffusion_noise)

    spec = BlockDiffusionSpec(block=4, mask_id=62, t_min=t_min)
    tokens = np.random.default_rng(0).integers(0, 62, (64, 1024)).astype(
        np.int32)
    noised, level, masked = map(np.asarray, block_diffusion_noise(
        jax.random.PRNGKey(7), tokens, spec))
    assert level.shape == (64, 256) and level.dtype == np.float32
    assert t_min < level.min() and level.max() <= 1.0
    np.testing.assert_array_equal(noised, np.where(masked, 62, tokens))
    share = masked.reshape(64, 256, 4).mean(-1)
    width = (1 - t_min) / 8
    for lo in t_min + width * np.arange(8):     # blocks binned by their level
        pick = (level > lo) & (level <= lo + width)
        assert pick.sum() > 1000
        assert abs(share[pick].mean() - level[pick].mean()) < 0.02
    assert abs(masked.mean() - (1 + t_min) / 2) < 0.01
    again = block_diffusion_noise(jax.random.PRNGKey(7), tokens, spec)
    np.testing.assert_array_equal(again[0], noised)
    other = block_diffusion_noise(jax.random.PRNGKey(8), tokens, spec)
    assert (np.asarray(other[0]) != noised).mean() > 0.2
    with pytest.raises(ValueError, match="whole number of blocks"):
        block_diffusion_noise(jax.random.PRNGKey(0), tokens[:, :30], spec)


# --------------------------------------------------- (c) the head loss
@pytest.mark.parametrize("chunk", [7, 64])
def test_the_per_position_head_loss_is_the_plain_log_softmax_form(chunk):
    """Value, rows and both gradients against ``log_softmax`` written out,
    chunks that divide the positions and chunks that do not; and with
    uniform weights over shifted labels it is the old loss."""
    import jax
    import jax.numpy as jnp
    from raydp_tpu.models.transformer import lm_head_loss

    rng = np.random.default_rng(0)
    b, t, d, vocab = 3, 24, 16, 40
    hidden = jnp.asarray(rng.normal(size=(b, t, d)), jnp.float32)
    kernel = jnp.asarray(rng.normal(size=(d, vocab)), jnp.float32)
    tokens = jnp.asarray(rng.integers(0, vocab, (b, t)), jnp.int32)
    rows_w = jnp.asarray([0.5, 0.3, 0.2], jnp.float32)
    pos_w = jnp.asarray(rng.random((b, t)) * (rng.random((b, t)) < 0.5),
                        jnp.float32)

    def plain(hidden, kernel):
        logp = jax.nn.log_softmax(hidden @ kernel, axis=-1)
        ce = -jnp.take_along_axis(logp, tokens[..., None], -1)[..., 0]
        rows = jnp.sum(pos_w * ce, axis=1) / t
        return jnp.sum(rows_w * rows), rows

    def fused(hidden, kernel):
        return lm_head_loss(hidden, kernel, tokens, rows_w, chunk,
                            position_weights=pos_w)

    (want, want_rows), want_g = jax.value_and_grad(plain, (0, 1),
                                                   has_aux=True)(hidden,
                                                                 kernel)
    (got, got_rows), got_g = jax.value_and_grad(fused, (0, 1), has_aux=True)(
        hidden, kernel)
    np.testing.assert_allclose(got, want, rtol=1e-5)
    np.testing.assert_allclose(got_rows, want_rows, rtol=1e-5)
    _close(got_g, want_g)
    np.testing.assert_allclose(jax.jit(fused)(hidden, kernel)[0], want,
                               rtol=1e-5)
    # uniform weights on positions 0..T-2 against the labels shifted by one:
    # the next-token loss as it was
    shifted = jnp.concatenate([tokens[:, 1:], tokens[:, :1]], axis=1)
    ones = jnp.ones((b, t), jnp.float32).at[:, -1].set(0) * t / (t - 1)
    old = jax.value_and_grad(lambda h, k: lm_head_loss(
        h, k, tokens, rows_w, chunk)[0], (0, 1))(hidden, kernel)
    new = jax.value_and_grad(lambda h, k: lm_head_loss(
        h, k, shifted, rows_w, chunk, position_weights=ones)[0], (0, 1))(
            hidden, kernel)
    np.testing.assert_allclose(new[0], old[0], rtol=1e-5)
    _close(new[1], old[1])


# ------------------------------------- (d) the program against the reference
def test_the_parameter_tree_is_the_published_layers():
    """Names and shapes at the tiny widths; and at the PUBLISHED widths, by
    ``jax.eval_shape`` (nothing is allocated), the parameter count the
    configuration's file states."""
    import jax
    from chipbench import manifest

    cfg, pipeline, _ = _files()
    model = pipeline.build_model(cfg)
    params = _moved_norms(model, _tokens(cfg, 1, pipeline=pipeline))["params"]
    shapes = {k: v.shape for k, v in _leaves(params).items()}
    assert {k: v for k, v in shapes.items() if k.startswith("block_1/")} == {
        "block_1/ln1/scale": (32,), "block_1/ln2/scale": (32,),
        "block_1/attn/q/kernel": (32, 8, 8),
        "block_1/attn/k/kernel": (32, 1, 8),
        "block_1/attn/v/kernel": (32, 1, 8),
        "block_1/attn/o/kernel": (8, 8, 32),
        "block_1/attn/q_norm/scale": (8,), "block_1/attn/k_norm/scale": (8,),
        "block_1/moe/router": (32, 16),
        "block_1/moe/experts_gate": (2, 32, 16),
        "block_1/moe/experts_up": (2, 32, 16),
        "block_1/moe/experts_down": (2, 16, 32)}
    assert shapes["embed/embedding"] == shapes["lm_head/kernel"][::-1] \
        == (64, 32)
    assert model.rng_streams == ("diffusion",)
    assert model.attention_layers == {"blockdiff": 2}
    assert model.loss_counters[-2:] == (
        ("train_diffusion_tokens_total", "masked"),
        ("train_diffusion_tokens_total", "all"))
    full = manifest.load_json(ROOT, "configs", f"{CONFIG}.json")
    flops = manifest.load_module(ROOT, "flops", f"{full['family']}.py")
    assert sum(flops.parameters(full).values()) == 645_623_296
    assert "645,623,296" in full["assumed"]["parameters"]
    # one of the six layers traced at the published widths: a layer is
    # 94,638,336 parameters, embedding, head and final norm 77,793,280
    one = dict(full, layers=1)
    tree = jax.eval_shape(lambda: pipeline.build_model(one).init(
        jax.random.PRNGKey(0), np.zeros((1, 8), np.int32)))["params"]
    count = sum(int(np.prod(x.shape)) for x in jax.tree.leaves(tree))
    assert count == 94_638_336 + 77_793_280 == sum(
        flops.parameters(one).values())
    assert 6 * 94_638_336 + 77_793_280 == 645_623_296
    assert tree["block_0"]["moe"]["experts_up"].shape == (16, 2048, 768)
    assert tree["block_0"]["attn"]["k"]["kernel"].shape == (2048, 4, 128)
    assert tree["lm_head"]["kernel"].shape == (2048, 18992)


@pytest.mark.parametrize("dtype,attention,tol", [
    ("float32", "dense", 10 * F32_TOL), ("bfloat16", "flash", 0.05)])
def test_forward_logits_match_the_reference(dtype, attention, tol):
    """The program's plain call (it noises with the configuration's fixed
    key) against the reference handed the same noised copy: the logits at
    the last noised positions, as check (a) compares them."""
    import jax
    from chipbench import harness

    cfg, pipeline, reference = _files(compute_dtype=dtype,
                                      attention=attention)
    model = pipeline.build_model(cfg)
    tokens = _tokens(cfg, 3, pipeline=pipeline)
    variables = _moved_norms(model, tokens)
    noised, level, masked = _noised(cfg, pipeline, tokens)
    assert 0.2 < masked.mean() < 0.8 and (noised[masked] == 62).all()
    logits = np.asarray(jax.jit(model.apply)(variables, tokens))
    assert logits.shape == (3, 32, 64) and logits.dtype == np.float32
    got = np.asarray(pipeline.compared(logits, cfg))
    forward = jax.jit(lambda x_t: reference.forward(
        variables, (tokens, x_t, level), cfg))
    want = np.asarray(forward(noised))
    assert got.shape == want.shape == (3, 8, 64)
    assert harness.relative_rms_error(got, want) <= tol
    # the wrong noised copy is another model input: far outside
    other = np.where(masked, tokens, 62)
    assert harness.relative_rms_error(got, np.asarray(forward(other))) > 0.1


def test_a_planted_fault_reads_outside_the_tolerance():
    """``benchmarks/blockdiff_control.py``'s planted faults (on the chip they
    are run at the published widths beside check (a)): a noised query that
    also sees its own block's clean keys, position ids that run on into the
    noised copy, and a held expert that adds nothing each move the compared
    logits far outside what float32 rounding does, and each is taken back
    off the reference afterwards."""
    import importlib.util
    import jax
    from chipbench import harness

    path = os.path.join(ROOT, "benchmarks", "blockdiff_control.py")
    spec = importlib.util.spec_from_file_location("blockdiff_control", path)
    control = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(control)
    cfg, pipeline, reference = _files()
    model = pipeline.build_model(cfg)
    tokens = _tokens(cfg, 3, pipeline=pipeline)
    variables = _moved_norms(model, tokens)
    noised, level, _ = _noised(cfg, pipeline, tokens)
    got = np.asarray(pipeline.compared(
        jax.jit(model.apply)(variables, tokens), cfg))

    def error(handed):
        want = jax.jit(lambda: reference.forward(
            handed, (tokens, noised, level), cfg))()
        return harness.relative_rms_error(got, np.asarray(want))

    assert error(variables) <= 10 * F32_TOL
    faults = control._faults(reference, variables)
    assert sorted(faults) == ["held_expert_dropped",
                              "mask_clean_sees_own_noised_block",
                              "mask_noised_sees_no_clean_key",
                              "mask_noised_sees_own_clean_block",
                              "positions_run_on"]
    for name, (patch, handed) in faults.items():
        with control.patched(reference, patch):     # 100 x the rounding
            assert error(handed) > 1000 * F32_TOL, name
    assert error(variables) <= 10 * F32_TOL


@pytest.mark.parametrize("remat,attention", [(True, "flash")])
def test_the_loss_and_its_gradients_match_the_reference(remat, attention):
    """``loss_rows`` (the fused per-position head loss over the noised half,
    the held share's walk, blocks recomputed or not) against ``jax.grad`` of
    the reference's written-out loss on the same noised copy; the counts end
    in the tokens masked and all tokens."""
    import jax
    import jax.numpy as jnp

    cfg, pipeline, reference = _files(remat_blocks=remat,
                                      attention=attention)
    model = pipeline.build_model(cfg)
    tokens = _tokens(cfg, 2, pipeline=pipeline)
    variables = _moved_norms(model, tokens)
    noised, level, masked = _noised(cfg, pipeline, tokens)
    weights = jnp.full((2,), 0.5, jnp.float32)

    def program(params):
        loss, counts = model.apply({"params": params}, tokens, tokens,
                                   weights, method=model.loss_rows)
        return loss, counts

    (got, counts), got_g = jax.jit(jax.value_and_grad(
        program, has_aux=True))(variables["params"])
    want, want_g = jax.jit(jax.value_and_grad(lambda p: reference.loss(
        p, tokens, noised, level, cfg)))(variables["params"])
    np.testing.assert_allclose(got, want, rtol=1e-5)
    _close(got_g, want_g, tol=20 * F32_TOL)
    assert float(counts[-2]) == masked.sum() and float(counts[-1]) == 64
    # 2 layers x 2 rows x 64 POSITIONS x 4 a token: twice a row's tokens
    assert float(counts[1]) == 2 * 2 * 64 * 4


def test_the_clean_half_does_not_see_the_noise_and_with_blocks_of_one_is_causal():
    """A clean query sees no noised key: whatever the noise, the clean
    half's hidden states are the same (so they are the block-causal model's
    on x0 alone: the mask's clean-on-clean region is the brute-force table's,
    above); with blocks of one token they are the causal model's on x0; and
    the noised half does see the noise."""
    import jax

    cfg, pipeline, _ = _files()
    tokens = _tokens(cfg, 2, pipeline=pipeline)
    model = pipeline.build_model(cfg)
    variables = _moved_norms(model, tokens)
    noisy = jax.jit(lambda key: model.apply(
        variables, tokens, return_hidden=True, rngs={"diffusion": key}))
    hidden = [np.asarray(noisy(jax.random.PRNGKey(seed))) for seed in (1, 2)]
    assert hidden[0].shape == (2, 64, 32)
    np.testing.assert_allclose(hidden[0][:, :32], hidden[1][:, :32],
                               rtol=1e-5, atol=1e-6)
    assert np.abs(hidden[0][:, 32:] - hidden[1][:, 32:]).max() > 0.1
    ones = pipeline.build_model(_files(diffusion={"block_length": 1})[0])
    hidden_of = lambda m: np.asarray(jax.jit(lambda: m.apply(  # noqa: E731
        variables, tokens, return_hidden=True))())
    causal = hidden_of(ones.clone(diffusion=None))
    np.testing.assert_allclose(hidden_of(ones)[:, :32], causal, rtol=1e-4,
                               atol=1e-5)
    # blocks of four are not causal: a clean query sees its block's end
    assert np.abs(hidden[0][:, :32] - causal).max() > 0.01


def test_the_eight_shares_of_an_expert_layer_add_up_to_the_uncut_layer():
    """Experts 0-1, 2-3, ... 14-15 of 16, 4 a token: each chip routes over
    all sixteen (softmax, the weights renormalised over all four choices) and
    computes its own experts' part; the parts sum to the reference's uncut
    layer (no shared expert to count once) and the held slots to all slots."""
    import jax
    import jax.numpy as jnp
    from raydp_tpu.models.moe import MoE

    cfg, _, reference = _files()
    rng = np.random.default_rng(0)
    m = rng.normal(size=(48, 32)).astype(np.float32)
    full = {"router": rng.normal(0, 0.5, (32, 16)).astype(np.float32),
            "experts_gate": rng.normal(0, 0.3, (16, 32, 16)).astype(
                np.float32),
            "experts_up": rng.normal(0, 0.3, (16, 32, 16)).astype(np.float32),
            "experts_down": rng.normal(0, 0.3, (16, 16, 32)).astype(
                np.float32)}

    def share(first, held):
        return {k: v if k == "router" else v[first:first + held]
                for k, v in full.items()}

    def program(first, held):
        layer = MoE(16, 4, 16, jnp.float32, jax.nn.initializers.normal(0.3),
                    first, held, "silu", True)
        return layer.apply({"params": share(first, held)}, m[None])

    want = np.asarray(reference.expert_layer(full, m, dict(
        cfg, first_expert=0, experts_held=16)))
    parts, held_slots = [], 0.0
    for first in range(0, 16, 2):
        y, aux = program(first, 2)
        np.testing.assert_allclose(y[0], reference.expert_layer(
            share(first, 2), m, dict(cfg, first_expert=first,
                                     experts_held=2)), rtol=1e-4, atol=1e-5)
        parts.append(np.asarray(y[0]))
        held_slots += float(aux["slots_held"])
        assert float(aux["slots_all"]) == 4 * 48
    np.testing.assert_allclose(sum(parts), want, rtol=1e-4, atol=1e-5)
    assert held_slots == 4 * 48 and np.abs(want).max() > 0.1
    y, aux = program(0, 16)
    np.testing.assert_allclose(y[0], want, rtol=1e-4, atol=1e-5)
    assert "slots_held" not in aux


# ------------------------------------------------------ (e) the train step
def test_a_train_step_draws_its_noise_from_the_seed_and_the_step():
    """The estimator's step folds the key from the fit's seed and the
    optimizer step: the same state and batch give the same loss and count,
    the next step (the same batch again: a row met in a second epoch) and
    another seed draw anew; an accumulated step draws a micro-batch at a
    time; the loss is finite and the count is the device's."""
    import jax
    import optax

    cfg, pipeline, _ = _files(layers=1)
    model = pipeline.build_model(cfg)
    tokens = np.concatenate([_tokens(cfg, 2, pipeline=pipeline)] * 2)
    params = _moved_norms(model, tokens)["params"]

    def run(seed, accum=1):
        """(loss, masked, all) of step 0, of step 0 again, and of step 1."""
        step, create, arguments = _train_step(model, optax.sgd(0.0), accum,
                                              seed)
        jitted, start = jax.jit(step), create(params)
        out, state = [], start
        for state_in in (start, start, None):
            state, loss, (counts,) = jitted(*arguments(
                state if state_in is None else state_in, tokens))
            out.append((float(loss), float(counts[-2]), float(counts[-1])))
        return out

    first, again, second = run(0)
    assert again == first and first[2] == second[2] == 4 * 32
    assert first[1] != second[1] and first[0] != second[0]   # sgd(0): noise
    assert run(1)[0][1] != first[1]
    assert np.isfinite(first[0]) and 0.2 < first[1] / first[2] < 0.8
    halves = run(0, accum=2)[0]
    assert halves[2] == 4 * 32 and np.isfinite(halves[0])
    # rows 0-1 and rows 2-3 are the same tokens: had the two micro-batches
    # shared a draw, an even number of tokens would be masked
    assert halves[1] != first[1]


def test_a_model_without_streams_gets_no_key(monkeypatch):
    """A model that names no stream is applied without ``rngs``: the step's
    jaxpr holds no random bits at all."""
    import jax
    import optax
    from raydp_tpu.models import TransformerLM

    model = TransformerLM(vocab_size=64, dim=32, num_heads=4, num_layers=1,
                          attention="dense")
    assert not hasattr(model, "rng_streams") or model.rng_streams == ()
    tokens = np.zeros((2, 16), np.int32)
    params = model.init(jax.random.PRNGKey(0), tokens)["params"]
    step, create, arguments = _train_step(model, optax.sgd(0.1))
    text = str(jax.make_jaxpr(step)(*arguments(create(params), tokens)))
    assert "random_bits" not in text and "threefry" not in text


# ------------------------------------------------------- (f) older models
# sha256 of the estimator's train step as jax lowers it (the StableHLO text,
# no source locations; every op on its ``jax.numpy`` path) for the newest of
# the five older families' CPU cuts, computed on the commit before this
# family (e3766c2) with ``_step_text``. The other four are held, with the
# hashes they had on that commit too, by ``tests/test_ssm_moe_lm.py`` (and
# ``tests/test_mla_moe_lm.py``), which this PR leaves as they are. A PR that
# means to change one of these programs replaces its line (PR 58 its one
# line: a share's held rows come back to token order in runs; PR 62 too:
# its one attention layer keeps its inputs).
# Since PR 64 (a pair with a delta-rule operator, latent attention without
# positions: both off by default) the convolution family's cut is held too,
# with the hash it had on the commit before that PR (17a7a40).
PARENT_STEP = {
    "nemotron-3-nano-30b-a3b":
        "9b91afeb9d12fc00b28cc06749aee99ca1b7fa8d56ff779cf4d86707faa440cf",
    "lfm2-8b-a1b":
        "ba865e986f9083cbcd3290636a0a25f21a201abf9fa0852079acf3702d04b74c",
}


@pytest.mark.parametrize("config,cell", [
    ("nemotron-3-nano-30b-a3b", "nemotron3_nano_30ba3b_16k_train"),
    ("lfm2-8b-a1b", "lfm2_8ba1b_8k_train")])
def test_an_older_familys_step_is_the_parents_text(config, cell):
    """The new mask, the per-position weights and the step's key are off by
    default: a model without ``diffusion`` names no stream, its attention
    takes the mask it took, its head loss shifts the labels, and the lowered
    step is the text it was. So is no ``K`` layer."""
    model, text, _ = _step_text(config, cell)
    assert model.diffusion is None and model.rng_streams == ()
    assert "blockdiff" not in model.attention_layers
    assert model.kda is None and not any(model.kda_layers.values())
    assert "K" not in model.layer_kinds
    assert hashlib.sha256(text.encode()).hexdigest() == PARENT_STEP[config]


def test_the_new_familys_step_holds_the_noise_and_the_new_mask():
    """And the new family's CPU-cut step is another program: it draws random
    bits, and its attention layers count under the new mask."""
    model, text, _ = _step_text(CONFIG, "sdar_30ba3b_8k_blockdiff_train")
    assert model.rng_streams == ("diffusion",)
    assert model.attention_layers == {"blockdiff": 1}
    assert "diffusion" in text or "threefry" in text or "rng" in text
