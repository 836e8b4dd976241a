"""SmallThinker-style LM (window and full attention mixed on grouped-query
heads, the router on the attention's input, ReLU-gated experts of which one
chip holds a share): the flash op, the expert layer's shares, the whole model
and a fit, against dense masked attention and the plain reference
(``chipbench/reference/smallthinker-21b-a3b.py``: float32 ``jax.numpy``,
attention by blocks of queries, every held expert on every token), at small
sizes on the CPU, seeded random weights. Widths are small here, and only here
(the fit at the CPU cut keeps them).
"""

import functools
import re

import numpy as np
import pytest

from tests import lm_testing
from tests.lm_testing import (F32_TOL, ROOT, close as _close,
                              estimator as _estimator, leaves as _leaves,
                              token_frame as _token_frame, tokens as _tokens,
                              variables as _variables)

CONFIG = "smallthinker-21b-a3b"

# 14 query heads on 2 K/V heads (seven a group, as published), four layers in
# the published pattern, 8 experts of which experts 2-3 are held, 3 a token,
# 64 of 256 vocabulary rows, 32 positions with a window of 8
TINY = {"hidden_size": 32, "head_dim": 8, "num_attention_heads": 14,
        "num_key_value_heads": 2, "moe_ffn_hidden_size": 16,
        "moe_num_primary_experts": 8, "first_expert": 2, "experts_held": 2,
        "moe_num_active_primary_experts": 3, "vocab_size": 256,
        "vocab_rows_held": 64, "max_position_embeddings": 32,
        "sliding_window_size": 8, "layers": 4, "compared_positions": 8,
        "compute_dtype": "float32", "attention": "dense", "init_std": 0.3}
_files = functools.partial(lm_testing.files, CONFIG, TINY)


# ------------------------------------------------- (a) the flash op
# (T, window, block_q, block_k, query heads, K/V heads[, keys' width,
# values' width: 16 and 16 where not given])
FLASH_CASES = {
    "t_below_the_window": (32, 48, 16, 16, 4, 2),
    "t_at_the_window": (32, 32, 16, 16, 4, 2),
    "t_four_windows": (64, 16, 16, 16, 4, 2),
    "window_no_multiple_of_the_block": (64, 24, 16, 16, 4, 2),
    "window_odd": (128, 37, 16, 16, 4, 4),
    "seven_query_heads_a_kv_head": (64, 16, 16, 16, 14, 2),
    "seven_query_heads_full": (64, None, 16, 16, 7, 1),
    "k_blocks_wider": (64, 16, 16, 32, 4, 2),
    "q_blocks_wider": (128, 32, 32, 16, 4, 2),
    "window_of_one": (64, 1, 16, 16, 4, 2),
    "as_before": (64, None, 16, 16, 4, 4),
    # latent attention's two widths: keys and queries of 192, values of 128
    "keys_192_values_128": (64, None, 16, 16, 4, 4, 192, 128),
    "keys_192_values_128_window": (64, 16, 16, 16, 4, 4, 192, 128),
    "keys_192_values_128_group": (64, None, 16, 16, 4, 2, 192, 128),
    "keys_192_values_128_window_group": (64, 24, 16, 32, 6, 2, 192, 128),
}


def _qkv(t, h, hk, d=16, seed=0, d_v=None):
    import jax
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    return (jax.random.normal(ks[0], (2, t, h, d)),
            jax.random.normal(ks[1], (2, t, hk, d)),
            jax.random.normal(ks[2], (2, t, hk, d_v or d)),
            jax.random.normal(ks[3], (2, t, h, d_v or d)))


@pytest.mark.parametrize("interpret", [False, True],
                         ids=["jnp_path", "pallas_interpret"])
@pytest.mark.parametrize("case", list(FLASH_CASES))
def test_flash_with_window_and_grouped_kv_matches_dense_masked_attention(
        case, interpret):
    """Forward and all three gradients; dK and dV are the sums over a
    group's query heads, each at its own width."""
    import jax
    import jax.numpy as jnp
    from raydp_tpu.ops.flash_attention import flash_attention
    from raydp_tpu.ops.ring_attention import dense_attention

    t, window, blk_q, blk_k, h, hk, d, d_v = (FLASH_CASES[case]
                                              + (16, 16))[:8]
    q, k, v, w = _qkv(t, h, hk, d, d_v=d_v)
    got = jax.value_and_grad(lambda *a: jnp.sum(flash_attention(
        *a, window=window, block_q=blk_q, block_k=blk_k,
        interpret=interpret) * w), (0, 1, 2))(q, k, v)
    want = jax.value_and_grad(lambda *a: jnp.sum(dense_attention(
        *a, window=window) * w), (0, 1, 2))(q, k, v)
    assert got[1][1].shape == (2, t, hk, d)
    assert got[1][2].shape == (2, t, hk, d_v)
    for g, x in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_allclose(g, x, rtol=2e-4, atol=2e-4)


# (T, window, block_q, block_k): sizes at which a q block has a far-edge, an
# interior and a diagonal block, and (the first three) an edge block is walked
# in tiles of 256 or 128; interpret mode has no lane rule, so the head is 16
# wide
EDGE_CASES = {
    "full_causal": (1024, None, 512, 512),
    "full_causal_keys_24_values_16": (1024, None, 512, 512, 24),
    "window_keys_24_values_16": (2048, 1024, 512, 512, 24),
    "window_a_multiple_of_the_block": (2048, 1024, 512, 512),
    "window_of_two_small_blocks": (1024, 512, 256, 256),
    "window_no_multiple_whole_edges": (1024, 1000, 256, 256),
    "q_and_k_blocks_differ_whole_edges": (1024, 512, 512, 256),
}


@pytest.mark.parametrize("heads", [(2, 2), (4, 1)],
                         ids=["a_kv_head_a_query_head", "groups_of_four"])
@pytest.mark.parametrize("case", list(EDGE_CASES))
def test_the_kernels_follow_the_masks_two_edges(case, heads):
    """Forward and the three gradients against dense masked attention where
    the kernels sort their block pairs by the mask's edges: interior blocks
    take no mask, an edge block computes only the tiles a query can see (or,
    where the geometry is not static, is masked whole)."""
    import jax
    import jax.numpy as jnp
    from raydp_tpu.ops import flash_attention as fa
    from raydp_tpu.ops.ring_attention import dense_attention

    t, window, blk_q, blk_k, d = (EDGE_CASES[case] + (16,))[:5]
    assert (fa._tile(blk_q, blk_k, window) is None) == ("whole" in case)
    h, hk = heads
    ks = jax.random.split(jax.random.PRNGKey(7), 4)
    q, k = (jax.random.normal(key, (1, t, n, d))
            for key, n in zip(ks[:2], heads))
    w, v = (jax.random.normal(key, (1, t, n, 16))
            for key, n in zip(ks[2:], heads))
    got = jax.value_and_grad(lambda *a: jnp.sum(fa.flash_attention(
        *a, window=window, block_q=blk_q, block_k=blk_k,
        interpret=True) * w), (0, 1, 2))(q, k, v)
    want = jax.value_and_grad(lambda *a: jnp.sum(dense_attention(
        *a, window=window) * w), (0, 1, 2))(q, k, v)
    for g, x in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_allclose(g, x, rtol=2e-4, atol=2e-4)


def test_dense_attention_with_a_window_is_the_masked_softmax():
    """The dense path itself, against numpy: query i sees keys i-w+1..i of
    K/V head h // group."""
    from raydp_tpu.ops.ring_attention import dense_attention

    q, k, v, _ = _qkv(16, 4, 2, d=8, seed=3)
    got = np.asarray(dense_attention(q, k, v, window=5))
    q, k, v = (np.asarray(a, np.float64) for a in (q, k, v))
    for h in range(4):
        s = q[0, :, h] @ k[0, :, h // 2].T / np.sqrt(8)
        i, j = np.indices(s.shape)
        s[(j > i) | (i - j >= 5)] = -np.inf
        p = np.exp(s - s.max(-1, keepdims=True))
        want = (p / p.sum(-1, keepdims=True)) @ v[0, :, h // 2]
        np.testing.assert_allclose(got[0, :, h], want, rtol=1e-5, atol=1e-5)


def test_no_window_and_equal_heads_is_the_op_as_it_was():
    """``window=None`` with as many K/V heads as query heads: the forward is
    bit for bit the fused jnp math the op had before either option (written
    out here), the kernels built are the ones named before, over the whole
    square of blocks with the causal skip in the kernel."""
    import jax
    import jax.numpy as jnp
    from raydp_tpu.ops import flash_attention as fa

    q, k, v, _ = _qkv(64, 4, 4)
    got = fa.flash_attention(q, k, v, block_q=16, block_k=16)

    def as_before(q, k, v):
        to3 = lambda x: x.transpose(0, 2, 1, 3).reshape(8, 64, 16)  # noqa
        s = jnp.einsum("bqd,bkd->bqk", to3(q), to3(k)) * 0.25
        s = jnp.where(jnp.tril(jnp.ones((64, 64), bool))[None], s, -1e30)
        p = jnp.exp(s - jax.nn.logsumexp(s, axis=-1)[..., None])
        out = jnp.einsum("bqk,bkd->bqd", p, to3(v))
        return out.reshape(2, 4, 64, 16).transpose(0, 2, 1, 3)

    np.testing.assert_array_equal(np.asarray(got),
                                  np.asarray(as_before(q, k, v)))
    text = str(jax.make_jaxpr(jax.grad(lambda *a: fa.flash_attention(
        *a, block_q=16, block_k=16, interpret=True).sum(), (0, 1, 2)))(
            q, k, v))
    # the forward and the one backward kernel; the pair's names are the
    # fallback's (``test_one_pallas_call_a_kernel_...``)
    fwd, dkdv, dq, fused = fa.KERNEL_NAMES
    assert set(re.findall(r"name=(rdt_flash_(?:fwd|bwd)\w*)", text)) == {
        fwd, fused}
    assert fused == dkdv + "_dq" and f"name={dq}" not in text
    assert "rdt_flash_win" not in text
    grids = re.findall(r"grid=\(([\d, ]+)\)", text.replace(", ", ","))
    assert sorted(grids) == ["8,1,4,4", "8,4,4"]
    windowed = str(jax.make_jaxpr(lambda *a: fa.flash_attention(
        *a, block_q=16, block_k=16, interpret=True, window=16))(q, k, v))
    assert fa.WINDOW_KERNEL_NAMES[0] in windowed


def test_the_windowed_kernels_walk_the_band_and_count_its_blocks():
    """A window of 4096 over 16,384 positions in 1024-blocks: five steps a q
    block, whatever the sequence length; the counter says what became of the
    256 block pairs a head."""
    from raydp_tpu import metrics as registry
    from raydp_tpu.ops import flash_attention as fa

    assert fa._band_steps(16384, 1024, 1024, 4096) == (5, 5)
    assert fa._band_steps(16384, 1024, 1024, None) == (16, 16)
    assert fa._band_steps(64, 16, 16, 24) == (3, 3)
    assert fa._band_steps(64, 16, 32, 16) == (2, 3)
    before = dict(registry.snapshot()["counters"].get(
        "flash_blocks_total", {}))
    fa._count_blocks(1, 28, 16384, 1024, 1024, 4096, True)
    after = registry.snapshot()["counters"]["flash_blocks_total"]
    moved = {k: after[k] - before.get(k, 0) for k in after}
    assert moved == {"computed": 28 * 70, "skipped_causal": 28 * 120,
                     "skipped_window": 28 * 66}
    fa._count_blocks(2, 1, 64, 16, 16, None, True)
    full = registry.snapshot()["counters"]["flash_blocks_total"]
    assert full["computed"] - after["computed"] == 2 * 10
    assert full["skipped_window"] == after["skipped_window"]


# (T, window, blocks, query heads, K/V heads, causal) -> a head's computed
# block pairs
BACKWARD_COUNTS = {
    "full_causal": ((64, None, 16, 4, 4, True), 10),
    "group_of_four_window": ((64, 32, 16, 4, 1, True), 9),
    "window_no_multiple": ((64, 24, 16, 2, 2, True), 9),
    "one_block": ((64, None, 64, 2, 1, True), 1),
    "not_causal": ((64, None, 16, 2, 2, False), 16),
}


@pytest.mark.parametrize("kernels", ["fused", "split"])
@pytest.mark.parametrize("case", list(BACKWARD_COUNTS))
def test_a_backward_counts_itself_and_its_block_pairs_once_a_kernel(
        case, kernels, monkeypatch):
    """``flash_backward_total`` says which backward a layer call built;
    ``flash_blocks_total`` gains a head's computed pairs once from the one
    kernel and twice from the pair (and once from the forward)."""
    import jax
    from raydp_tpu.ops import flash_attention as fa

    (t, window, blk, h, hk, causal), pairs = BACKWARD_COUNTS[case]
    if kernels == "split":
        monkeypatch.setattr(fa, "_fused_backward_fits", lambda *a: False)
    q, k, v, _ = _qkv(t, h, hk)

    def build():
        jax.make_jaxpr(jax.grad(lambda *a: fa.flash_attention(
            *a, causal=causal, window=window, block_q=blk, block_k=blk,
            interpret=True).sum(), (0, 1, 2)))(q, k, v)

    assert _counted("flash_backward_total", build) == {kernels: 1}
    times = 1 + (1 if kernels == "fused" else 2)    # the forward's, then
    assert _counted("flash_blocks_total", build)["computed"] == (
        times * 2 * h * pairs)


def _counted(name, call):
    """What counter ``name`` gains from ``call()``, by label."""
    before = lm_testing.counters()
    call()
    return lm_testing.moved(before, name)


# a head's tiles at the three cells' geometries (1024-blocks in tiles of 512,
# four a block): (T, window) -> (interior blocks, edge blocks) a head
CELL_GEOMETRIES = {
    "trinity_window": ((8192, 2048), (7, 14)),          # 17.5 blocks' worth
    "trinity_full": ((8192, None), (28, 8)),            # 34
    "smallthinker_window": ((16384, 4096), (42, 28)),   # 63
    "smallthinker_full": ((16384, None), (120, 16)),    # 132
    "olmoe_full": ((4096, None), (6, 4)),               # 9
}


@pytest.mark.parametrize("cell", list(CELL_GEOMETRIES))
def test_the_tiles_of_the_cells_geometries_by_what_becomes_of_them(cell):
    """An interior block is four unmasked tiles; an edge block a triangle:
    two crossed by the edge, one inside the mask, one never computed. No
    edge block of a cell stays whole, and the block counter reads what it
    read."""
    from raydp_tpu.ops import flash_attention as fa

    (t, window), (interior, edge) = CELL_GEOMETRIES[cell]
    assert fa._tile(1024, 1024, window) == 1024 // fa._TILES_A_SIDE == 512
    tiles = _counted("flash_tiles_total", lambda: fa._count_blocks(
        1, 3, t, 1024, 1024, window, True))
    assert tiles == {"unmasked": 3 * (4 * interior + edge),
                     "masked": 3 * 2 * edge, "skipped": 3 * edge}
    blocks = _counted("flash_blocks_total", lambda: fa._count_blocks(
        2, 1, t, 1024, 1024, window, True))
    assert blocks["computed"] == 2 * (interior + edge)
    if cell == "trinity_window":
        assert (tiles["unmasked"] + tiles["masked"]) / (3 * 4) == 17.5


@pytest.mark.parametrize("geometry,fates", [
    ((1024, 256, 256, 1000), {"unmasked": 4 * 5, "whole_edge": 4 * 5}),
    ((1024, 512, 256, None), {"unmasked": 4 * 2, "whole_edge": 4 * 4}),
    ((256, 128, 128, None), {"unmasked": 4, "whole_edge": 4 * 2}),
    ((1024, 256, 256, 512), {"unmasked": 4 * 3 + 6, "masked": 2 * 6,
                             "skipped": 6}),
], ids=["window_no_multiple", "blocks_differ", "block_of_one_tile",
        "tiles_of_128"])
def test_the_tiles_where_an_edge_block_stays_whole_or_a_tile_is_small(
        geometry, fates):
    from raydp_tpu.ops import flash_attention as fa

    t, blk_q, blk_k, window = geometry
    assert _counted("flash_tiles_total", lambda: fa._count_blocks(
        1, 1, t, blk_q, blk_k, window, True)) == fates
    # without a mask there is no edge: every tile of every block
    assert _counted("flash_tiles_total", lambda: fa._count_blocks(
        1, 1, t, blk_q, blk_k, None, False)) == {
            "unmasked": 4 * (t // blk_q) * (t // blk_k)}


@pytest.mark.parametrize("kernels", ["fused", "split"])
@pytest.mark.parametrize("window", [None, 512], ids=["full", "windowed"])
def test_one_pallas_call_a_kernel_under_the_pinned_names_and_grids(
        window, kernels, monkeypatch):
    """The edges' paths live inside the kernels: a call's jaxpr holds one
    ``pallas_call`` a kernel, named as a trace's readers expect. The backward
    is ONE kernel ``..._bwd_dkdv_dq`` over ``(bkv, group, T/blk_q, k_steps)``
    and none named ``..._bwd_dq``; where a head's gradients do not fit, the
    pair over ``(bkv, T/blk_k, group * q_steps)`` and ``(bh, T/blk_q,
    k_steps)``."""
    import jax
    from raydp_tpu.ops import flash_attention as fa

    if kernels == "split":
        monkeypatch.setattr(fa, "_fused_backward_fits", lambda *a: False)
    q, k, v, _ = _qkv(1024, 4, 2)
    text = str(jax.make_jaxpr(jax.grad(lambda *a: fa.flash_attention(
        *a, block_q=256, block_k=256, interpret=True, window=window).sum(),
        (0, 1, 2)))(q, k, v))
    names = re.findall(r"name=(rdt_flash(?:_win)?_(?:fwd|bwd_\w+))", text)
    fwd, dkdv, dq, fused = fa._names(window)
    k_steps, q_steps = fa._band_steps(1024, 256, 256, window)
    assert (k_steps, q_steps) == ((4, 4) if window is None else (3, 3))
    grids = [tuple(int(n) for n in g.split(","))
             for g in re.findall(r"grid=\(([\d, ]+)\)", text)]
    if kernels == "fused":
        assert sorted(names) == sorted([fwd, fused])
        assert re.match(r"^rdt_flash(_win)?_bwd_dkdv", fused)  # a layer each
        assert sorted(grids) == sorted([(8, 4, k_steps), (4, 2, 4, k_steps)])
    else:
        assert sorted(names) == sorted([fwd, dkdv, dq])
        assert sorted(grids) == sorted([(8, 4, k_steps), (4, 4, 2 * q_steps),
                                        (8, 4, k_steps)])
    assert text.count("pallas_call[") == len(names)


# (T, window, block, query heads, K/V heads, keys' width, values' width,
# causal)
FUSED_CASES = {
    "a_kv_head_a_query_head": (64, None, 16, 4, 4, 16, 16, True),
    "group_of_four": (64, None, 16, 4, 1, 16, 16, True),
    "keys_192_values_128": (64, None, 16, 2, 2, 192, 128, True),
    "keys_192_values_128_group_window": (64, 32, 16, 4, 1, 192, 128, True),
    "window_a_multiple_of_the_block": (64, 32, 16, 4, 2, 16, 16, True),
    "window_no_multiple_of_the_block": (64, 24, 16, 4, 1, 16, 16, True),
    "window_in_tiles": (1024, 512, 256, 4, 1, 16, 16, True),
    "full_causal_in_tiles": (1024, None, 512, 2, 1, 24, 16, True),
    "t_of_one_block": (64, None, 64, 4, 1, 16, 16, True),
    "not_causal": (64, None, 16, 4, 2, 16, 16, False),
    "not_causal_group_of_four": (64, None, 16, 4, 1, 24, 16, False),
}


@pytest.mark.parametrize("case", list(FUSED_CASES))
def test_the_one_kernel_backward_gives_the_pairs_and_the_blockwise_gradients(
        case, monkeypatch):
    """dq, dk and dv of the one kernel against the pair of kernels (the same
    block products, dk and dv summed in another order) and against the jnp
    path's blockwise backward, to float32 accumulation."""
    import jax
    import jax.numpy as jnp
    from raydp_tpu.ops import flash_attention as fa

    t, window, blk, h, hk, d, d_v, causal = FUSED_CASES[case]
    q, k, v, w = _qkv(t, h, hk, d, d_v=d_v)

    def grads(**how):
        return jax.grad(lambda *a: jnp.sum(fa.flash_attention(
            *a, causal=causal, window=window, block_q=blk, block_k=blk,
            **how) * w), (0, 1, 2))(q, k, v)

    fused = _counted("flash_backward_total", lambda: grads(interpret=True))
    assert fused == {"fused": 1}
    one = grads(interpret=True)
    blockwise = grads()
    monkeypatch.setattr(fa, "_fused_backward_fits", lambda *a: False)
    pair = grads(interpret=True)
    for got, same, want in zip(one, pair, blockwise):
        scale = float(jnp.abs(want).max())
        np.testing.assert_allclose(got, same, rtol=0, atol=2e-6 * scale)
        np.testing.assert_allclose(got, want, rtol=0, atol=2e-5 * scale)


# what the shape rule reads: (T, keys' width, values' width) -> one kernel?
HELD_CASES = {
    "olmoe_4k": ((4096, 128, 128), True),
    "trinity_8k": ((8192, 128, 128), True),
    "smallthinker_16k": ((16384, 128, 128), True),
    "kanana2_16k_192_128": ((16384, 192, 128), True),
    "32k_at_128": ((32768, 128, 128), True),
    "32k_at_192_128": ((32768, 192, 128), False),
    "64k_at_128": ((65536, 128, 128), False),
}


@pytest.mark.parametrize("case", list(HELD_CASES))
def test_a_head_too_long_to_hold_takes_the_two_kernels(case):
    """The shape alone decides: a K/V head's float32 accumulators and out
    blocks (each width in whole tiles of 128 lanes) inside the budget take
    the one kernel, and the call asks the compiler for them plus the working
    room; a longer sequence still lowers the pair, under the default limit."""
    import jax
    import jax.numpy as jnp
    from raydp_tpu.ops import flash_attention as fa

    (t, d, d_v), fits = HELD_CASES[case]
    lanes = -(-d // 128) * 128 + d_v
    held = fa._fused_resident_bytes(t, d, d_v, jnp.bfloat16)
    assert held == t * lanes * (4 + 2 * 2)
    assert fa._fused_backward_fits(t, d, d_v, jnp.bfloat16) is fits
    assert (held <= fa.FUSED_BWD_RESIDENT_BYTES) is fits
    assert fa.FUSED_BWD_RESIDENT_BYTES + fa._VMEM_WORKING_BYTES < 128 << 20
    mk = lambda heads, width: jax.ShapeDtypeStruct(  # noqa: E731
        (1, t, heads, width), jnp.bfloat16)
    text = str(jax.make_jaxpr(jax.grad(lambda *a: fa.flash_attention(
        *a, interpret=True).astype(jnp.float32).sum(), (0, 1, 2)))(
            mk(2, d), mk(1, d), mk(1, d_v)))
    names = set(re.findall(r"name=(rdt_flash_bwd_\w+)", text))
    fwd, dkdv, dq, fused = fa.KERNEL_NAMES
    assert names == ({fused} if fits else {dkdv, dq})
    limits = re.findall(r"vmem_limit_bytes=(\d+)", text)
    if fits:
        assert [int(n) for n in limits] == [held + fa._VMEM_WORKING_BYTES]
    else:
        assert not limits


# the forward's chunked update: FUSED_CASES' geometries and the rows of a
# chunk the test gives the op (a block holds two to four chunks)
CHUNKED_ROWS = {case: 8 for case in FUSED_CASES}
CHUNKED_ROWS.update(window_in_tiles=64, full_causal_in_tiles=128,
                    t_of_one_block=16)


@pytest.mark.parametrize("case", list(FUSED_CASES))
def test_the_chunked_forward_is_the_whole_block_forward(case, monkeypatch):
    """Output and ``lse`` of the forward with an unmasked block's rows
    updated a chunk at a time against the whole-block body (the constant set
    aside) and against the jnp path: a row's state is its own, so a chunk
    changes no sum."""
    import jax.numpy as jnp
    from raydp_tpu.ops import flash_attention as fa

    t, window, blk, h, hk, d, d_v, causal = FUSED_CASES[case]
    q3, k3, v3 = (x.transpose(0, 2, 1, 3).reshape(-1, t, x.shape[3])
                  for x in _qkv(t, h, hk, d, d_v=d_v)[:3])
    how = dict(scale=d ** -0.5, causal=causal, window=window)

    def kernel(rows):
        monkeypatch.setattr(fa, "_ROW_CHUNK", rows)
        return fa._fwd_pallas(q3, k3, v3, blk_q=blk, blk_k=blk,
                              interpret=True, **how)

    got, whole = [], []
    assert _counted("flash_forward_total", lambda: got.extend(kernel(
        CHUNKED_ROWS[case]))) == {"chunked": 1}
    assert fa._row_chunk(blk) == CHUNKED_ROWS[case] < blk
    assert _counted("flash_forward_total",
                    lambda: whole.extend(kernel(blk))) == {"whole": 1}
    scale = float(jnp.abs(v3).max())
    for (out, lse), tol in ((whole, 2e-6), (fa._fwd_jnp(q3, k3, v3, **how),
                                            2e-5)):
        np.testing.assert_allclose(got[0], out, rtol=0, atol=tol * scale)
        np.testing.assert_allclose(got[1], lse, rtol=2e-6, atol=tol)


# rows of the slice a forward step updates -> rows a chunk
ROW_CHUNKS = {
    "the_four_cells_block": (1024, 256),
    "two_chunks_exactly": (512, 256),
    "a_block_of_one_chunk_and_a_half": (384, 384),
    "a_block_under_two_chunks": (256, 256),
    "the_cpu_cuts_block_of_16": (16, 16),
    "rows_no_multiple_of_a_chunk": (640, 640),
}


@pytest.mark.parametrize("case", list(ROW_CHUNKS))
def test_the_rows_of_a_chunk_follow_from_the_rows_of_the_slice(case):
    """The shape alone decides: 256 rows a chunk at every width of the four
    cells (128/128 and 192/128), the slice in one piece where it holds fewer
    than two chunks or no whole number of them, and wherever a mask lies on
    it: an edge block's tiles, an edge block kept whole."""
    import jax.numpy as jnp
    from raydp_tpu.ops import flash_attention as fa

    n, rows = ROW_CHUNKS[case]
    free, kept = [(slice(None), None)], jnp.ones((n, n), bool)
    assert fa._row_chunk(n) == rows
    chunks = fa._row_chunks(slice(None), free, n)
    assert [c.indices(n)[:2] for c, _ in chunks] == [
        (at, at + rows) for at in range(0, n, rows)]
    assert all(pieces is free for _, pieces in chunks)
    masked = [(slice(None), kept)] + free
    assert fa._row_chunks(slice(0, n), masked, n) == [(slice(0, n), masked)]


@pytest.mark.parametrize("rows,update", [(None, "whole"), (128, "chunked")])
@pytest.mark.parametrize("window", [None, 512], ids=["full", "windowed"])
def test_a_chunked_forward_is_one_call_and_counts_what_a_whole_one_counts(
        window, rows, update, monkeypatch):
    """The chunks live inside the kernel: one ``rdt_flash(_win)?_fwd`` call a
    layer over the same grid, the same block pairs and tiles counted, and
    ``flash_forward_total`` says which body it lowered."""
    import jax
    from raydp_tpu.ops import flash_attention as fa

    if rows is not None:
        monkeypatch.setattr(fa, "_ROW_CHUNK", rows)
    q, k, v, _ = _qkv(1024, 4, 2)

    def build():
        return str(jax.make_jaxpr(lambda *a: fa.flash_attention(
            *a, block_q=256, block_k=256, interpret=True, window=window))(
                q, k, v))

    text = build()
    assert re.findall(r"name=(rdt_flash(?:_win)?_(?:fwd|bwd)\w*)",
                      text) == [fa._names(window)[0]]
    assert text.count("pallas_call[") == 1
    k_steps, _ = fa._band_steps(1024, 256, 256, window)
    assert re.findall(r"grid=\(([\d, ]+)\)", text) == [f"8, 4, {k_steps}"]
    assert _counted("flash_forward_total", build) == {update: 1}
    pairs = 10 if window is None else 9      # of a head's 16
    assert _counted("flash_blocks_total", build)["computed"] == 8 * pairs
    tiles = _counted("flash_tiles_total", build)
    assert tiles == ({"unmasked": 8 * 28, "masked": 8 * 8, "skipped": 8 * 4}
                     if window is None else
                     {"unmasked": 8 * 18, "masked": 8 * 12, "skipped": 8 * 6})


def test_a_window_needs_causal_and_the_heads_have_to_group():
    from raydp_tpu.ops.flash_attention import (flash_attention,
                                               kernel_ineligible)
    q, k, v, _ = _qkv(32, 4, 3)
    with pytest.raises(ValueError, match="K/V heads"):
        flash_attention(q, k, v)
    q, k, v, _ = _qkv(32, 4, 2)
    with pytest.raises(ValueError, match="causal"):
        flash_attention(q, k, v, causal=False, window=8)
    assert kernel_ineligible(16384, 128, window=4096) is None
    assert kernel_ineligible(16384, 128, window=1000) is None
    assert "window 0" in kernel_ineligible(16384, 128, window=0)


@pytest.mark.parametrize("window,raises", [(8, True), (None, False)])
def test_auto_attention_never_falls_back_to_dense_under_a_window_on_the_chip(
        monkeypatch, window, raises):
    """On a TPU backend a shape the kernel cannot take raises when a window
    is set (its dense scores are what the window exists to avoid); without
    one ``auto`` keeps its dense fallback."""
    import jax
    from raydp_tpu.models.transformer import Attention

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    layer = Attention(num_heads=4, window=window)
    if raises:
        with pytest.raises(ValueError, match="windowed attention"):
            layer._dispatch(20, 8)      # 20 positions: blocks of 20
        assert layer._dispatch(128, 8) == "flash"
    else:
        assert layer._dispatch(20, 8) == "dense"


# ------------------------------------- (b) the shares of one expert layer
def _expert_layer(seed=0):
    """An uncut layer's seeded weights, tokens and the router's input."""
    rng = np.random.default_rng(seed)
    d, f, e, n = 32, 16, 8, 48
    full = {"experts_gate": rng.normal(0, 0.3, (e, d, f)),
            "experts_up": rng.normal(0, 0.3, (e, d, f)),
            "experts_down": rng.normal(0, 0.3, (e, f, d))}
    return (jax_f32(full), jax_f32(rng.normal(0, 0.3, (d, e))),
            jax_f32(rng.normal(size=(n, d))), jax_f32(rng.normal(size=(n, d))))


def jax_f32(tree):
    import jax
    return jax.tree.map(lambda a: np.asarray(a, np.float32), tree)


def _share_of(full, first, held):
    return {k: v[first:first + held] for k, v in full.items()}


def _program_share(kernels, router, m, u, first, held):
    from raydp_tpu.models.moe import MoE, router_logits
    layer = MoE(8, 3, 16, first_expert=first, experts_held=held,
                activation="relu", normalize_top_k=True)
    return layer.apply({"params": kernels}, m, router_logits(u, router))


def test_the_four_shares_of_an_expert_layer_add_up_to_the_uncut_layer():
    """Experts 0-1, 2-3, 4-5, 6-7 of 8, 3 a token: each chip routes over all
    eight with the weights normalised over all three choices and computes
    its own experts' part; the four parts sum to the reference's uncut
    layer, and so do the held slots to all slots."""
    _, _, reference = _files()
    full, router, m, u = _expert_layer()
    cfg = {"moe_num_primary_experts": 8, "moe_num_active_primary_experts": 3,
           "norm_topk_prob": True, "first_expert": 0, "experts_held": 8}
    logits = u @ router
    want = np.asarray(reference.expert_layer(full, m, logits, cfg))
    parts, held_slots = [], 0.0
    for first in (0, 2, 4, 6):
        y, aux = _program_share(_share_of(full, first, 2), router, m, u,
                                first, 2)
        one = dict(cfg, first_expert=first, experts_held=2)
        np.testing.assert_allclose(
            y, reference.expert_layer(_share_of(full, first, 2), m, logits,
                                      one), rtol=1e-4, atol=1e-5)
        parts.append(np.asarray(y))
        held_slots += float(aux["slots_held"])
        assert float(aux["slots_all"]) == 3 * 48
    np.testing.assert_allclose(sum(parts), want, rtol=1e-4, atol=1e-5)
    assert held_slots == 3 * 48
    assert np.abs(want).max() > 0.1 and np.abs(parts[0] - want).max() > 0.01
    # the uncut program layer is the same sum, and counts no share
    y, aux = _program_share(full, router, m, u, 0, 8)
    np.testing.assert_allclose(y, want, rtol=1e-4, atol=1e-5)
    assert "slots_held" not in aux


@pytest.mark.parametrize("first", [0, 2, 4, 6])
def test_a_shares_gradients_match_the_references(first):
    """Of the router, the held kernels and both inputs: an absent expert's
    slots give the router only what the normalisation over all choices
    gives, and the tokens nothing."""
    import jax
    import jax.numpy as jnp
    _, _, reference = _files()
    full, router, m, u = _expert_layer(seed=first + 1)
    kernels = _share_of(full, first, 2)
    cfg = {"moe_num_primary_experts": 8, "moe_num_active_primary_experts": 3,
           "norm_topk_prob": True, "first_expert": first, "experts_held": 2}
    w = np.random.default_rng(9).normal(size=m.shape).astype(np.float32)
    got = jax.grad(lambda k, r, m, u: jnp.sum(_program_share(
        k, r, m, u, first, 2)[0] * w), (0, 1, 2, 3))(kernels, router, m, u)
    want = jax.grad(lambda k, r, m, u: jnp.sum(reference.expert_layer(
        k, m, u @ r, cfg) * w), (0, 1, 2, 3))(kernels, router, m, u)
    _close(got, want)
    assert np.abs(np.asarray(want[1])).max() > 1e-3


def test_the_grouped_products_are_given_the_held_groups_alone():
    """The slots sort with the held experts' first and the walk's trips hand
    the three products the held experts' group sizes clipped to the trip:
    fewer rows than all the slots, a trip's sizes sum to its held rows and
    all trips' to ``slots_held``; the rows of a trip after them are masked
    (whatever a grouped product leaves in rows that belong to no group goes
    nowhere)."""
    import jax
    import jax.numpy as jnp
    from raydp_tpu.models import moe

    full, router, m, u = _expert_layer()
    seen = []
    real = jax.lax.ragged_dot

    def spy(lhs, rhs, sizes, **kw):
        jax.debug.callback(lambda s, shapes=(lhs.shape, rhs.shape): seen.append(
            shapes + (np.asarray(s),)), sizes, ordered=True)
        out = real(lhs, rhs, sizes, **kw)
        # poison the rows that belong to no group
        rows = jnp.arange(lhs.shape[0])[:, None] >= jnp.sum(sizes)
        return out + jnp.where(rows, 1e6, 0.0).astype(jnp.float32)

    y0, aux = _program_share(_share_of(full, 2, 2), router, m, u, 2, 2)
    jax.lax.ragged_dot = spy
    try:
        y1, _ = _program_share(_share_of(full, 2, 2), router, m, u, 2, 2)
        jax.effects_barrier()
    finally:
        jax.lax.ragged_dot = real
    assert moe.jax.lax.ragged_dot is real
    chunk = moe._chunk_rows(3 * 48, 2, 8, np.float32)
    held = int(aux["slots_held"])
    trips = -(-held // chunk)
    assert 1 < trips and trips * chunk == float(aux["slots_moved"]) < 3 * 48
    assert [s[1] for s in seen] == [(2, 32, 16), (2, 32, 16),
                                    (2, 16, 32)] * trips
    for i, (lhs, _, sizes) in enumerate(seen):
        assert lhs[0] == chunk < 3 * 48 and sizes.shape == (2,)
        assert sizes.sum() == min(chunk, held - (i // 3) * chunk)
    assert sum(s[2].sum() for s in seen[::3]) == held < 3 * 48
    np.testing.assert_allclose(y1, y0, rtol=1e-5, atol=1e-5)


# the parent's formulation of a share (PR 31): every one of the top_k * N slot
# rows gathered, masked in and out, multiplied and permuted back, the grouped
# products given the held groups' sizes. What the walk over the held slots is
# compared with, and what the layer that holds every expert still runs.
def _full_size_share(kernels, logits, m, first, held, e=8, k=3,
                     normalize=True):
    import jax
    import jax.numpy as jnp
    from raydp_tpu.models import moe

    @jax.custom_vjp
    def where_rows(x, keep):
        return jnp.where(keep[:, None], x, 0)

    where_rows.defvjp(lambda x, keep: (where_rows(x, keep), keep),
                      lambda keep, g: (jnp.where(keep[:, None], g, 0), None))
    n, dim = m.shape
    share = held < e
    _, ids, weights = moe.route(logits, k, normalize)
    slots = ids.reshape(-1)
    sizes = jnp.sum(jax.nn.one_hot(slots, e, dtype=jnp.int32), axis=0)
    if share:
        slots = (slots - first) % e
        sizes = sizes[first:first + held]
        here = jnp.arange(k * n) < jnp.sum(sizes)
    order = jnp.argsort(slots, stable=True)
    inverse = jnp.argsort(order)
    xs = moe._dispatch(m, order, inverse, k)
    if share:
        xs = where_rows(xs, here)
    act = jax.nn.relu(jax.lax.ragged_dot(xs, kernels["experts_gate"], sizes)) \
        * jax.lax.ragged_dot(xs, kernels["experts_up"], sizes)
    out = jax.lax.ragged_dot(act, kernels["experts_down"], sizes)
    if share:
        out = where_rows(out, here)
    back = moe._permute(out, inverse, order).reshape(n, k, dim)
    return jnp.sum(back.astype(jnp.float32) * weights[..., None], axis=1)


# experts 2-5 of 8 held, 3 a token over 48 tokens: 144 slots, a trip of the
# walk carries 40 (half the even share of 72, up to a tile of 8 rows)
def _routed(routing, seed=0):
    """Seeded logits [48, 8] that send the slots where ``routing`` says, and
    the number of slots on the held experts 2-5."""
    logits = np.random.default_rng(seed).normal(size=(48, 8))
    held, absent = [2, 3, 4, 5], [0, 1, 6, 7]
    if routing == "all_on_held_experts":
        logits[:, held] += 20
    elif routing == "none_on_held_experts":
        logits[:, absent] += 20
    elif routing == "one_past_a_trip":
        # 41 tokens with one slot on a held expert, 7 with none
        logits[:, absent[:3]] += 20
        logits[:41, absent[2]] -= 20
        logits[np.arange(41), np.asarray(held)[np.arange(41) % 4]] += 20
    ids = np.argsort(-logits, axis=1)[:, :3]
    return logits.astype(np.float32), int(np.isin(ids, held).sum())


ROUTINGS = {"random": None, "all_on_held_experts": 144,
            "none_on_held_experts": 0, "one_past_a_trip": 41}


@pytest.mark.parametrize("routing", list(ROUTINGS))
def test_the_walk_over_the_held_slots_drops_none_at_any_routing(routing):
    """Output and every gradient (held kernels, handed-in logits, the
    experts' input) against the reference and against the full-size
    formulation: with every slot on a held expert (the most trips), with none
    (no trip: zeros, exactly), one slot past a trip's end, and as seeded."""
    import jax
    import jax.numpy as jnp
    from raydp_tpu.models import moe
    _, _, reference = _files()
    full, _, m, _ = _expert_layer(seed=5)
    kernels = _share_of(full, 2, 4)
    logits, held = _routed(routing)
    assert moe._chunk_rows(144, 4, 8, np.float32) == 40
    if ROUTINGS[routing] is not None:
        assert held == ROUTINGS[routing]
    cfg = {"moe_num_primary_experts": 8, "moe_num_active_primary_experts": 3,
           "norm_topk_prob": True, "first_expert": 2, "experts_held": 4}
    layer = moe.MoE(8, 3, 16, first_expert=2, experts_held=4,
                    activation="relu", normalize_top_k=True)
    w = np.random.default_rng(9).normal(size=m.shape).astype(np.float32)

    def graded(fn):
        return jax.value_and_grad(
            lambda k, lg, m: jnp.sum(fn(k, lg, m) * w), (0, 1, 2))(
                kernels, logits, m)

    y, aux = layer.apply({"params": kernels}, m, logits)
    assert float(aux["slots_held"]) == held
    assert float(aux["slots_moved"]) == -(-held // 40) * 40
    got = graded(lambda k, lg, m: layer.apply({"params": k}, m, lg)[0])
    for other in (lambda k, lg, m: reference.expert_layer(k, m, lg, cfg),
                  lambda k, lg, m: _full_size_share(k, lg, m, 2, 4)):
        np.testing.assert_allclose(y, other(kernels, logits, m),
                                   rtol=1e-4, atol=1e-5)
        want = graded(other)
        _close(got[1], want[1])
    if held == 0:
        assert not np.any(np.asarray(y))
        assert not any(np.any(g) for g in _leaves(got[1]).values())
    else:
        assert np.abs(np.asarray(got[1][1])).max() > 1e-4


def _primitives_over(jaxpr, rows, seen=None):
    """Every (primitive, output shape) in a jaxpr and its sub-jaxprs whose
    output has ``rows`` leading rows or more and a trailing dimension."""
    import jax
    seen = [] if seen is None else seen
    for eqn in jaxpr.eqns:
        for v in eqn.outvars:
            shape = getattr(v.aval, "shape", ())
            if len(shape) >= 2 and np.prod(shape[:-1]) >= rows:
                seen.append((eqn.primitive.name, tuple(shape)))
        for sub in jax.core.jaxprs_in_params(eqn.params):
            _primitives_over(sub, rows, seen)
    return seen


def _allocations(jaxpr, under=False, seen=None):
    """(rows, whether under a ``cond``) of every ``empty`` in a jaxpr and
    its sub-jaxprs."""
    import jax
    seen = [] if seen is None else seen
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "empty":
            seen.append((eqn.outvars[0].aval.shape[0], under))
        for sub in jax.core.jaxprs_in_params(eqn.params):
            _allocations(sub, under or eqn.primitive.name == "cond", seen)
    return seen


def test_the_walks_buffers_are_allocated_under_the_cond_on_a_held_slot():
    """Forward and backward each run under one ``cond`` on a slot being held,
    and every buffer of ``top_k * N`` rows is allocated inside its branch:
    an allocation with no operand outside it is one the compiler may place
    at the start of the step (it did, every layer's at once)."""
    import jax
    import jax.numpy as jnp
    from raydp_tpu.models import moe
    full, _, m, _ = _expert_layer()
    kernels, (logits, _) = _share_of(full, 2, 4), _routed("random")
    layer = moe.MoE(8, 3, 16, first_expert=2, experts_held=4,
                    activation="relu", normalize_top_k=True)
    text = jax.make_jaxpr(jax.grad(lambda k, lg, m: jnp.sum(
        layer.apply({"params": k}, m, lg)[0]), (0, 1, 2)))(kernels, logits, m)
    found = _allocations(text.jaxpr)
    # expert order and token order, forward and backward, and the weights'
    # gradients' rows
    assert sorted(found) == [(160, True)] * 5
    assert str(text).count(" cond[") == 2


def test_a_share_moves_no_full_size_rows_and_one_gather_of_the_tokens_rows():
    """The jaxpr of a share's forward and backward: arrays of ``top_k * N``
    rows of width D or F are the buffers the trips write into (uninitialised,
    updated a trip's rows at a time, carried by the loops) and nothing else:
    no gather, no ``where``, no elementwise op and no product over them. Rows
    come back to token order a trip of held rows at a time (a gather of a
    trip and its halo, summed in runs) and then as ONE gather of ``N`` rows a
    pass, the forward's and its mirror in the backward, not ``top_k``. The
    full-size formulation has all of those."""
    import jax
    import jax.numpy as jnp
    from raydp_tpu.models import moe
    full, _, m, _ = _expert_layer()
    kernels, (logits, _) = _share_of(full, 2, 4), _routed("random")
    layer = moe.MoE(8, 3, 16, first_expert=2, experts_held=4,
                    activation="relu", normalize_top_k=True)

    def ops(fn, rows):
        text = jax.make_jaxpr(jax.grad(
            lambda k, lg, m: jnp.sum(fn(k, lg, m)), (0, 1, 2)))(
                kernels, logits, m)
        wide = {}
        for name, shape in _primitives_over(text.jaxpr, rows):
            if shape[-1] in (16, 32):           # F and D; not [N, E] routing
                wide.setdefault(name, []).append(shape)
        return wide

    walk = ops(lambda k, lg, m: layer.apply({"params": k}, m, lg)[0], 144)
    assert set(walk) == {"empty", "dynamic_update_slice", "while"}, walk
    # the forward's output rows and the backward's gradient rows, in expert
    # order and then summed in runs by token (144 slots in trips of 40: 160
    # rows); nothing of width F is kept
    assert sorted(walk["empty"]) == [(160, 32)] * 4
    by_tokens = ops(lambda k, lg, m: layer.apply({"params": k}, m, lg)[0], 40)
    # a trip's token rows forward, and again with the gradient's backward
    # (40 rows); a pass's trip of held rows behind its halo of 8 and its one
    # gather of the N tokens' rows (48 rows both, here), forward and backward
    assert sorted(by_tokens["gather"]) == [(40, 32)] * 3 + [(48, 32)] * 4
    full_size = ops(lambda k, lg, m: _full_size_share(k, lg, m, 2, 4), 144)
    assert len(full_size["gather"]) == 4 and len(full_size["select_n"]) >= 4
    assert {"ragged_dot_general", "max", "mul"} <= set(full_size)


# experts 2-3 of 8 held, 3 a token over 48 tokens: at most one held slot a
# token in the mean, so the held rows come back to token order in runs; a trip
# carries 24 rows (half the even share of 36, up to a tile), a run is of two
def _held_by(routing, seed=0):
    """Seeded logits [48, 8] whose top three give token ``t`` the held
    experts that ``routing(t)`` names (of 2 and 3), and the slots held."""
    logits = np.random.default_rng(seed).normal(size=(48, 8))
    if routing is None:
        ids = np.argsort(-logits, axis=1)[:, :3]
        return logits.astype(np.float32), int(np.isin(ids, [2, 3]).sum())
    logits[:, [0, 1, 4]] += 20
    for t in range(48):
        logits[t, list(routing(t))] += 40
    return logits.astype(np.float32), sum(len(routing(t)) for t in range(48))


RUN_ROUTINGS = {
    "as_seeded": (None, None),
    "no_slot_held": (lambda t: (), 0),
    "every_token_a_whole_run": (lambda t: (2, 3), 96),
    # ranks 0-24: the twenty-fifth row is alone in the second trip
    "one_row_past_a_trip": (lambda t: (2 + t % 2,) if t < 25 else (), 25),
    # tokens 0-22 hold one slot, the others two: token 23's run lies on
    # ranks 23 and 24, either side of the first trip's edge, token 35's on
    # 47 and 48, either side of the second's
    "a_run_across_a_trips_edge":
        (lambda t: (2 + t % 2,) if t < 23 else (2, 3), 23 + 2 * 25),
}


def _every_held_expert_on_every_token(kernels, logits, m, gated):
    """The share of experts 2-3 with no dispatch at all: each held expert on
    all the tokens, times the weight the token gives it (0 for most)."""
    import jax
    import jax.numpy as jnp
    from raydp_tpu.models import moe
    _, ids, weights = moe.route(logits, 3, True)
    out = 0.0
    for i, expert in enumerate((2, 3)):
        hidden = jax.nn.relu(m @ kernels["experts_up"][i])
        if gated:
            hidden = jax.nn.relu(m @ kernels["experts_gate"][i]) \
                * (m @ kernels["experts_up"][i])
        weight = jnp.sum(jnp.where(ids == expert, weights, 0), axis=1)
        out = out + weight[:, None] * (hidden @ kernels["experts_down"][i])
    return out


@pytest.mark.parametrize("gated", [True, False], ids=["gated", "two_matrices"])
@pytest.mark.parametrize("routing", list(RUN_ROUTINGS))
def test_the_runs_give_every_held_experts_sum_at_any_routing(routing, gated):
    """Output and all five gradients (the experts' input, the weights through
    the handed-in logits, gate, up and down) of a share, against every held
    expert run on every token and, gated, against the full-size formulation:
    with no slot held (zeros, exactly), every token holding a whole run, one
    row past a trip, a run that lies across a trip's edge, and as seeded;
    gated experts and experts of two matrices."""
    import jax
    import jax.numpy as jnp
    from raydp_tpu.models import moe
    full, _, m, _ = _expert_layer(seed=7)
    kernels = _share_of(full, 2, 2)
    if not gated:
        del kernels["experts_gate"]
    by, want_held = RUN_ROUTINGS[routing]
    logits, held = _held_by(by)
    assert want_held in (None, held)
    assert moe._chunk_rows(144, 2, 8, np.float32) == 24
    layer = moe.MoE(8, 3, 16, first_expert=2, experts_held=2,
                    activation="relu", normalize_top_k=True, gated=gated)
    w = np.random.default_rng(9).normal(size=m.shape).astype(np.float32)

    def graded(fn):
        return jax.jit(jax.value_and_grad(
            lambda k, lg, m: jnp.sum(fn(k, lg, m) * w), (0, 1, 2)))(
                kernels, logits, m)

    def share(k, lg, m):
        return layer.apply({"params": k}, m, lg)[0]

    y, aux = layer.apply({"params": kernels}, m, logits)
    assert float(aux["slots_held"]) == held
    got = graded(share)
    assert len(_leaves(got[1])) == (5 if gated else 4)
    others = [functools.partial(_every_held_expert_on_every_token,
                                gated=gated)]
    if gated:
        others.append(lambda k, lg, m: _full_size_share(k, lg, m, 2, 2))
    for other in others:
        np.testing.assert_allclose(y, other(kernels, logits, m),
                                   rtol=1e-4, atol=1e-5)
        _close(got[1], graded(other)[1])
    if held == 0:
        assert not np.any(np.asarray(y))
        assert not any(np.any(g) for g in _leaves(got[1]).values())
    else:
        assert np.abs(np.asarray(got[1][2])).max() > 1e-4
        # a token's one held expert has all its weight: nothing to move
        assert routing == "one_row_past_a_trip" \
            or np.abs(np.asarray(got[1][1])).max() > 1e-4


def _sorted_slots(logits):
    """(order, inverse) of the 144 slots sorted with experts 2-3 first."""
    from raydp_tpu.models import moe
    _, ids, _ = moe.route(logits, 3, True)
    order = np.argsort((np.asarray(ids).reshape(-1) - 2) % 8, kind="stable")
    return order.astype(np.int32), np.argsort(order).astype(np.int32)


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("weighted", [True, False],
                         ids=["weighted", "unweighted"])
@pytest.mark.parametrize("routing", list(RUN_ROUTINGS))
def test_the_runs_sum_what_top_k_gathers_of_the_tokens_rows_sum(
        routing, weighted, dtype):
    """The walk's return to token order alone, on a buffer of seeded rows
    with NaN in every row past the held slots: each token's sum over its
    ``top_k`` slots' rows with the absent ones left out (the ``top_k``
    gathers of ``N`` rows this form replaced, in numpy), with the router's
    weights (the forward's call) and without (the backward's); float32 rows
    in trips of 24 behind a halo of 8, bfloat16 rows in trips of 32 behind
    their tile of 16, summed in float32 and cast once."""
    import jax
    import jax.numpy as jnp
    from raydp_tpu.models import moe
    logits, held = _held_by(RUN_ROUTINGS[routing][0])
    order, inverse = _sorted_slots(logits)
    chunk = moe._chunk_rows(144, 2, 8, dtype)
    assert chunk == {"float32": 24, "bfloat16": 32}[dtype]
    rng = np.random.default_rng(3)
    sizes = np.asarray([held - held // 2, held // 2], np.int32)
    walk = moe._Walk(jnp.asarray(order), jnp.asarray(sizes), chunk)
    rows = rng.normal(size=(walk.rows, 32)).astype(np.float32)
    rows = np.array(jnp.asarray(rows, dtype), np.float32)
    rows[held:] = np.nan
    weights = rng.uniform(0.5, 1.5, (48, 3)).astype(np.float32) \
        if weighted else None
    index = inverse.reshape(48, 3)
    want = np.zeros((48, 32), np.float32)
    for j in range(3):
        picked = np.where((index[:, j] < held)[:, None],
                          np.nan_to_num(rows[index[:, j]]), 0)
        want += picked if weights is None else picked * weights[:, j, None]
    got = jax.jit(lambda buffer, inverse, total: moe._runs_to_tokens(
        buffer, moe._runs_by_token(inverse, total, 3, 2, chunk, dtype),
        walk, 3, 2, weights))(jnp.asarray(rows, dtype), inverse, held)
    assert got.dtype == jnp.dtype(dtype) and got.shape == (48, 32)
    tol = {"float32": 1e-6, "bfloat16": 2.0 ** -8}[dtype]
    np.testing.assert_allclose(np.asarray(got, np.float32), want,
                               rtol=tol, atol=tol)
    assert (held == 0) == (not np.any(np.asarray(got, np.float32)))


def test_the_runs_index_lists_each_tokens_held_slots_where_they_lie():
    """The index the forward and the backward share: after the halo of no
    token, the held positions by token, each token's in a run, and where each
    token's run ends (-1: no slot held); what follows the held slots is of
    no token either."""
    import jax.numpy as jnp
    from raydp_tpu.models import moe
    logits, held = _held_by(RUN_ROUTINGS["a_run_across_a_trips_edge"][0])
    order, inverse = _sorted_slots(logits)
    slot, position, last = moe._runs_by_token(
        jnp.asarray(inverse, jnp.int32), held, 3, 2, 24, np.float32)
    slot, position, last = map(np.asarray, (slot, position, last))
    halo = 8                                # one predecessor, up to a tile
    assert slot.shape == position.shape == (halo + 144,)
    assert np.all(slot[:halo] == -1) and np.all(slot[halo + held:] == 144)
    token = slot[halo:halo + held] // 3
    assert np.all(np.diff(token) >= 0)                  # sorted by token
    assert sorted(position[halo:halo + held]) == list(range(held))
    assert np.all(order[position[halo:halo + held]] == slot[halo:halo + held])
    assert list(token[22:26]) == [22, 23, 23, 24]       # across rank 24
    assert list(last[:24]) == list(range(23)) + [24]
    assert np.all(token[last[23:]] == np.arange(23, 48))
    none_held = moe._runs_by_token(jnp.asarray(inverse, jnp.int32), 0, 3, 2,
                                   24, np.float32)
    assert np.all(np.asarray(none_held[2]) == -1)


@pytest.mark.parametrize("handed_in", [False, True],
                         ids=["own_router", "logits_handed_in"])
def test_a_layer_that_holds_every_expert_is_the_layer_as_it_was(handed_in):
    """``experts_held`` all of them (or not given): the forward and backward
    jaxpr is the full-size formulation's, text for text."""
    import jax
    import jax.numpy as jnp
    from jax.interpreters import partial_eval as pe
    from raydp_tpu.models import moe
    full, router, m, u = _expert_layer()

    def text(fn):
        # without what only the layer's ``aux`` reads
        closed = jax.make_jaxpr(jax.grad(
            lambda k, r, m: jnp.sum(fn(k, r, m)), (0, 1, 2)))(
                full, router, m)
        return str(pe.dce_jaxpr(closed.jaxpr, [True] * 5)[0])

    layer = moe.MoE(8, 3, 16, activation="relu", normalize_top_k=True)
    if handed_in:
        got = text(lambda k, r, m: layer.apply(
            {"params": k}, m, moe.router_logits(u, r))[0])
    else:
        got = text(lambda k, r, m: layer.apply(
            {"params": dict(k, router=r)}, m)[0])
    want = text(lambda k, r, m: _full_size_share(
        k, moe.router_logits(u if handed_in else m, r), m, 0, 8))
    assert "while[" not in got and got.count("= ragged_dot_general[") == 9
    assert got == want


# ----------------------------------------------------- (c) the whole model
def test_the_parameter_tree_is_the_published_layers():
    """Grouped-query projections at the heads' own width, the router in the
    block (it reads the attention's input), the held experts' kernels, the
    sliced embedding and head; no bias, no router in the expert layer."""
    cfg, pipeline, _ = _files()
    model = pipeline.build_model(cfg)
    shapes = {k: v.shape for k, v in _leaves(
        _variables(model, _tokens(cfg, 1))[0]).items()}
    assert {k: v for k, v in shapes.items() if k.startswith("block_1/")} == {
        "block_1/ln1/scale": (32,), "block_1/ln2/scale": (32,),
        "block_1/router": (32, 8),
        "block_1/attn/q/kernel": (32, 14, 8),
        "block_1/attn/k/kernel": (32, 2, 8),
        "block_1/attn/v/kernel": (32, 2, 8),
        "block_1/attn/o/kernel": (14, 8, 32),
        "block_1/moe/experts_gate": (2, 32, 16),
        "block_1/moe/experts_up": (2, 32, 16),
        "block_1/moe/experts_down": (2, 16, 32)}
    assert shapes["embed/embedding"] == (64, 32)
    assert shapes["lm_head/kernel"] == (32, 64)
    assert model.attention_layers == {"window": 3, "full": 1}
    assert model.loss_counters == (("moe_slots_total", "max_expert"),
                                   ("moe_slots_total", "all"),
                                   ("moe_slots_total", "held"),
                                   ("moe_slots_total", "moved"))


@pytest.mark.parametrize("dtype,attention,tol", [
    ("float32", "dense", F32_TOL), ("float32", "flash", F32_TOL),
    # at hidden 32 one flipped near-tied choice of 3 in 8 is a larger share
    # of a token's output than at the published widths (the chip's check (a)
    # holds 4 ulps there): 8 ulps here
    ("bfloat16", "flash", 8 * 2.0 ** -8)])
def test_forward_logits_match_the_reference(dtype, attention, tol):
    from chipbench.harness import relative_rms_error
    cfg, pipeline, _ = _files(compute_dtype=dtype,
                                      attention=attention)
    model = pipeline.build_model(cfg)
    tokens = _tokens(cfg, 3)
    params, _ = _variables(model, tokens)
    got = pipeline.compared(lm_testing.logits(model, {"params": params},
                                              tokens), cfg)
    want = lm_testing.reference_program(CONFIG, cfg, "forward")(
        {"params": params}, tokens)
    assert got.shape == want.shape == (3, 8, 64)
    assert relative_rms_error(got, want) <= tol
    if dtype == "bfloat16":     # and the tolerance does separate precisions
        assert relative_rms_error(got, want) > F32_TOL


def test_the_reference_by_blocks_of_queries_is_the_reference():
    """A query block smaller than the sequence changes nothing."""
    import jax
    cfg, pipeline, reference = _files()
    tokens = _tokens(cfg, 2, seed=3)
    params, _ = _variables(pipeline.build_model(cfg), tokens)
    forward = lambda: jax.jit(lambda p: reference.forward(  # noqa: E731
        {"params": p}, tokens, cfg))(params)
    whole = forward()
    block, reference.QUERY_BLOCK = reference.QUERY_BLOCK, 8
    try:
        blocked = forward()
    finally:
        reference.QUERY_BLOCK = block
    np.testing.assert_allclose(blocked, whole, rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("remat,attention,forward,kernels", [
    (False, "dense", "once", 0), (True, "dense", "twice", 0),
    (False, "flash", "once", 4), (True, "flash", "once", 4)],
    ids=["kept", "recomputed", "kept_flash", "recomputed_flash"])
def test_loss_rows_and_every_gradient_leaf_match_the_reference(
        remat, attention, forward, kernels, forward_flash_kernels):
    """The model's own loss (fused head over the rows held, both auxiliary
    losses over all experts) and its gradients, with the blocks' activations
    kept and with the blocks recomputed; and what it counts. A recomputed
    block whose attention is the flash kernel keeps the kernel's output and
    row sums: the gradient's program holds one forward kernel a layer, as
    when nothing is recomputed."""
    import jax
    cfg, pipeline, _ = _files(remat_blocks=remat, attention=attention)
    model = pipeline.build_model(cfg)
    assert model.attention_forward == {forward: 4}
    tokens = _tokens(cfg, 4, seed=1)
    params, _ = _variables(model, tokens)
    w = np.full(4, 0.25, np.float32)
    value_and_grad = lm_testing.loss_program(model)
    assert forward_flash_kernels(jax.make_jaxpr(value_and_grad)(
        params, None, tokens, w)) == kernels
    (loss, counts), grads = value_and_grad(params, None, tokens, w)
    want_loss, want_grads = lm_testing.reference_program(
        CONFIG, cfg, "loss", grad=True)(params, tokens)
    assert abs(float(loss) - float(want_loss)) <= F32_TOL * float(want_loss)
    _close(grads, want_grads)
    ids = np.stack(lm_testing.reference_program(CONFIG, cfg, "top_k_ids")(
        params, tokens))
    per_expert = np.stack([np.bincount(layer.ravel(), minlength=8)
                           for layer in ids])
    assert float(counts[1]) == tokens.size * 3 * 4
    assert float(counts[0]) == per_expert.max(axis=1).sum()
    assert float(counts[2]) == per_expert[:, 2:4].sum() < float(counts[1])
    # 384 slots a layer, 2 of 8 experts held: trips of 48 rows
    from raydp_tpu.models import moe
    assert moe._chunk_rows(384, 2, 8, np.float32) == 48
    assert float(counts[3]) == sum(
        -(-held // 48) * 48 for held in per_expert[:, 2:4].sum(axis=1))
    assert float(counts[2]) <= float(counts[3]) < float(counts[1])


@pytest.mark.parametrize("attention", ["dense", "flash"])
def test_a_block_without_second_norms_names_no_sublayer_output(
        attention, policy_without_sublayer_out):
    """No norm follows this family's sub-layers, so its backward reads none
    of their outputs and a recomputed block names none to keep (80 MiB a
    layer at the cell's size, for nothing): the gradient's lowered program
    is the same text with and without ``SUBLAYER_OUT`` in the policy, and
    the model has nothing for ``train_sublayer_out_total`` to count."""
    cfg, pipeline, _ = _files(remat_blocks=True, attention=attention)
    model = pipeline.build_model(cfg)
    assert not model.sandwich_norms and model.sublayer_out == {}
    tokens = _tokens(cfg, 4, seed=1)
    params, _ = _variables(model, tokens)
    w = np.full(4, 0.25, np.float32)
    lowered = lambda: lm_testing.loss_program(model).lower(  # noqa: E731
        params, None, tokens, w).as_text()
    with_the_name = lowered()
    policy_without_sublayer_out()
    assert lowered() == with_the_name


def test_the_kept_pair_survives_the_map_over_a_mesh(forward_flash_kernels):
    """On four devices the flash op runs under ``shard_map`` (a chip its own
    rows): the names it gives its output and row sums are honoured inside
    the map, so a recomputed block still holds one forward kernel a layer,
    and loss and gradients are the one-device, nothing-recomputed ones."""
    import jax
    from raydp_tpu.models import TransformerLM
    from raydp_tpu.parallel import MeshSpec, make_mesh

    mesh = make_mesh(MeshSpec(data=4), devices=jax.devices()[:4])
    sizes = dict(vocab_size=64, dim=32, num_heads=4, num_kv_heads=2,
                 head_dim=8, num_layers=2, sliding_window=16,
                 window_layers=(0, 1), attention="flash")
    plain = TransformerLM(**sizes)
    mapped = TransformerLM(**sizes, mesh=mesh, remat_blocks=True)
    assert mapped.attention_forward == plain.attention_forward == {"once": 2}
    tokens = np.random.default_rng(5).integers(0, 64, (4, 32), np.int32)
    params, _ = _variables(plain, tokens)
    w = np.full(4, 0.25, np.float32)
    of_mapped = lm_testing.loss_program(mapped)
    program = str(jax.make_jaxpr(of_mapped)(params, None, tokens, w))
    assert "shard_map" in program and forward_flash_kernels(program) == 2
    (loss, _), grads = of_mapped(params, None, tokens, w)
    (want_loss, _), want_grads = lm_testing.loss_and_grads(
        plain, params, None, tokens, w)
    assert abs(float(loss) - float(want_loss)) <= F32_TOL * float(want_loss)
    _close(grads, want_grads)


def test_the_pattern_decides_each_layers_window_and_rope():
    """Layer 0 of a period: every earlier key and no position embedding (a
    shuffled prefix changes nothing but through the causal mask); layers 1-3:
    the window, with RoPE."""
    from raydp_tpu.models import TransformerLM
    model = TransformerLM(vocab_size=16, num_layers=6, sliding_window=4,
                          window_layers=(0, 1, 1, 1), rope_layers=(0, 1, 1, 1))
    assert [model._windowed(i) for i in range(6)] == [0, 1, 1, 1, 0, 1]
    assert [model._rope(i) for i in range(6)] == [0, 1, 1, 1, 0, 1]
    assert model.attention_layers == {"window": 4, "full": 2}
    plain = TransformerLM(vocab_size=16, num_layers=2)
    assert plain.attention_layers == {"window": 0, "full": 2}
    assert [plain._rope(i) for i in range(2)] == [True, True]
    assert plain.loss_counters == ()


# -------------------------------------------------------------- (d) a fit
def test_fit_on_frame_at_the_cpu_cut_learns_and_counts(session, tmp_path):
    """The cell's own CPU cut (four layers in the pattern, 8 experts of which
    2 held, 2 a token, 512 of 2048 vocabulary rows, 256 positions with a
    window of 64, no width cut) through ``fit_on_frame``: the loss falls, the
    held slots are some and not all of the slots, a built step counts its
    layers by kind."""
    import jax
    from chipbench import manifest
    from raydp_tpu.parallel import make_mesh

    cfg = manifest.load_json(ROOT, "configs", f"{CONFIG}.json")
    pipeline = manifest.load_module(ROOT, "pipelines", f"{CONFIG}.py")
    wl = {"seq_len": cfg["max_position_embeddings"], "batch_per_replica": 1}
    rows = pipeline.cpu_cut(cfg, wl, 1)
    assert (cfg["hidden_size"], cfg["head_dim"],
            cfg["moe_ffn_hidden_size"]) == (2560, 128, 768)
    cfg["compute_dtype"] = "float32"
    df, info, _ = _token_frame(session, tmp_path, cfg, pipeline, rows, 3)
    mesh = make_mesh(None, devices=jax.devices()[:1])
    before = lm_testing.counters()
    est = _estimator(cfg, pipeline, info, mesh, num_epochs=3, batch_size=1,
                     checkpoint_interval=3)
    history = est.fit_on_frame(df).history
    losses = [e["train_loss"] for e in history]
    assert np.all(np.isfinite(losses)) and losses[-1] < losses[0]
    slots = lm_testing.moved(before, "moe_slots_total")
    assert slots["all"] == 3 * rows * 256 * 2 * 4   # epochs, tokens, top-2, layers
    assert 0 < slots["held"] <= slots["moved"] < slots["all"]
    assert slots["moved"] % 64 == 0     # 512 slots a layer: trips of 64 rows
    assert slots["all"] / 8 <= slots["max_expert"] <= slots["all"]
    assert lm_testing.moved(before, "train_attention_layers_total") == {
        "window": 3, "full": 1}
    # the cell's blocks are recomputed and their attention is the flash op
    assert (cfg["remat_blocks"], cfg["attention"]) == (True, "flash")
    assert lm_testing.moved(before, "train_attention_forward_total") == {
        "once": 4}
    # and no second norm reads a sub-layer's output
    assert lm_testing.moved(before, "train_sublayer_out_total") == {}


def test_moved_slots_are_counted_where_a_share_is_held_and_only_there(
        session, tmp_path):
    """A fit of the share reads ``held <= moved < all`` from the registry
    (the walk carried the held slots rounded up to its trips, and not every
    slot); a model that holds every expert still counts two entries."""
    import jax
    from raydp_tpu.parallel import make_mesh

    cfg, pipeline, _ = _files()
    df, info, _ = _token_frame(session, tmp_path, cfg, pipeline, 8, 2)
    mesh = make_mesh(None, devices=jax.devices()[:1])
    before = lm_testing.counters()
    _estimator(cfg, pipeline, info, mesh, num_epochs=1,
               batch_size=4).fit_on_frame(df)
    slots = lm_testing.moved(before, "moe_slots_total")
    assert set(slots) >= {"max_expert", "all", "held", "moved"}
    assert slots["all"] == 8 * 32 * 3 * 4       # tokens, top-3, layers
    assert 0 < slots["held"] <= slots["moved"] < slots["all"]
    assert slots["moved"] % 48 == 0
    assert slots["moved"] - slots["held"] < 2 * 4 * 48  # steps x layers

    whole = pipeline.build_model(dict(cfg, first_expert=0, experts_held=8))
    assert whole.loss_counters == (("moe_slots_total", "max_expert"),
                                   ("moe_slots_total", "all"))
    tokens = _tokens(cfg, 2)
    (_, counts), _ = lm_testing.loss_and_grads(
        whole, _variables(whole, tokens)[0], None, tokens,
        np.full(2, 0.5, np.float32))
    assert counts.shape == (2,) and float(counts[1]) == 2 * 32 * 3 * 4


def test_an_expert_sharded_fit_of_the_share_gives_the_one_device_losses(
        session, tmp_path):
    """``expert`` 2 over two virtual devices: the held experts' stacked
    kernels split on dim 0 by the role policy, the grouped-query K and V
    kernels replicated; same losses."""
    import jax
    from raydp_tpu.parallel import make_mesh

    cfg, pipeline, _ = _files()
    df, info, _ = _token_frame(session, tmp_path, cfg, pipeline, 8, 6)
    losses = {}
    for name, devices, spec in (("one", 1, None), ("two", 2, {"expert": 2})):
        mesh = make_mesh(spec, devices=jax.devices()[:devices])
        est = _estimator(cfg, pipeline, info, mesh, num_epochs=2,
                         batch_size=4)
        losses[name] = [e["train_loss"]
                        for e in est.fit_on_frame(df).history]
        if name == "two":
            gate = est.get_state().params["block_0"]["moe"]["experts_gate"]
            assert gate.sharding.spec[0] == "expert"
            assert {s.data.shape[0] for s in gate.addressable_shards} == {1}
    np.testing.assert_allclose(losses["two"], losses["one"], rtol=1e-5)


def test_tensor_parallel_rules_name_the_grouped_query_kernels():
    """``transformer_param_rules``: q over the query heads, k and v over the
    (fewer) K/V heads, o's rows; the held experts keep the expert role."""
    import jax
    from jax.sharding import PartitionSpec as P
    from raydp_tpu.models.transformer import transformer_param_rules
    from raydp_tpu.parallel import make_mesh, param_sharding_rules
    from raydp_tpu.parallel.roles import classify_param

    cfg, pipeline, _ = _files()
    model = pipeline.build_model(cfg)
    shapes = jax.eval_shape(lambda: model.init(
        jax.random.PRNGKey(0), _tokens(cfg, 1))["params"])
    mesh = make_mesh({"tensor": 2, "data": 1}, devices=jax.devices()[:2])
    placed = param_sharding_rules(mesh, transformer_param_rules())(shapes)
    attn = placed["block_1"]["attn"]
    assert attn["q"]["kernel"].spec == P(None, "tensor", None)
    assert attn["k"]["kernel"].spec == P(None, "tensor", None)
    assert attn["v"]["kernel"].spec == P(None, "tensor", None)
    assert attn["o"]["kernel"].spec == P("tensor", None, None)
    assert classify_param("params/block_1/moe/experts_gate",
                          (2, 32, 16)) == "expert"
    assert classify_param("params/block_1/router", (32, 8)) == "kernel"
