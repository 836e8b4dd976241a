"""Flash attention: a first-party Pallas TPU kernel for the attention hot op.

Forward is a Pallas kernel (``_fwd_kernel``): the grid is
``(batch*heads, q_blocks, k_blocks)`` with the k dimension innermost, so the
online-softmax state (running max ``m``, normalizer ``l``, accumulator ``acc``)
lives in VMEM scratch and carries across k steps — the [T, T] score matrix
never exists, each program touches one ``[blk_q, D] × [blk_k, D]`` tile pair on
the MXU. A step updates a block no edge of the mask crosses a chunk of its q
rows at a time (``_ROW_CHUNK``: 256; a block too small for two is one piece):
a chunk's QK^T is issued before the softmax and PV of the chunk before it, so
a product and the vector passes that do not wait for it stand side by side.
The kernel also emits the log-sum-exp per query row, which makes the
backward pass a pure recompute: ``custom_vjp`` re-forms each score block from
(Q, K, LSE). On TPU the backward is one Pallas kernel (``_bwd_fused_kernel``):
it walks the visible block pairs once, q blocks outermost, forms the scores
and dP once a pair and adds to all three gradients from them — dq in a q
block's scratch, dk and dv into a whole K/V head's float32 accumulators, which
stay in VMEM over the head's walk and are written once. A sequence too long
for a head's gradients to be held there (``FUSED_BWD_RESIDENT_BYTES``) takes
two kernels instead (dk/dv walking q blocks, dq walking k blocks: the scores
and dP formed twice); elsewhere a blockwise ``lax.scan`` computes the same
math — HBM stays O(T·blk) in both directions.

Three options follow a published layer. ``window`` (with ``causal``): a query
attends to itself and the ``window - 1`` keys before it. The kernels then walk
only the blocks of the band: the grid's inner dimension holds as many steps as
a block's band has blocks (five 1024-blocks for a window of 4096 at any
length); blocks above the diagonal and blocks wholly behind the window are
never fetched or computed. Grouped-query heads: K and V may hold fewer heads
than Q (``H = G * Hk``); a K/V head is read by its ``G`` query heads from
where it lies, and the backward sums a group's query heads in VMEM. Two
widths: V, the output and its cotangent may be ``Dv`` wide beside Q and K of
``D`` (latent attention: 128 beside 192), every block at its own width.

The mask has two edges, the causal diagonal and the window's far side, and
every kernel step sorts its block pair by them (``_by_edges``): a block no edge
crosses is computed whole with no mask at all; a block an edge crosses is
walked in tiles (``_tile``: half a block wide), of which those with no
visible pair run nothing, the ones the edge crosses are masked element by
element and the rest are not. Where the geometry is not static (the q and k
blocks differ, the window is no multiple of a block, a block too small for
two tiles of 128 lanes) an edge block is computed whole and masked.
``flash_tiles_total`` counts the tiles by what becomes of them.

A third mask, ``blockdiff`` (block-diffusion training): the row is a clean and
a noised copy of ``T / 2`` tokens in blocks of ``Bd``, and the mask has three
regions with an edge each (the section at the file's end): the same kernels
under names of their own, walking only the block pairs that hold a visible
pair (80 of 256 at 8,192 tokens in 1024-blocks).

Dispatch: on a TPU backend the Pallas kernel runs, and a shape it cannot take
is an error that says why — never a quiet switch to another path. Off the chip
a fused jnp path computes the same math (it materializes the [T, T] scores, so
it is the CPU implementation for tests, which also run the kernel in interpret
mode). The TPU build adds this op beyond reference parity — the reference has
no attention anywhere (SURVEY.md §2.4). It is the per-device attention of
:class:`raydp_tpu.models.transformer.TransformerLM`
(:func:`flash_attention_sharded` maps it over a mesh's batch and head axes);
the sequence-sharded path uses :mod:`raydp_tpu.ops.ring_attention` instead.
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax

# 1024x1024 compiles under the default scoped-VMEM limit at head_dim 64 and
# 128 (jax 0.9.0 / libtpu 0.0.34, TPU v5 lite, 2026-09-26) and was the
# fastest of the five shapes from 256x512 up, forward and forward+backward,
# at T=8192; the sweep is benchmarks/flash_block_sweep.py, its record PERF.md.
DEFAULT_BLOCK_Q = 1024
DEFAULT_BLOCK_K = 1024
_NEG_INF = -1e30
#: the kernels' names as a device trace shows them (forward; the split
#: backward's dk/dv walk and its dq walk; the one-kernel backward): what a
#: roofline reader sums. A layer's backward is the last, or the two before it
#: where a K/V head's gradients do not fit (``_fused_backward_fits``)
KERNEL_NAMES = ("rdt_flash_fwd", "rdt_flash_bwd_dkdv", "rdt_flash_bwd_dq",
                "rdt_flash_bwd_dkdv_dq")
#: the same four of a call with a window, so that a trace tells a windowed
#: layer's events from a full one's
WINDOW_KERNEL_NAMES = ("rdt_flash_win_fwd", "rdt_flash_win_bwd_dkdv",
                       "rdt_flash_win_bwd_dq", "rdt_flash_win_bwd_dkdv_dq")
#: and of a call under the block-diffusion mask (``blockdiff``)
BLOCKDIFF_KERNEL_NAMES = ("rdt_flash_bd_fwd", "rdt_flash_bd_bwd_dkdv",
                          "rdt_flash_bd_bwd_dq", "rdt_flash_bd_bwd_dkdv_dq")
#: what the one-kernel backward may hold in VMEM for a K/V head's dk and dv:
#: float32 accumulators [T, D] and [T, Dv] and the two out blocks the
#: pipeline keeps of each, T * (D + Dv) * (4 + 2 * itemsize) bytes with each
#: width rounded up to 128 lanes. A v5e has 128 MiB; a step's own blocks and
#: its [blk_q, blk_k] float32 temporaries take under the 16 MiB scoped
#: default (the split kernels compile inside it), and ``_VMEM_WORKING_BYTES``
#: is what the call asks for on top of the held bytes. In bfloat16: 16,384
#: positions at 192/128 hold 48 MiB, at 128/128 32 MiB; 32,768 at 128/128
#: hold 64 MiB; 32,768 at 192/128 (96 MiB) and 65,536 at 128/128 (128 MiB)
#: take the two kernels.
FUSED_BWD_RESIDENT_BYTES = 80 << 20
_VMEM_WORKING_BYTES = 32 << 20


# ---------------------------------------------------------------------------
# Which blocks a step works on. Without a window the inner grid dimension is
# the other side's block index itself and the causal skip is a condition in
# the kernel; with one it counts the steps of the band.
# ---------------------------------------------------------------------------
def _k_band(qi, blk_q: int, blk_k: int, window: int):
    """First and last k block that hold a key some query of q block ``qi``
    sees (``qi`` a Python or a traced integer)."""
    most = max if isinstance(qi, int) else jnp.maximum
    lo = most(qi * blk_q - (window - 1), 0) // blk_k
    return lo, (qi * blk_q + blk_q - 1) // blk_k


def _q_band(ki, blk_q: int, blk_k: int, window: int, num_q: int):
    """First and last q block that hold a query which sees some key of k
    block ``ki``."""
    least = min if isinstance(ki, int) else jnp.minimum
    hi = least((ki * blk_k + blk_k - 1 + window - 1) // blk_q, num_q - 1)
    return (ki * blk_k) // blk_q, hi


def _band_steps(t: int, blk_q: int, blk_k: int, window: Optional[int],
                bd: Optional[int] = None):
    """(steps of a q block's walk over k blocks, steps of a k block's walk
    over q blocks): the other side's block count without a window, the
    widest band's with one; under the block-diffusion mask the longest of
    ``_bd_k_step``'s walks (a k block's walk is every q block's there)."""
    num_q, num_k = t // blk_q, t // blk_k
    if bd is not None and _bd_compact(t // 2, blk_q, blk_k, bd):
        return num_k // 2 + 1, num_q
    if window is None:
        return num_k, num_q
    k_steps = max(hi - lo + 1 for lo, hi in (
        _k_band(qi, blk_q, blk_k, window) for qi in range(num_q)))
    q_steps = max(hi - lo + 1 for lo, hi in (
        _q_band(ki, blk_q, blk_k, window, num_q) for ki in range(num_k)))
    return k_steps, q_steps


def _k_step(qi, j, *, blk_q: int, blk_k: int, window: Optional[int],
            steps: int, bd: Optional[int] = None, half: int = 0):
    """Step ``j`` of q block ``qi``'s walk: (the k block it works on, whether
    it works at all; None = the kernel's causal condition decides). With a
    window the walk ends on the diagonal block; the steps before the band's
    first block stay on that block, which is then fetched once."""
    if bd is not None:
        return _bd_k_step(qi, j, blk_q, blk_k, bd, half)
    if window is None:
        return j, None
    lo, hi = _k_band(qi, blk_q, blk_k, window)
    kb = hi - (steps - 1) + j
    return jnp.maximum(kb, lo), kb >= lo


def _q_step(b, ki, j, *, group: int, blk_q: int, blk_k: int,
            window: Optional[int], steps: int, num_q: int,
            bd: Optional[int] = None, half: int = 0):
    """Step ``j`` of the dK/dV walk of K/V head ``b``, k block ``ki``: (the
    query head, the q block, whether it works at all or None). The walk goes
    through the group's query heads one after another, each over ``steps`` q
    blocks."""
    head = b if group == 1 else b * group + j // steps
    jj = j if group == 1 else j % steps
    if window is None:
        return head, jj, None
    lo, hi = _q_band(ki, blk_q, blk_k, window, num_q)
    qb = lo + jj
    return head, jnp.minimum(qb, hi), qb <= hi


def _count_blocks(kernels: int, heads: int, t: int, blk_q: int, blk_k: int,
                  window: Optional[int], causal: bool,
                  bd: Optional[int] = None) -> None:
    """``flash_blocks_total``: the (q block, k block) pairs of the kernels
    being built, by what becomes of them; and ``flash_tiles_total``: the
    computed pairs' tiles (``_TILES_A_SIDE`` a side), by what a step does
    with them."""
    if bd is not None:
        return _bd_count_blocks(kernels, heads, t, blk_q, blk_k, bd)
    num_q, num_k = t // blk_q, t // blk_k
    above = behind = 0
    tiled = _tile(blk_q, blk_k, window) is not None
    a_block = _TILES_A_SIDE ** 2
    off_edge = (a_block - _TILES_A_SIDE) // 2   # each side of a crossed block
    tiles = dict.fromkeys(("unmasked", "masked", "skipped", "whole_edge"), 0)
    for qi in range(num_q):
        last = (qi * blk_q + blk_q - 1) // blk_k if causal else num_k - 1
        first = 0 if window is None else _k_band(qi, blk_q, blk_k,
                                                 window)[0]
        above += num_k - 1 - last
        behind += first
        for ki in range(first, last + 1):
            if not causal or _interior(qi, ki, blk_q, blk_k, window):
                tiles["unmasked"] += a_block
            elif not tiled:
                tiles["whole_edge"] += a_block
            else:       # a triangle of tiles: the crossed ones its diagonal
                tiles["masked"] += _TILES_A_SIDE
                tiles["unmasked"] += off_edge
                tiles["skipped"] += off_edge
    blocks = {"computed": num_q * num_k - above - behind,
              "skipped_causal": above, "skipped_window": behind}
    _count_fates(kernels * heads, blocks, tiles)


def _count_fates(times: int, blocks: dict, tiles: dict) -> None:
    """Add a kernel's block pairs and tiles, by fate, ``times`` over."""
    from raydp_tpu import metrics as rdt_metrics

    for name, fates in (("flash_blocks_total", blocks),
                        ("flash_tiles_total", tiles)):
        for label, n in fates.items():
            if n:
                rdt_metrics.inc(name, times * n, label)


# ---------------------------------------------------------------------------
# Pallas forward kernel
# ---------------------------------------------------------------------------
def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, m_scr, l_scr, acc_scr,
                *, scale: float, causal: bool, blk_q: int, blk_k: int,
                window: Optional[int] = None, steps: int = 0,
                bd: Optional[int] = None, half: int = 0):
    from jax.experimental import pallas as pl

    qi = pl.program_id(1)
    j = pl.program_id(2)
    num_k = pl.num_programs(2)
    ki, in_band = _k_step(qi, j, blk_q=blk_q, blk_k=blk_k, window=window,
                          steps=steps, bd=bd, half=half)

    @pl.when(j == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, _NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    def _scores(rows, pieces):
        """The scores of the q rows ``rows`` against the keys of ``pieces``:
        (k rows, the pairs to keep or None for all), a list by piece."""
        q = q_ref[0, rows]                      # [rows, D], native dtype
        # native-dtype MXU matmul (bf16 x bf16 -> f32); upcasting inputs to
        # f32 first would cost ~4x MXU throughput for no accuracy gain over
        # the f32 accumulator
        return [_masked(lax.mul(lax.dot_general(
            q, k_ref[0, cols], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32), scale), keep)
            for cols, keep in pieces]                       # [rows, cols]

    def _update(rows, pieces, scores):
        """The online-softmax state of the q rows ``rows`` moved on by their
        ``scores`` against ``pieces``."""
        # a row that sees no key of a block on the band's far edge keeps
        # m = -1e30 and adds p = 1 for each of them; the first block that
        # holds a key it does see (its own diagonal at the latest) scales
        # that away with exp(-1e30 - m) = 0
        m_prev = m_scr[rows, 0]                             # [rows]
        m_new = m_prev
        for s in scores:
            m_new = lax.max(m_new, lax.reduce_max(s, (1,)))
        correction = lax.exp(lax.sub(m_prev, m_new))
        l_new = lax.mul(l_scr[rows, 0], correction)
        acc = lax.mul(acc_scr[rows], _col(correction))
        for s, (cols, _) in zip(scores, pieces):
            p = lax.exp(lax.sub(s, _col(m_new)))
            l_new = lax.add(l_new, lax.reduce_sum(p, (1,)))
            v = v_ref[0, cols]                              # [cols, D]
            acc = lax.add(acc, lax.dot_general(
                p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32))
        acc_scr[rows] = acc
        m_scr[rows, 0] = m_new
        l_scr[rows, 0] = l_new

    def _rows(rows, pieces):
        """One online-softmax update of the q rows ``rows`` by the keys of
        ``pieces``, where no mask lies on them a chunk of rows at a time
        (``_row_chunks``; a row's state is its own). A chunk's scores are
        formed before the softmax of the chunk before it: the compiler keeps
        close to the order it is given, and so one chunk's QK^T on the MXU
        stands beside the other's vector passes, which do not wait for it."""
        chunks = _row_chunks(rows, pieces, blk_q)
        ahead = _scores(*chunks[0])
        for (chunk, kept), after in zip(chunks, chunks[1:] + [None]):
            scores, ahead = ahead, after and _scores(*after)
            _update(chunk, kept, scores)

    def _body(edge):
        for rows, pieces in _walk(edge, True, qi, ki, blk_q, blk_k, window,
                                  bd, half):
            _rows(rows, pieces)

    _by_edges(_body, in_band, causal, qi, ki, blk_q, blk_k, window, bd,
              half)

    @pl.when(j == num_k - 1)
    def _finalize():
        l_fin = jnp.maximum(l_scr[:, 0], 1e-30)
        o_ref[0] = (acc_scr[:] / l_fin[:, None]).astype(o_ref.dtype)
        lse_ref[0, 0] = m_scr[:, 0] + jnp.log(l_fin)


def _maps(group: int, band: dict):
    """The index maps of a grid ``(q head, q block, step)``: a q-side block,
    the K/V block of the step (the K/V head is the query head's group), and
    the ``(1, 1, blk_q)`` block of a per-row float32 array."""
    def q_map(b, qi, j):
        return (b, qi, 0)

    def kv_map(b, qi, j):
        return (b if group == 1 else b // group, _k_step(qi, j, **band)[0], 0)

    def row_map(b, qi, j):
        return (b, 0, qi)

    return q_map, kv_map, row_map


def _fwd_pallas(q3, k3, v3, *, scale: float, causal: bool, blk_q: int,
                blk_k: int, interpret: bool, window: Optional[int] = None,
                bd: Optional[int] = None):
    """q3 [BH,T,D], k3 [BHk,T,D], v3 [BHk,T,Dv] → (out [BH,T,Dv], lse [BH,T])."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    from raydp_tpu import metrics as rdt_metrics

    (bh, t, d), d_v = q3.shape, v3.shape[2]
    group = bh // k3.shape[0]
    k_steps, _ = _band_steps(t, blk_q, blk_k, window, bd)
    band = _band(blk_q, blk_k, window, k_steps, bd, t)
    q_map, kv_map, row_map = _maps(group, band)
    _count_blocks(1, bh, t, blk_q, blk_k, window, causal, bd)
    rdt_metrics.inc("flash_forward_total", label="chunked" if _row_chunk(
        blk_q) < blk_q else "whole")
    grid = (bh, t // blk_q, k_steps)
    vma = jax.typeof(q3).vma     # inside a shard_map the outputs vary as q does

    out, lse = pl.pallas_call(
        functools.partial(_fwd_kernel, scale=scale, causal=causal, **band),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, blk_q, d), q_map),
            pl.BlockSpec((1, blk_k, d), kv_map),
            pl.BlockSpec((1, blk_k, d_v), kv_map),
        ],
        out_specs=[
            pl.BlockSpec((1, blk_q, d_v), q_map),
            # [BH, 1, T]: trailing block dims (1, blk_q) satisfy TPU tiling
            pl.BlockSpec((1, 1, blk_q), row_map),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, t, d_v), q3.dtype, vma=vma),
            jax.ShapeDtypeStruct((bh, 1, t), jnp.float32, vma=vma),
        ],
        scratch_shapes=[
            pltpu.VMEM((blk_q, 128), jnp.float32),   # m (lane-padded)
            pltpu.VMEM((blk_q, 128), jnp.float32),   # l
            pltpu.VMEM((blk_q, d_v), jnp.float32),   # acc
        ],
        # bh and q blocks are independent; only the k walk carries state
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name=_names(window, bd)[0],
    )(q3, k3, v3)
    return out, lse.reshape(bh, t)


def _names(window: Optional[int], bd: Optional[int] = None):
    if bd is not None:
        return BLOCKDIFF_KERNEL_NAMES
    return KERNEL_NAMES if window is None else WINDOW_KERNEL_NAMES


def _band(blk_q: int, blk_k: int, window: Optional[int], steps: int,
          bd: Optional[int], t: int) -> dict:
    """What a kernel and its index maps are told of the walk; the two keys
    of the block-diffusion mask only where a call has it."""
    band = dict(blk_q=blk_q, blk_k=blk_k, window=window, steps=steps)
    if bd is not None:
        band.update(bd=bd, half=t // 2)
    return band


# ---------------------------------------------------------------------------
# Fused jnp path: the implementation off the chip (materializes [T, T] scores)
# ---------------------------------------------------------------------------
def _visible(t: int, window: Optional[int]):
    """[T, T] bool: query i sees key j."""
    mask = jnp.tril(jnp.ones((t, t), dtype=bool))
    if window is not None:
        mask = mask & ~jnp.tril(jnp.ones((t, t), dtype=bool), -window)
    return mask


def _repeat_kv(q3, k3, v3):
    """K and V repeated to a head a query head (the jnp paths only: the
    kernels read a group's one copy)."""
    group = q3.shape[0] // k3.shape[0]
    if group == 1:
        return k3, v3, group
    return jnp.repeat(k3, group, axis=0), jnp.repeat(v3, group, axis=0), group


def _fwd_jnp(q3, k3, v3, *, scale: float, causal: bool,
             window: Optional[int] = None, bd: Optional[int] = None):
    k3, v3, _ = _repeat_kv(q3, k3, v3)
    s = jnp.einsum("bqd,bkd->bqk", q3.astype(jnp.float32),
                   k3.astype(jnp.float32)) * scale
    if bd is not None:
        at = jnp.arange(q3.shape[1])
        s = jnp.where(blockdiff_visible(at[:, None], at[None, :], bd,
                                        q3.shape[1] // 2)[None], s, _NEG_INF)
    elif causal:
        s = jnp.where(_visible(q3.shape[1], window)[None], s, _NEG_INF)
    lse = jax.nn.logsumexp(s, axis=-1)
    p = jnp.exp(s - lse[..., None])
    out = jnp.einsum("bqk,bkd->bqd", p, v3.astype(jnp.float32))
    return out.astype(q3.dtype), lse


# ---------------------------------------------------------------------------
# Pallas backward kernels: recompute p from (q, k, lse), causal block skip.
# One kernel walks the pairs once and holds a K/V head's dk and dv in VMEM
# (`_bwd_fused_kernel`). The two before it split the work in the standard
# way — one accumulates dk/dv walking q blocks, one dq walking k blocks — so
# each output block is written once from a block's scratch: the path of a
# sequence whose head does not fit.
# ---------------------------------------------------------------------------
def _keep_causal(qi, ki, blk_q: int, blk_k: int,
                 window: Optional[int] = None):
    """[blk_q, blk_k] bool: the pairs of a block that the causal mask, and
    the window's, keep (shared by fwd + both bwds)."""
    q_pos = qi * blk_q + lax.broadcasted_iota(jnp.int32, (blk_q, blk_k), 0)
    k_pos = ki * blk_k + lax.broadcasted_iota(jnp.int32, (blk_q, blk_k), 1)
    keep = q_pos >= k_pos
    if window is not None:
        keep = keep & (q_pos - k_pos < window)
    return keep


def _masked(s, keep):
    """The scores ``s`` with the pairs ``keep`` drops (None: none) at -1e30.
    Written, as the kernels' other elementwise math is, in ``lax`` ops: a
    ``jnp`` wrapper is a jitted function traced anew at every call, and a
    train step traces each kernel body several times (PERF.md, PR 39)."""
    if keep is None:
        return s
    return lax.select(keep, s, lax.full_like(s, _NEG_INF))


def _col(rows):
    """A per-row vector as a column, to broadcast over a row's keys."""
    return lax.broadcast_in_dim(rows, (rows.shape[0], 1), (0,))


def _recompute_p_ds(q, k, v, do, lse, delta, keep, *, scale: float):
    """Re-form the scores of (q rows, k rows) from (q, k, lse) and compute
    (p, ds) — the flash backward identity ds = p ⊙ (do·vᵀ − delta)·scale,
    shared by the dk/dv and dq kernels so forward and backward masking cannot
    desynchronize (``keep``: the pairs the mask keeps, None for all)."""
    s = _masked(lax.mul(lax.dot_general(
        q, k, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32), scale), keep)  # [rows, cols]
    p = lax.exp(lax.sub(s, _col(lse)))                    # true softmax rows
    dp = lax.dot_general(
        do, v, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)
    ds = lax.mul(lax.mul(p, lax.sub(dp, _col(delta))), scale)
    return p, ds


def _bwd_dkdv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                     dk_ref, dv_ref, dk_scr, dv_scr,
                     *, scale: float, causal: bool, blk_q: int, blk_k: int,
                     window: Optional[int] = None, steps: int = 0,
                     group: int = 1, num_q: int = 0,
                     bd: Optional[int] = None, half: int = 0):
    from jax.experimental import pallas as pl

    ki = pl.program_id(1)
    j = pl.program_id(2)
    last = pl.num_programs(2)
    _, qi, in_band = _q_step(0, ki, j, group=group, blk_q=blk_q, blk_k=blk_k,
                             window=window, steps=steps, num_q=num_q)

    @pl.when(j == 0)
    def _init():
        dk_scr[:] = jnp.zeros_like(dk_scr)
        dv_scr[:] = jnp.zeros_like(dv_scr)

    def _body(edge):
        for cols, pieces in _walk(edge, False, qi, ki, blk_q, blk_k, window,
                                  bd, half):
            k, v = k_ref[0, cols], v_ref[0, cols]           # [cols, D]
            dk, dv = dk_scr[cols], dv_scr[cols]
            for rows, keep in pieces:
                q, do = q_ref[0, rows], do_ref[0, rows]     # [rows, D]
                p, ds = _recompute_p_ds(
                    q, k, v, do, lse_ref[0, 0, rows], delta_ref[0, 0, rows],
                    keep, scale=scale)
                dv = lax.add(dv, lax.dot_general(
                    p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32))
                dk = lax.add(dk, lax.dot_general(
                    ds.astype(q.dtype), q, (((0,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32))
            dk_scr[cols] = dk
            dv_scr[cols] = dv

    _by_edges(_body, in_band, causal, qi, ki, blk_q, blk_k, window, bd,
              half)

    @pl.when(j == last - 1)
    def _finalize():
        dk_ref[0] = dk_scr[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[:].astype(dv_ref.dtype)


def _bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                   dq_ref, dq_scr,
                   *, scale: float, causal: bool, blk_q: int, blk_k: int,
                   window: Optional[int] = None, steps: int = 0,
                   bd: Optional[int] = None, half: int = 0):
    from jax.experimental import pallas as pl

    qi = pl.program_id(1)
    j = pl.program_id(2)
    num_k = pl.num_programs(2)
    ki, in_band = _k_step(qi, j, blk_q=blk_q, blk_k=blk_k, window=window,
                          steps=steps, bd=bd, half=half)

    @pl.when(j == 0)
    def _init():
        dq_scr[:] = jnp.zeros_like(dq_scr)

    def _body(edge):
        for rows, pieces in _walk(edge, True, qi, ki, blk_q, blk_k, window,
                                  bd, half):
            dq = dq_scr[rows]
            for cols, keep in pieces:
                k = k_ref[0, cols]
                _, ds = _recompute_p_ds(
                    q_ref[0, rows], k, v_ref[0, cols], do_ref[0, rows],
                    lse_ref[0, 0, rows], delta_ref[0, 0, rows], keep,
                    scale=scale)
                dq = lax.add(dq, lax.dot_general(
                    ds.astype(k.dtype), k, (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32))
            dq_scr[rows] = dq

    _by_edges(_body, in_band, causal, qi, ki, blk_q, blk_k, window, bd,
              half)

    @pl.when(j == num_k - 1)
    def _finalize():
        dq_ref[0] = dq_scr[:].astype(dq_ref.dtype)


def _held(ki, cols: slice, blk_k: int):
    """Where the k rows ``cols`` of k block ``ki`` lie in a K/V head's held
    gradients."""
    from jax.experimental import pallas as pl

    start, stop, _ = cols.indices(blk_k)
    return pl.ds(pl.multiple_of(ki * blk_k + start, math.gcd(blk_k, start)),
                 stop - start)


def _bwd_fused_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                      dq_ref, dk_ref, dv_ref, dq_scr, dk_scr, dv_scr,
                      *, scale: float, causal: bool, blk_q: int, blk_k: int,
                      window: Optional[int] = None, steps: int = 0,
                      bd: Optional[int] = None, half: int = 0):
    """Grid ``(K/V head, query head of its group, q block, step)``: dq is a q
    block's walk as in ``_bwd_dq_kernel``; dk and dv of the whole K/V head
    gather every pair's share in ``dk_scr`` / ``dv_scr`` ([T, D], [T, Dv])
    from the head's first step to its last."""
    from jax.experimental import pallas as pl

    g, qi, j = (pl.program_id(axis) for axis in (1, 2, 3))
    last_g, last_qi, last_j = (pl.num_programs(axis) - 1
                               for axis in (1, 2, 3))
    ki, in_band = _k_step(qi, j, blk_q=blk_q, blk_k=blk_k, window=window,
                          steps=steps, bd=bd, half=half)

    def _k_blocks(fn):
        """``fn`` on each k block's rows of the held gradients in turn: a
        head's are tens of MiB, a block of them a value the size of a step's
        own."""
        def one(i, _):
            fn(pl.ds(pl.multiple_of(i * blk_k, blk_k), blk_k))
        lax.fori_loop(0, dk_scr.shape[0] // blk_k, one, None)

    @pl.when((g == 0) & (qi == 0) & (j == 0))
    def _init_head():
        def zero(at):
            dk_scr[at] = jnp.zeros((blk_k, dk_scr.shape[1]), jnp.float32)
            dv_scr[at] = jnp.zeros((blk_k, dv_scr.shape[1]), jnp.float32)
        _k_blocks(zero)

    @pl.when(j == 0)
    def _init():
        dq_scr[:] = jnp.zeros_like(dq_scr)

    def _body(edge):
        for rows, pieces in _walk(edge, True, qi, ki, blk_q, blk_k, window,
                                  bd, half):
            q, do = q_ref[0, rows], do_ref[0, rows]         # [rows, D]
            dq = dq_scr[rows]
            for cols, keep in pieces:
                k = k_ref[0, cols]                          # [cols, D]
                p, ds = _recompute_p_ds(
                    q, k, v_ref[0, cols], do, lse_ref[0, 0, rows],
                    delta_ref[0, 0, rows], keep, scale=scale)
                ds = ds.astype(k.dtype)
                dq = lax.add(dq, lax.dot_general(
                    ds, k, (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32))
                at = _held(ki, cols, blk_k)
                dv_scr[at] = lax.add(dv_scr[at], lax.dot_general(
                    p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32))
                dk_scr[at] = lax.add(dk_scr[at], lax.dot_general(
                    ds, q, (((0,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32))
            dq_scr[rows] = dq

    _by_edges(_body, in_band, causal, qi, ki, blk_q, blk_k, window, bd,
              half)

    @pl.when(j == last_j)
    def _finalize():
        dq_ref[0] = dq_scr[:].astype(dq_ref.dtype)

    @pl.when((g == last_g) & (qi == last_qi) & (j == last_j))
    def _finalize_head():
        def store(at):
            dk_ref[0, at] = dk_scr[at].astype(dk_ref.dtype)
            dv_ref[0, at] = dv_scr[at].astype(dv_ref.dtype)
        _k_blocks(store)


def _fused_resident_bytes(t: int, d: int, d_v: int, dtype) -> int:
    """What ``_bwd_fused_kernel`` holds in VMEM for one K/V head's dk and dv
    (the arithmetic of ``FUSED_BWD_RESIDENT_BYTES``; VMEM lays a row out in
    whole tiles of 128 lanes, so a width of 192 takes 256)."""
    lanes = sum(-(-width // 128) * 128 for width in (d, d_v))
    return t * lanes * (4 + 2 * jnp.dtype(dtype).itemsize)


def _fused_backward_fits(t: int, d: int, d_v: int, dtype) -> bool:
    """The shape rule: one backward kernel where a K/V head's gradients fit
    the budget, the pair of kernels where they do not."""
    return _fused_resident_bytes(t, d, d_v, dtype) <= FUSED_BWD_RESIDENT_BYTES


def _bwd_pallas(res, g, *, scale: float, causal: bool, blk_q: int,
                blk_k: int, interpret: bool, window: Optional[int] = None,
                bd: Optional[int] = None):
    from raydp_tpu import metrics as rdt_metrics

    q3, k3, v3, out, lse = res
    (bh, t, d), d_v = q3.shape, v3.shape[2]
    delta = jnp.sum(g.astype(jnp.float32) * out.astype(jnp.float32),
                    axis=-1).reshape(bh, 1, t)
    fused = _fused_backward_fits(t, d, d_v, k3.dtype)
    rdt_metrics.inc("flash_backward_total", label="fused" if fused
                    else "split")
    _count_blocks(1 if fused else 2, bh, t, blk_q, blk_k, window, causal, bd)
    return (_bwd_fused if fused else _bwd_split)(
        q3, k3, v3, g, lse.reshape(bh, 1, t), delta, scale=scale,
        causal=causal, blk_q=blk_q, blk_k=blk_k, interpret=interpret,
        window=window, bd=bd)


def _bwd_fused(q3, k3, v3, do, lse3, delta, *, scale: float, causal: bool,
               blk_q: int, blk_k: int, interpret: bool,
               window: Optional[int], bd: Optional[int] = None):
    """dq, dk, dv from one kernel: see ``_bwd_fused_kernel``."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    (bh, t, d), d_v = q3.shape, v3.shape[2]
    bkv = k3.shape[0]
    group = bh // bkv
    k_steps, _ = _band_steps(t, blk_q, blk_k, window, bd)
    band = _band(blk_q, blk_k, window, k_steps, bd, t)
    vma = jax.typeof(q3).vma

    def of_head(index_map):
        """A map of ``_maps``' grid on this one, which splits the query head
        into (its K/V head, its place in the group)."""
        return lambda h, g, qi, j: index_map(h * group + g, qi, j)

    q_map, kv_map, row_map = map(of_head, _maps(group, band))

    def held(h, g, qi, j):     # one block a K/V head: it stays over the walk
        return (h, 0, 0)

    return tuple(pl.pallas_call(
        functools.partial(_bwd_fused_kernel, scale=scale, causal=causal,
                          **band),
        grid=(bkv, group, t // blk_q, k_steps),
        in_specs=[
            pl.BlockSpec((1, blk_q, d), q_map),     # q
            pl.BlockSpec((1, blk_k, d), kv_map),    # k
            pl.BlockSpec((1, blk_k, d_v), kv_map),  # v
            pl.BlockSpec((1, blk_q, d_v), q_map),   # do
            pl.BlockSpec((1, 1, blk_q), row_map),   # lse
            pl.BlockSpec((1, 1, blk_q), row_map),   # delta
        ],
        out_specs=[
            pl.BlockSpec((1, blk_q, d), q_map),
            pl.BlockSpec((1, t, d), held),
            pl.BlockSpec((1, t, d_v), held),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, t, d), q3.dtype, vma=vma),
            jax.ShapeDtypeStruct((bkv, t, d), k3.dtype, vma=vma),
            jax.ShapeDtypeStruct((bkv, t, d_v), v3.dtype, vma=vma),
        ],
        scratch_shapes=[
            pltpu.VMEM((blk_q, d), jnp.float32),
            pltpu.VMEM((t, d), jnp.float32),
            pltpu.VMEM((t, d_v), jnp.float32),
        ],
        # a K/V head's gradients gather over every other axis
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary",
                                 "arbitrary"),
            vmem_limit_bytes=_fused_resident_bytes(t, d, d_v, k3.dtype)
            + _VMEM_WORKING_BYTES),
        interpret=interpret,
        name=_names(window, bd)[3],
    )(q3, k3, v3, do, lse3, delta))


def _bwd_split(q3, k3, v3, do, lse3, delta, *, scale: float, causal: bool,
               blk_q: int, blk_k: int, interpret: bool,
               window: Optional[int], bd: Optional[int] = None):
    """dq, dk, dv from two kernels, each output block in its own scratch:
    what a sequence too long for ``_bwd_fused`` takes."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    (bh, t, d), d_v = q3.shape, v3.shape[2]
    bkv = k3.shape[0]
    group = bh // bkv
    num_q, num_k = t // blk_q, t // blk_k
    k_steps, q_steps = _band_steps(t, blk_q, blk_k, window, bd)
    vma = jax.typeof(q3).vma
    names = _names(window, bd)

    # dK/dV: one K/V head and k block a program row, walking the q blocks of
    # each of the group's query heads in turn
    walk = dict(_band(blk_q, blk_k, window, q_steps, bd, t), group=group,
                num_q=num_q)

    def q_side(b, ki, j):
        head, qi, _ = _q_step(b, ki, j, **walk)
        return (head, qi, 0)

    def q_rows(b, ki, j):
        head, qi, _ = _q_step(b, ki, j, **walk)
        return (head, 0, qi)

    def k_side(b, ki, j):
        return (b, ki, 0)

    dk, dv = pl.pallas_call(
        functools.partial(_bwd_dkdv_kernel, scale=scale, causal=causal,
                          **walk),
        grid=(bkv, num_k, group * q_steps),
        in_specs=[
            pl.BlockSpec((1, blk_q, d), q_side),    # q
            pl.BlockSpec((1, blk_k, d), k_side),    # k
            pl.BlockSpec((1, blk_k, d_v), k_side),  # v
            pl.BlockSpec((1, blk_q, d_v), q_side),  # do
            pl.BlockSpec((1, 1, blk_q), q_rows),    # lse
            pl.BlockSpec((1, 1, blk_q), q_rows),    # delta
        ],
        out_specs=[
            pl.BlockSpec((1, blk_k, d), k_side),
            pl.BlockSpec((1, blk_k, d_v), k_side),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bkv, t, d), k3.dtype, vma=vma),
            jax.ShapeDtypeStruct((bkv, t, d_v), v3.dtype, vma=vma),
        ],
        scratch_shapes=[
            pltpu.VMEM((blk_k, d), jnp.float32),
            pltpu.VMEM((blk_k, d_v), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name=names[1],
    )(q3, k3, v3, do, lse3, delta)

    band = _band(blk_q, blk_k, window, k_steps, bd, t)
    q_map, kv_map, row_map = _maps(group, band)
    dq = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, scale=scale, causal=causal, **band),
        grid=(bh, num_q, k_steps),
        in_specs=[
            pl.BlockSpec((1, blk_q, d), q_map),     # q
            pl.BlockSpec((1, blk_k, d), kv_map),    # k
            pl.BlockSpec((1, blk_k, d_v), kv_map),  # v
            pl.BlockSpec((1, blk_q, d_v), q_map),   # do
            pl.BlockSpec((1, 1, blk_q), row_map),   # lse
            pl.BlockSpec((1, 1, blk_q), row_map),   # delta
        ],
        out_specs=[
            pl.BlockSpec((1, blk_q, d), q_map),
        ],
        out_shape=[jax.ShapeDtypeStruct((bh, t, d), q3.dtype, vma=vma)],
        scratch_shapes=[pltpu.VMEM((blk_q, d), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name=names[2],
    )(q3, k3, v3, do, lse3, delta)[0]
    return dq, dk, dv


# ---------------------------------------------------------------------------
# Blockwise backward (flash recompute from LSE), shared by both paths
# ---------------------------------------------------------------------------
def _bwd_blockwise(res, g, *, scale: float, causal: bool, blk_k: int,
                   window: Optional[int] = None, bd: Optional[int] = None):
    q3, k3, v3, out, lse = res
    bkv = k3.shape[0]
    k3, v3, group = _repeat_kv(q3, k3, v3)
    (bh, t, d), d_v = q3.shape, v3.shape[2]
    blk = _fit_block(t, blk_k)
    num_k = t // blk

    qf = q3.astype(jnp.float32)
    do = g.astype(jnp.float32)
    delta = jnp.sum(do * out.astype(jnp.float32), axis=-1)   # [BH, Tq]
    q_pos = jnp.arange(t)

    def step(dq, j):
        k_blk = lax.dynamic_slice_in_dim(k3, j * blk, blk, 1).astype(jnp.float32)
        v_blk = lax.dynamic_slice_in_dim(v3, j * blk, blk, 1).astype(jnp.float32)
        s = jnp.einsum("bqd,bkd->bqk", qf, k_blk) * scale
        if bd is not None:
            k_pos = j * blk + jnp.arange(blk)
            s = jnp.where(blockdiff_visible(q_pos[:, None], k_pos[None, :],
                                            bd, t // 2)[None], s, _NEG_INF)
        elif causal:
            k_pos = j * blk + jnp.arange(blk)
            keep = q_pos[:, None] >= k_pos[None, :]
            if window is not None:
                keep = keep & (q_pos[:, None] - k_pos[None, :] < window)
            s = jnp.where(keep[None], s, _NEG_INF)
        p = jnp.exp(s - lse[..., None])                      # [BH, Tq, blk]
        dv_blk = jnp.einsum("bqk,bqd->bkd", p, do)
        dp = jnp.einsum("bqd,bkd->bqk", do, v_blk)
        ds = p * (dp - delta[..., None]) * scale
        dq = dq + jnp.einsum("bqk,bkd->bqd", ds, k_blk)
        dk_blk = jnp.einsum("bqk,bqd->bkd", ds, qf)
        return dq, (dk_blk, dv_blk)

    dq, (dk_blocks, dv_blocks) = lax.scan(
        step, jnp.zeros_like(qf), jnp.arange(num_k))
    dk = dk_blocks.transpose(1, 0, 2, 3).reshape(bh, t, d)
    dv = dv_blocks.transpose(1, 0, 2, 3).reshape(bh, t, d_v)
    if group > 1:       # a K/V head's gradient: the sum over its query heads
        dk = dk.reshape(bkv, group, t, d).sum(axis=1)
        dv = dv.reshape(bkv, group, t, d_v).sum(axis=1)
    return dq.astype(q3.dtype), dk.astype(k3.dtype), dv.astype(v3.dtype)


# ---------------------------------------------------------------------------
# Public op with custom VJP, [B, T, H, D] layout
# ---------------------------------------------------------------------------
def _fit_block(t: int, blk: int) -> int:
    """Shrink blk by halving until it divides t (down to 1), so the grid and
    the blockwise backward always cover the full sequence."""
    blk = min(blk, t)
    while t % blk:
        blk //= 2
    return max(blk, 1)


def kernel_ineligible(t: int, d: int, block_q: int = DEFAULT_BLOCK_Q,
                      block_k: int = DEFAULT_BLOCK_K,
                      window: Optional[int] = None,
                      d_v: Optional[int] = None) -> Optional[str]:
    """Why the compiled Pallas kernel cannot take a [.., T=t, .., D=d] call
    with values of width ``d_v`` (None: ``d``; None back when it can). Block
    dims equal to the full array dim satisfy TPU tiling, so neither width
    needs 128 alignment; the q/k blocks must be sublane-aligned themselves
    (``_fit_block`` caps them at t: t=20 → blk=20) and the (1, 1, blk_q) LSE
    blocks put blk_q on the lanes. A window need not be a multiple of a block
    (edge blocks are then masked whole); it has to hold the query itself."""
    blk_q, blk_k = _fit_block(t, block_q), _fit_block(t, block_k)
    if window is not None and window < 1:
        return f"window {window} holds no key, not even the query's own"
    if d % 8 or (d_v or d) % 8:
        return f"head_dim {d} (values {d_v or d}) is not a multiple of 8"
    if blk_q % 8 or blk_k % 8:
        return (f"sequence length {t} only divides into blocks "
                f"({blk_q}, {blk_k}) that are not multiples of 8")
    if blk_q % 128 and blk_q != t:
        return (f"sequence length {t} only divides into q blocks of "
                f"{blk_q}, neither a multiple of 128 nor the whole sequence")
    return None


def _use_pallas(t: int, d: int, blk_q: int, blk_k: int, interpret: bool,
                window: Optional[int] = None, d_v=None) -> bool:
    """Can the Pallas kernel take this call? In interpret mode: whenever the
    blocks divide the sequence. Otherwise whenever the compiled kernel is
    eligible; on a TPU backend an ineligible shape is an error. Whether the
    kernel then *runs* is decided when the program is lowered
    (:func:`_by_platform`): for a TPU it does, for anything else the jnp
    path."""
    if interpret:
        return t % blk_q == 0 and t % blk_k == 0
    why = kernel_ineligible(t, d, blk_q, blk_k, window, d_v)
    if why is None:
        return True
    if jax.default_backend() == "tpu":
        raise ValueError(
            f"flash_attention cannot run its Pallas kernel on this TPU "
            f"backend: {why}. The jnp path would materialize the "
            f"[B*H, {t}, {t}] scores the kernel exists to avoid, so it is "
            f"not substituted; pad the sequence or use dense_attention.")
    return False


def _by_platform(pallas_fn, jnp_fn, interpret: bool, *args):
    """The kernel where the program is lowered for a TPU, the jnp path
    elsewhere: chosen at lowering, not from the process's default backend,
    so a step compiled ahead of time for a described TPU (no chip attached)
    holds the kernel it will run. Interpret mode always takes the kernel."""
    if interpret:
        return pallas_fn(*args)
    return lax.platform_dependent(*args, tpu=pallas_fn, default=jnp_fn)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8, 9))
def _flash(q3, k3, v3, scale, causal, blk_q, blk_k, interpret, window, bd):
    out, _ = _flash_fwd(q3, k3, v3, scale, causal, blk_q, blk_k, interpret,
                        window, bd)
    return out


def _flash_fwd(q3, k3, v3, scale, causal, blk_q, blk_k, interpret, window,
               bd):
    t, d = q3.shape[1], q3.shape[2]
    jnp_fn = functools.partial(_fwd_jnp, scale=scale, causal=causal,
                               window=window, bd=bd)
    if _use_pallas(t, d, blk_q, blk_k, interpret, window, v3.shape[2]):
        out, lse = _by_platform(
            functools.partial(_fwd_pallas, scale=scale, causal=causal,
                              blk_q=blk_q, blk_k=blk_k, interpret=interpret,
                              window=window, bd=bd),
            jnp_fn, interpret, q3, k3, v3)
    else:
        out, lse = jnp_fn(q3, k3, v3)
    return _named(q3, k3, v3, out, lse)


def _flash_bwd(scale, causal, blk_q, blk_k, interpret, window, bd, res, g):
    t, d = res[0].shape[1], res[0].shape[2]
    jnp_fn = functools.partial(_bwd_blockwise, scale=scale, causal=causal,
                               blk_k=blk_k, window=window, bd=bd)
    if _use_pallas(t, d, blk_q, blk_k, interpret, window, res[2].shape[2]):
        return _by_platform(
            functools.partial(_bwd_pallas, scale=scale, causal=causal,
                              blk_q=blk_q, blk_k=blk_k, interpret=interpret,
                              window=window, bd=bd),
            jnp_fn, interpret, res, g)
    return jnp_fn(res, g)


_flash.defvjp(_flash_fwd, _flash_bwd)


def flash_attention(q, k, v, causal: bool = True,
                    scale: Optional[float] = None,
                    block_q: int = DEFAULT_BLOCK_Q,
                    block_k: int = DEFAULT_BLOCK_K,
                    interpret: bool = False,
                    window: Optional[int] = None,
                    blockdiff: Optional[int] = None):
    """Memory-efficient exact attention. q: [B, T, H, D]; k: [B, T, Hk, D];
    v: [B, T, Hk, Dv], ``H`` a multiple of ``Hk`` (query head ``h`` reads K/V
    head ``h // (H // Hk)``) → [B, T, H, Dv]; the default scale is D^-1/2.
    ``window`` (causal only): a query sees itself and ``window - 1`` keys.
    ``blockdiff`` (causal only, no window): the row is a clean copy and a
    noised copy of ``T / 2`` tokens in blocks of ``blockdiff``
    (:func:`blockdiff_visible`)."""
    b, t, h, d = q.shape
    hk = k.shape[2]
    if h % hk or v.shape[:3] != k.shape[:3] or k.shape[3] != d:
        raise ValueError(f"q {q.shape} over K {k.shape} / V {v.shape}: K/V "
                         f"heads divide the query heads, keys are q's width")
    if window is not None and not causal:
        raise ValueError("a window is one-sided: it needs causal=True")
    if blockdiff is not None:
        _check_blockdiff(t, blockdiff, causal, window)
    _count_mask(causal, window, blockdiff)
    scale = scale if scale is not None else 1.0 / (d ** 0.5)
    blk_q = _fit_block(t, block_q)
    blk_k = _fit_block(t, block_k)

    def to3(x):
        return x.transpose(0, 2, 1, 3).reshape(b * x.shape[2], t, x.shape[3])

    out3 = _flash(to3(q), to3(k), to3(v), scale, causal, blk_q, blk_k,
                  interpret, window, blockdiff)
    return out3.reshape(b, h, t, v.shape[3]).transpose(0, 2, 1, 3)


def flash_attention_sharded(q, k, v, mesh, causal: bool = True, **kwargs):
    """:func:`flash_attention` mapped over the mesh (None or one device: the
    plain call): batch over the data axes, heads over ``tensor`` when present
    — attention is independent along both, and the partitioner cannot split a
    custom call, so without the map q/k/v would be all-gathered and every
    chip would run the full batch. The sequence stays whole per device (a >1
    ``seq`` axis is ring attention's job)."""
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    from raydp_tpu.parallel.mesh import data_axes

    fn = functools.partial(flash_attention, causal=causal, **kwargs)
    if mesh is None or mesh.size == 1:
        return fn(q, k, v)
    batch = data_axes(mesh)
    heads = "tensor" if mesh.shape.get("tensor", 1) > 1 else None
    spec = P(batch if len(batch) > 1 else batch[0], None, heads, None)
    return shard_map(fn, mesh=mesh, in_specs=(spec, spec, spec),
                     out_specs=spec)(q, k, v)


# What the backward kernels read beside q, k and v, by the names a
# ``jax.checkpoint`` policy can keep them under
# (``save_only_these_names(*RESIDUAL_NAMES)``): a recomputed block then keeps
# these two and its recomputation holds no forward kernel. Outside a
# checkpoint, and under a policy that names nothing, a name is the identity.
# Down here so that no line above moves: the kernels' payloads embed the
# source lines of their call stack, and a moved line misses the compile cache.
RESIDUAL_NAMES = ("rdt_flash_out", "rdt_flash_lse")


def _named(q3, k3, v3, out, lse):
    """What ``_flash_fwd`` returns: the output and the residuals, with the
    kernel's two products under their names in both (and ``INPUT_NAMES``)."""
    from jax.ad_checkpoint import checkpoint_name

    out, lse = map(checkpoint_name, (out, lse), RESIDUAL_NAMES)
    return out, (*_named_inputs(q3, k3, v3), out, lse)


# ---------------------------------------------------------------------------
# The mask's two edges. A block pair that holds a visible pair is *interior*
# (no edge crosses it: no mask) or an *edge* block: the causal diagonal
# crosses it, or the window's far side does. Down here for the reason above.
# ---------------------------------------------------------------------------
#: tiles a side of an edge block's walk: 1024-blocks in tiles of 512, which
#: compute three tiles of four where tiles of 256 compute ten of sixteen and
#: were the slower in every geometry swept (PERF.md, PR 39: a tile's products
#: stream its few rows against each latched key tile)
_TILES_A_SIDE = 2


def _tile(blk_q: int, blk_k: int, window: Optional[int]) -> Optional[int]:
    """The width of the tiles an edge block is walked in, or None where it
    is computed whole and masked: the q and k blocks differ or the window is
    no multiple of them (the edges are then no triangles of whole tiles), or
    a tile would be no multiple of 128 lanes."""
    if blk_q != blk_k or (window is not None and window % blk_k):
        return None
    ts = blk_q // _TILES_A_SIDE
    return ts if ts and ts % 128 == 0 else None


def _interior(qi, ki, blk_q: int, blk_k: int, window: Optional[int]):
    """Whether no edge crosses block pair (``qi``, ``ki``) of the band:
    every key at or before every query and, under a window, every pair
    inside it (Python or traced integers)."""
    inside = ki * blk_k + blk_k - 1 <= qi * blk_q
    if window is not None:
        inside = inside & (qi * blk_q + blk_q - 1 - ki * blk_k < window)
    return inside


def _by_edges(body, in_band, causal: bool, qi, ki, blk_q: int, blk_k: int,
              window: Optional[int], bd: Optional[int] = None, half: int = 0):
    """Run ``body(edge)`` on the path block pair (``qi``, ``ki``) takes.
    A pair with no visible (query, key) pair runs nothing: with a window the
    walk itself is the band (``in_band``); without one a k block strictly
    above the triangle (its first key after this q block's last query)
    contributes exactly zero. An interior pair runs ``body(None)``: no mask.
    An edge pair runs ``body("diagonal")`` or ``body("far")``, a walk in
    tiles, or ``body("whole")`` where ``_tile`` has none."""
    from jax.experimental import pallas as pl

    if bd is not None:
        return _bd_by_edges(body, in_band, qi, ki, blk_q, blk_k, bd, half)
    if not causal:
        return body(None)
    visible = (in_band if in_band is not None
               else qi * blk_q + (blk_q - 1) >= ki * blk_k)
    interior = visible & _interior(qi, ki, blk_q, blk_k, window)
    pl.when(interior)(functools.partial(body, None))
    if _tile(blk_q, blk_k, window) is None:
        pl.when(visible & ~interior)(functools.partial(body, "whole"))
        return
    pl.when(visible & (ki == qi))(functools.partial(body, "diagonal"))
    if window is not None:
        pl.when(visible & (ki == qi - window // blk_k))(
            functools.partial(body, "far"))


def _walk(edge: Optional[str], by_rows: bool, qi, ki, blk_q: int, blk_k: int,
          window: Optional[int], bd: Optional[int] = None, half: int = 0):
    """How a kernel step covers its block: a list of (slice of the walked
    side, [(slice of the other side, the pairs to keep or None for all)]),
    the walked side being the q rows (``by_rows``: forward and dq, whose
    state is a row's) or the k rows (dk/dv). An interior block is one piece
    with no mask and a whole edge block one piece with the block's; a tiled
    edge block gives every tile of the walked side the crossing tile,
    masked, and the other side's tiles that lie wholly inside the mask
    (before it under the diagonal seen by rows, after it seen by columns;
    the far edge the other way round), in one piece; the tiles beyond the
    edge appear nowhere."""
    whole = slice(None)
    if edge is None:
        return [(whole, [(whole, None)])]
    if edge == "whole" and bd is not None:
        return [(whole, [(whole, _bd_keep_block(qi, ki, blk_q, blk_k, bd,
                                                half))])]
    if edge == "whole":
        return [(whole, [(whole, _keep_causal(qi, ki, blk_q, blk_k,
                                              window))])]
    ts = _tile(blk_q, blk_k, window)
    row = lax.broadcasted_iota(jnp.int32, (ts, ts), 0)
    col = lax.broadcasted_iota(jnp.int32, (ts, ts), 1)
    if bd is not None:
        # a tile holds whole diffusion blocks: a place's block from its
        # place in the tile
        row, col = _bd_block_of(row, bd), _bd_block_of(col, bd)
    # within a crossed tile the diagonal keeps key <= query; the far edge,
    # `window` keys back, keeps the keys strictly after the query's own place
    keep = {"diagonal": lax.ge, "far": lambda r, c: lax.gt(c, r),
            "blocks_upto": lax.ge, "blocks_before": lax.gt,
            "own_block": lax.eq}[edge](row, col)
    before = (edge != "far") == by_rows
    steps = []
    for a in range(_TILES_A_SIDE):
        crossed = slice(a * ts, (a + 1) * ts)
        inside = slice(0, a * ts) if before else slice((a + 1) * ts, blk_q)
        pieces = [(crossed, keep)]
        if inside.stop > inside.start and edge != "own_block":
            pieces.append((inside, None))
        steps.append((crossed, pieces))
    return steps


# ---------------------------------------------------------------------------
# The forward's unit of work inside a block. Down here for the reason above.
# ---------------------------------------------------------------------------
#: q rows a chunk of the forward's online-softmax update. Kernel-alone on a
#: v5e (PERF.md, PR 45) 256 rows were the fastest of 128 / 256 / 512 in five
#: geometries of six (128 at keys of 192 beside values of 128): fewer rows
#: latch a key tile for too few of them, more leave a block too few chunks to
#: stand beside one another. And every chunk is more of a kernel body for
#: Python to trace and lower in every run: chunks of 128 everywhere cost a
#: 16,384-token step of six layers 11 s of its warm set-up.
_ROW_CHUNK = 256


def _row_chunk(n: int) -> int:
    """The rows of a chunk where the forward updates ``n`` q rows; ``n``
    itself (one piece) where they hold fewer than two chunks or no whole
    number of them: small blocks."""
    c = _ROW_CHUNK
    return c if n >= 2 * c and n % c == 0 else n


def _row_chunks(rows: slice, pieces, blk_q: int):
    """``(rows, pieces)`` of a forward step cut into chunks of rows: a list
    of (slice of the q rows, the pieces). A slice with a mask on it (an edge
    block's tile, half a block already, or an edge block kept whole) stays
    one piece."""
    start, stop, _ = rows.indices(blk_q)
    n = stop - start
    c = _row_chunk(n)
    if c == n or any(keep is not None for _, keep in pieces):
        return [(rows, pieces)]
    return [(slice(start + at, start + at + c), pieces)
            for at in range(0, n, c)]


# ---------------------------------------------------------------------------
# The block-diffusion mask (``blockdiff=Bd``). A row of T positions is a clean
# copy of L = T / 2 tokens and, after it, a noised copy of the same tokens,
# both in blocks of Bd tokens (block b holds tokens b * Bd .. b * Bd + Bd - 1).
# A clean query sees the clean keys of its own and of earlier blocks and no
# noised key; a noised query sees the clean keys of strictly earlier blocks
# and the noised keys of its own block, before and after it. Three regions,
# each with an edge of its own kind: ``blocks_upto`` (the causal diagonal
# moved up to the block's end), ``blocks_before`` (moved down to its start)
# and ``own_block`` (a band Bd wide on the noised half's diagonal). Down here
# for the reason ``RESIDUAL_NAMES`` is.
# ---------------------------------------------------------------------------
def _bd_block_of(at, bd: int):
    """The diffusion block of the places ``at`` (none negative) in a half."""
    if bd & (bd - 1) == 0:
        return lax.shift_right_logical(at, jnp.full_like(at, bd.bit_length()
                                                         - 1))
    return lax.div(at, jnp.full_like(at, bd))


def blockdiff_visible(q_pos, k_pos, bd: int, half: int):
    """bool, broadcast of ``q_pos`` against ``k_pos`` (int32 positions in the
    row of ``2 * half``): query sees key under the block-diffusion mask."""
    q_noised, k_noised = q_pos >= half, k_pos >= half
    qb = _bd_block_of(jnp.where(q_noised, q_pos - half, q_pos), bd)
    kb = _bd_block_of(jnp.where(k_noised, k_pos - half, k_pos), bd)
    return jnp.where(k_noised, q_noised & (qb == kb),
                     jnp.where(q_noised, kb < qb, kb <= qb))


def _check_blockdiff(t: int, bd: int, causal: bool, window) -> None:
    if not causal or window is not None:
        raise ValueError("the block-diffusion mask takes causal=True and no "
                         "window")
    if bd < 1 or t % 2 or (t // 2) % bd:
        raise ValueError(f"a row of {t} positions is no clean and noised "
                         f"copy of whole blocks of {bd} tokens")


def _bd_compact(half: int, blk_q: int, blk_k: int, bd: int) -> bool:
    """Whether the kernels walk only the visible block pairs and sort them by
    block index alone: square blocks, each half a whole number of them, and
    no diffusion block across a tile's border (a block's, where it has no
    tiles). Elsewhere every pair is looked at (``_bd_visible``) and a visible
    one computed whole and masked."""
    unit = _tile(blk_q, blk_k, None) or blk_q
    return blk_q == blk_k and half % blk_q == 0 and unit % bd == 0


def _bd_k_step(qi, j, blk_q: int, blk_k: int, bd: int, half: int):
    """Step ``j`` of q block ``qi``'s walk (``_k_step``). Compact: a clean q
    block walks the clean k blocks up to its own; a noised one the clean k
    blocks up to its place in its half, then its own; the steps left over
    stay on the last block (fetched once) and do nothing."""
    if not _bd_compact(half, blk_q, blk_k, bd):
        return j, None
    nh = half // blk_k
    noised = qi >= nh
    last_clean = jnp.where(noised, qi - nh, qi)
    ki = jnp.where(noised & (j > last_clean), qi, jnp.minimum(j, last_clean))
    return ki, j <= last_clean + noised.astype(jnp.int32)


def _bd_sort(qi, ki, nh: int):
    """Compact geometry: whether block pair (``qi``, ``ki``) is interior, or
    cut by ``blocks_upto``, ``blocks_before``, ``own_block`` (at most one
    holds; none: no visible pair). Python or traced integers."""
    clean_q, noised_q, place = qi < nh, qi >= nh, qi - nh
    interior = (ki < nh) & ((clean_q & (ki < qi)) | (noised_q & (ki < place)))
    return (interior, clean_q & (ki == qi), noised_q & (ki == place),
            noised_q & (ki == qi))


def _bd_visible(qi, ki, blk_q: int, blk_k: int, bd: int, half: int):
    """Any geometry: whether block pair (``qi``, ``ki``) may hold a visible
    pair, from the blocks' first and last places in each half (Python or
    traced integers)."""
    traced = not (isinstance(qi, int) and isinstance(ki, int))
    most, least = (jnp.maximum, jnp.minimum) if traced else (max, min)
    q0, q1 = qi * blk_q, qi * blk_q + blk_q - 1
    k0, k1 = ki * blk_k, ki * blk_k + blk_k - 1
    clean_q, noised_q, clean_k, noised_k = (q0 < half, q1 >= half, k0 < half,
                                            k1 >= half)
    # the last clean query's block, the noised queries' first and last, the
    # first clean key's, the noised keys' first and last
    cq = least(q1, half - 1) // bd
    nq0, nq1 = (most(q0, half) - half) // bd, most(q1 - half, 0) // bd
    ck = k0 // bd
    nk0, nk1 = (most(k0, half) - half) // bd, most(k1 - half, 0) // bd
    return ((clean_q & clean_k & (ck <= cq))
            | (noised_q & clean_k & (ck < nq1))
            | (noised_q & noised_k & (nk0 <= nq1) & (nk1 >= nq0)))


def _bd_keep_block(qi, ki, blk_q: int, blk_k: int, bd: int, half: int):
    """[blk_q, blk_k] bool: the pairs of a block the mask keeps."""
    q_pos = qi * blk_q + lax.broadcasted_iota(jnp.int32, (blk_q, blk_k), 0)
    k_pos = ki * blk_k + lax.broadcasted_iota(jnp.int32, (blk_q, blk_k), 1)
    return blockdiff_visible(q_pos, k_pos, bd, half)


_BD_EDGES = ("blocks_upto", "blocks_before", "own_block")


def _bd_by_edges(body, in_band, qi, ki, blk_q: int, blk_k: int, bd: int,
                 half: int):
    """``_by_edges`` under the block-diffusion mask. ``in_band``: whether the
    walk's step works at all (None: every pair is a step, as in the dK/dV
    kernel, and the pair's place decides)."""
    from jax.experimental import pallas as pl

    if not _bd_compact(half, blk_q, blk_k, bd):
        pl.when(_bd_visible(qi, ki, blk_q, blk_k, bd, half))(
            functools.partial(body, "whole"))
        return
    interior, *edges = _bd_sort(qi, ki, half // blk_k)
    works = True if in_band is None else in_band
    pl.when(works & interior)(functools.partial(body, None))
    if _tile(blk_q, blk_k, None) is None:
        pl.when(works & (edges[0] | edges[1] | edges[2]))(
            functools.partial(body, "whole"))
        return
    for edge, crossed in zip(_BD_EDGES, edges):
        pl.when(works & crossed)(functools.partial(body, edge))


def _bd_count_blocks(kernels: int, heads: int, t: int, blk_q: int,
                     blk_k: int, bd: int) -> None:
    """``_count_blocks`` under the block-diffusion mask, pair by pair as
    ``_bd_by_edges`` sorts them."""
    half = t // 2
    compact = _bd_compact(half, blk_q, blk_k, bd)
    tiled = compact and _tile(blk_q, blk_k, None) is not None
    a_block = _TILES_A_SIDE ** 2
    off_edge = (a_block - _TILES_A_SIDE) // 2
    tiles = dict.fromkeys(("unmasked", "masked", "skipped", "whole_edge"), 0)
    computed = 0
    for qi in range(t // blk_q):
        for ki in range(t // blk_k):
            if not compact:
                interior, edges = False, [_bd_visible(qi, ki, blk_q, blk_k,
                                                      bd, half)]
            else:
                interior, *edges = _bd_sort(qi, ki, half // blk_k)
            if not (interior or any(edges)):
                continue
            computed += 1
            if interior:
                tiles["unmasked"] += a_block
            elif not tiled:
                tiles["whole_edge"] += a_block
            else:       # a triangle of tiles, or the diagonal's tiles alone
                own = edges[2]
                tiles["masked"] += _TILES_A_SIDE
                tiles["unmasked"] += 0 if own else off_edge
                tiles["skipped"] += (a_block - _TILES_A_SIDE) if own \
                    else off_edge
    _count_fates(kernels * heads, {
        "computed": computed,
        "skipped_blockdiff": (t // blk_q) * (t // blk_k) - computed}, tiles)


def _count_mask(causal: bool, window, blockdiff) -> None:
    """``flash_mask_total``: one more traced call of the op, by its mask."""
    from raydp_tpu import metrics as rdt_metrics

    rdt_metrics.inc("flash_mask_total", label=(
        "blockdiff" if blockdiff is not None else "window"
        if window is not None else "causal" if causal else "none"))


# What the backward kernels read beside ``RESIDUAL_NAMES``: the forward
# kernel's three inputs as it takes them (``[B * heads, T, d]``: after a head
# norm, RoPE and the transposes into that layout), by the names a
# ``jax.checkpoint`` policy can keep them under, so that a recomputed layer
# forms none of them again. Only the residuals carry a name, and only while
# the layer being traced keeps it (``kept_names``, which the model sets round
# such a layer: ``transformer._keeping``): a ``name`` equation renumbers the
# functions of a lowered module, so a call that keeps none binds none and
# traces the program it traced before these names were. On q, k and v as the
# caller hands them over (``[B, T, heads, d]``) the transposes ran again in
# the backward pass: 1.9-3.5 ms a step slower in three cells (PERF.md, PR 62).
# At the file's end for the reason ``RESIDUAL_NAMES`` is.
import contextvars  # noqa: E402  (with its section: no line above moves)

INPUT_NAMES = ("rdt_flash_q", "rdt_flash_k", "rdt_flash_v")
kept_names = contextvars.ContextVar("rdt_kept_names", default=frozenset())


def _named_inputs(q3, k3, v3):
    from jax.ad_checkpoint import checkpoint_name

    kept = kept_names.get()
    return tuple(checkpoint_name(x, name) if name in kept else x
                 for x, name in zip((q3, k3, v3), INPUT_NAMES))
