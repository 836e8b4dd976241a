"""The chunked scan of a state-space layer (Mamba-2's "state-space duality"
form), forward and backward, as two Pallas kernels with a ``custom_vjp``.

A head ``h`` with state ``S [N, P]`` (``N`` the state size, ``P`` the head's
width) runs, position by position::

    S_t = exp(dt_t A) S_{t-1} + dt_t B_t (x) x_t          A < 0, dt_t > 0
    y_t = C_t . S_t + D x_t

``B_t`` and ``C_t`` (``[N]``) are shared by the ``H / G`` heads of a group.
Cut into chunks of ``Q`` positions with ``l_t = sum_{s <= t} dt_s A`` inside
a chunk (``l <= 0``) the same recurrence is three matrix products a chunk::

    y_t   = sum_{s <= t} exp(l_t - l_s) (C_t . B_s) dt_s x_s
            + exp(l_t) C_t . S_prev + D x_t
    S_end = exp(l_Q) S_prev + sum_s exp(l_Q - l_s) dt_s B_s (x) x_s

and only ``S`` passes from a chunk to the next. Written in ``jax.numpy`` alone
that lays a ``[H, T / Q, Q, Q]`` float32 decay matrix out in HBM a layer (537
MB at 64 heads and 16,384 positions); the kernels keep it in VMEM a head at a
time.

**Forward kernel ``rdt_ssd_fwd``.** Grid (sequence, group, chunk), the chunk
axis last and walked in order; a step loads one chunk of one group: ``x``
``[Q, heads x P]`` (the group's heads side by side on the lanes, as the
projection lays them out: nothing is transposed in HBM), ``B`` and ``C``
``[Q, N]``, and the group's ``l`` and ``dt`` both as rows ``[2 hg, Q]`` and as
columns ``[Q, 2 hg]``. The group's state ``[N, heads x P]`` lies in VMEM in
float32 through the walk. A step does a group's work once a group and only a
head's own a head:

- *once a group*: ``C B^T``; from the columns, the factors of all its heads
  at once ``[Q, hg]`` (``exp(l_Q - l_s) dt_s``); the state's update as ONE
  product over all the heads.
- *once a piece* of the group's lanes (a 128-lane tile: two heads of 64;
  :func:`_tiles`), every lane of the piece at once: ``C S_prev``; the output
  ``y = within + exp(l_t) C S_prev + D x`` and ``x`` weighed for the state's
  update, each stored once, whole tiles. A ``[Q, hg]`` factor reaches its
  heads' lanes exactly (:func:`_over_lanes`).
- *a head*: its masked decay ``exp(l_t - l_s)`` ``[Q, Q]`` (float32, of the
  difference), the intra-chunk product that takes it (against the piece's
  ``x``: the head keeps its own lanes of the result, so no operand is cut
  inside a tile) and ``exp(l_t)`` on the piece's lanes, from the same lane
  broadcast of ``l_t``.

Differentiated, it also writes the state each chunk STARTS from (float32:
``T / Q`` of them a head), which the backward walk reads.

**Backward kernel ``rdt_ssd_bwd``.** The same grid with the chunk axis walked
from the last chunk to the first and the state's gradient carried in VMEM.
One walk gives ``dx``, ``dB``, ``dC`` (a group's heads summed in float32),
``dD`` and, for ``dt`` and ``A``, two per-position sums: with ``E[t, s] =
dy_t . x_s`` and ``W[t, s] = (C_t . B_s) exp(l_t - l_s)``, the explicit
``d dt_s = sum_t W E + exp(l_Q - l_s) <dS, B_s (x) x_s>`` and ``dl_t`` (what
``l_t`` moves: its row of ``W dt E``, minus its column, the carried state's
term and the next state's). The same three levels:

- *once a group*: ``C B^T``, the factors ``[Q, hg]``; at the end the two
  per-position sums of all its heads put together ``[Q, 2 hg]`` and written
  once, the heads' ``[Q, Q]`` column sums collected ``[hg, Q]`` and written
  once, ``dB``, ``dC`` and the state's gradient as products over all heads.
- *once a piece*: ``C S_prev`` and ``B dS_end``; ``dx``, ``dy`` decayed to
  the chunk's start and ``x`` weighed to its end, ``dD``'s sum, each stored
  once, whole tiles; a head's sums over its own lanes (``dy . C S_prev``,
  ``B dS . x``, ``<dS, S>``) land in its column of ``[Q, hg]``
  (:func:`_head_sums`).
- *a head*: its decay, ``E`` (``dy`` zeroed off the head's lanes against the
  piece's ``x``), ``Z = decay dt E`` summed over heads, the two sums over
  ``[Q, Q]`` (``sum_t W E`` and ``sum_s (C B^T) Z``) and ``(W dt)^T dy``.

``l`` is a cumulative sum of ``dt A``, so outside the kernel ``d(dt A)`` is
``dl``'s reverse cumulative sum in the chunk, ``d dt`` adds ``A`` times it and
``dA`` sums ``dt`` times it: three small ``[B, T, H]`` float32 passes.

Decays (``l``, every ``exp``) are float32, and a decay between two
positions is ``exp`` of the DIFFERENCE ``l_t - l_s`` (late in training a
chunk's ``l`` reaches -200: ``exp(-l_s)`` alone is no float32); the products'
operands are the activations' dtype and accumulate in float32; a state is
rounded to the activations' dtype only as a product's operand.

The kernels take ``T`` a multiple of the chunk (and lane-sized blocks: the
chunk and the state multiples of 128, a group's heads x width too). Anything
else, and any platform but a TPU, takes :func:`_ssd_jnp`: a ``lax.scan`` over
the chunks in ``jax.numpy``, differentiated by autodiff, chosen when the
program is lowered as :mod:`raydp_tpu.ops.flash_attention` chooses, so a step
compiled ahead of time for a described TPU holds the kernels. ``interpret``
runs the kernels through the Pallas interpreter (tests).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax

from raydp_tpu.ops.flash_attention import _by_platform

KERNEL_NAMES = ("rdt_ssd_fwd", "rdt_ssd_bwd")
_MASKED = -1e30         # exp() of it is 0: a pair the causal mask drops


def kernel_ineligible(t: int, chunk: int, heads_a_group: int, p: int,
                      n: int) -> Optional[str]:
    """Why the compiled kernels cannot take a scan of ``t`` positions in
    chunks of ``chunk`` with groups of ``heads_a_group`` heads of width ``p``
    and a state of ``n`` (None where they can): every block's last dimension
    lies on the 128 lanes."""
    if t % chunk:
        return f"{t} positions are no whole number of chunks of {chunk}"
    if chunk % 128 or n % 128 or (heads_a_group * p) % 128:
        return (f"chunk {chunk}, state {n} and a group's {heads_a_group} x "
                f"{p} channels have to be multiples of 128")
    return None


def _in_chunks(a, chunk: int):
    """``[B, T, ...]`` -> ``[B, T / chunk, chunk, ...]``."""
    return a.reshape(a.shape[0], a.shape[1] // chunk, chunk, *a.shape[2:])


def _decays(dt, a, chunk: int):
    """``l [B, T, H]`` float32: the cumulative sum of ``dt A`` inside each
    chunk."""
    steps = dt.astype(jnp.float32) * a.astype(jnp.float32)
    return jnp.cumsum(_in_chunks(steps, chunk), axis=2).reshape(dt.shape)


# ---------------------------------------------------------------------------
# jax.numpy path
# ---------------------------------------------------------------------------
def _ssd_jnp(x, dt, a, b, c, d, chunk: int):
    """(``y [B, T, H, P]``, the states the chunks start from ``[B, T / Q, G,
    N, H / G x P]`` float32) by a ``lax.scan`` over the chunks; any ``T`` (a
    last chunk is filled with ``dt = 0``, which moves no state)."""
    bsz, t, h, p = x.shape
    g, n = b.shape[2:]
    hg = h // g
    pad = (-t) % chunk
    if pad:
        x, dt, b, c = (jnp.pad(v, ((0, 0), (0, pad)) + ((0, 0),) * (v.ndim - 2))
                       for v in (x, dt, b, c))
    f32, dtype = jnp.float32, x.dtype
    l = _decays(dt, a, chunk)
    cut = lambda v: _in_chunks(v, chunk).swapaxes(0, 1)  # noqa: E731
    xs = cut(x.reshape(bsz, t + pad, g, hg, p))
    dts, ls = (cut(v.astype(f32).reshape(bsz, t + pad, g, hg))
               for v in (dt, l))
    visible = jnp.tril(jnp.ones((chunk, chunk), bool))[None, :, :, None, None]
    d_heads = d.astype(f32).reshape(g, hg, 1)

    def body(state, of_chunk):
        xc, dtc, lc, bc, cc = of_chunk
        cb = jnp.einsum("btgn,bsgn->btsg", cc, bc, preferred_element_type=f32)
        decay = jnp.exp(jnp.where(visible, lc[:, :, None] - lc[:, None],
                                  _MASKED))                 # [b, t, s, g, hg]
        w = (cb[..., None] * decay * dtc[:, None]).astype(dtype)
        y = jnp.einsum("btsgh,bsghp->btghp", w, xc, preferred_element_type=f32)
        y += jnp.exp(lc)[..., None] * jnp.einsum(
            "btgn,bghnp->btghp", cc, state.astype(dtype),
            preferred_element_type=f32)
        y += d_heads * xc.astype(f32)
        last = lc[:, -1]                                    # [b, g, hg]
        weighed = (xc.astype(f32) * (jnp.exp(last[:, None] - lc)
                                     * dtc)[..., None]).astype(dtype)
        new = jnp.exp(last)[..., None, None] * state + jnp.einsum(
            "bsgn,bsghp->bghnp", bc, weighed, preferred_element_type=f32)
        return new, (y.astype(dtype), state)

    # zeros that vary over a mesh as x does (inside a shard_map a carry's
    # type has to say so from the start)
    first = jnp.zeros((n, 1), f32) * xs[0][:, 0, :, :, None].astype(f32)
    _, (ys, states) = lax.scan(body, first, (xs, dts, ls, cut(b), cut(c)))
    y = ys.swapaxes(0, 1).reshape(bsz, t + pad, h, p)[:, :t]
    # [chunks, b, g, hg, n, p] -> [b, chunks, g, n, hg x p]
    states = states.transpose(1, 0, 2, 4, 3, 5).reshape(
        bsz, -1, g, n, hg * p)
    return y, states


# ---------------------------------------------------------------------------
# Pallas kernels
# ---------------------------------------------------------------------------
def _nt(a, b):
    """``a b^T`` with float32 accumulation."""
    return lax.dot_general(a, b, (((1,), (1,)), ((), ())),
                           preferred_element_type=jnp.float32)


def _tn(a, b):
    """``a^T b`` with float32 accumulation."""
    return lax.dot_general(a, b, (((0,), (0,)), ((), ())),
                           preferred_element_type=jnp.float32)


def _nn(a, b):
    return lax.dot_general(a, b, (((1,), (0,)), ((), ())),
                           preferred_element_type=jnp.float32)


def _visible(chunk: int):
    """``[Q, Q]``: position ``t`` (rows) sees position ``s`` (columns)."""
    return (lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
            >= lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1))


def _tiles(hg: int, p: int):
    """A group's ``hg x P`` lanes in pieces of whole heads, ``[(heads,
    lanes)]``: 128-lane tiles where the heads fill them (two heads of 64, a
    head of 128 or 256), else the whole width in one piece (the tests'
    widths). What a group does once it does a piece at a time, every lane
    of the piece at once: a piece's float32 arrays are 16 vregs, the whole
    width's 64 (all the vregs there are)."""
    heads = max(1, 128 // p)
    if hg % heads or (heads * p) % 128:
        heads = hg
    return [(range(first, first + heads),
             slice(first * p, (first + heads) * p))
            for first in range(0, hg, heads)]


def _own_lanes(heads, p: int):
    """``[1, piece]`` a head: which of a piece's lanes are its own (None:
    all of them)."""
    if len(heads) == 1:
        return [None]
    lane = lax.broadcasted_iota(jnp.int32, (1, len(heads) * p), 1)
    return [(lane >= k * p) & (lane < (k + 1) * p) for k in range(len(heads))]


def _over_lanes(per_head, heads, p: int):
    """``[R, hg]`` float32 -> ``[R, piece]``: each of the piece's heads'
    numbers on its own ``P`` lanes, exactly, on the MXU: the numbers as
    three bfloat16 pieces whose sum they are (8 + 8 + 8 bits), each piece's
    one-pass product with the 0/1 ``[hg, piece]`` matrix (one term a sum, so
    the product is the piece), the three added in float32. (A lane broadcast
    a head and a select is as exact and books the XLU, which the backward
    needs for its lane sums; ``Precision.HIGHEST`` is six passes:
    ``benchmarks/ssd_scan_sweep.py`` times the three.)"""
    f32, bf16 = jnp.float32, jnp.bfloat16
    hg, width = per_head.shape[1], len(heads) * p
    lane = lax.broadcasted_iota(jnp.int32, (hg, width), 1)
    first = (lax.broadcasted_iota(jnp.int32, (hg, width), 0) - heads[0]) * p
    spread = ((lane >= first) & (lane < first + p)).astype(bf16)
    high = per_head.astype(bf16)
    rest = per_head - high.astype(f32)
    middle = rest.astype(bf16)
    low = (rest - middle.astype(f32)).astype(bf16)
    return (_nn(high, spread) + _nn(middle, spread)) + _nn(low, spread)


def _head_sums(piece, heads, p: int, into):
    """``[R, piece]`` float32 summed over each head's own lanes, into that
    head's column of ``into [R, hg]``."""
    column = lax.broadcasted_iota(jnp.int32, (1, into.shape[1]), 1)
    for head, mine in zip(heads, _own_lanes(heads, p)):
        kept = piece if mine is None else jnp.where(mine, piece, 0.0)
        into = jnp.where(column == head,
                         jnp.sum(kept, axis=1, keepdims=True), into)
    return into


def _of_head(rows, l, head: int, hg: int, visible, width: int):
    """What differs by head: its masked decay ``exp(l_t - l_s)`` ``[Q, Q]``
    (float32, of the difference), its ``dt`` as a row ``[1, Q]``, and the
    decay to ``t`` from the chunk's start ``exp(l_t)`` on ``width`` lanes
    (from the lane broadcast of ``l_t`` the first already makes, where the
    two widths are one)."""
    l_row, dt_row = rows[head:head + 1], rows[hg + head:hg + head + 1]
    l_col = l[:, head:head + 1]
    return (jnp.exp(jnp.where(visible, l_col - l_row, _MASKED)), dt_row,
            jnp.exp(jnp.broadcast_to(l_col, (l.shape[0], width))))


def _fwd_kernel(x_ref, b_ref, c_ref, rows_ref, cols_ref, d_ref, ends_ref,
                y_ref, *rest,
                hg: int, p: int, chunk: int, emit_states: bool):
    from jax.experimental import pallas as pl

    if emit_states:
        states_ref, state, weighed = rest
    else:
        state, weighed = rest

    @pl.when(pl.program_id(2) == 0)
    def _start():
        state[:] = jnp.zeros_like(state)

    if emit_states:
        states_ref[0, 0, 0] = state[:]      # what this chunk starts from
    bm, cm = b_ref[0], c_ref[0]
    rows, cols = rows_ref[0, 0], cols_ref[0, 0]
    dtype, f32 = x_ref.dtype, jnp.float32
    # once a group: its heads' per-position factors [Q, hg], and C B^T
    l, dt = cols[:, :hg], cols[:, hg:]
    to_end_dt = jnp.exp(l[chunk - 1:] - l) * dt
    cb = _nt(cm, bm)                                        # [t, s] float32
    visible = _visible(chunk)
    for heads, lanes in _tiles(hg, p):
        x = x_ref[0, :, lanes]
        xf = x.astype(f32)
        carried = _nn(cm, state[:, lanes].astype(dtype))    # C S_prev
        within = to_t = None
        for head, mine in zip(heads, _own_lanes(heads, p)):
            # a head: its decay, and the one product that takes it
            decay, dt_row, reached = _of_head(rows, l, head, hg, visible,
                                              xf.shape[1])
            y = _nn((cb * decay * dt_row).astype(dtype), x)
            within = y if within is None else jnp.where(mine, y, within)
            to_t = reached if to_t is None else jnp.where(mine, reached, to_t)
        # once a piece, all its lanes at once
        y_ref[0, :, lanes] = (within + to_t * carried
                              + d_ref[:, lanes] * xf).astype(dtype)
        weighed[:, lanes] = (xf * _over_lanes(to_end_dt, heads, p)
                             ).astype(dtype)
    state[:] = state[:] * ends_ref[0, 0, 0] + _tn(bm, weighed[:])


def _bwd_kernel(x_ref, b_ref, c_ref, rows_ref, cols_ref, d_ref, ends_ref,
                states_ref, dy_ref, dx_ref, db_ref, dc_ref, by_row_ref, by_col_ref,
                dd_ref, d_state, dy_decayed, weighed,
                *, hg: int, p: int, chunk: int):
    from jax.experimental import pallas as pl

    @pl.when(pl.program_id(2) == 0)
    def _start():
        d_state[:] = jnp.zeros_like(d_state)
        dd_ref[...] = jnp.zeros_like(dd_ref)

    bm, cm = b_ref[0], c_ref[0]
    rows, cols = rows_ref[0, 0], cols_ref[0, 0]
    dtype, f32 = x_ref.dtype, jnp.float32
    # once a group: its heads' per-position factors [Q, hg], and C B^T
    l, dt = cols[:, :hg], cols[:, hg:]
    l_last = l[chunk - 1:]
    to_end = jnp.exp(l_last - l)
    to_end_dt = to_end * dt
    cb = _nt(cm, bm)                                        # [t, s]
    visible = _visible(chunk)
    column = lax.broadcasted_iota(jnp.int32, (1, hg), 1)
    row = lax.broadcasted_iota(jnp.int32, (hg, 1), 0)
    z_sum = jnp.zeros((chunk, chunk), f32)
    by_row = jnp.zeros((hg, chunk), f32)
    moved, carried_sums, q, kept = (
        jnp.zeros((chunk, hg), f32), jnp.zeros((chunk, hg), f32),
        jnp.zeros((chunk, hg), f32), jnp.zeros((1, hg), f32))
    for heads, lanes in _tiles(hg, p):
        x, dy = x_ref[0, :, lanes], dy_ref[0, :, lanes]
        xf, dyf = x.astype(f32), dy.astype(f32)
        s0, ds1 = states_ref[0, 0, 0, :, lanes], d_state[:, lanes]
        carried = _nn(cm, s0.astype(dtype))                 # C S_prev
        from_next = _nn(bm, ds1.astype(dtype))              # B dS_end
        within = to_t = None
        for head, mine in zip(heads, _own_lanes(heads, p)):
            # a head: its decay, the two products and the two sums over
            # [Q, Q] that take it
            decay, dt_row, reached = _of_head(rows, l, head, hg, visible,
                                              xf.shape[1])
            dyh = dy if mine is None else jnp.where(mine, dyf, 0.0).astype(
                dtype)                                      # zero off its lanes
            e = _nt(dyh, x)                                 # dy_t . x_s
            reach = decay * dt_row
            z = reach * e
            z_sum += z
            by_row = jnp.where(row == head, jnp.sum(
                cb * decay * e, axis=0, keepdims=True), by_row)
            moved = jnp.where(column == head, jnp.sum(
                cb * z, axis=1, keepdims=True), moved)
            dx = _tn((cb * reach).astype(dtype), dyh)       # 0 off its lanes
            within = dx if within is None else within + dx
            to_t = reached if to_t is None else jnp.where(mine, reached, to_t)
        # once a piece, all its lanes at once
        to_end_dt_over = _over_lanes(to_end_dt, heads, p)
        dx_ref[0, :, lanes] = (within + to_end_dt_over * from_next
                               + d_ref[:, lanes] * dyf).astype(dtype)
        dy_decayed[:, lanes] = (dyf * to_t).astype(dtype)
        weighed[:, lanes] = (xf * to_end_dt_over).astype(dtype)
        dd_ref[0, :, lanes] += jnp.sum(dyf * xf, axis=0, keepdims=True)
        carried_sums = _head_sums(dyf * carried, heads, p, carried_sums)
        q = _head_sums(from_next * xf, heads, p, q)
        kept = _head_sums(jnp.sum(ds1 * s0, axis=0, keepdims=True), heads, p,
                          kept)
    # once a group: the two per-position sums of all its heads, [Q, hg] each
    q *= to_end
    tail = jnp.sum(dt * q, axis=0, keepdims=True) + jnp.exp(l_last) * kept
    last_row = lax.broadcasted_iota(jnp.int32, (chunk, 1), 0) == chunk - 1
    moved += jnp.exp(l) * carried_sums + jnp.where(last_row, tail, 0.0)
    by_row_ref[0, 0] = by_row
    by_col_ref[0, 0] = jnp.concatenate([moved, q], axis=1)
    s0c, ds1 = states_ref[0, 0, 0].astype(dtype), d_state[:]
    zc = z_sum.astype(dtype)
    dc_ref[0] = (_nn(zc, bm) + _nt(dy_decayed[:], s0c)).astype(dtype)
    db_ref[0] = (_tn(zc, cm) + _nt(weighed[:], ds1.astype(dtype))).astype(dtype)
    d_state[:] = ds1 * ends_ref[0, 0, 0] + _tn(cm, dy_decayed[:])


def _packed(dt, l, g: int, chunk: int, p: int):
    """A group's ``l`` and ``dt`` side by side, both ways up: rows ``[B, G,
    2 H/G, T]`` (positions on the lanes) and columns ``[B, G, T, 2 H/G]``;
    and each chunk's whole decay ``exp(l_Q)`` laid over its head's ``P``
    lanes, ``[B, G, T / Q, 1, H/G x P]`` (what a state is scaled by from a
    chunk to the next)."""
    bsz, t, h = dt.shape
    ends = jnp.exp(_in_chunks(l, chunk)[:, :, -1])          # [B, chunks, H]
    ends = jnp.repeat(ends.reshape(bsz, -1, g, h // g), p, axis=-1)
    ends = ends.transpose(0, 2, 1, 3)[:, :, :, None]
    both = jnp.concatenate([l.reshape(bsz, t, g, h // g),
                            dt.astype(jnp.float32).reshape(bsz, t, g, h // g)],
                           axis=-1)                         # [B, T, G, 2 hg]
    return both.transpose(0, 2, 3, 1), both.transpose(0, 2, 1, 3), ends


def _specs(hg, p, n, chunk, chunk_of):
    """The block specs the two kernels share, for a grid (sequence, group,
    step) whose step ``j`` walks chunk ``chunk_of(j)``: x-shaped, B-shaped,
    rows, columns, D, the chunks' decays, the states."""
    from jax.experimental import pallas as pl

    wide = hg * p
    return (
        pl.BlockSpec((1, chunk, wide), lambda i, k, j: (i, chunk_of(j), k)),
        pl.BlockSpec((1, chunk, n), lambda i, k, j: (i, chunk_of(j), k)),
        pl.BlockSpec((1, 1, 2 * hg, chunk),
                     lambda i, k, j: (i, k, 0, chunk_of(j))),
        pl.BlockSpec((1, 1, chunk, 2 * hg),
                     lambda i, k, j: (i, k, chunk_of(j), 0)),
        pl.BlockSpec((1, wide), lambda i, k, j: (0, k)),
        pl.BlockSpec((1, 1, 1, 1, wide),
                     lambda i, k, j: (i, k, chunk_of(j), 0, 0)),
        pl.BlockSpec((1, 1, 1, n, wide),
                     lambda i, k, j: (i, chunk_of(j), k, 0, 0)))


def _count_chunks(pass_: str, chunks: int) -> None:
    from raydp_tpu import metrics as rdt_metrics

    rdt_metrics.inc("ssd_chunks_total", chunks, pass_)


def _fwd_pallas(x, dt, a, b, c, d, *, chunk: int, interpret: bool,
                emit_states: bool):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    bsz, t, h, p = x.shape
    g, n = b.shape[2:]
    hg, chunks = h // g, t // chunk
    wide = hg * p
    rows, cols, ends = _packed(dt, _decays(dt, a, chunk), g, chunk, p)
    x_spec, b_spec, row_spec, col_spec, d_spec, end_spec, state_spec = _specs(
        hg, p, n, chunk, lambda j: j)
    _count_chunks("forward", bsz * g * chunks)
    vma = jax.typeof(x).vma     # inside a shard_map the outputs vary as x does
    out_specs, out_shape = [x_spec], [jax.ShapeDtypeStruct(
        (bsz, t, h * p), x.dtype, vma=vma)]
    if emit_states:
        out_specs.append(state_spec)
        out_shape.append(jax.ShapeDtypeStruct(
            (bsz, chunks, g, n, wide), jnp.float32, vma=vma))
    out = pl.pallas_call(
        functools.partial(_fwd_kernel, hg=hg, p=p, chunk=chunk,
                          emit_states=emit_states),
        grid=(bsz, g, chunks),
        in_specs=[x_spec, b_spec, b_spec, row_spec, col_spec, d_spec,
                  end_spec],
        out_specs=out_specs, out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM((n, wide), jnp.float32),  # the state
                        pltpu.VMEM((chunk, wide), x.dtype)],
        # sequences and groups are independent; the chunks carry the state
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret, name=KERNEL_NAMES[0],
    )(x.reshape(bsz, t, h * p), b.reshape(bsz, t, g * n),
      c.reshape(bsz, t, g * n), rows, cols,
      jnp.repeat(d.astype(jnp.float32), p)[None], ends)
    y = out[0].reshape(x.shape)
    return (y, out[1]) if emit_states else (y, None)


def _bwd_pallas(x, dt, a, b, c, d, states, dy, *, chunk: int,
                interpret: bool):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    bsz, t, h, p = x.shape
    g, n = b.shape[2:]
    hg, chunks = h // g, t // chunk
    wide, f32 = hg * p, jnp.float32
    rows, cols, ends = _packed(dt, _decays(dt, a, chunk), g, chunk, p)
    x_spec, b_spec, row_spec, col_spec, d_spec, end_spec, state_spec = _specs(
        hg, p, n, chunk, lambda j: chunks - 1 - j)
    from_row_spec = pl.BlockSpec(
        (1, 1, hg, chunk), lambda i, k, j: (i, k, 0, chunks - 1 - j))
    _count_chunks("backward", bsz * g * chunks)
    dx, db, dc, by_row, by_col, dd = pl.pallas_call(
        functools.partial(_bwd_kernel, hg=hg, p=p, chunk=chunk),
        grid=(bsz, g, chunks),
        in_specs=[x_spec, b_spec, b_spec, row_spec, col_spec, d_spec,
                  end_spec, state_spec, x_spec],
        out_specs=[x_spec, b_spec, b_spec, from_row_spec, col_spec,
                   # a sequence's sum over its chunks: the block stays put
                   pl.BlockSpec((1, 1, wide), lambda i, k, j: (i, 0, k))],
        out_shape=[jax.ShapeDtypeStruct(shape, dtype, vma=jax.typeof(x).vma)
                   for shape, dtype in (
                       ((bsz, t, h * p), x.dtype), ((bsz, t, g * n), b.dtype),
                       ((bsz, t, g * n), c.dtype), ((bsz, g, hg, t), f32),
                       ((bsz, g, t, 2 * hg), f32), ((bsz, 1, h * p), f32))],
        scratch_shapes=[pltpu.VMEM((n, wide), f32),     # the state's gradient
                        pltpu.VMEM((chunk, wide), x.dtype),
                        pltpu.VMEM((chunk, wide), x.dtype)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret, name=KERNEL_NAMES[1],
    )(x.reshape(bsz, t, h * p), b.reshape(bsz, t, g * n),
      c.reshape(bsz, t, g * n), rows, cols,
      jnp.repeat(d.astype(f32), p)[None], ends, states,
      dy.reshape(bsz, t, h * p))
    # per position and head: what the explicit dt moves, and what l moves
    to_heads = lambda v: v.transpose(0, 2, 1, 3).reshape(bsz, t, h)  # noqa: E731
    explicit = by_row.transpose(0, 3, 1, 2).reshape(bsz, t, h) + to_heads(
        by_col[..., hg:])
    dtf, af = dt.astype(f32), a.astype(f32)
    dl = to_heads(by_col[..., :hg]) - dtf * explicit
    # l is dt A summed up to a position of its chunk: a step's gradient is
    # the sum of dl from it to the chunk's end
    steps = jnp.flip(jnp.cumsum(jnp.flip(_in_chunks(dl, chunk), 2), axis=2),
                     2).reshape(bsz, t, h)
    return (dx.reshape(x.shape), (explicit + steps * af).astype(dt.dtype),
            jnp.sum(steps * dtf, axis=(0, 1)).astype(a.dtype),
            db.reshape(b.shape), dc.reshape(c.shape),
            dd.reshape(bsz, h, p).sum(axis=(0, 2)).astype(d.dtype))


# ---------------------------------------------------------------------------
# The op
# ---------------------------------------------------------------------------
def _use_pallas(x, b, chunk: int, interpret: bool) -> bool:
    """Can the kernels take this call? Interpreted: whenever the chunks
    divide the sequence. Compiled: whenever :func:`kernel_ineligible` says
    nothing; whether they then *run* is decided when the program is lowered
    (for a TPU they do, elsewhere the jnp path)."""
    t, h, p = x.shape[1:]
    g, n = b.shape[2:]
    if interpret:
        return t % chunk == 0
    return kernel_ineligible(t, chunk, h // g, p, n) is None


@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7))
def _ssd(x, dt, a, b, c, d, chunk, interpret):
    jnp_fn = lambda *args: _ssd_jnp(*args, chunk)[0]  # noqa: E731
    if not _use_pallas(x, b, chunk, interpret):
        return jnp_fn(x, dt, a, b, c, d)
    return _by_platform(
        lambda *args: _fwd_pallas(*args, chunk=chunk, interpret=interpret,
                                  emit_states=False)[0],
        jnp_fn, interpret, x, dt, a, b, c, d)


def _ssd_fwd(x, dt, a, b, c, d, chunk, interpret):
    inputs = (x, dt, a, b, c, d)
    if not _use_pallas(x, b, chunk, interpret):
        y, states = _ssd_jnp(*inputs, chunk)
    else:
        y, states = _by_platform(
            functools.partial(_fwd_pallas, chunk=chunk, interpret=interpret,
                              emit_states=True),
            functools.partial(_ssd_jnp, chunk=chunk), interpret, *inputs)
    return y, (inputs, states)


def _ssd_bwd(chunk, interpret, residuals, g):
    inputs, states = residuals

    def jnp_fn(x, dt, a, b, c, d, states, g):
        del states      # autodiff of the scan keeps its own
        return jax.vjp(lambda *args: _ssd_jnp(*args, chunk)[0],
                       x, dt, a, b, c, d)[1](g)

    if not _use_pallas(inputs[0], inputs[3], chunk, interpret):
        return jnp_fn(*inputs, states, g)
    return _by_platform(
        functools.partial(_bwd_pallas, chunk=chunk, interpret=interpret),
        jnp_fn, interpret, *inputs, states, g)


_ssd.defvjp(_ssd_fwd, _ssd_bwd)


def ssd_scan(x, dt, A, B, C, D, chunk: int = 128, interpret: bool = False):
    """The state-space scan of the module's head. ``x [B, T, H, P]``, ``dt
    [B, T, H]`` (positive: after its softplus; float32), ``A [H]`` (negative;
    float32), ``B`` and ``C`` ``[B, T, G, N]`` with ``H`` a multiple of ``G``
    (head ``h`` reads group ``h // (H / G)``), ``D [H]`` -> ``y [B, T, H, P]``
    in ``x``'s dtype. Differentiable in all six; ``chunk`` is the chunk's
    length (the result does not depend on it but for rounding). The state
    starts at zero and is carried through the whole sequence."""
    bsz, t, h, p = x.shape
    if (B.shape != C.shape or B.shape[:2] != (bsz, t) or h % B.shape[2]
            or dt.shape != (bsz, t, h) or A.shape != (h,) or D.shape != (h,)):
        raise ValueError(
            f"x {x.shape}, dt {dt.shape}, A {A.shape}, B {B.shape}, C "
            f"{C.shape}, D {D.shape}: dt is x's without the width, A and D a "
            f"head's, B and C alike over groups that divide the heads")
    return _ssd(x, dt, A, B, C, D, int(chunk), bool(interpret))


def ssd_scan_sharded(x, dt, A, B, C, D, mesh, **kwargs):
    """:func:`ssd_scan` mapped over the mesh's data axes (None or one device:
    the plain call): the scan is independent along the batch, and the
    partitioner cannot split a custom call. Heads and groups stay whole on
    every device (``tensor`` replicates them)."""
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    from raydp_tpu.parallel.mesh import data_axes

    fn = functools.partial(ssd_scan, **kwargs)
    if mesh is None or mesh.size == 1:
        return fn(x, dt, A, B, C, D)
    batch = data_axes(mesh)
    batch = batch if len(batch) > 1 else batch[0]
    rows = lambda rank: P(batch, *(None,) * (rank - 1))  # noqa: E731
    return shard_map(fn, mesh=mesh,
                     in_specs=(rows(4), rows(3), P(), rows(4), rows(4), P()),
                     out_specs=rows(4))(x, dt, A, B, C, D)
