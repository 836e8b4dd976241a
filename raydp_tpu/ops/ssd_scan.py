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
``[Q, N]``, and the group's ``l`` and ``dt`` both as rows and as columns. It
forms ``C B^T`` once for the group and ``C S_prev`` and the state's update as
ONE product each over all the group's heads, then a head at a time the masked
decay ``exp(l_t - l_s)`` (float32), the intra-chunk product and the output.
The group's state ``[N, heads x P]`` lies in VMEM in float32 through the walk.
Differentiated, it also writes the state each chunk STARTS from (float32:
``T / Q`` of them a head), which the backward walk reads.

**Backward kernel ``rdt_ssd_bwd``.** The same grid with the chunk axis walked
from the last chunk to the first and the state's gradient carried in VMEM.
One walk gives ``dx``, ``dB``, ``dC`` (a group's heads summed in float32),
``dD`` and, for ``dt`` and ``A``, two per-position sums: with ``E[t, s] =
dy_t . x_s`` and ``W[t, s] = (C_t . B_s) exp(l_t - l_s)``, the explicit
``d dt_s = sum_t W E + exp(l_Q - l_s) <dS, B_s (x) x_s>`` and ``dl_t`` (what
``l_t`` moves: its row of ``W dt E``, minus its column, the carried state's
term and the next state's). ``l`` is a cumulative sum of ``dt A``, so outside
the kernel ``d(dt A)`` is ``dl``'s reverse cumulative sum in the chunk, ``d
dt`` adds ``A`` times it and ``dA`` sums ``dt`` times it: three small
``[B, T, H]`` float32 passes.

Decays (``l``, every ``exp``) are float32; the products' operands are the
activations' dtype and accumulate in float32; a state is rounded to the
activations' dtype only as a product's operand.

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


def _of_head(rows, cols, head: int, hg: int):
    """A head's ``l`` and ``dt`` out of its group's packed blocks: as columns
    ``[Q, 1]`` (a position a row) and as rows ``[1, Q]``, and ``l`` at the
    chunk's last position ``[1, 1]``."""
    l_row, dt_row = rows[head:head + 1], rows[hg + head:hg + head + 1]
    l_col, dt_col = (cols[:, head:head + 1],
                     cols[:, hg + head:hg + head + 1])
    return l_col, dt_col, l_row, dt_row, l_row[:, -1:]


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
    x, bm, cm = x_ref[0], b_ref[0], c_ref[0]
    rows, cols = rows_ref[0, 0], cols_ref[0, 0]
    dtype = x.dtype
    visible = _visible(chunk)
    cb = _nt(cm, bm)                                        # [t, s] float32
    carried = _nn(cm, state[:].astype(dtype))               # C S_prev, all heads
    for head in range(hg):
        lanes = slice(head * p, (head + 1) * p)
        l_col, dt_col, l_row, dt_row, l_last = _of_head(rows, cols, head, hg)
        decay = jnp.exp(jnp.where(visible, l_col - l_row, _MASKED))
        xh = x[:, lanes]
        y = _nn((cb * decay * dt_row).astype(dtype), xh)
        y += jnp.exp(l_col) * carried[:, lanes]
        y += d_ref[:, lanes] * xh
        y_ref[0, :, lanes] = y.astype(dtype)
        weighed[:, lanes] = (xh * (jnp.exp(l_last - l_col)
                                   * dt_col)).astype(dtype)
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

    x, bm, cm, dy = x_ref[0], b_ref[0], c_ref[0], dy_ref[0]
    rows, cols = rows_ref[0, 0], cols_ref[0, 0]
    dtype, f32 = x.dtype, jnp.float32
    s0, ds1 = states_ref[0, 0, 0], d_state[:]               # [N, heads x P]
    s0c, ds1c = s0.astype(dtype), ds1.astype(dtype)
    visible = _visible(chunk)
    last_row = lax.broadcasted_iota(jnp.int32, (chunk, 1), 0) == chunk - 1
    cb = _nt(cm, bm)                                        # [t, s]
    carried = _nn(cm, s0c)                                  # C S_prev
    from_next = _nn(bm, ds1c)                               # B dS_end
    z_sum = jnp.zeros((chunk, chunk), f32)
    for head in range(hg):
        lanes = slice(head * p, (head + 1) * p)
        l_col, dt_col, l_row, dt_row, l_last = _of_head(rows, cols, head, hg)
        decay = jnp.exp(jnp.where(visible, l_col - l_row, _MASKED))
        xh, dyh = x[:, lanes], dy[:, lanes]
        xf, dyf = xh.astype(f32), dyh.astype(f32)
        e = _nt(dyh, xh)                                    # dy_t . x_s
        z = decay * dt_row * e
        z_sum += z
        w = cb * decay
        by_row = jnp.sum(w * e, axis=0, keepdims=True)      # [1, s]
        moved = jnp.sum(cb * z, axis=1, keepdims=True)      # [t, 1]
        to_t, to_end = jnp.exp(l_col), jnp.exp(l_last - l_col)
        moved += to_t * jnp.sum(dyf * carried[:, lanes], axis=1,
                                keepdims=True)
        q = to_end * jnp.sum(from_next[:, lanes] * xf, axis=1, keepdims=True)
        tail = jnp.sum(dt_col * q, axis=0, keepdims=True) + jnp.exp(
            l_last) * jnp.sum(jnp.sum(ds1[:, lanes] * s0[:, lanes], axis=1,
                                      keepdims=True), axis=0, keepdims=True)
        moved += jnp.where(last_row, tail, 0.0)
        dx = dt_col * _tn(w.astype(dtype), dyh)
        dx += (to_end * dt_col) * from_next[:, lanes]
        dx += d_ref[:, lanes] * dyf
        dx_ref[0, :, lanes] = dx.astype(dtype)
        by_row_ref[0, 0, head:head + 1, :] = by_row
        by_col_ref[0, 0, :, head:head + 1] = moved
        by_col_ref[0, 0, :, hg + head:hg + head + 1] = q
        dy_decayed[:, lanes] = (dyf * to_t).astype(dtype)
        weighed[:, lanes] = (xf * (to_end * dt_col)).astype(dtype)
        dd_ref[0, :, lanes] += jnp.sum(dyf * xf, axis=0, keepdims=True)
    zc = z_sum.astype(dtype)
    dc_ref[0] = (_nn(zc, bm) + _nt(dy_decayed[:], s0c)).astype(dtype)
    db_ref[0] = (_tn(zc, cm) + _nt(weighed[:], ds1c)).astype(dtype)
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
