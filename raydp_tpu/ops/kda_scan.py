"""The scan of a Kimi Delta Attention layer (the ``kimi_linear`` family's): a
delta rule with a decay a key CHANNEL, position by position and in chunks.

A head with state ``S [K, V]`` (``K`` the keys' width, ``V`` the values'; 128
each as published) runs, position by position (:func:`kda_recurrent_jnp`, the
tests' ground)::

    S' = diag(exp(g_t)) S_{t-1}                   g_t in R^K, <= 0, float32
    S_t = S' + b_t k_t (v_t - S'^T k_t)^T         b_t in (0, 1)
    o_t = S_t^T q_t

``q`` and ``k`` come normed (and ``q`` scaled), ``S_0 = 0`` at a row's first
position and the state is carried through the whole row.

**The chunked form** (:func:`kda_chunked_jnp`: what every platform runs, and
the specification a kernel would have to meet). A chunk of ``C`` rows, ``G``
the inclusive running sum of ``g`` inside it (``G <= 0``, falling)::

    A[r, i]  = sum_d k_r,d k_i,d exp(G_r,d - G_i,d)          i < r
    P[r, i]  = sum_d q_r,d k_i,d exp(G_r,d - G_i,d)          i <= r
    [W | U~] = (I + diag(b) tril(A, -1))^-1 diag(b) [K * e^G | V]
    U        = U~ - W S_0                         the rows' corrected values
    o        = (Q * e^G) S_0 + P U
    S_C      = diag(e^{G_C}) S_0 + (K * e^{G_C - G})^T U

Only ``S`` passes from a chunk to the next, so everything above ``U`` is
computed for ``SEGMENT`` = 16 chunks at once (all heads and sequences; the
``[chunks, C, C]`` and ``[chunks, C, K]`` float32 intermediates of a whole
16,384-token row would be gigabytes) and a ``lax.scan`` walks the three
products a chunk that read the state; an outer scan walks the segments.

**``exp(-G)`` is never formed.** ``ops/ssd_scan.py`` has ONE scalar decay a
position and head and factors it out of a chunk's products; here the decay is
a vector over the key channels, so ``A`` is no product of two decayed
matrices unless one of them carries ``exp(-G_i)``, and with ``A_log`` up to
``ln 16`` and an unbounded softplus a chunk's ``G`` reaches -1000 (float32
ends at e^88). Every exponent here is a DIFFERENCE of two ``G`` that is never
positive: the chunk is cut into sub-blocks of ``SUB`` = 8 rows; a pair of
rows in different sub-blocks goes through the first row ``s`` of the later
one (``exp(G_r - G_s) exp(G_s - G_i)``, ``i < s <= r``: two decayed matrices
multiplied), a pair inside one sub-block is the elementwise sum over the
channels (one ``[8, 8, K]`` broadcast a sub-block, all of a chunk's at
once). Eight rows, a float32 register's sublanes: sub-blocks of 16 summed
twice as many pairs channel by channel and ran the scan 5% slower on the chip
(PERF.md, PR 64); the pairs they leave go through the MXU.

**No kernel ships yet.** The unit-lower-triangular solve is
``lax.linalg.triangular_solve`` (float32), the products take operands at the
activations' dtype and accumulate in float32, the state is rounded only as a
product's operand. Differentiated (a ``custom_vjp``), the op keeps its five
inputs and the state each segment starts from (``T / 1024`` of them a head,
float32) and nothing else: the backward pass walks the segments from the
last to the first with the state's gradient carried, forms a segment again
from its state and transposes it (autodiff of the segment, the walk's body
recomputed a chunk at a time), as a kernel pair would. The counters ``kda_scan_total{path}`` (a built call;
``jnp`` is the one path today) and ``kda_chunks_total{pass}`` (sequences x
heads x chunks a built pass) say what a program holds.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

SUB = 8         # rows of a sub-block: pairs inside one are summed elementwise
SEGMENT = 16    # chunks formed at once: what bounds a pass's intermediates


def _count(name: str, amount: int, label: str) -> None:
    from raydp_tpu import metrics as rdt_metrics

    rdt_metrics.inc(name, amount, label)


# ---------------------------------------------------------------------------
# A position at a time
# ---------------------------------------------------------------------------
def kda_recurrent_jnp(q, k, v, g, beta):
    """The module's equations a position at a time, float32: ``q``, ``k``
    ``[B, T, H, K]``, ``v`` ``[B, T, H, V]``, ``g`` ``[B, T, H, K]``, ``beta``
    ``[B, T, H]`` -> ``o [B, T, H, V]`` float32."""
    f32 = jnp.float32
    q, k, v, g, beta = (a.astype(f32) for a in (q, k, v, g, beta))

    def step(state, at):
        qt, kt, vt, gt, bt = at
        state = jnp.exp(gt)[..., None] * state
        seen = jnp.einsum("bhkv,bhk->bhv", state, kt)
        state = state + jnp.einsum("bhk,bhv->bhkv", bt[..., None] * kt,
                                   vt - seen)
        return state, jnp.einsum("bhkv,bhk->bhv", state, qt)

    first = jnp.zeros(k.shape[:1] + k.shape[2:] + v.shape[3:], f32)
    _, out = lax.scan(step, first, tuple(
        jnp.moveaxis(a, 1, 0) for a in (q, k, v, g, beta)))
    return jnp.moveaxis(out, 0, 1)


# ---------------------------------------------------------------------------
# In chunks
# ---------------------------------------------------------------------------
def _mm(a, b):
    """``a b`` over the last two dimensions, float32 accumulation."""
    return jnp.matmul(a, b, preferred_element_type=jnp.float32)


def _scores(q, k, total, dtype):
    """``(A, P)`` of the module's head, ``[..., C, C]`` float32 each, from
    ``q``, ``k`` and ``G`` (``total``) ``[..., C, K]`` float32: ``A`` strictly
    under the diagonal, ``P`` with it. A product's operands are rounded to
    ``dtype``; the pairs inside a sub-block stay float32. All the chunk's
    sub-blocks at once, so that the traced program does not grow with ``C``
    (a Python loop over sub-blocks and their columns lowers to six times the
    text and doubles the step's compile time: PERF.md, PR 64)."""
    chunk, keys = k.shape[-2:]
    sub = SUB if chunk % SUB == 0 else chunk
    blocks = chunk // sub
    own_q, own_k, own_g = (
        a.reshape(a.shape[:-2] + (blocks, sub, keys)) for a in (q, k, total))
    # inside a sub-block: the [sub, sub, K] elementwise sum, row r on column i
    reach = own_k[..., None, :, :] * jnp.exp(jnp.minimum(
        own_g[..., :, None, :] - own_g[..., None, :, :], 0.0))
    inside = (jnp.sum(own_k[..., :, None, :] * reach, axis=-1),
              jnp.sum(own_q[..., :, None, :] * reach, axis=-1))
    # against the rows before a sub-block: through its first row, G_s
    first = own_g[..., :1, :]
    decay = jnp.exp(own_g - first)
    left = jnp.concatenate([own_k * decay, own_q * decay], axis=-2)
    right = k[..., None, :, :] * jnp.exp(jnp.minimum(
        first - total[..., None, :, :], 0.0))
    across = jnp.einsum("...ard,...aid->...ari", left.astype(dtype),
                        right.astype(dtype),
                        preferred_element_type=jnp.float32)
    # row (a, r) on column (b, i): an earlier sub-block's, its own, or none
    a, r, b, i = (lax.broadcasted_iota(jnp.int32, (blocks, sub) * 2, n)
                  for n in range(4))

    def whole(inside, across, kept):
        inside = jnp.broadcast_to(inside[..., None, :],
                                  inside.shape[:-1] + (blocks, sub))
        across = across.reshape(inside.shape)
        out = jnp.where(b < a, across,
                        jnp.where((b == a) & kept, inside, 0.0))
        return out.reshape(out.shape[:-4] + (chunk, chunk))

    return (whole(inside[0], across[..., :sub, :], r > i),
            whole(inside[1], across[..., sub:, :], r >= i))


def _segment(state, of_segment, dtype):
    """``SEGMENT`` chunks at once: ``state`` ``[B, H, K, V]`` float32 and the
    segment's ``(q, k, v, g, beta)`` as ``[B, H, chunks, C, ...]`` -> (the
    state after it, ``o [chunks, B, H, C, V]`` in ``dtype``). Everything above
    ``U`` for all the segment's chunks at once, then the walk."""
    f32 = jnp.float32
    qc, kc, vc, gc, bc = (a.astype(f32) for a in of_segment)
    bc = bc[..., None]                                      # [B, H, N, C, 1]
    total = jnp.cumsum(gc, axis=-2)                         # G, inclusive
    last = total[..., -1:, :]
    reached = jnp.exp(total)
    a_kk, a_qk = _scores(qc, kc, total, dtype)
    # XLA's own solve: a blocked forward substitution written out here
    # (16-row sub-blocks inverted a row at a time, the rest products at the
    # highest precision) ran the scan 28% slower on the chip (PERF.md, PR 64)
    solved = lax.linalg.triangular_solve(
        bc * a_kk, bc * jnp.concatenate([kc * reached, vc], axis=-1),
        left_side=True, lower=True, unit_diagonal=True)
    keys = kc.shape[-1]
    per_chunk = (
        solved[..., :keys].astype(dtype),                   # W
        solved[..., keys:],                                 # U~, float32
        (qc * reached).astype(dtype),                       # Q e^G
        a_qk.astype(dtype),                                 # P
        (kc * jnp.exp(last - total)).astype(dtype),         # K e^{G_C - G}
        jnp.exp(last[..., 0, :]))                           # e^{G_C}

    def body(state, of_chunk):
        w, u, q_in, p, k_out, kept = of_chunk
        rounded = state.astype(dtype)
        u = (u - _mm(w, rounded)).astype(dtype)
        out = _mm(q_in, rounded) + _mm(p, u)
        state = kept[..., None] * state + jnp.einsum(
            "...ck,...cv->...kv", k_out, u, preferred_element_type=f32)
        return state, out.astype(dtype)

    return lax.scan(jax.checkpoint(body), state, tuple(
        jnp.moveaxis(a, 2, 0) for a in per_chunk))


def _in_segments(a, chunk: int, chunks: int):
    """``[B, T, H, ...]`` -> ``[T / (chunks x chunk), B, H, chunks, chunk,
    ...]``: the segments first, as a scan walks them."""
    b, t, h = a.shape[:3]
    a = a.reshape(b, t // (chunks * chunk), chunks, chunk, h, *a.shape[3:])
    return jnp.moveaxis(a, (1, 4), (0, 2))


def _laid_out(q, k, v, g, beta, chunk: int):
    """The five inputs padded to whole segments (a filling row has ``k = 0``,
    ``beta = 0``, ``g = 0``, which move no state) and cut into them, and the
    chunks a segment holds."""
    t = k.shape[1]
    chunks = min(SEGMENT, -(-t // chunk))
    pad = (-t) % (chunks * chunk)
    if pad:
        q, k, v, g, beta = (
            jnp.pad(a, ((0, 0), (0, pad)) + ((0, 0),) * (a.ndim - 2))
            for a in (q, k, v, g, beta))
    return tuple(_in_segments(a, chunk, chunks)
                 for a in (q, k, v, g, beta)), chunks


def _joined(out, shape):
    """``[segments, chunks, B, H, C, V]`` -> ``[B, T, H, V]``, the filling
    rows cut off."""
    bsz, t, h, width = shape
    out = jnp.moveaxis(out, (2, 3), (0, 3))     # [B, segments, chunks, H, ..]
    return jnp.moveaxis(out, 3, 4).reshape(bsz, -1, h, width)[:, :t]


def _forward(q, k, v, g, beta, chunk: int):
    """(``o [B, T, H, V]`` in ``v``'s dtype, the state each segment starts
    from ``[segments, B, H, K, V]`` float32)."""
    dtype = v.dtype
    xs, _ = _laid_out(q, k, v, g, beta, chunk)
    # zeros that vary over a mesh as the inputs do (inside a shard_map a
    # carry's type has to say so from the start)
    first = (xs[1][0, :, :, 0, 0, :, None].astype(jnp.float32)
             * xs[2][0, :, :, 0, 0, None, :].astype(jnp.float32)) * 0.0

    def step(state, of_segment):
        after, out = _segment(state, of_segment, dtype)
        return after, (out, state)

    _, (out, states) = lax.scan(step, first, xs)
    return _joined(out, v.shape), states


def kda_chunked_jnp(q, k, v, g, beta, chunk: int = 64):
    """The chunked form of the module's head in ``jax.numpy``: the shapes of
    :func:`kda_recurrent_jnp` -> ``o [B, T, H, V]`` in ``v``'s dtype. Any
    ``T`` (a last segment is filled with rows that move no state)."""
    return _forward(q, k, v, g, beta, chunk)[0]


# ---------------------------------------------------------------------------
# The op
# ---------------------------------------------------------------------------
def _chunks(q, chunk: int) -> int:
    bsz, t, h = q.shape[:3]
    return bsz * h * -(-t // chunk)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5,))
def _kda(q, k, v, g, beta, chunk):
    return kda_chunked_jnp(q, k, v, g, beta, chunk)


def _kda_fwd(q, k, v, g, beta, chunk):
    out, states = _forward(q, k, v, g, beta, chunk)
    return out, (q, k, v, g, beta, states)


def _kda_bwd(chunk, residuals, d_out):
    """The segments from the last to the first with the state's gradient
    carried: a segment is formed again from the state it started from (which
    the forward pass kept, ``T / 1024`` of them a head) and transposed."""
    *inputs, states = residuals
    q, k, v, g, beta = inputs
    _count("kda_chunks_total", _chunks(q, chunk), "backward")
    dtype = v.dtype
    xs, chunks = _laid_out(*inputs, chunk)
    pad = xs[0].shape[0] * chunks * chunk - q.shape[1]
    d_out = jnp.pad(d_out, ((0, 0), (0, pad), (0, 0), (0, 0)))
    # [segments, chunks, B, H, C, V], as a segment hands its output over
    d_outs = jnp.moveaxis(_in_segments(d_out, chunk, chunks), 3, 1)

    def step(d_state, of_segment):
        state, x, d_o = of_segment
        _, pull = jax.vjp(
            lambda s, *of: _segment(s, of, dtype), state, *x)
        d_state, *d_x = pull((d_state, d_o))
        return d_state, tuple(d_x)

    _, d_xs = lax.scan(step, jnp.zeros_like(states[0]),
                       (states, xs, d_outs), reverse=True)

    def back(d, like):      # [segments, B, H, chunks, C, ...] -> like's
        d = jnp.moveaxis(d, (0, 2), (1, 4))     # [B, segments, chunks, C, H]
        return d.reshape(like.shape[0], -1, *like.shape[2:])[
            :, :like.shape[1]].astype(like.dtype)

    return tuple(back(d, a) for d, a in zip(d_xs, inputs))


_kda.defvjp(_kda_fwd, _kda_bwd)


def kda_scan(q, k, v, g, beta, chunk: int = 64):
    """The scan of the module's head. ``q``, ``k`` ``[B, T, H, K]`` (normed;
    ``q`` scaled), ``v`` ``[B, T, H, V]``, ``g`` ``[B, T, H, K]`` (float32, not
    positive), ``beta`` ``[B, T, H]`` (float32) -> ``o [B, T, H, V]`` in
    ``v``'s dtype. Differentiable in all five; ``chunk`` is the chunk's length
    (the result does not depend on it but for rounding). The state starts at
    zero and is carried through the whole row."""
    if (q.shape != k.shape or g.shape != k.shape or v.shape[:3] != k.shape[:3]
            or beta.shape != k.shape[:3] or v.ndim != 4):
        raise ValueError(
            f"q {q.shape}, k {k.shape}, v {v.shape}, g {g.shape}, beta "
            f"{beta.shape}: q, k and g alike [B, T, H, K], v [B, T, H, V], "
            f"beta [B, T, H]")
    _count("kda_scan_total", 1, "jnp")
    _count("kda_chunks_total", _chunks(q, int(chunk)), "forward")
    return _kda(q, k, v, g, beta, int(chunk))
