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

**The chunked form** (:func:`kda_chunked_jnp`: what every platform but a TPU
runs, and the specification the kernels meet). A chunk of ``C`` rows, ``G``
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

**Forward kernel ``rdt_kda_fwd``.** Grid (sequence, group of four heads,
segment), the segment axis last and walked in order; a grid step loads one
SEGMENT of four heads (``[1024, 4 x 128]`` blocks of ``q``, ``k``, ``v``,
``g`` as the projection lays them out, a head a 128-lane tile: nothing is
transposed in HBM; ``beta [1024, H]`` whole, the head's column picked by a
lane mask) and keeps each head's state in VMEM in float32 through the walk,
TRANSPOSED (``[V, K]``: a chunk's decay ``e^{G_C}`` then scales its columns,
a row vector, and the products with it are ``a b^T`` and ``a^T b`` on the
MXU), zeroed at a row's first segment. Inside a step a loop walks SPANS of two chunks (128
rows: the MXU's tile): everything above ``U`` is formed for a span of each
head at once, none of it reading the state, and then the span's chunks are
walked one after the other. **The four heads go through a span IN STEP**
(:func:`_in_step`): a chunk's inverse is ten float32 products each waiting on
the one before it, and so is the walk, so a head alone leaves the MXUs
waiting (one head a step ran 18.7 ms where four run 12.7: PERF.md, PR 65);
each head's work is written as a generator that yields after a product, and
the heads take turns, which puts four independent products side by side in
the kernel's text for the compiler's schedule to overlap. A span, the
docstring's equations to the letter:

- ``G``: the running sum inside each chunk as ONE product of a 0/1 triangle
  with ``g`` cut into three bfloat16 pieces (exact to float32's 24 bits);
- ``A`` and ``P`` by the sub-block rule with the kernels' own ``KERNEL_SUB``
  = 8 rows. Inside a sub-block one DIAGONAL at a time: offset ``j`` pairs
  row ``r`` with row ``r - j`` (``k`` and ``G`` rolled down ``j`` sublanes),
  so every sub-block of the span is worked in the same ``[128, K]`` float32
  arrays: ``k_r k_{r-j} exp(min(G_r - G_{r-j}, 0))`` summed over the lanes
  is the ``j``-th diagonal. Against the rows of its chunk before a sub-block,
  one ``[16, K] x [K, 128]`` product a sub-block through its first row
  (``k`` and ``q`` of the sub-block stacked), operands at the activations'
  dtype;
- the unit-lower-triangular solve as an explicit inverse, float32 products
  (``Precision.HIGHEST``) all through: the 8-row diagonal blocks of ``L =
  diag(b) tril(A, -1)`` by ``(I - D)(I + D^2)(I + D^4)``, exact since ``D^8
  = 0``, then pairs of blocks put together three times, 8 -> 16 -> 32 -> 64
  (``[[X, 0], [Z, Y]]^-1 = [[X^-1, 0], [-Y^-1 Z X^-1, Y^-1]]``: only true
  inverses of leading blocks are ever formed; the powers of a whole chunk's
  ``L`` grow as binomials where keys repeat). Ten ``[128, 128]`` products
  for the span's two chunks, then ``[W | U~] = T diag(b) [K e^G | V]``;
- the walk, a chunk: ``U = U~ - W S``, ``S <- e^{G_C} S + U^T (K e^{G_C -
  G})``, ``(Q e^G) S`` kept; after the span's chunks ``o = (Q e^G) S + P U``
  as one product.

It also writes the state each segment STARTS from, in the ``jax.numpy``
form's own shape (33.5 MB a layer at the published shape), differentiated or
not: a model's plain and recomputed forward are then one kernel.

**Backward kernel ``rdt_kda_bwd``.** The same grid with the segment axis
walked from the last to the first and ``dS`` carried in VMEM. A grid step
first walks its segment FORWARD (no ``P``, no output), keeping in VMEM what
the way back needs: the state each chunk starts from (16 x 64 KB a head), a
span's inverse and its ``[W | U~]`` (float32). Then the spans from the last
to the first: scores formed again (with ``P``), the chunks' three products
with the state transposed one chunk after the other (``dU = P^T do + (K
e^{G_C - G}) dS``, ``dS <- e^{G_C} dS + do^T (Q e^G) - dU^T W``), then for
the whole span at once the solve transposed (``dR = T^T [dW | dU~]``, ``dL =
-tril(dR [W | U~]^T, -1)``), the scores transposed pair by pair as they were
formed (a diagonal's gradient rolled back up ``j`` sublanes; a sub-block's
against its chunk's earlier rows as two products), every ``dG`` put
together, and ``dg`` as the reverse running sum inside each chunk (the
transposed 0/1 triangle). ``dq``, ``dk``, ``dv`` leave at the activations'
dtype, ``dg`` and ``dbeta`` float32.

The kernels take whole chunks in whole segments, chunks of ``8 x 2^n`` rows
on the 16 sublanes of a bfloat16 tile, and widths on the 128 lanes
(:func:`kernel_ineligible`). Anything else (a ragged row whole), and any
platform but a TPU, takes the chunked ``jax.numpy`` form above: the
unit-lower-triangular solve as ``lax.linalg.triangular_solve`` (float32),
the backward pass the segments from the last to the first with the state's
gradient carried, each formed again from its state and transposed by
autodiff. The choice is made when the program is lowered, as
:mod:`raydp_tpu.ops.flash_attention` chooses, so a step compiled ahead of
time for a described TPU holds the kernels; ``interpret`` runs them through
the Pallas interpreter (tests). In both the products take operands at the
activations' dtype and accumulate in float32, ``g``, ``G``, every ``exp``
and the solve are float32, and the state is rounded only as a product's
operand. The counters ``kda_scan_total{path}`` (a built call: ``kernel``
where it holds the kernel pair, ``jnp`` wherever the form is traced, the
fallback branch of a call that holds the kernels included) and
``kda_chunks_total{pass}`` (sequences x heads x chunks a built pass) say
what a program holds.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax

from raydp_tpu.ops.flash_attention import _by_platform

SUB = 8         # rows of a sub-block: pairs inside one are summed elementwise
KERNEL_SUB = 8  # the kernels' own: a float32 register's sublanes
KERNEL_NAMES = ("rdt_kda_fwd", "rdt_kda_bwd")
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
    # because every row step was a program over HBM. The kernels' differs
    # (_kernel_inverse): the chunk's inverse by float32 products of whole
    # [128, 128] tiles in VMEM, no row steps (PERF.md, PR 65)
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
# Pallas kernels
# ---------------------------------------------------------------------------
def kernel_ineligible(t: int, chunk: int, keys: int,
                      values: int) -> Optional[str]:
    """Why the compiled kernels cannot take a scan of ``t`` positions in
    chunks of ``chunk`` with keys of ``keys`` and values of ``values``
    channels (None where they can): whole chunks in whole segments (a grid
    step is a segment, and both branches of the dispatch keep the states the
    segments start from), a chunk of ``KERNEL_SUB`` rows times a power of
    two on the bfloat16 tile's 16 sublanes, a head's widths on the 128
    lanes."""
    if t % chunk:
        return f"{t} positions are no whole number of chunks of {chunk}"
    why = _chunk_refused(chunk)
    if why is not None:
        return why
    if chunk % 16:
        return f"a chunk of {chunk} rows is no multiple of 16 sublanes"
    if keys % 128 or values % 128:
        return (f"keys of {keys} and values of {values} channels have to be "
                f"multiples of the 128 lanes")
    chunks = t // chunk
    if chunks % min(SEGMENT, chunks):
        return (f"{chunks} chunks are no whole number of segments of "
                f"{SEGMENT}")
    return None


def _chunk_refused(chunk: int) -> Optional[str]:
    """A chunk the kernels' inverse cannot double its way up to: not
    ``KERNEL_SUB`` rows (or fewer) times a power of two."""
    blocks = chunk // min(KERNEL_SUB, chunk)
    if chunk % min(KERNEL_SUB, chunk) or blocks & (blocks - 1):
        return (f"a chunk of {chunk} rows is not {KERNEL_SUB} (or fewer) "
                f"times a power of two")
    return None


_HIGHEST = lax.Precision.HIGHEST


def _nn(a, b, precision=None):
    return lax.dot_general(a, b, (((1,), (0,)), ((), ())), precision=precision,
                           preferred_element_type=jnp.float32)


def _nt(a, b, precision=None):
    """``a b^T`` with float32 accumulation."""
    return lax.dot_general(a, b, (((1,), (1,)), ((), ())), precision=precision,
                           preferred_element_type=jnp.float32)


def _tn(a, b, precision=None):
    """``a^T b`` with float32 accumulation."""
    return lax.dot_general(a, b, (((0,), (0,)), ((), ())), precision=precision,
                           preferred_element_type=jnp.float32)


def _exact_nn(ones, x):
    """``ones x`` for a 0/1 matrix and a float32 ``x``, to float32's own 24
    bits in three one-pass products: ``x`` as three bfloat16 pieces whose sum
    it is (as ``ssd_scan._over_lanes`` does), each product exact."""
    f32, bf16 = jnp.float32, jnp.bfloat16
    ones = ones.astype(bf16)
    high = x.astype(bf16)
    rest = x - high.astype(f32)
    middle = rest.astype(bf16)
    low = (rest - middle.astype(f32)).astype(bf16)
    return (_nn(ones, high) + _nn(ones, middle)) + _nn(ones, low)


def _grid2(n: int):
    """(row, column) ``[n, n]`` int32."""
    return (lax.broadcasted_iota(jnp.int32, (n, n), 0),
            lax.broadcasted_iota(jnp.int32, (n, n), 1))


def _over(x, n: int):
    """``x // n`` of an int32 array (a shift where ``n`` is a power of two)."""
    if n & (n - 1) == 0:
        return lax.shift_right_logical(x, n.bit_length() - 1)
    return x // n


def _same_chunk(row, col, chunk: int):
    return _over(row, chunk) == _over(col, chunk)


def _rows_of(x, first: int, span: int):
    """``x [rows, K]`` as rows ``first`` .. of ``[span, K]``, zeros round it."""
    zeros = lambda rows: (  # noqa: E731
        [jnp.zeros((rows, x.shape[1]), x.dtype)] if rows else [])
    return jnp.concatenate(
        zeros(first) + [x] + zeros(span - first - x.shape[0]), axis=0)


def _kernel_scores(qf, kf, total, dtype, sub: int, chunk: int,
                   with_p: bool = True):
    """(A generator, :func:`_in_step`.) ``(A, P)`` ``[R, R]`` float32 of a
    span of ``R / chunk`` chunks of one head (the chunks' own blocks on the
    diagonal, zeros off them) from float32 ``q``, ``k`` and ``G`` ``[R,
    K]``, by the module's sub-block rule. Inside a sub-block one diagonal at a time: offset ``j`` pairs row
    ``r`` with row ``r - j`` (the rows rolled down by ``j``), the ``[R, K]``
    products of all the span's sub-blocks at once, summed over the lanes.
    Against the rows of its chunk before a sub-block through the
    sub-block's first row, one product a sub-block."""
    from jax.experimental.pallas import tpu as pltpu

    f32 = jnp.float32
    span = kf.shape[0]
    row, col = _grid2(span)
    inside_row = row - _over(row, sub) * sub
    a_in = jnp.zeros((span, span), f32)
    p_in = jnp.zeros((span, span), f32)
    for j in range(sub):
        if j:
            reach = pltpu.roll(kf, j, 0) * jnp.exp(jnp.minimum(
                total - pltpu.roll(total, j, 0), 0.0))
        else:
            reach = kf
        on = (col == row - j) & (inside_row >= j)
        if with_p:
            p_in = jnp.where(on, jnp.sum(qf * reach, axis=1, keepdims=True),
                             p_in)
        if j:
            a_in = jnp.where(on, jnp.sum(kf * reach, axis=1, keepdims=True),
                             a_in)
    lane = lax.broadcasted_iota(jnp.int32, (1, span), 1)
    none = jnp.zeros((sub, span), f32)
    a_rows, p_rows = [], []
    for s in range(0, span, sub):
        start = s // chunk * chunk
        if s == start:      # a chunk's first sub-block: no rows before it
            a_rows.append(none)
            p_rows.append(none)
            continue
        first = total[s:s + 1]
        decay = jnp.exp(total[s:s + sub] - first)
        left = [kf[s:s + sub] * decay] + (
            [qf[s:s + sub] * decay] if with_p else [])
        right = _rows_of(kf[start:start + chunk] * jnp.exp(jnp.minimum(
            first - total[start:start + chunk], 0.0)), start, span)
        across = jnp.where((lane >= start) & (lane < s), _nt(
            jnp.concatenate(left, axis=0).astype(dtype), right.astype(dtype)),
            0.0)
        a_rows.append(across[:sub])
        p_rows.append(across[sub:] if with_p else none)
        yield
    a = a_in + jnp.concatenate(a_rows, axis=0)
    return a, (p_in + jnp.concatenate(p_rows, axis=0) if with_p else None)


def _in_step(heads):
    """A grid step's heads put through their products IN STEP: each head's
    work is a generator that yields where its next product waits on the
    last, and the heads take turns there, so that in the kernel's text
    another head's independent product stands next to a waiting one and the
    compiler's schedule fills the MXUs' waits with it. The generators'
    return values, in order."""
    results, live = [None] * len(heads), list(enumerate(heads))
    while live:
        still = []
        for n, head in live:
            try:
                next(head)
                still.append((n, head))
            except StopIteration as done:
                results[n] = done.value
        live = still
    return results


def _kernel_inverse(lower, sub: int, chunk: int):
    """(A generator, :func:`_in_step`.) ``(I + L)^-1`` ``[R, R]`` float32 for
    a strictly lower ``L`` with ``chunk``-row blocks on its diagonal and
    zeros off them, every product float32. The diagonal ``sub``-row blocks,
    all at once: ``(I - D)(I + D^2)(I + D^4)..`` is their inverse exactly,
    ``D^sub`` being zero. Then pairs of blocks put together, doubling up to
    a chunk: ``[[X, 0], [Z, Y]]^-1 = [[X^-1, 0], [-Y^-1 Z X^-1, Y^-1]]``,
    which forms true inverses of leading blocks only (powers of a whole
    chunk's ``L`` would grow as binomials where keys repeat)."""
    span = lower.shape[0]
    row, col = _grid2(span)
    power = jnp.where(_same_chunk(row, col, sub), lower, 0.0)
    inverse = jnp.where(row == col, 1.0, 0.0) - power
    n = 2
    while n < sub:
        power = _nn(power, power, _HIGHEST)
        yield
        inverse = inverse + _nn(inverse, power, _HIGHEST)
        yield
        n *= 2
    size = sub
    while size < chunk:
        under = (_same_chunk(row, col, 2 * size)
                 & ~_same_chunk(row, col, size))
        half = _nn(inverse, jnp.where(under, lower, 0.0), _HIGHEST)
        yield
        inverse = inverse - _nn(half, inverse, _HIGHEST)
        yield
        size *= 2
    return inverse


def _formed(q, k, v, g, beta, sub: int, chunk: int, with_p: bool = True,
            solve=None):
    """(A generator, :func:`_in_step`.) Everything above ``U`` of a span of
    whole chunks of one head (``R`` rows: two chunks of 64 fill the MXU's
    128), from ``q``, ``k`` ``[R, K]`` and ``v`` ``[R, V]`` at the
    activations' dtype, ``g`` ``[R, K]`` and ``beta`` ``[R, 1]`` float32: a
    dict of float32 arrays (``kept``: a ``[1, K]`` a chunk). ``solve``: the
    span's ``(inverse, [W | U~])`` where they are kept."""
    f32 = jnp.float32
    span = k.shape[0]
    qf, kf, vf = q.astype(f32), k.astype(f32), v.astype(f32)
    row, col = _grid2(span)
    total = _exact_nn((row >= col) & _same_chunk(row, col, chunk), g)
    yield
    reached = jnp.exp(total)
    a, p = yield from _kernel_scores(qf, kf, total, v.dtype, sub, chunk,
                                     with_p)
    plain = jnp.concatenate([kf * reached, vf], axis=1)     # [K e^G | V]
    if solve is None:
        inverse = yield from _kernel_inverse(beta * a, sub, chunk)
        solved = _nn(inverse, beta * plain, _HIGHEST)       # [W | U~]
        yield
    else:
        inverse, solved = solve
    lasts = [total[n + chunk - 1:n + chunk] for n in range(0, span, chunk)]
    to_end = jnp.concatenate(
        [jnp.exp(last - total[n:n + chunk])
         for n, last in zip(range(0, span, chunk), lasts)], axis=0)
    return dict(qf=qf, kf=kf, total=total, reached=reached, a=a, p=p,
                beta=beta, inverse=inverse, plain=plain, solved=solved,
                to_end=to_end, kept=[jnp.exp(last) for last in lasts])


def _walked(state, formed, dtype, chunk: int):
    """(A generator, :func:`_in_step`.) The products of a span's chunks that
    read the state ``[V, K]`` float32 (the module's ``S`` transposed, so that
    a chunk's decay scales its columns), a chunk after the other: (the state
    after the span, ``o [R, V]`` float32 or None where ``formed`` holds no
    ``P``, the state each chunk started from)."""
    keys = formed["kf"].shape[1]
    solved, p = formed["solved"], formed["p"]
    k_out = (formed["kf"] * formed["to_end"]).astype(dtype)
    q_in = (formed["qf"] * formed["reached"]).astype(dtype)
    us, carried, starts = [], [], []
    for n, kept in enumerate(formed["kept"]):
        rows = slice(n * chunk, (n + 1) * chunk)
        starts.append(state)
        rounded = state.astype(dtype)
        us.append((solved[rows, keys:] - _nt(
            solved[rows, :keys].astype(dtype), rounded)).astype(dtype))
        if p is not None:
            carried.append(_nt(q_in[rows], rounded))
        yield
        state = kept * state + _tn(us[-1], k_out[rows])
        yield
    out = None if p is None else jnp.concatenate(carried, axis=0) + _nn(
        p.astype(dtype), jnp.concatenate(us, axis=0))
    return state, out, starts


def _own_beta(b_ref, rows, head):
    """``[C, 1]``: the column of head ``head`` (traced) out of the chunk's
    ``beta [C, H]``."""
    betas = b_ref[0, rows, :]
    lane = lax.broadcasted_iota(jnp.int32, betas.shape, 1)
    return jnp.sum(jnp.where(lane == head, betas, 0.0), axis=1, keepdims=True)


def _loaded(refs, i, j: int, first_head, span: int, keys: int, values: int):
    """Span ``i`` (traced) of the step's head ``j``: (its rows, its lanes in
    a keys-wide and in a values-wide block, its ``(q, k, v, g, beta)``)."""
    from jax.experimental import pallas as pl

    q_ref, k_ref, v_ref, g_ref, b_ref = refs
    rows = pl.ds(pl.multiple_of(i * span, span), span)
    wide, tall = (slice(j * keys, (j + 1) * keys),
                  slice(j * values, (j + 1) * values))
    return rows, wide, tall, (
        q_ref[0, rows, wide], k_ref[0, rows, wide], v_ref[0, rows, tall],
        g_ref[0, rows, wide], _own_beta(b_ref, rows, first_head + j))


def _fwd_kernel(q_ref, k_ref, v_ref, g_ref, b_ref, o_ref, states_ref, state,
                *, heads: int, chunk: int, chunks: int, pack: int, sub: int):
    from jax.experimental import pallas as pl

    refs = (q_ref, k_ref, v_ref, g_ref, b_ref)
    keys = q_ref.shape[2] // heads
    values = v_ref.shape[2] // heads

    @pl.when(pl.program_id(2) == 0)
    def _start():
        state[...] = jnp.zeros_like(state)

    states_ref[0, :, 0] = state[...]        # what this segment starts from
    first_head = pl.program_id(1) * heads

    def a_head(i, j):
        rows, _, tall, inputs = _loaded(refs, i, j, first_head, pack * chunk,
                                        keys, values)
        formed = yield from _formed(*inputs, sub, chunk)
        state[j], out, _ = yield from _walked(state[j], formed, v_ref.dtype,
                                              chunk)
        o_ref[0, rows, tall] = out.astype(o_ref.dtype)

    def a_span(i, carry):
        _in_step([a_head(i, j) for j in range(heads)])
        return carry

    lax.fori_loop(0, chunks // pack, a_span, 0)


def _heads_a_step(h: int) -> int:
    """Heads side by side in a grid step, taken through it in step: their
    chains of products are independent, so one head's fills another's
    waits. Eight heads' blocks and scratch do not fit the backward's VMEM."""
    return next(n for n in (4, 2, 1) if h % n == 0)


def _chunks_a_span(chunks: int, chunk: int) -> int:
    """Chunks formed as one span: as many as fill the MXU's 128 rows (two of
    64) and divide a grid step's chunks."""
    return next(n for n in range(max(1, 128 // chunk), 0, -1)
                if chunks % n == 0)


def _segment_chunks(t: int, chunk: int) -> int:
    """Chunks a grid step walks: the ``jax.numpy`` form's segment where the
    chunks fill whole segments, else (interpreted only) their largest
    divisor a segment holds."""
    chunks = t // chunk
    return next(n for n in range(min(SEGMENT, chunks), 0, -1)
                if chunks % n == 0)


def _specs(heads, keys, values, rows, all_heads, segment_of):
    """The block specs the two kernels share, for a grid (sequence, group of
    heads, step) whose step ``j`` walks segment ``segment_of(j)``: keys-wide,
    values-wide, beta, the segments' states."""
    from jax.experimental import pallas as pl

    return (
        pl.BlockSpec((1, rows, heads * keys),
                     lambda i, n, j: (i, segment_of(j), n)),
        pl.BlockSpec((1, rows, heads * values),
                     lambda i, n, j: (i, segment_of(j), n)),
        pl.BlockSpec((1, rows, all_heads),
                     lambda i, n, j: (i, segment_of(j), 0)),
        pl.BlockSpec((1, heads, 1, values, keys),
                     lambda i, n, j: (i, n, segment_of(j), 0, 0)))


_VMEM_LIMIT = 96 * 2 ** 20      # of a v5e's 128 MiB: a segment of four heads


# Traced once a shape, whoever calls: a model's K layers are separate modules,
# and without the jit's cache every layer's every pass traces the kernel's
# body (four heads written out) again, +10 s of a step's trace at four layers.
# Inlined, so the step's program is the one it would be without it (as a call
# of its own it cost the step 2.6% on the chip: PERF.md, PR 65).
@functools.partial(jax.jit, inline=True,
                   static_argnames=("chunk", "interpret"))
def _fwd_pallas(q, k, v, g, beta, *, chunk: int, interpret: bool):
    """(``o``, the state each segment starts from ``[segments, B, H, K, V]``
    float32: 33.5 MB at the published shape, written whether or not the call
    is differentiated, so that a model's plain and recomputed forward are
    one kernel)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    bsz, t, h, keys = k.shape
    values = v.shape[3]
    heads, chunks = _heads_a_step(h), _segment_chunks(t, chunk)
    segments = t // (chunks * chunk)
    wide_spec, tall_spec, beta_spec, state_spec = _specs(
        heads, keys, values, chunks * chunk, h, lambda j: j)
    vma = jax.typeof(k).vma     # inside a shard_map the outputs vary as k does
    out, states = pl.pallas_call(
        functools.partial(_fwd_kernel, heads=heads, chunk=chunk,
                          chunks=chunks, pack=_chunks_a_span(chunks, chunk),
                          sub=min(KERNEL_SUB, chunk)),
        grid=(bsz, h // heads, segments),
        in_specs=[wide_spec, wide_spec, tall_spec, wide_spec, beta_spec],
        out_specs=[tall_spec, state_spec],
        out_shape=[
            jax.ShapeDtypeStruct((bsz, t, h * values), v.dtype, vma=vma),
            jax.ShapeDtypeStruct((bsz, h, segments, values, keys),
                                 jnp.float32, vma=vma)],
        scratch_shapes=[pltpu.VMEM((heads, values, keys), jnp.float32)],
        # sequences and heads are independent; the segments carry the state
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret, name=KERNEL_NAMES[0],
    )(q.reshape(bsz, t, h * keys), k.reshape(bsz, t, h * keys),
      v.reshape(bsz, t, h * values),
      g.astype(jnp.float32).reshape(bsz, t, h * keys),
      beta.astype(jnp.float32))
    # [B, H, segments, V, K] -> the jax.numpy form's [segments, B, H, K, V]
    return out.reshape(v.shape), jnp.transpose(states, (2, 0, 1, 4, 3))


def _scores_transposed(qf, kf, total, d_a, d_p, dtype, sub: int, chunk: int):
    """(A generator.) :func:`_kernel_scores` transposed: ``(dq, dk, dG)`` ``[R,
    K]`` float32
    from ``dA`` (strictly lower) and ``dP`` (lower) ``[R, R]``, pair by pair
    as they were formed: a diagonal of the sub-blocks at a time (what
    reaches the earlier row rolled back up), then a sub-block against the
    rows of its chunk before it through its first row."""
    from jax.experimental.pallas import tpu as pltpu

    f32 = jnp.float32
    span, keys = kf.shape
    row, col = _grid2(span)
    inside_row = row - _over(row, sub) * sub
    dq = dk = d_total = jnp.zeros((span, keys), f32)
    for j in range(sub):
        on = (col == row - j) & (inside_row >= j)
        dp_j = jnp.sum(jnp.where(on, d_p, 0.0), axis=1, keepdims=True)
        if not j:
            dq, dk = dq + dp_j * kf, dk + dp_j * qf
            continue
        da_j = jnp.sum(jnp.where(on, d_a, 0.0), axis=1, keepdims=True)
        shifted = pltpu.roll(kf, j, 0)
        decay = jnp.exp(jnp.minimum(total - pltpu.roll(total, j, 0), 0.0))
        reach = shifted * decay
        dq, dk = dq + dp_j * reach, dk + da_j * reach
        back = (da_j * kf + dp_j * qf) * decay  # to the k of row r - j
        moved = back * shifted                  # what the pair moves G by
        dk = dk + pltpu.roll(back, span - j, 0)
        d_total = d_total + moved - pltpu.roll(moved, span - j, 0)
    lane = lax.broadcasted_iota(jnp.int32, (1, span), 1)
    first_row = lax.broadcasted_iota(jnp.int32, (sub, 1), 0) == 0
    none = jnp.zeros((sub, keys), f32)
    dq_rows, dk_rows, dt_rows = [], [], []
    dk_cols = dt_cols = jnp.zeros((span, keys), f32)
    for s in range(0, span, sub):
        start = s // chunk * chunk
        if s == start:
            dq_rows.append(none)
            dk_rows.append(none)
            dt_rows.append(none)
            continue
        first = total[s:s + 1]
        decay = jnp.exp(total[s:s + sub] - first)
        k_own, q_own = kf[s:s + sub], qf[s:s + sub]
        left = jnp.concatenate([k_own * decay, q_own * decay],
                               axis=0).astype(dtype)
        to_first = _rows_of(jnp.exp(jnp.minimum(
            first - total[start:start + chunk], 0.0)), start, span)
        right = kf * to_first
        d_across = jnp.where((lane >= start) & (lane < s), jnp.concatenate(
            [d_a[s:s + sub], d_p[s:s + sub]], axis=0), 0.0).astype(dtype)
        d_left = _nn(d_across, right.astype(dtype))         # [2 sub, K]
        d_right = _tn(d_across, left)                       # [R, K]
        own = (d_left[:sub] * k_own + d_left[sub:] * q_own) * decay
        theirs = d_right * right
        d_first = (jnp.sum(theirs, axis=0, keepdims=True)
                   - jnp.sum(own, axis=0, keepdims=True))
        dk_rows.append(d_left[:sub] * decay)
        dq_rows.append(d_left[sub:] * decay)
        dt_rows.append(own + jnp.where(first_row, d_first, 0.0))
        dk_cols = dk_cols + d_right * to_first
        dt_cols = dt_cols - theirs
        yield
    return (dq + jnp.concatenate(dq_rows, axis=0),
            dk + jnp.concatenate(dk_rows, axis=0) + dk_cols,
            d_total + jnp.concatenate(dt_rows, axis=0) + dt_cols)


def _transposed(formed, states, d_state, d_out, sub: int, chunk: int):
    """(A generator, :func:`_in_step`.) A span of a head transposed, from what :func:`_formed` gives, the
    state ``[V, K]`` each of its chunks started from, the gradient of the
    state it ended in and ``do [R, V]``: (the gradient of the state it
    started from, ``dq``, ``dk``, ``dv``, ``dg`` ``[R, ..]`` and ``dbeta [R,
    1]``, float32). Only the chunks' walk back is one after the other; the
    solve, the scores and the sums are the span's at once.
    ``flops/kda_moe_lm.kda_backward`` lists the products."""
    dtype = d_out.dtype
    qf, kf, reached, to_end, solved, beta = (formed[name] for name in (
        "qf", "kf", "reached", "to_end", "solved", "beta"))
    span, keys = kf.shape
    row, col = _grid2(span)
    same = _same_chunk(row, col, chunk)
    q_in, k_out = (qf * reached).astype(dtype), (kf * to_end).astype(dtype)
    pieces = [slice(n, n + chunk) for n in range(0, span, chunk)]
    rounded = [state.astype(dtype) for state in states]
    ws = [solved[rows, :keys].astype(dtype) for rows in pieces]
    us = [(solved[rows, keys:] - _nt(w, at)).astype(dtype)
          for rows, w, at in zip(pieces, ws, rounded)]
    d_p = jnp.where((row >= col) & same,
                    _nt(d_out, jnp.concatenate(us, axis=0)), 0.0)
    through_p = _tn(formed["p"].astype(dtype), d_out)       # P^T do
    yield
    last_row = lax.broadcasted_iota(jnp.int32, (chunk, 1), 0) == chunk - 1
    d_solved, d_k_out, d_q_in, d_last = [], [], [], []
    for n in reversed(range(len(pieces))):
        rows, kept = pieces[n], formed["kept"][n]
        d_rounded = d_state.astype(dtype)
        d_u = through_p[rows] + _nt(k_out[rows], d_rounded)
        d_u_rounded = d_u.astype(dtype)
        d_k_out.append(_nn(us[n], d_rounded))
        d_q_in.append(_nn(d_out[rows], rounded[n]))
        d_solved.append(jnp.concatenate(
            [-_nn(d_u_rounded, rounded[n]), d_u], axis=1))  # [dW | dU~]
        to_the_end = d_k_out[-1] * kf[rows] * to_end[rows]
        d_last.append(jnp.where(last_row, jnp.sum(
            to_the_end, axis=0, keepdims=True) + jnp.sum(
                d_state * states[n], axis=0, keepdims=True) * kept, 0.0)
            - to_the_end)
        yield
        d_state = (kept * d_state + _tn(d_out[rows], q_in[rows])
                   - _tn(d_u_rounded, ws[n]))
        yield
    d_solved, d_k_out, d_q_in, d_last = (
        jnp.concatenate(of[::-1], axis=0)
        for of in (d_solved, d_k_out, d_q_in, d_last))
    # the solve transposed: to its right-hand side and to its matrix
    d_rhs = _tn(formed["inverse"], d_solved, _HIGHEST)
    yield
    d_lower = jnp.where((row > col) & same,
                        -_nt(d_rhs, solved, _HIGHEST), 0.0)
    yield
    d_beta = (jnp.sum(d_lower * formed["a"], axis=1, keepdims=True)
              + jnp.sum(d_rhs * formed["plain"], axis=1, keepdims=True))
    d_plain = beta * d_rhs
    d_reached_k = d_plain[:, :keys]                         # of K e^G
    dq, dk, d_total = yield from _scores_transposed(
        qf, kf, formed["total"], beta * d_lower, d_p, dtype, sub, chunk)
    d_total = (d_total + (d_reached_k * kf + d_q_in * qf) * reached
               + d_last)
    return (d_state, dq + d_q_in * reached,
            dk + d_reached_k * reached + d_k_out * to_end,
            d_plain[:, keys:],
            _exact_nn((row <= col) & same, d_total), d_beta)


def _bwd_kernel(q_ref, k_ref, v_ref, g_ref, b_ref, states_ref, do_ref,
                dq_ref, dk_ref, dv_ref, dg_ref, db_ref,
                d_state, starts, inverses, solveds,
                *, heads: int, chunk: int, chunks: int, pack: int, sub: int):
    from jax.experimental import pallas as pl

    keys = q_ref.shape[2] // heads
    values = v_ref.shape[2] // heads

    @pl.when(pl.program_id(2) == 0)
    def _start():
        d_state[...] = jnp.zeros_like(d_state)

    first_head = pl.program_id(1) * heads
    starts[:, 0] = states_ref[0, :, 0]
    refs = (q_ref, k_ref, v_ref, g_ref, b_ref)

    span = pack * chunk

    def of(i, j):
        return _loaded(refs, i, j, first_head, span, keys, values)

    # the segment walked forward: the state each chunk starts from, with
    # the span's inverse and [W | U~] kept in VMEM for the walk back
    def forward_head(i, j):
        rows, _, _, inputs = of(i, j)
        formed = yield from _formed(*inputs, sub, chunk, with_p=False)
        inverses[j, rows, :] = formed["inverse"]
        solveds[j, rows, :] = formed["solved"]
        after, _, started = yield from _walked(
            starts[j, i * pack], formed, v_ref.dtype, chunk)
        for n, state in enumerate(started[1:] + [after]):
            starts[j, i * pack + n + 1] = state

    def forward(i, carry):
        _in_step([forward_head(i, j) for j in range(heads)])
        return carry

    lax.fori_loop(0, chunks // pack, forward, 0)

    def backward_head(i, j):
        rows, wide, tall, inputs = of(i, j)
        formed = yield from _formed(*inputs, sub, chunk, solve=(
            inverses[j, rows, :], solveds[j, rows, :]))
        d_state[j], dq, dk, dv, dg, d_beta = yield from _transposed(
            formed, [starts[j, i * pack + n] for n in range(pack)],
            d_state[j], do_ref[0, rows, tall], sub, chunk)
        dq_ref[0, rows, wide] = dq.astype(dq_ref.dtype)
        dk_ref[0, rows, wide] = dk.astype(dk_ref.dtype)
        dv_ref[0, rows, tall] = dv.astype(dv_ref.dtype)
        dg_ref[0, rows, wide] = dg
        db_ref[0, 0, rows, j:j + 1] = d_beta

    def backward(step, carry):
        _in_step([backward_head(chunks // pack - 1 - step, j)
                  for j in range(heads)])
        return carry

    lax.fori_loop(0, chunks // pack, backward, 0)


@functools.partial(jax.jit, inline=True,
                   static_argnames=("chunk", "interpret"))
def _bwd_pallas(q, k, v, g, beta, states, d_out, *, chunk: int,
                interpret: bool):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    bsz, t, h, keys = k.shape
    values, f32 = v.shape[3], jnp.float32
    heads, chunks = _heads_a_step(h), _segment_chunks(t, chunk)
    segments, rows = t // (chunks * chunk), chunks * chunk
    wide_spec, tall_spec, beta_spec, state_spec = _specs(
        heads, keys, values, rows, h, lambda j: segments - 1 - j)
    vma = jax.typeof(k).vma
    dq, dk, dv, dg, db = pl.pallas_call(
        functools.partial(_bwd_kernel, heads=heads, chunk=chunk,
                          chunks=chunks, pack=_chunks_a_span(chunks, chunk),
                          sub=min(KERNEL_SUB, chunk)),
        grid=(bsz, h // heads, segments),
        in_specs=[wide_spec, wide_spec, tall_spec, wide_spec, beta_spec,
                  state_spec, tall_spec],
        out_specs=[wide_spec, wide_spec, tall_spec, wide_spec,
                   pl.BlockSpec((1, 1, rows, heads), lambda i, n, j: (
                       i, n, segments - 1 - j, 0))],
        out_shape=[jax.ShapeDtypeStruct(shape, dtype, vma=vma)
                   for shape, dtype in (
                       ((bsz, t, h * keys), q.dtype),
                       ((bsz, t, h * keys), k.dtype),
                       ((bsz, t, h * values), v.dtype),
                       ((bsz, t, h * keys), f32),
                       ((bsz, h // heads, t, heads), f32))],
        scratch_shapes=[
            pltpu.VMEM((heads, values, keys), f32),     # the state's gradient
            pltpu.VMEM((heads, chunks + 1, values, keys), f32),
            pltpu.VMEM((heads, rows, _chunks_a_span(chunks, chunk) * chunk),
                       f32),
            pltpu.VMEM((heads, rows, keys + values), f32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret, name=KERNEL_NAMES[1],
    )(q.reshape(bsz, t, h * keys), k.reshape(bsz, t, h * keys),
      v.reshape(bsz, t, h * values), g.astype(f32).reshape(bsz, t, h * keys),
      beta.astype(f32),
      # the jax.numpy form's [segments, B, H, K, V] -> [B, H, segments, V, K]
      jnp.transpose(states, (1, 2, 0, 4, 3)),
      d_out.astype(v.dtype).reshape(bsz, t, h * values))
    return (dq.reshape(q.shape), dk.reshape(k.shape), dv.reshape(v.shape),
            dg.reshape(g.shape).astype(g.dtype),
            db.transpose(0, 2, 1, 3).reshape(beta.shape).astype(beta.dtype))


# ---------------------------------------------------------------------------
# The op
# ---------------------------------------------------------------------------
def _chunks(q, chunk: int) -> int:
    bsz, t, h = q.shape[:3]
    return bsz * h * -(-t // chunk)


def _use_pallas(k, v, chunk: int, interpret: bool) -> bool:
    """Can the kernels take this call? Interpreted: whole chunks of a length
    the inverse doubles up to. Compiled: whenever :func:`kernel_ineligible`
    says nothing; whether they then *run* is decided when the program is
    lowered (for a TPU they do, elsewhere the ``jax.numpy`` form)."""
    t, keys, values = k.shape[1], k.shape[3], v.shape[3]
    if interpret:
        return t % chunk == 0 and _chunk_refused(chunk) is None
    return kernel_ineligible(t, chunk, keys, values) is None


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6))
def _kda(q, k, v, g, beta, chunk, interpret):
    jnp_fn = functools.partial(kda_chunked_jnp, chunk=chunk)
    if not _use_pallas(k, v, chunk, interpret):
        return jnp_fn(q, k, v, g, beta)
    return _by_platform(
        lambda *args: _fwd_pallas(*args, chunk=chunk, interpret=interpret)[0],
        jnp_fn, interpret, q, k, v, g, beta)


def _kda_fwd(q, k, v, g, beta, chunk, interpret):
    inputs = (q, k, v, g, beta)
    if not _use_pallas(k, v, chunk, interpret):
        out, states = _forward(*inputs, chunk)
    else:
        out, states = _by_platform(
            functools.partial(_fwd_pallas, chunk=chunk, interpret=interpret),
            functools.partial(_forward, chunk=chunk), interpret, *inputs)
    return out, (*inputs, states)


def _bwd_jnp(q, k, v, g, beta, states, d_out, *, chunk: int):
    """The segments from the last to the first with the state's gradient
    carried: a segment is formed again from the state it started from (which
    the forward pass kept, ``T / 1024`` of them a head) and transposed."""
    inputs = (q, k, v, g, beta)
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


def _kda_bwd(chunk, interpret, residuals, d_out):
    q, k, v = residuals[:3]
    _count("kda_chunks_total", _chunks(q, chunk), "backward")
    jnp_fn = functools.partial(_bwd_jnp, chunk=chunk)
    if not _use_pallas(k, v, chunk, interpret):
        return jnp_fn(*residuals, d_out)
    return _by_platform(
        functools.partial(_bwd_pallas, chunk=chunk, interpret=interpret),
        jnp_fn, interpret, *residuals, d_out)


_kda.defvjp(_kda_fwd, _kda_bwd)


def kda_scan(q, k, v, g, beta, chunk: int = 64, interpret: bool = False):
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
    chunk, interpret = int(chunk), bool(interpret)
    holds_kernels = _use_pallas(k, v, chunk, interpret)
    if holds_kernels:
        _count("kda_scan_total", 1, "kernel")
    if not (holds_kernels and interpret):   # the form is traced: a fallback
        _count("kda_scan_total", 1, "jnp")  # branch is one too
    _count("kda_chunks_total", _chunks(q, chunk), "forward")
    return _kda(q, k, v, g, beta, chunk, interpret)


def kda_scan_sharded(q, k, v, g, beta, mesh, **kwargs):
    """:func:`kda_scan` mapped over the mesh's data axes (None or one device:
    the plain call): the scan is independent along the batch, and the
    partitioner cannot split a custom call. Heads stay whole on every device
    (``tensor`` replicates them)."""
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    from raydp_tpu.parallel.mesh import data_axes

    fn = functools.partial(kda_scan, **kwargs)
    if mesh is None or mesh.size == 1:
        return fn(q, k, v, g, beta)
    batch = data_axes(mesh)
    batch = batch if len(batch) > 1 else batch[0]
    rows = lambda rank: P(batch, *(None,) * (rank - 1))  # noqa: E731
    return shard_map(fn, mesh=mesh,
                     in_specs=(rows(4), rows(4), rows(4), rows(4), rows(3)),
                     out_specs=rows(4))(q, k, v, g, beta)
