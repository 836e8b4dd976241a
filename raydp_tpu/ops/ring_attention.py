"""Ring attention: exact attention over sequence-sharded inputs.

The sequence axis is sharded over the mesh's ``seq`` axis; each device holds a
[B, T/n, H, D] slice of Q/K/V. K/V blocks rotate around the ring with
``ppermute`` while every device accumulates its queries' attention over each
passing block using the online-softmax (flash) recurrence, so the full [T, T]
score matrix never materializes and memory stays O(T/n). Collectives ride ICI
neighbor links — the layout the hardware gives ring ``ppermute`` for free.

The reference framework has no sequence parallelism at all (SURVEY.md §2.4: "every
other strategy is absent") — this op is the long-context capability the TPU build
adds. Two properties keep it viable at pod scale:

- **bounded local memory**: within a ring step the passing K/V block is folded
  in ``chunk_size`` key chunks (inner ``lax.scan``), so the largest live score
  block is [B, H, T/n, chunk] — without it a 128k-token sequence over 16
  devices would materialize 8k x 8k scores per head per step;
- **causal step skipping**: a block arriving from a strictly-future source
  contributes nothing under causality; ``lax.cond`` skips its entire update
  (the ``ppermute`` still runs — the ring must keep rotating), saving ~half
  the FLOPs the way the flash kernel skips whole blocks above the triangle.

The single-device memory-efficient kernel lives separately in
:mod:`raydp_tpu.ops.flash_attention`.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax


def _local_attention_update(q, k, v, m, l, acc, mask=None, scale=1.0):
    """One online-softmax update of (m, l, acc) with a new K/V block.

    q: [B, Tq, H, D]; k, v: [B, Tk, H, D]; m, l: [B, H, Tq]; acc: [B, Tq, H, D].
    """
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale  # [B, H, Tq, Tk]
    if mask is not None:
        scores = jnp.where(mask, scores, -jnp.inf)
    m_blk = jnp.max(scores, axis=-1)                      # [B, H, Tq]
    m_new = jnp.maximum(m, m_blk)
    # guard fully-masked rows: exp(-inf - -inf) -> exp(0) would be wrong
    safe_m = jnp.where(jnp.isneginf(m_new), 0.0, m_new)
    p = jnp.exp(scores - safe_m[..., None])
    p = jnp.where(jnp.isneginf(scores), 0.0, p)
    correction = jnp.where(jnp.isneginf(m), 0.0, jnp.exp(m - safe_m))
    l_new = l * correction + jnp.sum(p, axis=-1)
    pv = jnp.einsum("bhqk,bkhd->bqhd", p, v)
    acc_new = acc * correction.transpose(0, 2, 1)[..., None] + pv
    return m_new, l_new, acc_new


def _folded_block_update(q, k_blk, v_blk, m, l, acc, q_positions, k_pos0,
                         scale: float, causal: bool, chunk: Optional[int]):
    """Fold one K/V block into (m, l, acc), ``chunk`` keys at a time. The
    key dim is zero-padded up to a chunk multiple and the pad keys masked
    out, so the memory bound holds for EVERY t_local (a prime t_local does
    not degenerate into single-key chunks)."""
    b, tk, h, d = k_blk.shape

    if chunk is None or chunk >= tk:
        if causal:
            k_positions = k_pos0 + jnp.arange(tk)
            mask = (q_positions[:, None] >= k_positions[None, :])[None, None]
        else:
            mask = None
        return _local_attention_update(q, k_blk.astype(jnp.float32),
                                       v_blk.astype(jnp.float32),
                                       m, l, acc, mask=mask, scale=scale)

    n = -(-tk // chunk)                    # ceil: ragged tail padded + masked
    pad = n * chunk - tk
    if pad:
        k_blk = jnp.pad(k_blk, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v_blk = jnp.pad(v_blk, ((0, 0), (0, pad), (0, 0), (0, 0)))
    kc = k_blk.reshape(b, n, chunk, h, d).transpose(1, 0, 2, 3, 4)
    vc = v_blk.reshape(b, n, chunk, h, d).transpose(1, 0, 2, 3, 4)

    def inner(carry, xs):
        m, l, acc = carry
        k_c, v_c, i = xs
        offsets = i * chunk + jnp.arange(chunk)
        valid = (offsets < tk)[None, :]                       # mask pad keys
        if causal:
            k_positions = k_pos0 + offsets
            valid = valid & (q_positions[:, None] >= k_positions[None, :])
        m, l, acc = _local_attention_update(
            q, k_c.astype(jnp.float32), v_c.astype(jnp.float32),
            m, l, acc, mask=valid[None, None], scale=scale)
        return (m, l, acc), None

    (m, l, acc), _ = lax.scan(inner, (m, l, acc), (kc, vc, jnp.arange(n)))
    return m, l, acc


def ring_attention(q, k, v, axis_name: str = "seq", causal: bool = True,
                   scale: Optional[float] = None,
                   chunk_size: Optional[int] = 2048):
    """Exact attention for sequence-sharded q/k/v; call inside ``shard_map``.

    Shapes per device: q, k, v = [B, T_local, H, D]. Returns [B, T_local, H, D].
    ``chunk_size`` caps the live score block at [B, H, T_local, chunk_size]
    (None = fold each arriving block in one piece).
    """
    axis_size = lax.psum(1, axis_name)
    my_index = lax.axis_index(axis_name)
    b, t_local, h, d = q.shape
    scale = scale if scale is not None else 1.0 / (d ** 0.5)

    q_positions = my_index * t_local + jnp.arange(t_local)  # global q positions

    from raydp_tpu.parallel.mesh import vary_manual
    vma = tuple(jax.typeof(q).vma) or (axis_name,)
    m0 = vary_manual(jnp.full((b, h, t_local), -jnp.inf, jnp.float32), vma)
    l0 = vary_manual(jnp.zeros((b, h, t_local), jnp.float32), vma)
    acc0 = vary_manual(jnp.zeros((b, t_local, h, d), jnp.float32), vma)

    perm = [(i, (i + 1) % axis_size) for i in range(axis_size)]

    qf = q.astype(jnp.float32)

    def step(carry, step_idx):
        m, l, acc, k_blk, v_blk = carry
        # the block currently on this device originated at (my_index - step)
        src = (my_index - step_idx) % axis_size
        k_pos0 = src * t_local

        def update(args):
            m, l, acc = args
            return _folded_block_update(qf, k_blk, v_blk, m, l, acc,
                                        q_positions, k_pos0, scale, causal,
                                        chunk_size)

        if causal:
            # a block from a strictly-future source is fully masked: skip the
            # whole update (the rotation below still runs)
            m, l, acc = lax.cond(src <= my_index, update,
                                 lambda args: args, (m, l, acc))
        else:
            m, l, acc = update((m, l, acc))
        # rotate K/V to the next neighbor (overlaps with next local compute
        # when XLA schedules the collective-permute asynchronously)
        k_next = lax.ppermute(k_blk, axis_name, perm)
        v_next = lax.ppermute(v_blk, axis_name, perm)
        return (m, l, acc, k_next, v_next), None

    (m, l, acc, _, _), _ = lax.scan(
        step, (m0, l0, acc0, k, v), jnp.arange(axis_size))
    l = jnp.maximum(l, 1e-20)
    out = acc / l.transpose(0, 2, 1)[..., None]
    return out.astype(q.dtype)


def ring_attention_sharded(q, k, v, mesh, causal: bool = True,
                           seq_axis: str = "seq", batch_axes=("data", "fsdp"),
                           head_axis: str = "tensor",
                           chunk_size: Optional[int] = 2048):
    """shard_map wrapper: [B, T, H, D] arrays sharded (batch over data axes,
    sequence over ``seq_axis``, heads over ``head_axis`` when present) → same
    sharding out. Ring + head sharding compose: each (seq, tensor) tile ships
    only its own heads' K/V around the ring."""
    from jax.sharding import PartitionSpec as P
    from jax import shard_map

    batch = tuple(a for a in batch_axes if a in mesh.axis_names
                  and mesh.shape[a] > 1)
    bspec = batch if len(batch) > 1 else (batch[0] if batch else None)
    hspec = head_axis if (head_axis in mesh.axis_names
                          and mesh.shape[head_axis] > 1) else None
    spec = P(bspec, seq_axis, hspec, None)

    fn = functools.partial(ring_attention, axis_name=seq_axis, causal=causal,
                           chunk_size=chunk_size)
    return shard_map(fn, mesh=mesh, in_specs=(spec, spec, spec),
                     out_specs=spec)(q, k, v)


def dense_attention(q, k, v, causal: bool = True,
                    scale: Optional[float] = None,
                    window: Optional[int] = None,
                    blockdiff: Optional[int] = None):
    """Unsharded reference implementation (for tests and single-device use).
    ``k`` and ``v`` may hold fewer heads than ``q`` (grouped-query: query
    head ``h`` reads K/V head ``h // group``); ``window`` (causal only) keeps
    a query's own key and the ``window - 1`` before it; ``blockdiff`` is the
    block-diffusion mask of :func:`raydp_tpu.ops.flash_attention
    .blockdiff_visible` over a clean and a noised copy of ``T / 2`` tokens."""
    b, t, h, d = q.shape
    scale = scale if scale is not None else 1.0 / (d ** 0.5)
    group = h // k.shape[2]
    if group > 1:
        k, v = jnp.repeat(k, group, axis=2), jnp.repeat(v, group, axis=2)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32),
                        k.astype(jnp.float32)) * scale
    if blockdiff is not None:
        from raydp_tpu.ops.flash_attention import blockdiff_visible

        at = jnp.arange(t)
        scores = jnp.where(blockdiff_visible(
            at[:, None], at[None, :], blockdiff, t // 2)[None, None], scores,
            -jnp.inf)
    elif causal:
        mask = jnp.tril(jnp.ones((t, t), dtype=bool))
        if window is not None:
            mask = mask & ~jnp.tril(jnp.ones((t, t), dtype=bool), -window)
        scores = jnp.where(mask[None, None], scores, -jnp.inf)
    p = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bhqk,bkhd->bqhd", p, v.astype(jnp.float32))
    return out.astype(q.dtype)
