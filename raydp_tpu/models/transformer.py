"""Decoder-only Transformer LM — the long-context model family.

The reference exercises only MLPs/DLRM over tabular data and ships no
sequence parallelism (SURVEY.md §2.4, §5 "long-context: absent"); this model is
the capability the TPU build adds on top of parity. The attention layer
dispatches by configuration:

- ``attention="ring"`` — exact attention over a sequence-sharded batch via
  :func:`raydp_tpu.ops.ring_attention.ring_attention_sharded`: K/V blocks
  rotate around the mesh's ``seq`` axis with ``ppermute`` (ICI neighbor links),
  memory O(T / seq_devices) per device;
- ``attention="flash"`` — memory-efficient attention via the first-party
  Pallas kernel (:mod:`raydp_tpu.ops.flash_attention`), mapped over the
  mesh's batch and head axes when a mesh is given; on a TPU backend a shape
  the kernel cannot take raises (off the chip the op runs its jnp path);
- ``attention="dense"`` — reference path for tests;
- ``attention="auto"`` — ring when the mesh has a ``seq`` axis > 1, else flash
  on TPU for shapes the kernel takes, else dense.

Architecture: pre-RMSNorm blocks, rotary position embeddings, SwiGLU MLP —
all plain dense ops XLA tiles onto the MXU; bf16-friendly throughout
(``dtype`` controls activations, params stay f32 for stable optimization).
"""

from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp
import numpy as np
from flax import linen as nn


def rotary_embedding(x: jnp.ndarray, positions: jnp.ndarray,
                     base: float = 10000.0) -> jnp.ndarray:
    """Apply RoPE. x: [B, T, H, D]; positions: [T] global token positions."""
    d_half = x.shape[-1] // 2
    freqs = 1.0 / (base ** (np.arange(0, d_half) / d_half))
    angles = positions[:, None] * freqs[None, :]            # [T, D/2]
    cos = jnp.cos(angles)[None, :, None, :]
    sin = jnp.sin(angles)[None, :, None, :]
    x1, x2 = x[..., :d_half], x[..., d_half:]
    return jnp.concatenate([x1 * cos - x2 * sin,
                            x1 * sin + x2 * cos], axis=-1).astype(x.dtype)


class RMSNorm(nn.Module):
    eps: float = 1e-6

    @nn.compact
    def __call__(self, x):
        scale = self.param("scale", nn.initializers.ones, (x.shape[-1],))
        var = jnp.mean(jnp.square(x.astype(jnp.float32)), axis=-1,
                       keepdims=True)
        return (x * jax.lax.rsqrt(var + self.eps)).astype(x.dtype) * scale


class Attention(nn.Module):
    num_heads: int
    attention: str = "auto"
    mesh: Any = None
    dtype: Any = jnp.float32

    def _dispatch(self, t: int, head_dim: int) -> str:
        from raydp_tpu.ops.flash_attention import kernel_ineligible
        from raydp_tpu.parallel.mesh import seq_extent

        if self.attention != "auto":
            return self.attention
        if self.mesh is not None and seq_extent(self.mesh) > 1:
            return "ring"
        on_kernel = (jax.default_backend() == "tpu"
                     and kernel_ineligible(t, head_dim) is None)
        return "flash" if on_kernel else "dense"

    @nn.compact
    def __call__(self, x):
        from raydp_tpu.ops.flash_attention import flash_attention_sharded
        from raydp_tpu.ops.ring_attention import (
            dense_attention, ring_attention_sharded)

        b, t, dim = x.shape
        head_dim = dim // self.num_heads
        dense = lambda name: nn.DenseGeneral(  # noqa: E731
            (self.num_heads, head_dim), axis=-1, name=name, dtype=self.dtype,
            use_bias=False)
        q, k, v = dense("q")(x), dense("k")(x), dense("v")(x)

        positions = jnp.arange(t)
        q = rotary_embedding(q, positions)
        k = rotary_embedding(k, positions)

        kind = self._dispatch(t, head_dim)
        if kind == "ring":
            out = ring_attention_sharded(q, k, v, self.mesh, causal=True)
        elif kind == "flash":
            out = flash_attention_sharded(q, k, v, self.mesh, causal=True)
        else:
            out = dense_attention(q, k, v, causal=True)
        return nn.DenseGeneral(dim, axis=(-2, -1), name="o", dtype=self.dtype,
                               use_bias=False)(out)


class Block(nn.Module):
    num_heads: int
    mlp_ratio: int = 4
    attention: str = "auto"
    mesh: Any = None
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x):
        dim = x.shape[-1]
        x = x + Attention(self.num_heads, self.attention, self.mesh,
                          self.dtype, name="attn")(RMSNorm(name="ln1")(x))
        h = RMSNorm(name="ln2")(x)
        hidden = self.mlp_ratio * dim
        # SwiGLU
        gate = nn.Dense(hidden, use_bias=False, dtype=self.dtype,
                        name="gate")(h)
        up = nn.Dense(hidden, use_bias=False, dtype=self.dtype, name="up")(h)
        down = nn.Dense(dim, use_bias=False, dtype=self.dtype,
                        name="down")(nn.silu(gate) * up)
        return x + down


class TransformerLM(nn.Module):
    """Causal LM: tokens [B, T] int32 → logits [B, T, vocab]."""

    vocab_size: int
    dim: int = 256
    num_heads: int = 4
    num_layers: int = 2
    mlp_ratio: int = 4
    attention: str = "auto"
    mesh: Any = None
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, tokens, return_hidden: bool = False):
        """``return_hidden=True`` yields the post-norm hidden states [B,T,D]
        (the lm_head weight is still created so the param tree is identical);
        pair it with :func:`lm_loss_fused`, which applies the head per
        T-chunk so the [B,T,V] float32 logits never materialize — at 32k
        vocab and T=8192 those logits are ~2 GB per direction of pure HBM
        traffic, the single largest non-kernel cost in the train step."""
        x = nn.Embed(self.vocab_size, self.dim, name="embed",
                     dtype=self.dtype)(tokens)
        for i in range(self.num_layers):
            x = Block(self.num_heads, self.mlp_ratio, self.attention,
                      self.mesh, self.dtype, name=f"block_{i}")(x)
        x = RMSNorm(name="ln_f")(x)
        head = nn.Dense(self.vocab_size, use_bias=False, dtype=self.dtype,
                        name="lm_head")
        if return_hidden:
            head(x[:, :1])  # registers the kernel (result DCE'd); the head
            return x        # itself is applied chunk-wise by lm_loss_fused
        return head(x).astype(jnp.float32)


def lm_loss(logits: jnp.ndarray, tokens: jnp.ndarray) -> jnp.ndarray:
    """Next-token cross entropy (shifted); tokens [B, T], logits [B, T, V]."""
    import optax

    return optax.softmax_cross_entropy_with_integer_labels(
        logits[:, :-1], tokens[:, 1:]).mean()


def lm_loss_fused(hidden: jnp.ndarray, lm_head_kernel: jnp.ndarray,
                  tokens: jnp.ndarray, chunk: int = 1024,
                  remat: bool = True) -> jnp.ndarray:
    """Next-token cross entropy with the lm_head FUSED into the loss.

    The head matmul + softmax-CE run per T-chunk of ``chunk`` positions under
    ``jax.checkpoint`` inside a ``lax.scan``: forward keeps only the hidden
    states (already live) and per-chunk scalars, backward recomputes each
    chunk's logits — peak logits footprint is ``B×chunk×V`` instead of
    ``B×T×V`` f32 (64× smaller at T=8192/chunk=1024/f32), while each chunk
    matmul ``[B·chunk, D] @ [D, V]`` stays MXU-sized. This trades one extra
    head matmul (recompute) for ~4 GB of HBM round-trips per step at the
    bench shape, which is bandwidth the step actually runs out of — the
    round-2 gap between kernel MFU (51%) and e2e MFU (35%).

    ``hidden`` [B, T, D] from ``model(tokens, return_hidden=True)``;
    ``lm_head_kernel`` [D, V] = ``params["lm_head"]["kernel"]``.
    """
    import optax
    from jax import lax

    B, T, D = hidden.shape
    x = hidden[:, :-1]                   # predict positions 1..T-1
    y = tokens[:, 1:]
    n = T - 1
    pad = (-n) % chunk
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0)))
        y = jnp.pad(y, ((0, 0), (0, pad)))
    mask = (jnp.arange(n + pad) < n).astype(jnp.float32)[None, :]
    nchunks = (n + pad) // chunk
    xs = x.reshape(B, nchunks, chunk, D).swapaxes(0, 1)      # [N, B, C, D]
    ys = y.reshape(B, nchunks, chunk).swapaxes(0, 1)         # [N, B, C]
    ms = mask.reshape(1, nchunks, chunk).swapaxes(0, 1)      # [N, 1, C]

    def chunk_ce(total, xyz):
        xc, yc, mc = xyz
        logits = (xc @ lm_head_kernel.astype(xc.dtype)).astype(jnp.float32)
        ce = optax.softmax_cross_entropy_with_integer_labels(logits, yc)
        return total + (ce * mc).sum(), None

    body = jax.checkpoint(chunk_ce) if remat else chunk_ce
    total, _ = lax.scan(body, jnp.zeros((), jnp.float32), (xs, ys, ms))
    return total / (B * n)


def transformer_param_rules(axis: str = "tensor"):
    """Megatron-style tensor-parallel sharding rules for :class:`TransformerLM`
    (for ``FlaxEstimator(param_rules=...)`` / ``param_sharding_rules``).

    Column-parallel up-projections (q/k/v over heads, gate/up over hidden) and
    row-parallel down-projections (o, down) — GSPMD then inserts exactly one
    all-reduce per attention block and one per MLP block, the classic split.
    Embedding and lm_head shard over the vocab/feature dim. The ``tensor``
    axis should be innermost on hardware so these per-layer collectives ride
    the fastest ICI links (raydp_tpu/parallel/mesh.py axis order).
    """
    return [
        ("attn/q/kernel", (None, axis, None)),
        ("attn/k/kernel", (None, axis, None)),
        ("attn/v/kernel", (None, axis, None)),
        ("attn/o/kernel", (axis, None, None)),
        ("gate/kernel", (None, axis)),
        ("up/kernel", (None, axis)),
        ("down/kernel", (axis, None)),
        ("embed/embedding", (None, axis)),
        ("lm_head/kernel", (None, axis)),
    ]
