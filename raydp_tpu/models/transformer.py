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
The feed-forward width is ``mlp_ratio * dim`` or, given outright, ``ffn_dim``;
``rms_norm_eps``, ``rope_theta`` and ``qk_norm`` (RMSNorm over the whole query
and key projections before the split into heads) follow a published config.
With ``num_experts > 0`` every block's feed-forward is the sparse expert
layer of :mod:`raydp_tpu.models.moe` (``ffn_dim`` is then one expert's
width), and the model's training loss carries its two auxiliary losses.

A model that is trained by :class:`raydp_tpu.train.FlaxEstimator` hands the
train step its loss itself (``loss_rows``): next-token cross entropy with the
head applied chunk by chunk (:func:`lm_loss_fused`'s scan), so the
``[B, T, vocab]`` float32 logits never exist. Called plainly the model still
returns them.
"""

from __future__ import annotations

from typing import Any, Optional

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
        # statistics and scaling in float32, the result at the activations'
        # width: a float32 result would make every product after a norm
        # (and the attention kernel after a QK-norm) a float32 one
        return (x * jax.lax.rsqrt(var + self.eps) * scale).astype(x.dtype)


def _init(std: Optional[float], default):
    """``normal(std)`` where a configuration states one, else flax's own."""
    return default if std is None else nn.initializers.normal(std)


class Attention(nn.Module):
    num_heads: int
    attention: str = "auto"
    mesh: Any = None
    dtype: Any = jnp.float32
    rope_theta: float = 10000.0
    qk_norm: bool = False
    rms_norm_eps: float = 1e-6
    init_std: Optional[float] = None

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
        init = _init(self.init_std, nn.linear.default_kernel_init)
        dense = lambda name: nn.DenseGeneral(  # noqa: E731
            (self.num_heads, head_dim), axis=-1, name=name, dtype=self.dtype,
            use_bias=False, kernel_init=init)
        q, k, v = dense("q")(x), dense("k")(x), dense("v")(x)
        if self.qk_norm:
            # over the whole projection (all heads together), then split
            norm = lambda name, a: RMSNorm(  # noqa: E731
                self.rms_norm_eps, name=name)(
                    a.reshape(b, t, dim)).reshape(a.shape)
            q, k = norm("q_norm", q), norm("k_norm", k)

        positions = jnp.arange(t)
        q = rotary_embedding(q, positions, self.rope_theta)
        k = rotary_embedding(k, positions, self.rope_theta)

        kind = self._dispatch(t, head_dim)
        if kind == "ring":
            out = ring_attention_sharded(q, k, v, self.mesh, causal=True)
        elif kind == "flash":
            out = flash_attention_sharded(q, k, v, self.mesh, causal=True)
        else:
            out = dense_attention(q, k, v, causal=True)
        return nn.DenseGeneral(dim, axis=(-2, -1), name="o", dtype=self.dtype,
                               use_bias=False, kernel_init=init)(out)


class Block(nn.Module):
    """One pre-norm block. Dense (``num_experts == 0``): ``x -> x``. Sparse:
    ``x -> (x, aux)``, ``aux`` what :class:`raydp_tpu.models.moe.MoE`
    returns beside its output."""

    num_heads: int
    mlp_ratio: int = 4
    attention: str = "auto"
    mesh: Any = None
    dtype: Any = jnp.float32
    ffn_dim: Optional[int] = None
    rms_norm_eps: float = 1e-6
    rope_theta: float = 10000.0
    qk_norm: bool = False
    num_experts: int = 0
    experts_per_token: int = 0
    init_std: Optional[float] = None

    @nn.compact
    def __call__(self, x):
        from raydp_tpu.models.moe import MoE

        dim = x.shape[-1]
        eps = self.rms_norm_eps
        x = x + Attention(self.num_heads, self.attention, self.mesh,
                          self.dtype, self.rope_theta, self.qk_norm, eps,
                          self.init_std, name="attn")(
                              RMSNorm(eps, name="ln1")(x))
        h = RMSNorm(eps, name="ln2")(x)
        hidden = self.ffn_dim or self.mlp_ratio * dim
        init = _init(self.init_std, nn.linear.default_kernel_init)
        if self.num_experts:
            y, aux = MoE(self.num_experts, self.experts_per_token, hidden,
                         self.dtype, init, name="moe")(h)
            return x + y, aux
        # SwiGLU
        dense = lambda n, name: nn.Dense(  # noqa: E731
            n, use_bias=False, dtype=self.dtype, name=name, kernel_init=init)
        down = dense(dim, "down")(
            nn.silu(dense(hidden, "gate")(h)) * dense(hidden, "up")(h))
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
    ffn_dim: Optional[int] = None
    rms_norm_eps: float = 1e-6
    rope_theta: float = 10000.0
    qk_norm: bool = False
    num_experts: int = 0
    experts_per_token: int = 0
    balance_loss_weight: float = 0.01
    z_loss_weight: float = 0.001
    init_std: Optional[float] = None

    @nn.compact
    def __call__(self, tokens, return_hidden: bool = False, labels=None):
        """``return_hidden=True`` yields the post-norm hidden states [B,T,D]
        (the lm_head weight is still created so the param tree is identical);
        pair it with :func:`lm_loss_fused`, which applies the head per
        T-chunk so the [B,T,V] float32 logits never materialize — at 32k
        vocab and T=8192 those logits are ~2 GB per direction of pure HBM
        traffic, the single largest non-kernel cost in the train step.
        ``labels`` [B, T] (the tokens themselves) yields what
        :meth:`loss_rows` returns."""
        init = _init(self.init_std, nn.linear.default_embed_init)
        x = nn.Embed(self.vocab_size, self.dim, name="embed",
                     dtype=self.dtype, embedding_init=init)(tokens)
        aux = []
        for i in range(self.num_layers):
            x = Block(self.num_heads, self.mlp_ratio, self.attention,
                      self.mesh, self.dtype, self.ffn_dim, self.rms_norm_eps,
                      self.rope_theta, self.qk_norm, self.num_experts,
                      self.experts_per_token, self.init_std,
                      name=f"block_{i}")(x)
            if self.num_experts:
                x, layer_aux = x
                aux.append(layer_aux)
        x = RMSNorm(self.rms_norm_eps, name="ln_f")(x)
        head = nn.Dense(self.vocab_size, use_bias=False, dtype=self.dtype,
                        name="lm_head", kernel_init=_init(
                            self.init_std, nn.linear.default_kernel_init))
        if labels is None and not return_hidden:
            return head(x).astype(jnp.float32)
        head(x[:, :1])      # registers the kernel (result DCE'd); the head
        if labels is None:  # itself is applied chunk-wise by the fused loss
            return x
        with jax.named_scope("lm_head_loss"):
            rows = lm_rows_fused(x, head.variables["params"]["kernel"],
                                 labels, chunk=max(128, 2048 // x.shape[0]))
        if not aux:
            return rows, jnp.zeros((0,), jnp.float32)
        mean = lambda key: sum(a[key] for a in aux) / len(aux)  # noqa: E731
        rows = rows + (self.balance_loss_weight * mean("balance")
                       + self.z_loss_weight * mean("z"))
        return rows, jnp.stack([sum(a["slots_max"] for a in aux),
                                sum(a["slots_all"] for a in aux)])

    @property
    def loss_counters(self):
        """What the second output of :meth:`loss_rows` counts, as (registry
        counter, label) pairs."""
        return (("moe_slots_total", "max_expert"),
                ("moe_slots_total", "all")) if self.num_experts else ()

    def loss_rows(self, tokens, labels):
        """The training loss, a row: mean next-token cross entropy of each
        sequence (head fused into the loss, float32) plus, with experts, the
        weighted load-balancing and router z-losses of the batch (means over
        the layers), and the counts of :attr:`loss_counters`. The mean over
        rows is the loss; :class:`raydp_tpu.train.FlaxEstimator` takes it
        from here, so no ``[B, T, vocab]`` logits exist in its train step."""
        return self(tokens, labels=labels)


def lm_loss(logits: jnp.ndarray, tokens: jnp.ndarray) -> jnp.ndarray:
    """Next-token cross entropy (shifted); tokens [B, T], logits [B, T, V]."""
    import optax

    return optax.softmax_cross_entropy_with_integer_labels(
        logits[:, :-1], tokens[:, 1:]).mean()


def lm_rows_fused(hidden: jnp.ndarray, lm_head_kernel: jnp.ndarray,
                  tokens: jnp.ndarray, chunk: int = 1024,
                  remat: bool = True) -> jnp.ndarray:
    """Mean next-token cross entropy of each row, ``[B]`` float32, with the
    lm_head FUSED into the loss.

    The head matmul + softmax-CE run per T-chunk of ``chunk`` positions under
    ``jax.checkpoint`` inside a ``lax.scan``: forward keeps only the hidden
    states (already live) and per-chunk row sums, backward recomputes each
    chunk's logits — peak logits footprint is ``B×chunk×V`` instead of
    ``B×T×V`` f32 (64× smaller at T=8192/chunk=1024/f32), while each chunk
    matmul ``[B·chunk, D] @ [D, V]`` stays MXU-sized and accumulates in
    float32 (the logits are never rounded to the activations' dtype). This
    trades one extra head matmul (recompute) for ~4 GB of HBM round-trips per
    step at the bench shape, which is bandwidth the step actually runs out of
    — the round-2 gap between kernel MFU (51%) and e2e MFU (35%).

    ``hidden`` [B, T, D] from ``model(tokens, return_hidden=True)``;
    ``lm_head_kernel`` [D, V] = ``params["lm_head"]["kernel"]``.
    """
    import optax
    from jax import lax

    B, T, D = hidden.shape
    x = hidden[:, :-1]                   # predict positions 1..T-1
    y = tokens[:, 1:]
    n = T - 1
    chunk = min(chunk, n)
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
        logits = jnp.dot(xc, lm_head_kernel.astype(xc.dtype),
                         preferred_element_type=jnp.float32)
        ce = optax.softmax_cross_entropy_with_integer_labels(logits, yc)
        return total + (ce * mc).sum(axis=1), None

    body = jax.checkpoint(chunk_ce) if remat else chunk_ce
    total, _ = lax.scan(body, jnp.zeros((B,), jnp.float32), (xs, ys, ms))
    return total / n


def lm_loss_fused(hidden: jnp.ndarray, lm_head_kernel: jnp.ndarray,
                  tokens: jnp.ndarray, chunk: int = 1024,
                  remat: bool = True) -> jnp.ndarray:
    """Next-token cross entropy with the lm_head fused into the loss: the
    mean of :func:`lm_rows_fused` over the rows."""
    return lm_rows_fused(hidden, lm_head_kernel, tokens, chunk, remat).mean()


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
