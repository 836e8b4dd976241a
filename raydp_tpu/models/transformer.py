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

What else a published layer may ask for: ``head_dim`` given outright (heads
whose width is not ``dim / num_heads``), ``num_kv_heads`` (grouped-query: the
flash kernels read a group's one K/V head, the other paths repeat it),
``sliding_window`` with ``window_layers`` and ``rope_layers`` (a pattern over
the layers, repeated: 1 = this layer's attention has the window / applies
RoPE, 0 = it sees every earlier key / has no position embedding), the expert
layer's ``expert_activation``, ``normalize_top_k``, ``first_expert`` and
``experts_held`` (one chip's share of an expert-parallel layer), and
``router_input="attention"`` (the router reads the attention's normed input,
not the experts'). ``remat_blocks`` recomputes each block in the backward
pass from what it saved: its input, its flash kernel's output and row sums
(``flash_attention.RESIDUAL_NAMES``), its attention's inputs (the file's end)
and, under ``sandwich_norms``, the feed-forward's output (``SUBLAYER_OUT``: the
second norm reads it in the backward). So norms, ``W_o`` and router run
again; q, k, v with head norms and RoPE, the gate's projection, the forward
kernel and, where its output is kept, the held experts' forward walk do not
(``.attention_forward``, ``.attention_inputs``, ``.sublayer_out``). A
vocabulary-parallel share is a smaller ``vocab_size``, ids from the rows held.

And, since the ``afmoe`` family (Trinity): ``qk_norm="head"`` (RMSNorm over
each head's ``head_dim``, one weight for the query heads and one for the K/V
heads), ``attention_gate`` (``o(attn * sigmoid(W_g u))``, ``W_g`` to the query
heads' width), ``sandwich_norms`` (a second norm on each sub-layer's output:
``x + norm(attn(norm(x)))``, ``x + norm(ffn(norm(x)))``), ``embed_scale`` (the
embeddings times ``sqrt(dim)``), ``dense_layers`` (that many leading blocks
keep a dense SwiGLU of width ``dense_ffn_dim`` where the others hold the
expert layer), the expert layer's ``routing="sigmoid"`` with ``route_scale``
and the balancing bias that ``bias_update_rate`` moves once an optimizer step
(:meth:`TransformerLM.after_step`; state outside the parameters, see
:mod:`raydp_tpu.models.moe`), and ``shared_expert_dim``.

And, since the ``deepseek_v3`` family (latent attention): ``kv_lora_rank``
with ``qk_nope_head_dim``, ``qk_rope_head_dim`` and ``v_head_dim`` makes every
block's attention a :class:`LatentAttention` (keys and values come from one
low-rank latent a token and one rotary key all heads share; keys are wider
than values), ``q_lora_rank`` gives the queries a latent of their own, and
``rope_interleave`` rotates dimension pairs ``(2i, 2i + 1)``.

And, since the ``nemotron_h`` family (state-space hybrids): ``layer_kinds``,
one letter a layer, makes a layer ONE sub-layer, ``x + mixer(RMSNorm(x))``:
``M`` a :class:`Mamba2Mixer` (module ``ssm``; its sizes are the one field
``ssm``, an :class:`SSMSpec`), ``*`` attention alone (``attn``: the window,
RoPE and grouped K/V of the fields above), ``E`` the expert layer alone
(``moe``), ``B`` the pair of the older families (attention, then a
feed-forward part, two norms), which is also what every layer is where
``layer_kinds`` is empty. ``expert_gated=False`` gives experts (and a shared
expert) of TWO matrices, ``W_down act(W_up h)``, with ``expert_activation``
``relu2`` for ``relu(.)^2``. A recomputed state-space layer keeps nothing of
its scan: the forward scan kernel runs again in the backward pass, where it
hands the backward kernel the chunks' states (:attr:`TransformerLM.ssm_layers`;
doc/long_context.md has the chip's numbers).

And, since the ``sdar_moe`` family (block diffusion): ``diffusion``, a
:class:`BlockDiffusionSpec`, changes the OBJECTIVE and leaves the layers
alone: the model lays out ``[row ; noised row]`` (2T positions, position ids
0..T-1 twice), noises the copy itself (:func:`block_diffusion_noise`, under
the scope ``diffusion``; the key is the ``diffusion`` stream a train step
hands it, :attr:`TransformerLM.rng_streams`, or the spec's fixed one), runs
every attention under the flash kernels' ``blockdiff`` mask, and its logits
and its loss are the noised half's: position ``i`` against token ``i`` under
the weight ``masked_i / t`` (:func:`lm_head_loss`'s ``position_weights``).
``embed_init_std`` gives the embedding's rows a std of their own beside
``init_std`` (doc/training.md says when that matters).

And, since the ``lfm2`` family (convolution hybrids): the letter ``C`` in
``layer_kinds``, the pair of ``B`` with a :class:`ShortConv` (module
``short_conv``: a gated short convolution of ``conv_taps`` taps between two
projections, :mod:`raydp_tpu.ops.short_conv`) where ``B`` has ``attn``; the
letters are then ``B``, ``C``, ``M``, ``*``, ``E``, and ``B`` and ``C`` share
``dense_layers``. A recomputed ``C`` layer keeps nothing of its operator
(:attr:`TransformerLM.conv_layers`). ``tie_embeddings`` makes the head the
embedding (no ``lm_head``; the fused loss takes ``embed.embedding`` as it
lies, :func:`lm_head_loss`'s ``vocab_major``), and ``route_norm_eps`` is the
epsilon beside the sum a sigmoid routing's chosen scores are renormalised
over.

And, since the ``kimi_linear`` family (delta-rule hybrids): the letter ``K``
in ``layer_kinds``, the pair of ``B`` with a :class:`KimiDeltaAttention`
(module ``kda``; its sizes are the one field ``kda``, a :class:`KDASpec`)
where ``B`` has ``attn``: a delta rule with a decay a key channel, scanned in
chunks (:mod:`raydp_tpu.ops.kda_scan`) between a 4-tap convolution and a
gated norm a head; the letters are then ``B``, ``C``, ``K``, ``M``, ``*``,
``E``, and ``B``, ``C`` and ``K`` share ``dense_layers``. A recomputed ``K``
layer keeps nothing of its operator (:attr:`TransformerLM.kda_layers`). A
latent attention layer takes ``rope_layers`` too: where the pattern says 0
the layer has no position embedding (the shared key and the queries' last
``qk_rope_head_dim`` dimensions are kept and not rotated).

A model that is trained by :class:`raydp_tpu.train.FlaxEstimator` hands the
train step its loss itself (``loss_rows``): next-token cross entropy with the
head applied chunk by chunk (:func:`lm_head_loss`'s scan, which takes the
head's two gradients as it goes), so the ``[B, T, vocab]`` float32 logits
never exist. Called plainly the model still returns them.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, ClassVar, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from flax import linen as nn

from raydp_tpu.ops import ssm_glue
from raydp_tpu.ops.ssm_glue import causal_conv  # noqa: F401  (its home now)


def rotary_embedding(x: jnp.ndarray, positions: jnp.ndarray,
                     base: float = 10000.0,
                     interleaved: bool = False) -> jnp.ndarray:
    """Apply RoPE. x: [B, T, H, D]; positions: [T] global token positions.
    Pair ``i`` of the ``D / 2`` rotated pairs is dimensions ``(i, i + D/2)``
    or, ``interleaved``, ``(2i, 2i + 1)``; a pair keeps its places."""
    d_half = x.shape[-1] // 2
    freqs = 1.0 / (base ** (np.arange(0, d_half) / d_half))
    angles = positions[:, None] * freqs[None, :]            # [T, D/2]
    cos = jnp.cos(angles)[None, :, None, :]
    sin = jnp.sin(angles)[None, :, None, :]
    if interleaved:
        x1, x2 = x[..., 0::2], x[..., 1::2]
        return jnp.stack([x1 * cos - x2 * sin, x1 * sin + x2 * cos],
                         axis=-1).reshape(x.shape).astype(x.dtype)
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
    qk_norm: Any = False                    # True: whole projection; "head"
    rms_norm_eps: float = 1e-6
    init_std: Optional[float] = None
    head_dim: Optional[int] = None          # None: dim // num_heads
    num_kv_heads: Optional[int] = None      # None: num_heads
    window: Optional[int] = None            # None: every key up to its own
    rope: bool = True
    gate: bool = False                      # o(attn * sigmoid(W_g x))
    blockdiff: Optional[int] = None         # the block-diffusion mask's Bd

    def _dispatch(self, t: int, head_dim: int, d_v=None) -> str:
        from raydp_tpu.ops.flash_attention import kernel_ineligible
        from raydp_tpu.parallel.mesh import seq_extent

        if self.attention != "auto":
            return self.attention
        if self.mesh is not None and seq_extent(self.mesh) > 1:
            return "ring"
        if jax.default_backend() != "tpu":
            return "dense"
        why = kernel_ineligible(t, head_dim, window=self.window, d_v=d_v)
        if why is not None and self.window is not None:
            # a window is set for sequences whose dense [T, T] scores do not
            # fit: never a quiet switch to them
            raise ValueError(
                f"windowed attention (window {self.window}) over {t} "
                f"positions cannot run the flash kernel on this TPU backend: "
                f"{why}; pad the sequence, or ask for attention='dense'")
        return "flash" if why is None else "dense"

    @nn.compact
    def __call__(self, x):
        from raydp_tpu.ops.flash_attention import flash_attention_sharded
        from raydp_tpu.ops.ring_attention import (
            dense_attention, ring_attention_sharded)

        b, t, dim = x.shape
        head_dim = self.head_dim or dim // self.num_heads
        kv_heads = self.num_kv_heads or self.num_heads
        init = _init(self.init_std, nn.linear.default_kernel_init)
        dense = lambda name, heads: _raw(nn.DenseGeneral(  # noqa: E731
            (heads, head_dim), axis=-1, name=name, dtype=self.dtype,
            use_bias=False, kernel_init=init))
        q = dense("q", self.num_heads)(x)
        k, v = dense("k", kv_heads)(x), dense("v", kv_heads)(x)
        if self.qk_norm == "head":
            # head by head, over head_dim: one weight for q, one for k
            q = RMSNorm(self.rms_norm_eps, name="q_norm")(q)
            k = RMSNorm(self.rms_norm_eps, name="k_norm")(k)
        elif self.qk_norm:
            # over the whole projection (all heads together), then split
            norm = lambda name, a: RMSNorm(  # noqa: E731
                self.rms_norm_eps, name=name)(
                    a.reshape(b, t, -1)).reshape(a.shape)
            q, k = norm("q_norm", q), norm("k_norm", k)

        if self.rope:
            positions = jnp.arange(t)
            if self.blockdiff is not None:
                positions = _copies_positions(t)
            q = rotary_embedding(q, positions, self.rope_theta)
            k = rotary_embedding(k, positions, self.rope_theta)

        kind = self._dispatch(t, head_dim)
        mask = {"window": self.window}
        scope = "attn_full" if self.window is None else "attn_window"
        if self.blockdiff is not None:
            mask, scope = {"blockdiff": self.blockdiff}, "attn_blockdiff"
        with jax.named_scope(scope):
            if kind == "ring":
                if (self.window or self.blockdiff
                        or kv_heads != self.num_heads):
                    raise NotImplementedError(
                        "ring attention takes no window, no block-diffusion "
                        "mask and no grouped K/V")
                out = ring_attention_sharded(q, k, v, self.mesh, causal=True)
            elif kind == "flash":
                out = flash_attention_sharded(q, k, v, self.mesh, causal=True,
                                              **mask)
            else:
                out = dense_attention(q, k, v, causal=True, **mask)
        if self.gate:
            g = dense("gate", self.num_heads)(x)
            with jax.named_scope("attn_gate"):
                # at the activations' width: a float32 gate is one more
                # [B, T, heads, head_dim] float32 array kept a layer
                out = out * jax.nn.sigmoid(g)
        return nn.DenseGeneral(dim, axis=(-2, -1), name="o", dtype=self.dtype,
                               use_bias=False, kernel_init=init)(out)


class Block(nn.Module):
    """One pre-norm block. Dense (``num_experts == 0``): ``x -> x``. Sparse:
    ``x -> (x, aux)``, ``aux`` what :class:`raydp_tpu.models.moe.MoE`
    returns beside its output. ``router_input="attention"``: the router's
    kernel lies in the block (``router``) and reads the attention's normed
    input; its logits are handed to the expert layer. ``sandwich_norms``: each
    sub-layer's output is normed too (``ln1_post``, ``ln2_post``) before it is
    added to the stream. ``kv_lora_rank``: the attention is a
    :class:`LatentAttention` of the four widths given with it.
    ``conv_taps``: the block's operator is a :class:`ShortConv`
    (``short_conv``) where the others have ``attn``; the rest is the same.
    ``kda``: it is a :class:`KimiDeltaAttention` of those sizes (``kda``)."""

    num_heads: int
    mlp_ratio: int = 4
    attention: str = "auto"
    mesh: Any = None
    dtype: Any = jnp.float32
    ffn_dim: Optional[int] = None
    rms_norm_eps: float = 1e-6
    rope_theta: float = 10000.0
    qk_norm: Any = False
    num_experts: int = 0
    experts_per_token: int = 0
    init_std: Optional[float] = None
    head_dim: Optional[int] = None
    num_kv_heads: Optional[int] = None
    window: Optional[int] = None
    rope: bool = True
    first_expert: int = 0
    experts_held: Optional[int] = None
    expert_activation: str = "silu"
    normalize_top_k: bool = False
    router_input: str = "experts"
    attention_gate: bool = False
    sandwich_norms: bool = False
    routing: str = "softmax"
    route_scale: float = 1.0
    shared_expert_dim: int = 0
    kv_lora_rank: Optional[int] = None      # None: Attention as it is
    q_lora_rank: Optional[int] = None
    qk_nope_head_dim: Optional[int] = None
    qk_rope_head_dim: Optional[int] = None
    v_head_dim: Optional[int] = None
    rope_interleave: bool = False
    expert_gated: bool = True
    blockdiff: Optional[int] = None         # Attention's, handed on
    conv_taps: Optional[int] = None         # a number: the operator is a
    # ShortConv of that many taps (``short_conv``) in the attention's place
    route_norm_eps: float = 1e-20           # the expert layer's normalize_eps
    kda: Any = None                         # a KDASpec: the operator is a
    # KimiDeltaAttention of those sizes (``kda``) in the attention's place

    @nn.compact
    def __call__(self, x):
        from raydp_tpu.models.moe import MoE, router_logits

        dim = x.shape[-1]
        eps = self.rms_norm_eps
        init = _init(self.init_std, nn.linear.default_kernel_init)
        u = RMSNorm(eps, name="ln1")(x)
        logits = None
        if self.num_experts and self.router_input == "attention":
            router = self.param("router", init, (dim, self.num_experts))
            with jax.named_scope("moe"), jax.named_scope("router"):
                logits = router_logits(u.reshape(-1, dim), router)
        elif self.router_input != "experts":
            raise ValueError(f"router_input {self.router_input!r}: "
                             f"'experts' or 'attention'")
        post = (lambda name, y: RMSNorm(eps, name=name)(y)) \
            if self.sandwich_norms else (lambda name, y: y)
        x = x + post("ln1_post", _operator(self)(u))
        h = RMSNorm(eps, name="ln2")(x)
        hidden = self.ffn_dim or self.mlp_ratio * dim
        if self.num_experts:
            y, aux = MoE(self.num_experts, self.experts_per_token, hidden,
                         self.dtype, init, self.first_expert,
                         self.experts_held, self.expert_activation,
                         self.normalize_top_k, self.routing, self.route_scale,
                         self.shared_expert_dim, self.expert_gated,
                         self.route_norm_eps, name="moe")(h, logits)
            return x + post("ln2_post", _ffn_out(self, y)), aux
        # SwiGLU
        dense = lambda n, name: nn.Dense(  # noqa: E731
            n, use_bias=False, dtype=self.dtype, name=name, kernel_init=init)
        with jax.named_scope("mlp"):
            down = dense(dim, "down")(
                nn.silu(dense(hidden, "gate")(h)) * dense(hidden, "up")(h))
        return x + post("ln2_post", _ffn_out(self, down))


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
    qk_norm: Any = False
    num_experts: int = 0
    experts_per_token: int = 0
    balance_loss_weight: float = 0.01
    z_loss_weight: float = 0.001
    init_std: Optional[float] = None
    head_dim: Optional[int] = None
    num_kv_heads: Optional[int] = None
    sliding_window: Optional[int] = None
    window_layers: Tuple[int, ...] = ()     # () : no layer has the window
    rope_layers: Tuple[int, ...] = ()       # () : every layer applies RoPE
    first_expert: int = 0
    experts_held: Optional[int] = None
    expert_activation: str = "silu"
    normalize_top_k: bool = False
    router_input: str = "experts"
    remat_blocks: bool = False
    attention_gate: bool = False
    sandwich_norms: bool = False
    embed_scale: bool = False
    dense_layers: int = 0                   # leading blocks that stay dense
    dense_ffn_dim: Optional[int] = None     # their width; None: ffn_dim's rule
    routing: str = "softmax"
    route_scale: float = 1.0
    shared_expert_dim: int = 0
    bias_update_rate: float = 0.0           # sigmoid routing's balancing bias
    kv_lora_rank: Optional[int] = None      # latent attention: the K/V latent
    q_lora_rank: Optional[int] = None       # and, where given, the queries'
    qk_nope_head_dim: Optional[int] = None  # a key's part without position,
    qk_rope_head_dim: Optional[int] = None  # its rotary part (all heads' one)
    v_head_dim: Optional[int] = None        # and a value's width
    rope_interleave: bool = False           # RoPE over pairs (2i, 2i + 1)
    layer_kinds: str = ""                   # a letter a layer; "": all "B"
    ssm: Any = None                         # the "M" layers' SSMSpec
    expert_gated: bool = True               # False: experts of two matrices
    diffusion: Any = None                   # a BlockDiffusionSpec: the
    # model trains the block-diffusion objective, not next-token prediction
    embed_init_std: Optional[float] = None  # None: the embedding's is init_std
    # a looped model (the ``ouro`` family; the section at the file's end)
    total_ut_steps: int = 1                 # passes of the whole stack
    exit_entropy_weight: Optional[float] = None     # a float: the exit gate
    # exists and the loss is the expected loss less this times the entropy
    exit_probs_out: bool = False            # a plain call's logits end in
    # the exit distribution's ``total_ut_steps`` probabilities a position
    # the ``lfm2`` family (the section at the file's end)
    conv_taps: int = 3                      # a "C" layer's ShortConv
    tie_embeddings: bool = False            # the head IS the embedding
    route_norm_eps: float = 1e-20           # beside a renormalised top-k's sum
    kda: Any = None                         # the "K" layers' KDASpec

    def _kind(self, layer: int) -> str:
        """``B`` the pair (attention, then a feed-forward part), ``C`` the
        pair whose operator is a gated short convolution, ``K`` the pair
        whose operator is Kimi Delta Attention, or the one sub-layer the
        layer is: ``M`` state-space mixer, ``*`` attention, ``E`` experts."""
        kinds = self.layer_kinds or "B" * self.num_layers
        if len(kinds) != self.num_layers or set(kinds) - set("BCKM*E"):
            raise ValueError(f"layer_kinds {kinds!r}: {self.num_layers} "
                             f"letters of 'B', 'C', 'K', 'M', '*', 'E'")
        return kinds[layer]

    def _layers_of(self, kinds: str):
        return [i for i in range(self.num_layers) if self._kind(i) in kinds]

    def _windowed(self, layer: int) -> bool:
        pattern = self.window_layers
        return bool(self.sliding_window and pattern
                    and pattern[layer % len(pattern)])

    def _rope(self, layer: int) -> bool:
        pattern = self.rope_layers
        return bool(pattern[layer % len(pattern)]) if pattern else True

    @property
    def attention_layers(self):
        """How many attention layers of each kind a step executes (the
        layers of that kind times ``total_ut_steps``: a looped model runs
        each once a pass): what ``train_attention_layers_total`` counts once
        a built step. A latent layer counts under its kernel's kind and under
        ``latent``; a ``C`` or ``K`` layer has no attention and counts under
        none."""
        layers = self._layers_of("B*")
        windowed = sum(self._windowed(i) for i in layers)
        kinds = {"window": windowed, "full": len(layers) - windowed}
        if self.diffusion is not None:
            kinds = {"blockdiff": len(layers)}
        if self.kv_lora_rank is not None:
            kinds["latent"] = len(layers)
        return {k: n * self.total_ut_steps for k, n in kinds.items()}

    @property
    def ssm_layers(self):
        """The state-space layers by what a recomputed one does with its
        scan: what ``train_ssm_layers_total`` counts once a built step."""
        return {"rescanned" if self.remat_blocks else "plain":
                len(self._layers_of("M"))}

    @property
    def conv_layers(self):
        """The pairs whose operator is a gated short convolution (``C``) by
        what a recomputed one does with it: what ``train_conv_layers_total``
        counts once a built step. ``recomputed``: nothing of the operator is
        kept (``W_in u``, the stage and ``W_out`` run again in the backward
        pass); ``plain``: the layer is not recomputed."""
        return {"recomputed" if self.remat_blocks else "plain":
                len(self._layers_of("C"))}

    @property
    def kda_layers(self):
        """The pairs whose operator is Kimi Delta Attention (``K``) by what a
        recomputed one does with its scan: what ``train_kda_layers_total``
        counts once a built step. ``rescanned``: nothing of the operator is
        kept (projections, convolution, gates and the chunked scan run again
        in the backward pass); ``plain``: the layer is not recomputed."""
        return {"rescanned" if self.remat_blocks else "plain":
                len(self._layers_of("K"))}

    @property
    def attention_forward(self):
        """How often a train step runs each layer's forward attention, by
        the layers a step executes (layers times ``total_ut_steps``): what
        ``train_attention_forward_total`` counts once a built step. ``once``:
        the block is not recomputed, or it is and keeps its flash kernel's
        output and row sums; ``twice``: a recomputed block whose attention
        names nothing to keep (``dense`` and ``ring``: their ``[T, T]``
        scores must not be kept). ``auto`` counts as what it picks for a
        shape the kernel takes. A ``C`` layer has no attention to run."""
        d_qk, d_v = self.head_dim or self.dim // self.num_heads, None
        if self.kv_lora_rank is not None:
            d_qk, d_v = (self.qk_nope_head_dim + self.qk_rope_head_dim,
                         self.v_head_dim)
        kind = Attention(self.num_heads, self.attention,
                         self.mesh)._dispatch(8192, d_qk, d_v)
        kept = not self.remat_blocks or kind == "flash"
        return {"once" if kept else "twice":
                len(self._layers_of("B*")) * self.total_ut_steps}

    @property
    def _blockdiff(self) -> Optional[int]:
        return None if self.diffusion is None else self.diffusion.block

    @property
    def rng_streams(self):
        """The random streams a train step has to hand the model (flax's
        ``rngs=``): ``diffusion`` where the objective draws noise, else
        none, and a step built round the model is then the step it was."""
        return () if self.diffusion is None else ("diffusion",)

    @property
    def _share(self) -> bool:
        return bool(self.num_experts and self.experts_held is not None
                    and self.experts_held < self.num_experts)

    def _sparse(self, layer: int) -> bool:
        kind = self._kind(layer)
        return bool(self.num_experts) and (
            kind == "E" or (kind in "BCK" and layer >= self.dense_layers))

    @nn.compact
    def __call__(self, tokens, return_hidden: bool = False, labels=None,
                 weights=None):
        """``return_hidden=True`` yields the post-norm hidden states [B,T,D]
        (the lm_head weight is still created so the param tree is identical);
        pair it with :func:`lm_loss_fused`, which applies the head per
        T-chunk so the [B,T,V] float32 logits never materialize — at 32k
        vocab and T=8192 those logits are ~2 GB per direction of pure HBM
        traffic, the single largest non-kernel cost in the train step.
        ``labels`` [B, T] (the tokens themselves) and ``weights`` [B] yield
        what :meth:`loss_rows` returns. With ``diffusion`` the model runs
        ``[tokens ; noised copy]``, 2T positions, and its logits and its loss
        are those of the noised half (:func:`_noised_row`)."""
        noise = None
        if self.diffusion is not None:
            if self.sliding_window or self.kv_lora_rank is not None:
                raise ValueError("a block-diffusion model takes no window "
                                 "and no latent attention")
            tokens, noise = _noised_row(self, tokens)
        init = _init(self.init_std if self.embed_init_std is None
                     else self.embed_init_std, nn.linear.default_embed_init)
        embed = nn.Embed(self.vocab_size, self.dim, name="embed",
                         dtype=self.dtype, embedding_init=init)
        x = embed(tokens)
        if self.embed_scale:
            x = x * jnp.asarray(np.sqrt(self.dim), x.dtype)
        if _is_looped(self):
            return _looped(self, x, return_hidden, labels, weights, embed)
        aux, kept = [], _kept(self)
        block = _recomputed(Block, kept)        # one class for every pair
        for i in range(self.num_layers):
            x = _layer(self, i, kept, block)(x)
            if self._sparse(i):
                x, layer_aux = x
                aux.append(layer_aux)
        x = RMSNorm(self.rms_norm_eps, name="ln_f")(x)
        head = _head(self, embed)
        if labels is None and not return_hidden:
            if noise is not None:       # the noised half's, [B, T, vocab]
                x = x[:, x.shape[1] // 2:]
            return head(x).astype(jnp.float32)
        head(x[:, :1])      # registers the kernel (result DCE'd); the head
        if labels is None:  # itself is applied chunk-wise by the fused loss
            return x
        kernel = _head_kernel(self, embed, head)
        if noise is not None:
            return _diffusion_loss(self, x, kernel, labels, weights, noise,
                                   aux)
        loss, _ = lm_head_loss(x, kernel, labels, weights,
                               chunk=max(128, 2048 // x.shape[0]),
                               vocab_major=self.tie_embeddings)
        if not aux:
            return loss, jnp.zeros((0,), jnp.float32)
        return self._with_aux(loss, weights, aux)

    def _with_aux(self, loss, weights, aux):
        """The head's loss plus the expert layers' auxiliary losses, and the
        expert layers' counts."""
        mean = lambda key: sum(a[key] for a in aux) / len(aux)  # noqa: E731
        if self.balance_loss_weight or self.z_loss_weight:
            loss = loss + weights.sum() * (
                self.balance_loss_weight * mean("balance")
                + self.z_loss_weight * mean("z"))
        counts = [sum(a[f"slots_{kind}"] for a in aux)
                  for kind in self._slot_kinds]
        if self.routing == "sigmoid":       # the widest layer's
            counts.append(jnp.max(jnp.stack([a["bias_spread"] for a in aux])))
        return loss, jnp.stack(counts)

    @property
    def _slot_kinds(self):
        return ("max", "all", "held", "moved") if self._share \
            else ("max", "all")

    @property
    def loss_counters(self):
        """What the second output of :meth:`loss_rows` counts, as (registry
        metric, label) pairs: counters are summed over an epoch's steps, a
        gauge keeps the last step's value."""
        if self.exit_entropy_weight is not None:    # dense: no other counts
            return _exit_counters(self.total_ut_steps)
        noise = () if self.diffusion is None else tuple(
            ("train_diffusion_tokens_total", kind)
            for kind in ("masked", "all"))
        if not self.num_experts:
            return noise
        labels = {"max": "max_expert"}     # the other kinds label themselves
        names = tuple(("moe_slots_total", labels.get(kind, kind))
                      for kind in self._slot_kinds)
        if self.routing == "sigmoid":
            names += (("moe_router_bias_spread", ""),)
        return names + noise

    def after_step(self, state):
        """Once an optimizer step, after the gradients are applied (the train
        step calls it on the collection it carries beside the parameters):
        every expert layer's balancing bias moves towards the experts that
        were short of slots in the step's tokens, all micro-batches together
        (:func:`raydp_tpu.models.moe.balance_bias`). The state itself where
        the routing has no bias."""
        from raydp_tpu.models.moe import balance_bias

        if self.routing != "sigmoid" or state is None:
            return state
        return balance_bias(state, self.bias_update_rate)

    def loss_rows(self, tokens, labels, weights):
        """The training loss over the rows, under the rows' ``weights`` [B]
        (``1 / B`` each, or a pad-and-mask feed's ``mask / sum(mask)``): the
        weighted sum of each sequence's mean next-token cross entropy (head
        fused into the loss, float32: :func:`lm_head_loss`) plus, with
        experts and a weight on either, ``sum(weights)`` times the weighted
        load-balancing and router z-losses of the batch (means over the
        layers; no auxiliary loss where both weights are 0); and the counts of
        :attr:`loss_counters`. The scalar is the loss
        :class:`raydp_tpu.train.FlaxEstimator` differentiates, so no
        ``[B, T, vocab]`` logits exist in its train step, and the weights
        are what lets the head's gradients be taken in its forward scan."""
        return self(tokens, labels=labels, weights=weights)

    @property
    def sublayer_out(self):
        """The sub-layer outputs a second norm reads (two a block under
        ``sandwich_norms``) by what a recomputed block does with them: what
        ``train_sublayer_out_total`` counts once a built step, by the blocks
        a step executes (blocks times ``total_ut_steps``). ``kept``: the
        feed-forward's (``SUBLAYER_OUT``); ``rebuilt``: the operator's (the
        attention's output projection, or a ``C`` or ``K`` pair's whole
        operator, runs again). Nothing where no block is recomputed
        or no norm reads them."""
        if not (self.remat_blocks and self.sandwich_norms):
            return {}
        pairs = len(self._layers_of("BCK")) * self.total_ut_steps
        return {"kept": pairs, "rebuilt": pairs}

    @property
    def loop_passes(self):
        """The layer executions of a looped model's step, ``total_ut_steps``
        times the layers, by what the loop keeps of each (``recomputed``:
        ``remat_blocks``' set a pass and layer; ``plain``: all of it): what
        ``train_loop_passes_total`` counts once a built step. Nothing where
        the stack runs once."""
        if not _is_looped(self):
            return {}
        return {"recomputed" if self.remat_blocks else "plain":
                self.total_ut_steps * self.num_layers}


def lm_loss(logits: jnp.ndarray, tokens: jnp.ndarray) -> jnp.ndarray:
    """Next-token cross entropy (shifted); tokens [B, T], logits [B, T, V]."""
    import optax

    return optax.softmax_cross_entropy_with_integer_labels(
        logits[:, :-1], tokens[:, 1:]).mean()


def _head_chunks(hidden, tokens, chunk, position_weights=None,
                 shifted=False):
    """Positions 0..T-2 predict tokens 1..T-1: both cut into ``[N, B, C, ...]``
    chunks of ``C <= chunk`` positions (zero-padded to a whole number of
    chunks), with the ``[N, 1, C]`` mask of the real positions. With
    ``position_weights`` [B, T]: position ``i`` predicts token ``i`` (no
    shift, all T), and in the mask's place stand the weights, ``[N, B, C]``
    (the padding's are zero); ``shifted``, they weigh the next-token form
    (the last position's weight is not read)."""
    B, T, D = hidden.shape
    n = T - 1
    x, y = hidden[:, :-1], tokens[:, 1:]
    if shifted:
        position_weights = position_weights[:, :-1]
    elif position_weights is not None:
        n, x, y = T, hidden, tokens
    chunk = min(chunk, n)
    pad = (-n) % chunk
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0)))
        y = jnp.pad(y, ((0, 0), (0, pad)))
    cut = lambda a: a.reshape(  # noqa: E731
        (a.shape[0], (n + pad) // chunk, chunk) + a.shape[2:]).swapaxes(0, 1)
    if position_weights is not None:
        return cut(x), cut(y), cut(jnp.pad(
            position_weights.astype(jnp.float32), ((0, 0), (0, pad))))
    mask = (jnp.arange(n + pad) < n).astype(jnp.float32)
    return cut(x), cut(y), cut(mask[None])


def _head_scan(hidden, kernel, tokens, weights, chunk, with_grads,
               position_weights=None, shifted=False, vocab_major=False):
    """One scan over the chunks of :func:`_head_chunks`. A chunk's logits
    (``[B, C, V]`` float32: operands in the activations' dtype, float32
    accumulation) exist once, inside the scan's body; from them come the
    chunk's cross entropy and, ``with_grads``, both gradients of
    ``sum(weights * rows)`` at once: ``dlogits = (softmax - onehot) * mask *
    weights / (T - 1)`` (float32) against the kernel for the hidden states'
    and against the chunk's hidden states into a float32 ``[D, V]`` carry for
    the kernel's, with the operand and accumulation types of the products
    autodiff transposes out of the forward one. Returns ``(rows [B], d hidden
    [B, T, D] in hidden's dtype, d kernel [D, V] float32)``, the last two
    ``None`` without gradients. ``position_weights`` [B, T]: a row is
    ``sum_i w_i CE(logits_i, token_i) / T``, same position, all T of them
    (:func:`_head_chunks`); ``shifted``, ``sum_i w_i CE(logits_i, token_i+1)
    / (T - 1)``, and a fourth result: every position's cross entropy
    ``[B, T]`` float32 (the last position's is 0), which is the weights'
    gradient. ``vocab_major``: the kernel is ``[V, D]`` (a tied model's
    embedding, as it lies) and so is its gradient: the three products
    contract over the dimensions that layout gives them, and nothing of the
    embedding's size is transposed."""
    from jax import lax

    B, T, D = hidden.shape
    n = T - 1 if position_weights is None or shifted else T
    xs, ys, ms = _head_chunks(hidden, tokens, chunk, position_weights,
                              shifted)
    k = kernel.astype(hidden.dtype)      # cast once, not once a chunk
    vocab = lax.broadcasted_iota(
        jnp.int32, (1, 1, k.shape[0 if vocab_major else 1]), 2)
    scale = weights.astype(jnp.float32)[:, None] / n            # [B, 1]

    def body(carry, chunk_of):
        total, dk = carry
        xc, yc, mc = chunk_of
        if vocab_major:
            logits = lax.dot_general(xc, k, (((2,), (1,)), ((), ())),
                                     preferred_element_type=jnp.float32)
        else:
            logits = jnp.dot(xc, k, preferred_element_type=jnp.float32)
        top = logits.max(axis=-1, keepdims=True)
        e = jnp.exp(logits - top)
        z = e.sum(axis=-1, keepdims=True)
        label = vocab == yc[..., None]
        ce = (jnp.log(z) + top)[..., 0] - jnp.where(
            label, logits, 0.0).sum(axis=-1)
        total = total + (ce * mc).sum(axis=1)
        if not with_grads:
            return (total, None), None
        g = (scale * mc)[..., None]                             # [B, C, 1]
        dlogits = e * (g / z) - jnp.where(label, g, 0.0)
        dxc = lax.dot_general(
            dlogits, k, (((2,), (0 if vocab_major else 1,)), ((), ())),
            preferred_element_type=jnp.float32)
        pair = (dlogits, xc) if vocab_major else (xc, dlogits)
        dk = dk + lax.dot_general(*pair, (((0, 1), (0, 1)), ((), ())),
                                  preferred_element_type=jnp.float32)
        if shifted:
            return (total, dk), (dxc.astype(xc.dtype), ce)
        return (total, dk), dxc.astype(xc.dtype)

    dk0 = jnp.zeros(k.shape, jnp.float32) if with_grads else None
    (total, dk), dxs = lax.scan(
        body, (jnp.zeros((B,), jnp.float32), dk0), (xs, ys, ms))
    rows = total / n
    if not with_grads:
        return rows, None, None
    if shifted:
        dxs, ces = dxs
        ce = jnp.pad(ces.swapaxes(0, 1).reshape(B, -1)[:, :n],
                     ((0, 0), (0, 1)))
    dx = dxs.swapaxes(0, 1).reshape(B, -1, D)[:, :n]
    if position_weights is not None and not shifted:
        return rows, dx, dk
    dx = jnp.pad(dx, ((0, 0), (0, 1), (0, 0)))
    return (rows, dx, dk, ce) if shifted else (rows, dx, dk)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 6))
def _head_loss(hidden, kernel, tokens, weights, chunk, position_weights,
               vocab_major=False):
    rows, _, _ = _head_scan(hidden, kernel, tokens, weights, chunk, False,
                            position_weights, vocab_major=vocab_major)
    return jnp.sum(weights * rows), rows


def _head_loss_fwd(hidden, kernel, tokens, weights, chunk, position_weights,
                   vocab_major):
    rows, dh, dk = _head_scan(hidden, kernel, tokens, weights, chunk, True,
                              position_weights, vocab_major=vocab_major)
    return (jnp.sum(weights * rows), rows), (dh, dk.astype(kernel.dtype),
                                             rows)


def _head_loss_bwd(chunk, vocab_major, residuals, cotangents):
    dh, dk, rows = residuals
    g, _ = cotangents           # the rows are reported, not differentiated
    with jax.named_scope("lm_head_loss"):
        # (the position weights are the step's noise: no gradient)
        return ((g * dh).astype(dh.dtype), (g * dk).astype(dk.dtype), None,
                g * rows, None)


_head_loss.defvjp(_head_loss_fwd, _head_loss_bwd)


def lm_head_loss(hidden: jnp.ndarray, lm_head_kernel: jnp.ndarray,
                 tokens: jnp.ndarray, weights: jnp.ndarray,
                 chunk: int = 1024, position_weights=None,
                 next_token_weights=None, vocab_major: bool = False):
    """Next-token cross entropy with the lm_head FUSED into the loss:
    ``(sum(weights * rows), rows)``, ``rows`` ``[B]`` float32 the mean
    cross entropy of each sequence (reported: no gradient flows from them).

    The head's product and the softmax run per chunk of ``chunk`` positions
    inside one ``lax.scan``, so the peak logits footprint is ``B×chunk×V``
    float32 instead of ``B×T×V``; each chunk's product ``[B·chunk, D] @
    [D, V]`` stays MXU-sized and accumulates in float32 (the logits are never
    rounded to the activations' dtype). Differentiated, the scan takes both
    gradients while a chunk's logits are in hand (a ``jax.custom_vjp``: the
    rows' ``weights`` are what makes ``softmax - onehot`` final there), so a
    chunk costs three products of head size and the backward pass none: it
    scales what the forward kept, the hidden states' gradient ``[B, T, D]``
    and the kernel's ``[D, V]``, by the loss's cotangent. Not differentiated,
    it is the forward product and the loss alone.

    ``hidden`` [B, T, D] from ``model(tokens, return_hidden=True)``;
    ``lm_head_kernel`` [D, V] = ``params["lm_head"]["kernel"]``; ``weights``
    [B] float32: ``1 / B`` each makes the sum the mean loss.

    ``position_weights`` [B, T] float32 (off by default) makes it the
    cross entropy at the SAME position under a weight a position: a row is
    ``sum_i w_i CE(logits_i, tokens_i) / T`` over all T positions (a
    masked-token objective: ``w_i`` zero where a token carries no loss).

    ``next_token_weights`` [B, T] float32 (off by default) weighs the
    NEXT-token cross entropy a position, and the weights carry a gradient: a
    row is ``sum_i w_i CE(logits_i, tokens_i+1) / (T - 1)`` (the last
    position's weight is not read) and ``d loss / d w_i`` is that position's
    cross entropy times the row's weight over ``T - 1``. A looped model
    hands over its passes as rows (``[passes * B, T, D]``, the tokens and the
    rows' weights repeated) under each pass's exit probabilities: all of
    them share the one scan and its one ``[D, V]`` carry.

    ``vocab_major`` (off by default): ``lm_head_kernel`` is ``[V, D]``, a
    tied model's ``params["embed"]["embedding"]`` as it lies, and so is its
    gradient. The plain next-token form contracts over the dimensions that
    layout gives it; the two weighted forms take its transpose.
    """
    with jax.named_scope("lm_head_loss"):
        if vocab_major and (position_weights is not None
                            or next_token_weights is not None):
            lm_head_kernel, vocab_major = lm_head_kernel.T, False
        if next_token_weights is not None:
            if position_weights is not None:
                raise ValueError("position_weights or next_token_weights")
            return _weighted_head_loss(hidden, lm_head_kernel, tokens,
                                       weights, chunk, next_token_weights)
        return _head_loss(hidden, lm_head_kernel, tokens, weights, chunk,
                          position_weights, vocab_major)


def lm_loss_fused(hidden: jnp.ndarray, lm_head_kernel: jnp.ndarray,
                  tokens: jnp.ndarray, chunk: int = 1024) -> jnp.ndarray:
    """Mean next-token cross entropy with the lm_head fused into the loss:
    :func:`lm_head_loss` with uniform weights."""
    rows = hidden.shape[0]
    return lm_head_loss(hidden, lm_head_kernel, tokens,
                        jnp.full((rows,), 1.0 / rows, jnp.float32), chunk)[0]


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
        # a looped model's exit gate ([D, 1] and a bias) is replicated; met
        # first, because its path holds the SwiGLU's ``gate/kernel`` too
        ("exit_gate/", ()),
        # q over the query heads, k and v over the (with grouped-query
        # attention fewer) K/V heads: the axis has to divide both. The held
        # experts' stacked kernels [held, in, out] are the ``expert`` role's
        # (parallel/roles.py), not a rule's here
        ("attn/q/kernel", (None, axis, None)),
        ("attn/k/kernel", (None, axis, None)),
        ("attn/v/kernel", (None, axis, None)),
        ("attn/o/kernel", (axis, None, None)),
        # latent attention: the two up-projections over the heads; the
        # down-projections (q_a, kv_a) and the latents' norms match no rule
        # and stay replicated
        ("attn/q_b/kernel", (None, axis, None)),
        ("attn/kv_b/kernel", (None, axis, None)),
        # a state-space mixer (ssm/in_proj, conv, out_proj, ...) matches no
        # rule: its heads and groups stay whole on every device
        ("gate/kernel", (None, axis)),
        ("up/kernel", (None, axis)),
        ("down/kernel", (axis, None)),
        # a tied model (``tie_embeddings``) has the one array, placed as an
        # embedding is; a convolution operator (short_conv/in_proj, conv,
        # out_proj) and a delta-rule one (kda/in_proj, conv, gate_a, ...,
        # out_proj) match no rule and stay whole on every device
        ("embed/embedding", (None, axis)),
        ("lm_head/kernel", (None, axis)),
    ]


# What a recomputed block keeps beside its flash kernel's pair
# (``TransformerLM.remat_blocks``): its feed-forward sub-layer's output where a
# second norm reads it (``Block.sandwich_norms``). The norm's backward reads
# its input, and only the whole sub-layer can rebuild that: the held experts'
# walk with its three grouped products and its return to token order and the
# shared expert's down projection, or a SwiGLU's. Where no norm follows
# nothing is named: there the backward reads no sub-layer's output. The
# attention's output, which ``ln1_post`` reads, is NOT named: rebuilding it is
# one projection (``W_o``), and with it kept too the Trinity cell's step ran
# slower on the chip (+10.9% over keeping neither against +11.8%) and the TPU
# compiler reported 2.8 GiB more temporaries (PERF.md, PR 38). Defined at the file's end so that no line of the flash
# kernels' call stack moves (their payloads embed it: a moved line misses the
# compile cache).
SUBLAYER_OUT = "rdt_sublayer_out"


def _ffn_out(block, y):
    """``y``, a block's feed-forward output, named where a norm reads it."""
    if not block.sandwich_norms:
        return y
    from jax.ad_checkpoint import checkpoint_name

    return checkpoint_name(y, SUBLAYER_OUT)


class LatentAttention(nn.Module):
    """Multi-head latent attention (the ``deepseek_v3`` family's). A token's
    keys and values come from ONE latent of ``kv_lora_rank`` and one rotary
    key of ``qk_rope_head_dim`` that all heads share::

        q = W_q u                     [H, nope + rope]   (or W_qb norm(W_qa u))
        c, k_rope = split(W_kva u)    [rank], [rope]
        k_nope, v = split(W_kvb norm(c))   [H, nope], [H, v_head_dim]
        q_rope, k_rope = RoPE(q_rope), RoPE(k_rope)
        k = [k_nope, k_rope for every head]
        out = W_o softmax(q k^T / sqrt(nope + rope) + causal) v

    The keys are ``nope + rope`` wide and the values ``v_head_dim``: the
    flash kernels take the two widths as they are, nothing is padded. ``k`` is
    built in HBM by broadcasting ``k_rope`` over the heads (the scope
    ``latent`` holds that, both projections, the norm and RoPE, so a trace
    prices it: ``latent_kv_share``). ``rope=False``: no position embedding
    (``k_rope`` is broadcast as it is and ``q`` is taken whole; the widths
    and the scope stay). Defined at the file's end for the reason
    ``SUBLAYER_OUT`` is."""

    num_heads: int
    kv_lora_rank: int
    qk_nope_head_dim: int
    qk_rope_head_dim: int
    v_head_dim: int
    q_lora_rank: Optional[int] = None       # None: one full-rank W_q
    attention: str = "auto"
    mesh: Any = None
    dtype: Any = jnp.float32
    rope_theta: float = 10000.0
    rope_interleave: bool = False
    rms_norm_eps: float = 1e-6
    init_std: Optional[float] = None
    rope: bool = True                       # False: nothing is rotated

    window = None                           # every key up to a query's own
    _dispatch = Attention._dispatch

    @nn.compact
    def __call__(self, x):
        from raydp_tpu.ops.flash_attention import flash_attention_sharded
        from raydp_tpu.ops.ring_attention import dense_attention

        b, t, dim = x.shape
        heads, nope, rope = (self.num_heads, self.qk_nope_head_dim,
                             self.qk_rope_head_dim)
        eps = self.rms_norm_eps
        init = _init(self.init_std, nn.linear.default_kernel_init)
        dense = lambda name, features: nn.DenseGeneral(  # noqa: E731
            features, axis=-1, name=name, dtype=self.dtype, use_bias=False,
            kernel_init=init)
        if self.q_lora_rank is None:
            q = dense("q", (heads, nope + rope))(x)
        else:
            q = dense("q_b", (heads, nope + rope))(RMSNorm(
                eps, name="q_a_norm")(dense("q_a", self.q_lora_rank)(x)))
        with jax.named_scope("latent"):
            down = dense("kv_a", self.kv_lora_rank + rope)(x)
            latent = RMSNorm(eps, name="kv_norm")(
                down[..., :self.kv_lora_rank])
            kv = dense("kv_b", (heads, nope + self.v_head_dim))(latent)
            positions = jnp.arange(t)
            turn = (lambda a: rotary_embedding(  # noqa: E731
                a, positions, self.rope_theta, self.rope_interleave)
                    ) if self.rope else (lambda a: a)
            k_rope = turn(down[..., None, self.kv_lora_rank:])  # [B, T, 1, r]
            q = jnp.concatenate([q[..., :nope], turn(q[..., nope:])], axis=-1)
            k = jnp.concatenate([kv[..., :nope], jnp.broadcast_to(
                k_rope, (b, t, heads, rope))], axis=-1)
            v = kv[..., nope:]

        kind = self._dispatch(t, nope + rope, self.v_head_dim)
        with jax.named_scope("attn_full"):
            if kind == "ring":
                raise NotImplementedError(
                    "latent attention takes no seq axis: ring attention "
                    "rotates keys and values of one width")
            if kind == "flash":
                out = flash_attention_sharded(q, k, v, self.mesh, causal=True)
            else:
                out = dense_attention(q, k, v, causal=True)
        return nn.DenseGeneral(dim, axis=(-2, -1), name="o", dtype=self.dtype,
                               use_bias=False, kernel_init=init)(out)


def _operator(block):
    """A block's first sub-layer: ``attn``, :class:`Attention` or, where the
    block states a K/V latent, :class:`LatentAttention`; or, where it states
    a convolution's taps, ``short_conv``, a :class:`ShortConv`; or, where it
    states a :class:`KDASpec`, ``kda``, a :class:`KimiDeltaAttention`."""
    if block.conv_taps is not None:
        return ShortConv(block.conv_taps, block.dtype, block.init_std,
                         block.mesh, name="short_conv")
    if block.kda is not None:
        return KimiDeltaAttention(block.kda, block.dtype, block.rms_norm_eps,
                                  block.init_std, block.mesh, name="kda")
    if block.kv_lora_rank is None:
        return Attention(
            block.num_heads, block.attention, block.mesh, block.dtype,
            block.rope_theta, block.qk_norm, block.rms_norm_eps,
            block.init_std, block.head_dim, block.num_kv_heads, block.window,
            block.rope, block.attention_gate, block.blockdiff, name="attn")
    if (block.window or block.attention_gate
            or block.qk_norm or block.num_kv_heads or block.blockdiff):
        raise ValueError("latent attention has no window, "
                         "no gate, no QK norm, no grouped K/V and no "
                         "block-diffusion mask")
    return LatentAttention(
        block.num_heads, block.kv_lora_rank, block.qk_nope_head_dim,
        block.qk_rope_head_dim, block.v_head_dim, block.q_lora_rank,
        block.attention, block.mesh, block.dtype, block.rope_theta,
        block.rope_interleave, block.rms_norm_eps, block.init_std,
        block.rope, name="attn")


# ---------------------------------------------------------------------------
# Layers of ONE sub-layer and the state-space mixer (the ``nemotron_h``
# family). Down here for the reason ``SUBLAYER_OUT`` is.
# ---------------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class SSMSpec:
    """The sizes of a Mamba-2 mixer, as one field of a model: ``num_heads``
    heads of ``head_dim`` channels (the inner width is their product, whatever
    the model's ``dim``), ``n_groups`` groups of heads that share ``B`` and
    ``C``, a state of ``state_size``, a causal convolution of ``conv_kernel``
    taps, a scan in chunks of ``chunk_size``, and the limits ``dt``'s bias is
    initialised between."""

    num_heads: int
    head_dim: int
    n_groups: int
    state_size: int
    conv_kernel: int = 4
    chunk_size: int = 128
    dt_min: float = 0.001
    dt_max: float = 0.1
    dt_floor: float = 1e-4


def _dt_bias_init(spec: SSMSpec):
    """The inverse softplus of ``dt`` drawn log-uniformly between the spec's
    limits, floored."""
    def init(key, shape, dtype=jnp.float32):
        lo, hi = np.log(spec.dt_min), np.log(spec.dt_max)
        dt = jnp.maximum(jnp.exp(jax.random.uniform(key, shape) * (hi - lo)
                                 + lo), spec.dt_floor)
        return (dt + jnp.log(-jnp.expm1(-dt))).astype(dtype)
    return init


class Mamba2Mixer(nn.Module):
    """A Mamba-2 state-space mixer on a normed input ``u [B, T, D]``::

        z, xBC, dt = split(W_in u)        [inner], [inner + 2 G N], [H]
        xBC = silu(conv(xBC))             depthwise, causal, K taps, a bias
        x, B, C = split(xBC)              [H, P], [G, N], [G, N]
        dt = softplus(dt + dt_bias);  A = -exp(A_log)          float32
        y = scan(x, dt, A, B, C, D)       :func:`raydp_tpu.ops.ssd_scan`
        y = RMSNorm_by_group(y * silu(z)) * weight
        out = W_out y

    ``dt``, ``A``, ``D`` and the scan's decays are float32 whatever ``dtype``
    is. The state is carried through the whole sequence (a packed row's
    documents are not told apart, as attention attends across them). Each
    part lies under a scope of its own (``in_proj``, ``conv``, ``scan``,
    ``norm``, ``out_proj``) so that a trace prices it. Where the program is
    lowered for a TPU and the shapes allow (:mod:`raydp_tpu.ops.ssm_glue`,
    :mod:`raydp_tpu.ops.ssd_scan`: whole row tiles and chunks, widths of
    whole 128-lane tiles) the convolution and the gated norm are one Pallas
    pass over HBM each way (``rdt_ssm_conv_fwd|bwd`` under ``conv``, reading
    ``xBC`` out of ``W_in u`` by block index and writing ``x``, ``B``, ``C``
    as three arrays; ``rdt_ssm_norm_fwd|bwd`` under ``norm``, reading ``z``
    likewise) round the scan's two kernels; on every other platform and for
    every other shape all three are their ``jax.numpy`` forms. Over a mesh
    the three are mapped over the batch; heads and groups are not split over
    ``tensor``, and a ``seq`` axis raises."""

    spec: SSMSpec
    dtype: Any = jnp.float32
    rms_norm_eps: float = 1e-6
    init_std: Optional[float] = None
    mesh: Any = None

    @nn.compact
    def __call__(self, u):
        from raydp_tpu.ops.ssd_scan import ssd_scan_sharded
        from raydp_tpu.parallel.mesh import seq_extent

        if self.mesh is not None and seq_extent(self.mesh) > 1:
            raise NotImplementedError(
                "a state-space layer takes no seq axis: its state passes "
                "from a position to the next")
        s, f32 = self.spec, jnp.float32
        b, t, dim = u.shape
        inner, bc = s.num_heads * s.head_dim, s.n_groups * s.state_size
        init = _init(self.init_std, nn.linear.default_kernel_init)
        dense = lambda n, name: nn.Dense(  # noqa: E731
            n, use_bias=False, dtype=self.dtype, name=name, kernel_init=init)
        proj = dense(2 * inner + 2 * bc + s.num_heads, "in_proj")(u)
        # z, xBC, dt side by side: the two stages read their columns of it
        dt = proj[..., 2 * inner + 2 * bc:]
        kernel = self.param("conv", init, (s.conv_kernel, inner + 2 * bc))
        bias = self.param("conv_bias", nn.initializers.zeros,
                          (inner + 2 * bc,))
        dt_bias = self.param("dt_bias", _dt_bias_init(s), (s.num_heads,))
        a_log = self.param("A_log", lambda key, shape: jnp.log(
            jax.random.uniform(key, shape, minval=1.0, maxval=16.0)),
            (s.num_heads,))
        skip = self.param("D", nn.initializers.ones, (s.num_heads,))
        weight = self.param("norm", nn.initializers.ones, (inner,))
        with jax.named_scope("conv"):
            x, b_in, c_in = ssm_glue.conv_silu_sharded(
                proj, kernel, bias, (inner, bc, bc), self.mesh, offset=inner)
        with jax.named_scope("scan"):
            y = ssd_scan_sharded(
                x.reshape(b, t, s.num_heads, s.head_dim),
                jax.nn.softplus(dt.astype(f32) + dt_bias.astype(f32)),
                -jnp.exp(a_log.astype(f32)),
                b_in.reshape(b, t, s.n_groups, s.state_size),
                c_in.reshape(b, t, s.n_groups, s.state_size),
                skip.astype(f32), self.mesh, chunk=s.chunk_size)
        with jax.named_scope("norm"):
            y = ssm_glue.gated_norm_sharded(
                y.reshape(b, t, inner), proj, weight, s.n_groups,
                self.rms_norm_eps, self.mesh)
        return dense(dim, "out_proj")(y)


class Layer(nn.Module):
    """One pre-norm layer of ONE sub-layer: ``x + mixer(RMSNorm(x))``.
    ``mixer`` builds the sub-layer's module (called here, so the module lies
    under this layer by its own name: ``ssm``, ``attn`` or ``moe``); an
    expert layer's ``aux`` is handed on beside ``x``."""

    mixer: Any
    rms_norm_eps: float = 1e-6

    @nn.compact
    def __call__(self, x):
        out = self.mixer()(RMSNorm(self.rms_norm_eps, name="norm")(x))
        if isinstance(out, tuple):
            return x + out[0], out[1]
        return x + out


def _kept(model):
    """What a recomputed layer of ``model`` keeps beside its input, by name:
    its flash kernel's pair, its feed-forward's normed output and its
    attention's inputs (``_kept_inputs``). None where none is recomputed."""
    if not model.remat_blocks:
        return None
    from raydp_tpu.ops.flash_attention import RESIDUAL_NAMES

    return (*RESIDUAL_NAMES, SUBLAYER_OUT, *_kept_inputs(model))


def _recomputed(cls, kept):
    """``cls``, recomputed in the backward pass where ``kept`` names what it
    keeps."""
    return cls if kept is None else _keeping(kept, nn.remat(
        cls, policy=jax.checkpoint_policies.save_only_these_names(*kept)))


def _layer(model, i: int, kept, block):
    """Layer ``i`` of a model, ``block_<i>``: the pair (``B``; ``block`` is
    the class, ``_recomputed(Block, kept)`` made once a model so that the
    layers share what they trace alike; ``C``: the same class with a
    convolution operator; ``K``: with a delta-rule one) or the one sub-layer
    ``layer_kinds`` makes it."""
    kind = model._kind(i)
    if kind == "K" and model.kda is None:
        raise ValueError("a 'K' layer needs the model's kda=KDASpec(..)")
    if kind in "BCK":
        sparse = model._sparse(i)
        return block(
            model.num_heads, model.mlp_ratio, model.attention, model.mesh,
            model.dtype, model.ffn_dim if sparse
            or model.dense_ffn_dim is None else model.dense_ffn_dim,
            model.rms_norm_eps, model.rope_theta, model.qk_norm,
            model.num_experts if sparse else 0, model.experts_per_token,
            model.init_std, model.head_dim, model.num_kv_heads,
            model.sliding_window if model._windowed(i) else None,
            model._rope(i), model.first_expert, model.experts_held,
            model.expert_activation, model.normalize_top_k,
            model.router_input, model.attention_gate, model.sandwich_norms,
            model.routing, model.route_scale, model.shared_expert_dim,
            model.kv_lora_rank, model.q_lora_rank, model.qk_nope_head_dim,
            model.qk_rope_head_dim, model.v_head_dim, model.rope_interleave,
            model.expert_gated, model._blockdiff,
            model.conv_taps if kind == "C" else None, model.route_norm_eps,
            model.kda if kind == "K" else None, name=f"block_{i}")
    if kind == "M":
        if model.ssm is None:
            raise ValueError("an 'M' layer needs the model's ssm=SSMSpec(..)")
        mixer = functools.partial(
            Mamba2Mixer, model.ssm, model.dtype, model.rms_norm_eps,
            model.init_std, model.mesh, name="ssm")
    elif kind == "*":
        mixer = functools.partial(
            Attention, model.num_heads, model.attention, model.mesh,
            model.dtype, model.rope_theta, model.qk_norm, model.rms_norm_eps,
            model.init_std, model.head_dim, model.num_kv_heads,
            model.sliding_window if model._windowed(i) else None,
            model._rope(i), model.attention_gate, model._blockdiff,
            name="attn")
    else:
        from raydp_tpu.models.moe import MoE

        if not model.num_experts:
            raise ValueError("an 'E' layer needs num_experts")
        mixer = functools.partial(
            MoE, model.num_experts, model.experts_per_token,
            model.ffn_dim or model.mlp_ratio * model.dim, model.dtype,
            _init(model.init_std, nn.linear.default_kernel_init),
            model.first_expert, model.experts_held, model.expert_activation,
            model.normalize_top_k, model.routing, model.route_scale,
            model.shared_expert_dim, model.expert_gated,
            model.route_norm_eps, name="moe")
    return _recomputed(Layer, kept)(mixer, model.rms_norm_eps,
                                    name=f"block_{i}")


# ---------------------------------------------------------------------------
# Block-diffusion training (the ``sdar`` family). Down here for the reason
# ``SUBLAYER_OUT`` is.
# ---------------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class BlockDiffusionSpec:
    """The block-diffusion objective (arXiv:2503.09573), as one field of a
    model: a row is cut into blocks of ``block`` tokens; each block draws
    ``t ~ U(t_min, 1]`` and each of its tokens becomes ``mask_id`` with
    probability ``t`` (linear schedule, absorbing state); the model sees
    ``[row ; noised row]`` under the mask of
    :func:`raydp_tpu.ops.flash_attention.blockdiff_visible` and its output at
    a masked position predicts the token that stood there, under the weight
    ``1 / t``. ``eval_seed``: the key a call without the ``diffusion`` stream
    noises with, so that a plain call is a function of its inputs."""

    block: int
    mask_id: int
    t_min: float = 1e-3
    eval_seed: int = 0


def block_diffusion_noise(key, tokens, spec: BlockDiffusionSpec):
    """THE sampler: ``tokens`` [B, T] -> (the noised copy [B, T], ``t`` a
    block [B, T / block] float32, which tokens were masked [B, T] bool)."""
    b, t = tokens.shape
    if t % spec.block:
        raise ValueError(f"a row of {t} tokens is no whole number of blocks "
                         f"of {spec.block}")
    n = t // spec.block
    key_t, key_mask = jax.random.split(key)
    u = jax.random.uniform(key_t, (b, n))
    level = 1.0 - u * (1.0 - spec.t_min)            # u in [0, 1): (t_min, 1]
    masked = jax.random.uniform(key_mask, (b, t)) < jnp.repeat(
        level, spec.block, axis=1)
    return (jnp.where(masked, jnp.asarray(spec.mask_id, tokens.dtype),
                      tokens), level, masked)


def _copies_positions(t: int):
    """The position ids of ``[row ; noised row]``: 0..T/2-1 twice."""
    with jax.named_scope("diffusion"):
        return jnp.tile(jnp.arange(t // 2), 2)


def _noised_row(model, tokens):
    """``tokens`` [B, T] -> (``[tokens ; noised copy]`` [B, 2T], (the weight
    a position of the loss, ``masked / t`` [B, T] float32; how many tokens
    were masked)). The key is the ``diffusion`` stream's where the caller
    hands one (a train step does, a new one each optimizer step), else the
    spec's fixed one."""
    spec = model.diffusion
    with jax.named_scope("diffusion"):
        key = model.make_rng("diffusion") if model.has_rng("diffusion") \
            else jax.random.PRNGKey(spec.eval_seed)
        noised, level, masked = block_diffusion_noise(key, tokens, spec)
        weight = masked / jnp.repeat(level, spec.block, axis=1)
        return jnp.concatenate([tokens, noised], axis=1), (
            weight.astype(jnp.float32), jnp.sum(masked, dtype=jnp.float32))


def _diffusion_loss(model, x, kernel, labels, weights, noise, aux):
    """What :meth:`TransformerLM.loss_rows` returns for ``x`` [B, 2T, D], the
    normed hidden states of ``[row ; noised row]``: the head and the loss run
    over the noised half alone, position ``i`` against token ``i`` under
    ``masked_i / t``; the counts end in the tokens masked and all tokens."""
    weight, masked = noise
    half = x.shape[1] // 2
    loss, _ = lm_head_loss(x[:, half:], kernel, labels, weights,
                           chunk=max(128, 2048 // x.shape[0]),
                           position_weights=weight,
                           vocab_major=model.tie_embeddings)
    counts = jnp.stack([masked, jnp.float32(labels.size)])
    if not aux:
        return loss, counts
    loss, slots = model._with_aux(loss, weights, aux)
    return loss, jnp.concatenate([slots, counts])


# ---------------------------------------------------------------------------
# Looped models (the ``ouro`` family: "layers run several times"). Down here
# for the reason ``SUBLAYER_OUT`` is.
#
# ``total_ut_steps = P > 1`` applies the whole stack ``block_0..block_{N-1}``
# and the final norm P times to its own output on the SAME parameters:
# ``h_t = ln_f(Stack(h_{t-1}))``, ``h_0`` the embeddings. The passes are ONE
# ``lax.scan`` (flax's ``nn.scan`` with the parameters broadcast), so the
# program holds each layer once whatever P is, the parameter tree is the one
# a plain model has, and a shared weight's gradient is summed over the passes
# in the scan's transpose, into one float32 tree. A recomputed block
# (``remat_blocks``) keeps its set a pass AND a layer, stacked by the scan.
#
# ``exit_entropy_weight = beta`` adds the exit gate (``exit_gate``: 2048 + 1
# float32 parameters at a hidden size of 2048) and the objective of the
# family's first training stage: ``lambda_t = sigmoid(h_t w_g + b_g)`` a
# position, the exit distribution ``p_t = lambda_t S_{t-1}``, ``S_t = S_{t-1}
# (1 - lambda_t)`` (``S_0 = 1``, ``p_P = S_{P-1}``: the P sum to 1), and
#
#     loss = sum_b w_b mean_{i < T-1} [ sum_t p_t(i) CE_t(i) - beta H(p(i)) ]
#
# with ``CE_t`` pass t's next-token cross entropy. All P passes' hidden
# states go through the head as rows of ONE :func:`lm_head_loss` scan under
# ``next_token_weights = p`` (one ``[D, V]`` float32 carry, not P). Without
# the gate the loss is the last pass's. Called plainly the model returns the
# last pass's logits (no position leaves early) and, ``exit_probs_out``, the
# P exit probabilities after them in the last dimension.
# ---------------------------------------------------------------------------
def _is_looped(model) -> bool:
    return model.total_ut_steps > 1 or model.exit_entropy_weight is not None


def _exit_counters(passes: int):
    """A gated model's ``loss_counters``: a pass's exit mass each, then the
    positions they were summed over."""
    return tuple(("train_exit_mass_total", str(t + 1))
                 for t in range(passes)) + (
                     ("train_exit_positions_total", ""),)


def exit_distribution(gate_logits):
    """``gate_logits`` [P, ...] float32, a pass's gate before its sigmoid ->
    the logarithms of the exit probabilities [P, ...]: ``p_t = lambda_t
    prod_{s<t} (1 - lambda_s)`` for ``t < P`` and ``p_P = prod_{s<P} (1 -
    lambda_s)`` (the last pass's gate is not read). In logarithms, so that a
    saturated gate gives a finite entropy and finite gradients."""
    go = jax.nn.log_sigmoid(-gate_logits[:-1])          # log(1 - lambda_s)
    survived = jnp.concatenate([jnp.zeros_like(gate_logits[:1]),
                                jnp.cumsum(go, axis=0)])     # log S_{t-1}
    return survived + jnp.concatenate([
        jax.nn.log_sigmoid(gate_logits[:-1]),
        jnp.zeros_like(gate_logits[:1])])


def _looped(model, x, return_hidden, labels, weights, embed):
    """What :meth:`TransformerLM.__call__` returns for a looped model, from
    the embeddings ``x`` (of the module ``embed``) on."""
    if (model.num_experts or model.diffusion is not None
            or set(model.layer_kinds) - {"B"}):
        raise ValueError("a looped model (total_ut_steps > 1 or an exit "
                         "gate) takes dense blocks alone: no experts, no "
                         "layer of one sub-layer, no block diffusion")
    kept, f32 = _kept(model), jnp.float32
    block = _recomputed(Block, kept)

    def one_pass(mdl, h, _):
        for i in range(mdl.num_layers):
            h = _layer(mdl, i, kept, block)(h)
        h = RMSNorm(mdl.rms_norm_eps, name="ln_f")(h)
        return h, h

    with jax.named_scope("loop"):
        x, hs = nn.scan(one_pass, variable_broadcast="params",
                        split_rngs={"params": False},
                        length=model.total_ut_steps)(model, x, None)
    init = _init(model.init_std, nn.linear.default_kernel_init)
    head = _head(model, embed)
    log_p = None
    if model.exit_entropy_weight is not None:
        with jax.named_scope("exit_gate"):
            log_p = exit_distribution(nn.Dense(
                1, dtype=f32, name="exit_gate", kernel_init=init)(hs)[..., 0])
    if labels is None and not return_hidden:
        logits = head(x).astype(f32)
        if log_p is None or not model.exit_probs_out:
            return logits
        with jax.named_scope("exit_gate"):
            return jnp.concatenate(
                [logits, jnp.moveaxis(jnp.exp(log_p), 0, -1)], axis=-1)
    head(x[:, :1])      # registers the kernel, as the plain model does
    if labels is None:
        return x
    kernel = _head_kernel(model, embed, head)
    passes, rows = hs.shape[:2]
    tied = model.tie_embeddings
    if log_p is None:
        loss, _ = lm_head_loss(x, kernel, labels, weights,
                               chunk=max(128, 2048 // rows),
                               vocab_major=tied)
        return loss, jnp.zeros((0,), f32)
    with jax.named_scope("exit_gate"):
        p = jnp.exp(log_p)                                  # [P, B, T]
    loss, _ = lm_head_loss(
        hs.reshape((passes * rows,) + hs.shape[2:]), kernel,
        jnp.tile(labels, (passes, 1)), jnp.tile(weights, passes),
        chunk=max(128, 2048 // (passes * rows)),
        next_token_weights=p.reshape(passes * rows, -1), vocab_major=tied)
    with jax.named_scope("exit_gate"):
        # over the positions that carry a loss (the last predicts nothing)
        p, log_p = p[..., :-1], log_p[..., :-1]
        entropy = -jnp.sum(p * log_p, axis=0).mean(axis=-1)         # [B]
        loss = loss - model.exit_entropy_weight * jnp.sum(weights * entropy)
        real = (weights > 0).astype(f32)                # a padded row: none
        counts = jnp.concatenate([
            jnp.einsum("pbt,b->p", p, real),
            jnp.sum(real)[None] * p.shape[-1]])
    return loss, counts


@functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
def _weighted_head_loss(hidden, kernel, tokens, weights, chunk,
                        position_weights):
    """:func:`_head_loss` in the next-token form under weights a position
    that carry a gradient (:func:`lm_head_loss`'s ``next_token_weights``)."""
    rows, _, _ = _head_scan(hidden, kernel, tokens, weights, chunk, False,
                            position_weights, shifted=True)
    return jnp.sum(weights * rows), rows


def _weighted_head_loss_fwd(hidden, kernel, tokens, weights, chunk,
                            position_weights):
    rows, dh, dk, ce = _head_scan(hidden, kernel, tokens, weights, chunk,
                                  True, position_weights, shifted=True)
    scale = weights.astype(jnp.float32)[:, None] / (hidden.shape[1] - 1)
    return (jnp.sum(weights * rows), rows), (
        dh, dk.astype(kernel.dtype), rows, scale * ce)


def _weighted_head_loss_bwd(chunk, residuals, cotangents):
    dh, dk, rows, dw = residuals
    g, _ = cotangents           # the rows are reported, not differentiated
    with jax.named_scope("lm_head_loss"):
        return ((g * dh).astype(dh.dtype), (g * dk).astype(dk.dtype), None,
                g * rows, g * dw)


_weighted_head_loss.defvjp(_weighted_head_loss_fwd, _weighted_head_loss_bwd)


# ---------------------------------------------------------------------------
# A pair whose operator is a gated short convolution, and tied embeddings
# (the ``lfm2`` family). Down here for the reason ``SUBLAYER_OUT`` is.
# ---------------------------------------------------------------------------
class ShortConv(nn.Module):
    """A gated short convolution on a normed input ``u [B, T, D]``, the
    operator of a ``C`` pair::

        B, C, z = split(W_in u)           three widths of D, in this order
        out = W_out (C * conv(B * z))     depthwise, causal, ``taps`` taps,
                                          zeros before the sequence, no bias,
                                          no activation

    The stage between the projections is :func:`raydp_tpu.ops.short_conv.
    gated_conv`: float32 arithmetic, one Pallas pass over HBM each way where
    the program is lowered for a TPU and the shapes allow (``rdt_gated_conv_
    fwd|bwd``, reading the three widths out of ``W_in u`` by block index), its
    ``jax.numpy`` form elsewhere. Each part lies under a scope of its own
    (``in_proj``, ``conv``, ``out_proj``) so that a trace prices it. The
    window runs through the whole sequence (a packed row's documents are not
    told apart, as attention attends across them). A recomputed pair
    (``remat_blocks``) keeps nothing of it: ``W_in u`` is rebuilt. Over a
    mesh the stage is mapped over the batch; a ``seq`` axis raises."""

    taps: int = 3
    dtype: Any = jnp.float32
    init_std: Optional[float] = None
    mesh: Any = None

    @nn.compact
    def __call__(self, u):
        from raydp_tpu.ops.short_conv import gated_conv_sharded
        from raydp_tpu.parallel.mesh import seq_extent

        if self.mesh is not None and seq_extent(self.mesh) > 1:
            raise NotImplementedError(
                "a short convolution takes no seq axis: its window reaches "
                "back over the rows before a position")
        dim = u.shape[-1]
        init = _init(self.init_std, nn.linear.default_kernel_init)
        dense = lambda n, name: nn.Dense(  # noqa: E731
            n, use_bias=False, dtype=self.dtype, name=name, kernel_init=init)
        proj = dense(3 * dim, "in_proj")(u)
        taps = self.param("conv", init, (self.taps, dim))
        with jax.named_scope("conv"):
            y = gated_conv_sharded(proj, taps, dim, self.mesh)
        return dense(dim, "out_proj")(y)


def _head(model, embed):
    """The head of a model: ``lm_head``, a kernel of its own, or
    (``tie_embeddings``) the embedding read the other way: no parameter."""
    if model.tie_embeddings:
        return embed.attend
    return nn.Dense(model.vocab_size, use_bias=False, dtype=model.dtype,
                    name="lm_head", kernel_init=_init(
                        model.init_std, nn.linear.default_kernel_init))


def _head_kernel(model, embed, head):
    """What the fused loss takes as the head's kernel: ``lm_head``'s ``[D,
    V]`` or, tied, the embedding ``[V, D]`` as it lies (``lm_head_loss``'s
    ``vocab_major``): ONE float32 leaf then takes the gather's gradient and
    the head's, and has one optimizer state and one checkpoint array."""
    if model.tie_embeddings:
        return embed.embedding
    return head.variables["params"]["kernel"]


# ---------------------------------------------------------------------------
# What a recomputed attention sub-layer keeps of its INPUTS. Down here for the
# reason ``SUBLAYER_OUT`` is.
#
# The flash kernels' backward reads q, k and v as the forward kernel took them
# (``flash_attention.INPUT_NAMES``: after a head norm, RoPE and the transposes
# into the kernels' layout), a head norm's backward reads the raw projection it
# normed (``W_q u``, ``W_k u``) and a gate's and ``W_o``'s read the raw ``W_g
# u`` (``RAW_NAMES``). Kept by name, none of them is formed again in the
# backward pass: the recomputation of a block is then ``ln1``, ``W_o`` and the
# feed-forward's part. A name is bound only while a layer is traced whose
# checkpoint's policy lists it (``_keeping``), so a layer that keeps none (not
# recomputed, latent, looped, ``dense``) traces the program it traced before
# these names were.
# ---------------------------------------------------------------------------
RAW_NAMES = {"q": "rdt_attn_q_raw", "k": "rdt_attn_k_raw",
             "gate": "rdt_attn_gate_raw"}


def _keeping(kept, cls):
    """``cls``, a recomputed layer's class, traced under the names of ``kept``
    that an attention or its kernels bind (``flash_attention.kept_names``); as
    it is where there is none."""
    from raydp_tpu.ops.flash_attention import INPUT_NAMES, kept_names

    bound = frozenset(kept) & {*INPUT_NAMES, *RAW_NAMES.values()}
    if not bound:
        return cls
    call = cls.__call__

    def under_names(self, *args):
        token = kept_names.set(bound)
        try:
            return call(self, *args)
        finally:
            kept_names.reset(token)

    cls.__call__ = under_names
    return cls


def _raw(projection):
    """An :class:`Attention` projection with its output under its name of
    ``RAW_NAMES`` where the layer being traced keeps it (``v`` has none: the
    kernel's own input is all of it)."""
    from raydp_tpu.ops.flash_attention import kept_names

    name = RAW_NAMES.get(projection.name)
    if name not in kept_names.get():
        return projection
    from jax.ad_checkpoint import checkpoint_name

    return lambda x: checkpoint_name(projection(x), name)


def _kept_inputs(model):
    """The names of its attention's inputs that a recomputed layer of
    ``model`` keeps: THE rule, from the attention's class and fields, the
    stack's shape and the kind the attention dispatches to, nothing else.

    - :class:`Attention` on the flash kernels: the kernel's k and v (an
      eighth to a quarter of q under grouped K/V heads) and its q; where a
      head norm stands between projection and kernel (``qk_norm``) the raw
      ``W_q u`` and ``W_k u`` too, which that norm's backward reads (with the
      kernel's q alone the projection runs again for it, with the raw one
      alone norm, RoPE and the transposes do); where the attention is gated
      the raw ``W_g u``. Measured set by set on the chip (PERF.md, PR 62).
    - :class:`LatentAttention`: nothing (its k is a broadcast of one rotary key
      over every head: kept, it is the widest array of the layer).
    - a looped stack: nothing (the scan keeps a set a pass AND a layer).
    - ``dense`` and ``ring`` attention: nothing (they keep no output either:
      their whole forward runs again)."""
    if (_is_looped(model) or model.kv_lora_rank is not None
            or "once" not in model.attention_forward):
        return ()
    from raydp_tpu.ops.flash_attention import INPUT_NAMES

    raw = ("q", "k") * bool(model.qk_norm) + ("gate",) * model.attention_gate
    return (*INPUT_NAMES, *(RAW_NAMES[name] for name in raw))


def _attention_inputs(model):
    """The attention layers a step executes (layers times ``total_ut_steps``)
    in a model whose layers are recomputed, by what the recomputation does
    with the attention's inputs (q, k and v as the flash kernel takes them,
    and the raw projections a head norm or a gate reads): what
    ``train_attention_inputs_total`` counts once a built step. ``kept``: the
    policy lists them (``_kept_inputs``) and the backward runs no projection,
    head norm or RoPE a second time; ``rebuilt``: it lists none (latent
    attention, a looped stack, ``dense`` and ``ring``). Nothing where no layer
    is recomputed."""
    if not model.remat_blocks:
        return {}
    return {"kept" if _kept_inputs(model) else "rebuilt":
            len(model._layers_of("B*")) * model.total_ut_steps}


# attached here and not written in the class: its lines lie on the flash
# kernels' call stack (this section's first lines)
TransformerLM.attention_inputs = property(_attention_inputs)


# ---------------------------------------------------------------------------
# A pair whose operator is Kimi Delta Attention (the ``kimi_linear`` family).
# Down here for the reason ``SUBLAYER_OUT`` is.
# ---------------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class KDASpec:
    """The sizes of a Kimi Delta Attention operator, as one field of a model:
    ``num_heads`` heads of ``head_dim`` channels for keys and values alike
    (the inner width is their product, whatever the model's ``dim``), a
    causal convolution of ``conv_taps`` taps, the rank ``gate_rank`` of the
    two low-rank gates (the decay's and the output's) and a scan in chunks of
    ``chunk``. The limits the decay's bias is initialised between
    (:func:`_dt_bias_init`'s rule) are the family's, not arguments."""

    num_heads: int
    head_dim: int
    conv_taps: int = 4
    gate_rank: int = 128
    chunk: int = 64
    dt_min: ClassVar[float] = 0.001
    dt_max: ClassVar[float] = 0.1
    dt_floor: ClassVar[float] = 1e-4


class KimiDeltaAttention(nn.Module):
    """Kimi Delta Attention on a normed input ``u [B, T, D]``, the operator of
    a ``K`` pair (``H`` heads of ``P`` channels, ``W = H P``)::

        q~ | k~ | v~ = W_in u                 ONE matrix D -> 3 W, this order
        q^, k^, v = silu(conv(q~)), silu(conv(k~)), silu(conv(v~))
                                              depthwise, causal, ``conv_taps``
                                              taps, zeros before the row, no bias
        q, k = q^ / |q^|_2 * P^-1/2, k^ / |k^|_2      head by head, eps 1e-6
        g = -exp(A_log) * softplus(W_gb (W_ga u) + dt_bias)   [H, P], float32
        b = sigmoid(W_b u)                                    [H], float32
        o = scan(q, k, v, g, b)               :func:`raydp_tpu.ops.kda_scan`
        out = W_out (RMSNorm_P(o) * weight * sigmoid(W_zb (W_za u)))

    The norm's weight ``[P]`` is one for all heads; the decay, ``b``, the L2
    norms and the gated norm are float32 whatever ``dtype`` is. The state is
    carried through the whole sequence (a packed row's documents are not told
    apart, as attention attends across them). Each part lies under a scope
    of its own so that a trace prices it: ``in_proj``, ``conv`` (the
    convolution of :func:`raydp_tpu.ops.ssm_glue.conv_silu`, reading the
    three widths out of ``W_in u`` by block index where its kernels run),
    ``gate`` (the decay's two products, its softplus, ``b``, the L2 norms),
    ``scan``, ``norm`` (the gated norm and the output gate's two products),
    ``out_proj``. A recomputed pair (``remat_blocks``) keeps nothing of it.
    Over a mesh heads stay whole on every device (``tensor`` replicates
    them), and a ``seq`` axis raises."""

    spec: KDASpec
    dtype: Any = jnp.float32
    rms_norm_eps: float = 1e-6
    init_std: Optional[float] = None
    mesh: Any = None

    @nn.compact
    def __call__(self, u):
        from raydp_tpu.ops.kda_scan import kda_scan_sharded
        from raydp_tpu.parallel.mesh import seq_extent

        if self.mesh is not None and seq_extent(self.mesh) > 1:
            raise NotImplementedError(
                "a delta-rule layer takes no seq axis: its state passes "
                "from a position to the next")
        s, f32 = self.spec, jnp.float32
        b, t, dim = u.shape
        heads, width = s.num_heads, s.head_dim
        inner = heads * width
        init = _init(self.init_std, nn.linear.default_kernel_init)
        dense = lambda n, name: nn.Dense(  # noqa: E731
            n, use_bias=False, dtype=self.dtype, name=name, kernel_init=init)
        matrix = lambda name, *shape: self.param(  # noqa: E731
            name, init, shape).astype(self.dtype)
        proj = dense(3 * inner, "in_proj")(u)
        taps = self.param("conv", init, (s.conv_taps, 3 * inner))
        with jax.named_scope("conv"):
            q, k, v = (a.reshape(b, t, heads, width)
                       for a in ssm_glue.conv_silu_sharded(
                           proj, taps, jnp.zeros((3 * inner,), taps.dtype),
                           (inner,) * 3, self.mesh))
        with jax.named_scope("gate"):
            a_log = self.param("A_log", lambda key, shape: jnp.log(
                jax.random.uniform(key, shape, minval=1.0, maxval=16.0)),
                (heads,))
            dt_bias = self.param("dt_bias", _dt_bias_init(s), (inner,))
            step = jnp.dot(jnp.dot(u, matrix("gate_a", dim, s.gate_rank)),
                           matrix("gate_b", s.gate_rank, inner),
                           preferred_element_type=f32)
            g = -jnp.exp(a_log.astype(f32))[:, None] * jax.nn.softplus(
                step + dt_bias.astype(f32)).reshape(b, t, heads, width)
            beta = jax.nn.sigmoid(jnp.dot(u, matrix("beta", dim, heads),
                                          preferred_element_type=f32))
            unit = lambda a: a.astype(f32) * jax.lax.rsqrt(  # noqa: E731
                jnp.sum(jnp.square(a.astype(f32)), axis=-1, keepdims=True)
                + 1e-6)
            q = (unit(q) * width ** -0.5).astype(self.dtype)
            k = unit(k).astype(self.dtype)
        with jax.named_scope("scan"):
            o = kda_scan_sharded(q, k, v, g, beta, self.mesh, chunk=s.chunk)
        with jax.named_scope("norm"):
            z = jnp.dot(jnp.dot(u, matrix("out_gate_a", dim, s.gate_rank)),
                        matrix("out_gate_b", s.gate_rank, inner))
            weight = self.param("norm", nn.initializers.ones, (width,))
            o = o.astype(f32)
            o = o * jax.lax.rsqrt(jnp.mean(jnp.square(o), axis=-1,
                                           keepdims=True) + self.rms_norm_eps)
            o = (o * weight.astype(f32) * jax.nn.sigmoid(
                z.astype(f32)).reshape(o.shape)).astype(self.dtype).reshape(
                    b, t, inner)
        return dense(dim, "out_proj")(o)
