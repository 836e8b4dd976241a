"""Sparse expert feed-forward layer: a softmax router over ``num_experts``,
``top_k`` experts a token, **dropless** dispatch, gated experts (SiLU or
ReLU), the top-k weights as the softmax gives them or renormalised over the
chosen.

Every one of the ``top_k * N`` (token, expert) slots is computed, whatever
the imbalance: there is no capacity factor, nothing is padded to a capacity
and no token is dropped. The slots are sorted by expert, the tokens gathered
into that order, and the three expert products run as grouped GEMMs over the
stacked kernels ``[E, D, F]`` (gate, up) and ``[E, F, D]`` (down) with this
step's group sizes (:func:`jax.lax.ragged_dot`, which the TPU compiler lowers
to its grouped-matmul kernel; an empty group costs nothing). The outputs go
back to token order and are summed with the router's weights.

Both moves between token order and expert order are permutations of the
slots, and their gradients are written as the inverse permutation (a gather)
instead of the scatter-add autodiff would emit.

The router's logits, softmax, top-k and both auxiliary losses are float32
whatever the activations' dtype. Returned with the output: the
load-balancing loss ``E * sum_e f_e P_e`` (``f_e`` the share of slots routed
to expert ``e``, ``P_e`` its mean router probability; 1.0 when uniform), the
router z-loss ``mean(logsumexp(logits)^2)``, and the slots of the fullest
expert beside all slots (what ``moe_slots_total`` counts).

**One chip's share of an expert-parallel layer.** Told which experts it holds
(``first_expert``, ``experts_held``: the range ``[first, first + held)`` of
``num_experts``), the layer still routes over all of them, with the published
``top_k`` and weights normalised over all the chosen, and both auxiliary
losses over all experts' statistics. Its kernels are ``[held, D, F]``; the
slots are sorted with the held experts' first, the grouped products are given
the held experts' group sizes alone, so that a slot of an absent expert is in
no group, costs no product and adds nothing: the output is the part of the
layer's result that the held experts give. The shares of all the chips add up
to the whole layer (``tests/test_swa_moe_lm.py``). Nothing here stands in for
the absent chips or their exchange. ``slots_held`` counts the slots computed.

The router's logits can be handed in (a model whose router reads another
activation than the experts' input computes them itself with
:func:`router_logits`); the layer then holds no router kernel.
"""

from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from flax import linen as nn


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _dispatch(x, order, inverse, top_k: int):
    """Token rows ``[N, D]`` into slot order ``[top_k * N, D]``: slot ``s``
    of the sorted order holds token ``order[s] // top_k``."""
    return x[order // top_k]


def _dispatch_fwd(x, order, inverse, top_k):
    return x[order // top_k], inverse


def _dispatch_bwd(top_k, inverse, g):
    # a token's gradient is the sum over its top_k slots; back in token
    # order those are adjacent rows
    back = g[inverse]
    summed = back.reshape(-1, top_k, g.shape[-1]).astype(jnp.float32).sum(1)
    return summed.astype(g.dtype), None, None


_dispatch.defvjp(_dispatch_fwd, _dispatch_bwd)


@jax.custom_vjp
def _permute(x, perm, inverse):
    """``x[perm]`` for a permutation whose inverse is known: the gradient is
    ``g[inverse]``, a gather."""
    return x[perm]


def _permute_fwd(x, perm, inverse):
    return x[perm], inverse


def _permute_bwd(inverse, g):
    return g[inverse], None, None


_permute.defvjp(_permute_fwd, _permute_bwd)


@jax.custom_vjp
def _where_rows(x, keep):
    """Rows of ``x`` where ``keep``, zeros elsewhere, and the same of the
    gradient: whatever a grouped product leaves in the rows that belong to no
    group (forward or transposed) goes no further."""
    return jnp.where(keep[:, None], x, 0)


def _where_rows_fwd(x, keep):
    return _where_rows(x, keep), keep


def _where_rows_bwd(keep, g):
    return jnp.where(keep[:, None], g, 0), None


_where_rows.defvjp(_where_rows_fwd, _where_rows_bwd)


def router_logits(h: jnp.ndarray, kernel: jnp.ndarray) -> jnp.ndarray:
    """Float32 router logits ``[N, E]`` of token rows ``[N, D]``: a float32
    product of float32 operands (the TPU's default would run it in one
    bfloat16 pass and move near-tied top-k choices)."""
    return jnp.dot(h.astype(jnp.float32), kernel.astype(jnp.float32),
                   precision=jax.lax.Precision.HIGHEST)


def route(logits: jnp.ndarray, top_k: int, normalize: bool = False
          ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Float32 router logits ``[N, E]`` -> (probabilities ``[N, E]``, the
    ``top_k`` expert ids ``[N, top_k]``, their weights: their probabilities
    or, ``normalize``, those over their sum: the softmax over the chosen
    logits alone)."""
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    weights, ids = jax.lax.top_k(probs, top_k)
    if normalize:
        weights = weights / jnp.sum(weights, axis=-1, keepdims=True)
    return probs, ids, weights


ACTIVATIONS = {"silu": nn.silu, "relu": nn.relu}


class MoE(nn.Module):
    """``x [..., D]`` -> (``y [..., D]``, ``aux``) with ``aux`` a dict of
    float32 scalars: ``balance``, ``z``, ``slots_max``, ``slots_all`` and,
    where only a share of the experts is held, ``slots_held``."""

    num_experts: int
    top_k: int
    expert_dim: int
    dtype: Any = jnp.float32
    kernel_init: Any = nn.initializers.lecun_normal()
    first_expert: int = 0
    experts_held: Optional[int] = None      # None: all of them
    activation: str = "silu"
    normalize_top_k: bool = False

    @nn.compact
    def __call__(self, x, logits=None
                 ) -> Tuple[jnp.ndarray, Dict[str, jnp.ndarray]]:
        lead, dim = x.shape[:-1], x.shape[-1]
        e, k, f = self.num_experts, self.top_k, self.expert_dim
        first = self.first_expert
        held = e if self.experts_held is None else self.experts_held
        if not 0 <= first <= first + held <= e or held < 1:
            raise ValueError(f"experts [{first}, {first + held}) of {e}")
        share = held < e
        act_fn = ACTIVATIONS[self.activation]
        h = x.reshape(-1, dim)
        n = h.shape[0]
        if logits is None:
            router = self.param("router", self.kernel_init, (dim, e))
        gate = self.param("experts_gate", self.kernel_init, (held, dim, f))
        up = self.param("experts_up", self.kernel_init, (held, dim, f))
        down = self.param("experts_down", self.kernel_init, (held, f, dim))

        with jax.named_scope("router"):
            logits = router_logits(h, router) if logits is None \
                else logits.reshape(n, e)
            probs, ids, weights = route(logits, k, self.normalize_top_k)
            # read back only by a caller that asks (mutable="intermediates")
            self.sow("intermediates", "top_k_ids", ids)
            slots = ids.reshape(-1)                              # [k * N]
            sizes = jnp.sum(jax.nn.one_hot(slots, e, dtype=jnp.int32), axis=0)
            load = sizes.astype(jnp.float32) / (k * n)
            aux = {
                "balance": e * jnp.sum(load * jnp.mean(probs, axis=0)),
                "z": jnp.mean(jnp.square(
                    jax.nn.logsumexp(logits, axis=-1))),
                "slots_max": jnp.max(sizes).astype(jnp.float32),
                "slots_all": jnp.float32(k * n),
            }
            if share:
                # the held experts' slots sort first, in expert order; the
                # groups end where they end
                slots = (slots - first) % e
                sizes = sizes[first:first + held]
                here = jnp.arange(k * n) < jnp.sum(sizes)
                aux["slots_held"] = jnp.sum(sizes).astype(jnp.float32)

        with jax.named_scope("dispatch"):
            order = jnp.argsort(slots, stable=True)
            inverse = jnp.argsort(order)
            xs = _dispatch(h.astype(self.dtype), order, inverse, k)
            if share:
                xs = _where_rows(xs, here)

        with jax.named_scope("experts"):
            cast = lambda w: w.astype(self.dtype)  # noqa: E731
            act = act_fn(jax.lax.ragged_dot(xs, cast(gate), sizes)) \
                * jax.lax.ragged_dot(xs, cast(up), sizes)
            out = jax.lax.ragged_dot(act, cast(down), sizes)    # [k * N, D]
            if share:
                out = _where_rows(out, here)

        with jax.named_scope("combine"):
            back = _permute(out, inverse, order).reshape(n, k, dim)
            y = jnp.sum(back.astype(jnp.float32)
                        * weights[..., None], axis=1).astype(self.dtype)
        return y.reshape(*lead, dim), aux
