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
losses over all experts' statistics. Its kernels are ``[held, D, F]`` and its
output is the part of the layer's result that the held experts give: the
shares of all the chips add up to the whole layer
(``tests/test_swa_moe_lm.py``). Nothing here stands in for the absent chips
or their exchange.

A share moves and touches the held slots' rows and no others. The slots are
sorted with the held experts' first; how many there are, ``S``, is a run-time
value with no bound below ``top_k * N`` (dropless: no slot of a held expert is
dropped, at any routing), so the share walks the first ``S`` positions of the
sorted order ``c`` at a time, ``ceil(S / c)`` trips of a ``while``, none when
no slot is held (:func:`_held_share`). A trip gathers its ``c`` token rows,
gives the three grouped products the held groups' sizes clipped to the trip,
masks the rows of the last trip after the held slots (whatever a grouped
product leaves in rows of no group goes no further) and writes its ``c``
output rows at their place in an expert-order buffer that nothing initialises.
Back in token order a token's held slots are neighbours, so the held rows are
summed where they lie (:func:`_runs_to_tokens`): one integer sort lists the
held positions by token (:func:`_runs_by_token`, shared by the forward and the
backward), the same trips gather the buffer's rows in that order, ``c`` at a
time behind a halo of one tile, each row adds those of its predecessors that
are its token's (a run is of ``min(top_k, held)`` rows at most; times their
weights, in float32, one fused pass, no ``[N, top_k, D]`` array and no
scatter-add), and each token
reads the last row of its run, one gather of ``N`` rows: ``S + N`` rows moved
a pass, exact at any routing, ``S = 0`` and ``S = top_k * N`` included
(``top_k`` gathers of ``N`` rows, one a slot rank and every one over all the
tokens, moved ``top_k * N``: doc/long_context.md has both forms' readings at
0.5 to 2 held slots a token). The loop has no reverse rule, so the backward is
written here and walks the same trips: a slot's output gradient is its token's
row of ``g`` (a gather, token order -> expert order), its weight's gradient a
row dot product in expert order, the kernels' gradients are summed over the
trips in float32, and the input's gradient comes back as the forward's output
does; it keeps the layer's inputs and the routing's small arrays and forms the
two first products again, so a block that is recomputed anyway
(``remat_blocks``) has nothing to recompute here. ``c`` follows from the
shapes (:func:`_chunk_rows`: half the even share ``top_k * N * held / E``).
``slots_held`` counts the slots computed, ``slots_moved`` the rows the trips
carried (``ceil(S / c) * c``). Forward and backward each run under one
``cond`` on a slot being held, and the walk's buffers (``top_k * N`` rows each,
whatever the trips write) are allocated inside its branch: an allocation that
depends on nothing is one the compiler places where it likes, and it placed
every layer's at the start of the step.

The layer that holds every expert keeps the single-shot path above:
``top_k * N`` is then the exact number of rows, known when the step is built,
and one product over all of them is what the chip runs fastest. The two are
told apart by what the layer is configured with (``experts_held <
num_experts``), not by an option.

The router's logits can be handed in (a model whose router reads another
activation than the experts' input computes them itself with
:func:`router_logits`); the layer then holds no router kernel.

**Sigmoid routing with a balancing bias** (``routing="sigmoid"``; auxiliary-
loss-free balancing). The scores are ``sigmoid(logits)``, each expert's own.
The ``top_k`` experts are *picked* by ``score + bias`` and *weighed* by the
bare scores, renormalised over the chosen (``normalize_top_k``: over their
sum plus ``normalize_eps``, 1e-20 or what a family states) and scaled
(``route_scale``). ``bias [E]`` is float32 state that no gradient moves: it
lies in the variable collection :data:`STATE` (the one
:class:`raydp_tpu.train.FlaxEstimator` carries, shards and checkpoints beside
the parameters, with no optimizer moments), a forward pass under which the
collection is mutable adds the slots each of **all** ``E`` experts was picked
for to ``counts`` next to it, and :func:`balance_bias`, which the model runs
once an optimizer step after the gradients are applied, moves the bias by
``rate * sign(mean(counts) - counts)``, centred, and empties the counts: the
forward of a step reads the bias the step before left.

**A shared expert** (``shared_dim``): one more gated MLP of that width that
every token takes, beside the routed ones and unweighted. A share of the
layer (``experts_held``) holds it whole, so when the chips' shares are added
up it counts once.

**Experts of two matrices** (``gated=False``): ``W_down act(W_up h)`` with no
gate kernel (``experts_gate``, ``shared_gate`` do not exist), ``activation``
``relu2`` giving ``relu(.)^2``. Both paths and the shared expert take the one
first product where gated experts take two (:func:`_hidden`), so the held
share's walk is two grouped products a trip forward and its hand-written
backward drops the gate's three.
"""

from __future__ import annotations

import functools
import operator
from typing import Any, Dict, Mapping, Optional, Tuple

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


def _tile_rows(dtype) -> int:
    """The rows of the dtype's sublane tile: 8 of 32 bits."""
    return 8 * max(1, 4 // jnp.dtype(dtype).itemsize)


def _chunk_rows(slots: int, held: int, experts: int, dtype) -> int:
    """Rows a trip of the held share's walk carries: half the even share
    ``slots * held / experts`` (even routing takes two trips, the held
    experts at one and a half times their share three), rounded up to the
    dtype's sublane tile (8 rows of 32 bits) so that every trip's rows start
    on a tile. A trip costs 1-2 ms at the published widths whatever it
    carries (the kernels' float32 gradients are read and written once a
    trip), a trip's rows are what the step's temporaries grow with, and the
    rows of the last trip after the held slots cost next to nothing:
    doc/long_context.md has the measurements."""
    tile = _tile_rows(dtype)
    return max(tile, -(-slots * held // (experts * 2 * tile)) * tile)


def _transposed(product, like):
    """The transpose of a grouped product that is linear in its one
    argument of shape and dtype ``like``: what autodiff would emit for it."""
    pull = jax.linear_transpose(
        product, jax.ShapeDtypeStruct(like.shape, like.dtype))
    return lambda g: pull(g)[0]


class _Walk:
    """The held slots of a sorted order, ``chunk`` positions a trip: what
    the forward and the backward walk of :func:`_held_share` share. The
    sorted order holds the held experts' slots first, expert by expert
    (``sizes``), ``total`` of them; ``trips = ceil(total / chunk)`` is a
    run-time value, zero when no slot is held."""

    def __init__(self, order, sizes, chunk):
        self.chunk = chunk
        slots = order.shape[0]
        self.rows = -(-slots // chunk) * chunk      # the buffers' rows
        self.order = jnp.pad(order, (0, self.rows - slots))
        self.ends = jnp.cumsum(sizes)
        self.starts = self.ends - sizes
        self.total = self.ends[-1]
        self.trips = (self.total + chunk - 1) // chunk

    def trip(self, t):
        """(first position, the positions' slots ``[chunk]``, which of the
        positions hold a held slot ``[chunk, 1]``, the held groups' sizes
        clipped to the trip)."""
        lo = t * self.chunk
        slot = jax.lax.dynamic_slice(self.order, (lo,), (self.chunk,))
        live = (lo + jnp.arange(self.chunk) < self.total)[:, None]
        part = (jnp.clip(self.ends, lo, lo + self.chunk)
                - jnp.clip(self.starts, lo, lo + self.chunk))
        return lo, slot, live, part

    def buffer(self, dtype, *width):
        """Rows for the trips to write; what no trip wrote is not read."""
        return jax.lax.empty((self.rows, *width), dtype)

    def run(self, body, buffers):
        return jax.lax.fori_loop(0, self.trips, body, buffers)


def _rows_of(x, index):
    """``x[index]`` for indices known to lie inside ``x``."""
    return jnp.asarray(x).at[index].get(mode="promise_in_bounds")


def _put(buffer, rows, lo):
    return jax.lax.dynamic_update_slice_in_dim(buffer, rows, lo, axis=0)


def _runs_by_token(inverse, total, top_k: int, held: int, chunk: int, dtype):
    """The held positions ``[0, total)`` of the sorted order, sorted by
    token: (the slots ``[halo + rows]`` and their positions in the sorted
    order, ``halo`` entries of no token first (a run's ``min(top_k, held) -
    1`` predecessors, up to a tile), then each token's held slots in a run,
    the slots of no held expert last, ``rows`` a :class:`_Walk`'s; for each
    token ``[N]`` where its run ends, -1 where it holds no slot). Integers
    only, one sort; the forward's and the backward's return to token order
    share it."""
    slots = inverse.shape[0]
    rows = -(-slots // chunk) * chunk
    tile = _tile_rows(dtype)
    halo = -(-(min(top_k, held) - 1) // tile) * tile
    here = inverse < total
    slot, position = jax.lax.sort(
        (jnp.where(here, jnp.arange(slots, dtype=inverse.dtype), slots),
         inverse), num_keys=1)
    pad = (halo, rows - slots)
    count = jnp.sum(here.reshape(-1, top_k), axis=1)
    last = jnp.where(count > 0, jnp.cumsum(count) - 1, -1)
    return (jnp.pad(slot, pad, constant_values=-1),
            jnp.pad(position, pad), last)


def _runs_to_tokens(buffer, runs, walk, top_k, held, weights=None):
    """Expert-order rows ``[rows, D]``, of which the walk's trips wrote the
    first, -> ``[N, D]`` of the rows' dtype: each token's float32 sum over
    its held slots' rows (times ``weights [N, top_k]``), a token of no held
    slot reading nothing. The walk's trips again, each gathering ``chunk``
    rows of ``buffer`` in token order (``runs``: :func:`_runs_by_token`; and
    the halo before them, so that a run may cross a trip's edge); a row then
    adds those of its ``min(top_k, held) - 1`` predecessors that are its
    token's, in one pass; a token reads the last row of its run, one gather
    of ``N`` rows. What the last trip sums after the held slots, nothing
    reads."""
    slots, positions, last = runs
    run = min(top_k, held)
    halo = slots.shape[0] - walk.rows
    wide = walk.chunk + halo
    flat = None if weights is None else weights.reshape(-1)

    def body(t, summed):
        lo = t * walk.chunk
        slot = jax.lax.dynamic_slice(slots, (lo,), (wide,))
        rows = _rows_of(buffer, jax.lax.dynamic_slice(
            positions, (lo,), (wide,)))
        weight = None if flat is None else _rows_of(
            flat, jnp.clip(slot, 0, flat.shape[0] - 1))[:, None]
        token = slot // top_k

        def back(by):
            # the trip's rows, or those ``by`` before them: cut first, so
            # that no float32 copy of the gathered rows is laid out
            cut = slice(halo - by, wide - by)
            part = rows[cut].astype(jnp.float32)
            return part if weight is None else part * weight[cut]

        total = back(0)
        for by in range(1, run):
            total = total + jnp.where(
                (token[halo - by:wide - by] == token[halo:])[:, None],
                back(by), 0)
        return _put(summed, total.astype(buffer.dtype), lo)

    summed = walk.run(body, walk.buffer(buffer.dtype, buffer.shape[1]))
    return jnp.where((last >= 0)[:, None],
                     _rows_of(summed, jnp.maximum(last, 0)), 0)


@functools.partial(jax.custom_vjp, nondiff_argnums=(9, 10, 11))
def _held_share(h, weights, gate, up, down, order, inverse, sizes, runs,
                top_k: int, activation: str, chunk: int):
    """What the held experts give each token: ``sum_j weights[n, j] *
    expert(h[n])`` over the token's slots ``j`` on a held expert, by a walk
    over the held slots, ``chunk`` of them a trip. A trip gathers its slots'
    token rows, runs the three grouped products over the held groups' sizes
    clipped to the trip and writes its rows of the expert-order output;
    :func:`_runs_to_tokens` brings the output to token order (``runs``: the
    index of :func:`_runs_by_token`). The backward walks the same trips (a
    ``while`` with a run-time bound has no reverse rule, so it is written
    here): a slot's output gradient is its token's row of ``g``
    (a gather, token order -> expert order) times its weight, its weight's
    gradient a row dot product in expert order, the kernels' gradients add up
    over the trips in float32, the input's gradient comes back to token order
    as the output does. It keeps the inputs alone and forms both first
    products again: under a recomputed block (``remat_blocks``) the
    recomputed forward then has nothing to do.

    Either pass runs under ONE ``cond`` on a slot being held (with none the
    result is zeros, exactly). The branch is also where the walk's buffers
    are allocated, ``top_k * N`` rows each whatever the trips will write:
    allocated outside, nothing they depend on kept the compiler from placing
    every layer's allocations at the start of the step, where one
    configuration's step held eight of them, 4.3 GiB, from its first
    instruction to their layers' turn (PERF.md, PR 58)."""
    walk = _Walk(order, sizes, chunk)
    firsts, down = _kernels(h.dtype, gate, up, down)

    def body(t, out_rows):
        lo, slot, live, part = walk.trip(t)
        with jax.named_scope("dispatch"):
            xs = _rows_of(h, slot // top_k)
        with jax.named_scope("experts"):
            act = _hidden(activation, (jax.lax.ragged_dot(xs, w, part)
                                       for w in firsts))
            # whatever a grouped product leaves in the rows of no group
            # goes no further
            out = jnp.where(live, jax.lax.ragged_dot(act, down, part), 0)
        return _put(out_rows, out, lo)

    def held():
        out = walk.run(body, walk.buffer(h.dtype, h.shape[1]))
        with jax.named_scope("combine"):
            return _runs_to_tokens(out, runs, walk, top_k, sizes.shape[0],
                                   weights)

    return jax.lax.cond(walk.total > 0, held, lambda: jnp.zeros_like(h))


def _held_share_fwd(h, weights, gate, up, down, order, inverse, sizes, runs,
                    top_k, activation, chunk):
    inputs = (h, weights, gate, up, down, order, inverse, sizes, runs)
    return _held_share(*inputs, top_k, activation, chunk), inputs


def _held_share_bwd(top_k, activation, chunk, residuals, g):
    h, weights, gate, up, down, order, inverse, sizes, runs = residuals
    walk = _Walk(order, sizes, chunk)
    dtype, dim, f = h.dtype, h.shape[1], up.shape[-1]
    firsts, last = _kernels(dtype, gate, up, down)
    kernels = firsts + (last,)
    flat = weights.reshape(-1)
    rows_d = jax.ShapeDtypeStruct((chunk, dim), dtype)
    rows_f = jax.ShapeDtypeStruct((chunk, f), dtype)

    def body(t, carried):
        d_xs_rows, d_weight_rows, d_kernels = carried
        lo, slot, live, part = walk.trip(t)
        with jax.named_scope("dispatch"):
            token = slot // top_k
            xs, g_rows = _rows_of(h, token), _rows_of(g, token)
            weight = _rows_of(flat, slot)[:, None]
        with jax.named_scope("experts"):
            act, act_pull = jax.vjp(
                lambda *products: _hidden(activation, products),
                *(jax.lax.ragged_dot(xs, w, part) for w in firsts))
            # the slot's output is act @ down and its gradient weight * g:
            # the weight's own gradient <output, g> is <act, g @ down^T>, so
            # the weight goes on after that product, and on act for down's
            d_act = _transposed(lambda a: jax.lax.ragged_dot(
                a, last, part), rows_f)(g_rows)
            d_weight = jnp.where(live[:, 0], jnp.sum(
                act.astype(jnp.float32) * d_act.astype(jnp.float32),
                axis=-1), 0)
            d_firsts = act_pull(
                (d_act.astype(jnp.float32) * weight).astype(dtype))
            d_xs = functools.reduce(operator.add, (
                _transposed(lambda a, w=w: jax.lax.ragged_dot(a, w, part),
                            rows_d)(d) for w, d in zip(firsts, d_firsts)))
            d_xs = jnp.where(live, d_xs, 0)
            weighed = (act.astype(jnp.float32) * weight).astype(dtype)
            # the kernels' gradients add up over the trips in float32
            d_kernels = tuple(
                total + _transposed(lambda w, lhs=lhs: jax.lax.ragged_dot(
                    lhs, w, part), kernel)(rhs).astype(jnp.float32)
                for total, lhs, rhs, kernel in zip(
                    d_kernels, (xs,) * len(firsts) + (weighed,),
                    (*d_firsts, g_rows), kernels))
        return (_put(d_xs_rows, d_xs, lo), _put(d_weight_rows, d_weight, lo),
                d_kernels)

    given = (up, down) if gate is None else (gate, up, down)

    def held():
        d_xs, d_weight, d_kernels = walk.run(body, (
            walk.buffer(dtype, dim), walk.buffer(jnp.float32),
            tuple(jnp.zeros(w.shape, jnp.float32) for w in kernels)))
        d_kernels = tuple(d.astype(w.dtype) for d, w in zip(d_kernels, given))
        with jax.named_scope("dispatch"):
            # a token's gradient is the sum over its held slots
            d_h = _runs_to_tokens(d_xs, runs, walk, top_k, sizes.shape[0])
        d_weights = jnp.where(inverse < walk.total,
                              _rows_of(d_weight, inverse),
                              0).reshape(weights.shape)
        return (d_h, d_weights) + d_kernels

    def none():
        return (jnp.zeros_like(h), jnp.zeros_like(weights)) + tuple(
            jnp.zeros_like(w) for w in given)

    d = jax.lax.cond(walk.total > 0, held, none)
    if gate is None:
        d = d[:2] + (None,) + d[2:]
    return d + (None, None, None, None)


_held_share.defvjp(_held_share_fwd, _held_share_bwd)


def router_logits(h: jnp.ndarray, kernel: jnp.ndarray) -> jnp.ndarray:
    """Float32 router logits ``[N, E]`` of token rows ``[N, D]``: a float32
    product of float32 operands (the TPU's default would run it in one
    bfloat16 pass and move near-tied top-k choices)."""
    return jnp.dot(h.astype(jnp.float32), kernel.astype(jnp.float32),
                   precision=jax.lax.Precision.HIGHEST)


def route(logits: jnp.ndarray, top_k: int, normalize: bool = False,
          kind: str = "softmax", bias: Optional[jnp.ndarray] = None,
          scale: float = 1.0, eps: float = 1e-20
          ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Float32 router logits ``[N, E]`` -> (scores ``[N, E]``, the ``top_k``
    expert ids ``[N, top_k]``, their weights). ``kind="softmax"``: the scores
    are the softmax's probabilities, the experts those of the largest, the
    weights their probabilities or, ``normalize``, those over their sum (the
    softmax over the chosen logits alone). ``kind="sigmoid"``: the scores are
    ``sigmoid(logits)``, the experts are picked by ``score + bias`` (``bias
    [E]``, outside the gradient) and weighed by the bare scores, over their
    sum plus ``eps`` where ``normalize``, times ``scale``."""
    logits = logits.astype(jnp.float32)
    if kind == "softmax":
        probs = jax.nn.softmax(logits, axis=-1)
        weights, ids = jax.lax.top_k(probs, top_k)
        if normalize:
            weights = weights / jnp.sum(weights, axis=-1, keepdims=True)
        return probs, ids, weights
    if kind != "sigmoid":
        raise ValueError(f"routing {kind!r}: 'softmax' or 'sigmoid'")
    scores = jax.nn.sigmoid(logits)
    picked_by = scores if bias is None else scores + jax.lax.stop_gradient(
        bias.astype(jnp.float32))
    _, ids = jax.lax.top_k(picked_by, top_k)
    weights = jnp.take_along_axis(scores, ids, axis=-1)
    if normalize:
        weights = weights / (jnp.sum(weights, axis=-1, keepdims=True) + eps)
    return scores, ids, weights * scale


# the variable collection the balancing bias lives in: the one the estimator
# carries through its train step beside the parameters (donated, sharded,
# checkpointed; no gradient, no weight decay, no optimizer moments)
STATE = "batch_stats"


def balance_bias(state, rate: float):
    """One update of every expert layer's balancing bias in a ``STATE``
    collection: ``delta = rate * sign(mean(counts) - counts)``, ``bias +=
    delta - mean(delta)``, and the counts start again at zero. ``counts`` are
    the slots each expert was picked for since the last update (every
    micro-batch of an optimizer step adds its own)."""
    if not isinstance(state, Mapping):
        return state
    if "bias" in state and "counts" in state:
        counts = state["counts"]
        delta = rate * jnp.sign(jnp.mean(counts) - counts)
        return {**state, "bias": state["bias"] + delta - jnp.mean(delta),
                "counts": jnp.zeros_like(counts)}
    return type(state)({k: balance_bias(v, rate) for k, v in state.items()})


ACTIVATIONS = {"silu": nn.silu, "relu": nn.relu,
               "relu2": lambda x: jnp.square(nn.relu(x))}


def _hidden(activation: str, products):
    """What an expert makes of its first products, taken one at a time (an
    iterator's are formed as they are asked for, the activation between
    them): gated (two of them), ``act(gate) * up``; of two matrices (one),
    ``act(up)``."""
    products = iter(products)
    hidden = ACTIVATIONS[activation](next(products))
    for up in products:
        hidden = hidden * up
    return hidden


def _kernels(dtype, gate, up, down):
    """(the first products' kernels, the last's) at the activations' dtype:
    gate and up, or, for experts of two matrices (``gate`` None), up alone."""
    firsts = (up,) if gate is None else (gate, up)
    return tuple(w.astype(dtype) for w in firsts), down.astype(dtype)


class MoE(nn.Module):
    """``x [..., D]`` -> (``y [..., D]``, ``aux``) with ``aux`` a dict of
    float32 scalars: ``balance``, ``z``, ``slots_max``, ``slots_all``; where
    only a share of the experts is held, ``slots_held`` and ``slots_moved``;
    under sigmoid routing ``bias_spread``, ``max(bias) - min(bias)``."""

    num_experts: int
    top_k: int
    expert_dim: int
    dtype: Any = jnp.float32
    kernel_init: Any = nn.initializers.lecun_normal()
    first_expert: int = 0
    experts_held: Optional[int] = None      # None: all of them
    activation: str = "silu"
    normalize_top_k: bool = False
    routing: str = "softmax"                # or "sigmoid", with its bias
    route_scale: float = 1.0
    shared_dim: int = 0                     # 0: no shared expert
    gated: bool = True                      # False: experts of two matrices
    normalize_eps: float = 1e-20            # sigmoid routing: beside the sum

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
        h = x.reshape(-1, dim)
        n = h.shape[0]
        if logits is None:
            router = self.param("router", self.kernel_init, (dim, e))
        gate = self.param("experts_gate", self.kernel_init,
                          (held, dim, f)) if self.gated else None
        up = self.param("experts_up", self.kernel_init, (held, dim, f))
        down = self.param("experts_down", self.kernel_init, (held, f, dim))

        with jax.named_scope("router"):
            logits = router_logits(h, router) if logits is None \
                else logits.reshape(n, e)
            bias = None
            if self.routing == "sigmoid":
                bias = self.variable(STATE, "bias", jnp.zeros, (e,),
                                     jnp.float32)
                counts = self.variable(STATE, "counts", jnp.zeros, (e,),
                                       jnp.float32)
            probs, ids, weights = route(
                logits, k, self.normalize_top_k, self.routing,
                None if bias is None else bias.value, self.route_scale,
                self.normalize_eps)
            # read back only by a caller that asks (mutable="intermediates")
            self.sow("intermediates", "top_k_ids", ids)
            slots = ids.reshape(-1)                              # [k * N]
            sizes = jnp.sum(jax.nn.one_hot(slots, e, dtype=jnp.int32), axis=0)
            if bias is not None and self.is_mutable_collection(STATE) \
                    and not self.is_initializing():
                # what balance_bias reads once an optimizer step: all E
                # experts' slots, whatever is held here
                counts.value = counts.value + sizes.astype(jnp.float32)
            load = sizes.astype(jnp.float32) / (k * n)
            aux = {
                "balance": e * jnp.sum(load * jnp.mean(probs, axis=0)),
                "z": jnp.mean(jnp.square(
                    jax.nn.logsumexp(logits, axis=-1))),
                "slots_max": jnp.max(sizes).astype(jnp.float32),
                "slots_all": jnp.float32(k * n),
            }
            if bias is not None:
                aux["bias_spread"] = jnp.max(bias.value) - jnp.min(bias.value)
            if share:
                # the held experts' slots sort first, in expert order; the
                # groups end where they end
                slots = (slots - first) % e
                sizes = sizes[first:first + held]
                chunk = _chunk_rows(k * n, held, e, self.dtype)
                slots_held = jnp.sum(sizes)
                aux["slots_held"] = slots_held.astype(jnp.float32)
                # the rows the walk carries: the held slots, rounded up to
                # a trip
                aux["slots_moved"] = (-(-slots_held // chunk)
                                      * chunk).astype(jnp.float32)

        with jax.named_scope("dispatch"):
            order = jnp.argsort(slots, stable=True)
            inverse = jnp.argsort(order)
        if share:
            with jax.named_scope("dispatch"):
                runs = _runs_by_token(inverse, slots_held, k, held, chunk,
                                      self.dtype)
            y = _held_share(h.astype(self.dtype), weights, gate, up, down,
                            order, inverse, sizes, runs, k, self.activation,
                            chunk)
            return self._with_shared(y, h).reshape(*lead, dim), aux

        # every expert held: top_k * N is the exact number of rows, in one go
        with jax.named_scope("dispatch"):
            xs = _dispatch(h.astype(self.dtype), order, inverse, k)

        with jax.named_scope("experts"):
            cast = lambda w: w.astype(self.dtype)  # noqa: E731
            act = _hidden(self.activation, (
                jax.lax.ragged_dot(xs, cast(w), sizes)
                for w in ((gate, up) if self.gated else (up,))))
            out = jax.lax.ragged_dot(act, cast(down), sizes)    # [k * N, D]

        with jax.named_scope("combine"):
            back = _permute(out, inverse, order).reshape(n, k, dim)
            y = jnp.sum(back.astype(jnp.float32)
                        * weights[..., None], axis=1).astype(self.dtype)
        return self._with_shared(y, h).reshape(*lead, dim), aux

    @nn.nowrap      # no scope of its own: the ops lie under moe/shared
    def _with_shared(self, y, h):
        """``y [N, D]`` plus the shared expert's output for the token rows
        ``h``; ``y`` itself where the layer has none."""
        if not self.shared_dim:
            return y
        dense = lambda n, name: nn.Dense(  # noqa: E731
            n, use_bias=False, dtype=self.dtype, name=name,
            kernel_init=self.kernel_init)
        with jax.named_scope("shared"):
            h = h.astype(self.dtype)
            firsts = ("shared_gate", "shared_up") if self.gated \
                else ("shared_up",)
            return y + dense(h.shape[-1], "shared_down")(_hidden(
                self.activation, (dense(self.shared_dim, name)(h)
                                  for name in firsts)))
