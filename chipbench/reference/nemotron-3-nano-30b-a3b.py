"""Plain reference of NVIDIA-Nemotron-3-Nano-30B-A3B-BF16 (nvidia,
``nemotron_h``), as one chip of a sixteen-way expert-parallel deployment
holds it: forward pass, training loss and, through ``jax.grad`` of that loss,
gradients; the slots each expert was picked for, and the balancing bias's
update from them.

float32 ``jax.numpy`` under ``highest`` matmul precision; no flax, no kernel,
no sharding, and nothing shared with ``raydp_tpu/models`` or
``raydp_tpu/ops``. Written from the equations below. The state-space layer is
the recurrence ITSELF, a ``lax.scan`` over the positions with the ``[64, 64,
128]`` state (the program computes it in chunks of 128: the chunking is what
is under test). Attention is dense with an explicit mask, a block of 256
queries at a time, K and V repeated over the group of 16 query heads. The
expert layer is computed **densely**: every held expert on every token,
multiplied by the top-k mask times the weight. ``x`` is a layer's input ``[T,
2688]``, ``RMSNorm`` has eps 1e-5 and a weight, no linear layer has a bias,
and every layer is ONE sub-layer, by its letter in ``layer_pattern_held``::

    x0 = E[tokens];   x' = x + mixer(RMSNorm(x))

    M:  z, xBC, dt = split(u W_in)           4096, 6144, 64   (in this order)
        xBC[t] = silu(b + sum_{j=0..3} w[j] * xBC[t - 3 + j])  zeros before 0
        x, B, C = split(xBC)                 [64, 64], [8, 128], [8, 128]
        dt = softplus(dt + dt_bias);  A = -exp(A_log)
        S[t] = exp(dt[t] A) S[t-1] + dt[t] x[t] (x) B[t]    head h reads
        y[t] = S[t] C[t] + D x[t]                            group h // 8
        y = y * silu(z);  y = y / rms_512(y) * weight       8 groups of 512
        mixer = y W_out
    *:  q, k, v = u Wq, u Wk, u Wv           32 heads, 2 K/V heads of 128
        s_h[p, j] = q_h[p] . k_{h // 16}[j] / sqrt(128),  j <= p   no rotation
        mixer = concat_h(softmax_j(s_h) v_{h // 16}) Wo
    E:  s = sigmoid(u Wr)                    [128], float32
        top6 = the 6 largest of s + b        b: the layer's bias, no gradient
        w_e = 2.5 * s_e / (sum_{e in top6} s_e + 1e-20)
        mixer = relu(u Wsu)^2 Wsd            the shared expert, width 3712
                + sum_{e in top6, held} w_e * relu(u Wu_e)^2 Wd_e
    logits = RMSNorm_f(x_L) W_head

Departures from the published model, each listed under ``assumed`` in
``configs/nemotron-3-nano-30b-a3b.json``: the layers held are published
layers 0-8 of 52 (``MEMEM*EME``); the expert layer holds experts 0-7 of 128
(the router, the top-6 choice, the normalisation and the bias are over all
128; the held experts' part of the sum is the result, the shared expert
whole); the vocabulary is rows 0-16,383 of 131,072 (ids, logits and loss over
the slice). The forms ``config.json`` does not state are from memory of the
family's code: the order of ``W_in``'s split, the gated norm after the gate
over 8 groups, ``dt`` unclamped, no position embedding in attention, the
bias's update (centred, as the program's) and its rate.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

# bfloat16 activations (one ulp = 2**-8 relative) through nine layers and the
# 2688-wide head, against float32 at ``highest``: the relative RMS error of
# the compared logits (``harness.relative_rms_error``). Set between two
# readings on the chip at the published widths with a fit's own parameters
# and bias (PERF.md, PR 47): the program reads 0.0247-0.0437 over eight
# seeds, and this reference with 8-bit float operands (``at_precision``), the
# nearest precision below, reads 0.293 (e5m2) and 0.296 (e4m3): not correct.
# 0.1 is 2.3 times the first and 2.9 times under the second (their geometric
# middle is 0.113). The window lies inside the optimizer's warm-up, so the
# parameters are near their seeded initialisation, where what dominates the
# error is not rounding but the router: bfloat16 inputs flip near-tied top-6
# choices, and a flipped expert's ``relu(.)^2`` output is not small beside
# the stream; this reference with bfloat16 operands reads 0.0220 itself. A
# state that is reset or carried wrongly from chunk to chunk, a convolution
# that looks ahead, a gate after the norm, a rotation in attention, gated
# experts or a plain ReLU would read far above the tolerance. What it does
# NOT see at these weights: the scan's decays rounded to bfloat16 by hand
# read 0.0322 where the same seed read 0.0297 (near initialisation a chunk's
# ``dt A`` sums to a few units, so the rounding is one bfloat16 ulp of a
# small number); the CPU tests hold the decays to float32 (2e-5).
TOLERANCE = 0.1
# What check (a) compares: the logits at the last 256 positions of each of 2
# seeded 16,384-token sequences over the 16,384 rows held, pulled one
# sequence a batch. The state at position 16,128 depends on every position
# before it: the whole sequence runs through the recurrence.
SAMPLE = {"rows": 2, "batch": 1}
QUERY_BLOCK = 256       # queries whose scores against every key exist at once
STATE = "batch_stats"   # the collection the program keeps the bias in


# None: plain float32. A dtype: every product's operands (activations,
# weights and the recurrence's state alike) are rounded to it first and the
# product still accumulates in float32, which is what computing "in that
# precision" means on this chip. Only ``at_precision`` sets it, to show what
# TOLERANCE separates.
_ROUND_TO = None


def _f32(a):
    return jnp.asarray(a, jnp.float32)


def _r(x):
    return x if _ROUND_TO is None else _f32(x.astype(_ROUND_TO))


def _mm(a, b):
    return _r(a) @ _r(b)


def at_precision(dtype, fn, *args):
    """``fn(*args)`` with every product's operands rounded to ``dtype``."""
    global _ROUND_TO
    _ROUND_TO = dtype
    try:
        return fn(*args)
    finally:
        _ROUND_TO = None


def _rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * _f32(scale)


def convolution(x, taps, bias):
    """x [B, T, C], taps [4, C], bias [C]: a sum over four copies of ``x``,
    copy ``j`` shifted ``3 - j`` positions towards the end, zeros shifted
    in."""
    t = x.shape[1]
    total = _f32(bias) + jnp.zeros_like(x)
    for j in range(taps.shape[0]):
        back = taps.shape[0] - 1 - j
        shifted = jnp.concatenate(
            [jnp.zeros_like(x[:, :back]), x[:, :t - back]], axis=1)
        total = total + _f32(taps[j]) * shifted
    return total


def recurrence(x, dt, a, b, c, d):
    """The state-space recurrence, one position a step. x [B, T, H, P], dt
    [B, T, H], a [H], b and c [B, T, G, N], d [H] -> y [B, T, H, P]."""
    heads, groups = x.shape[2], b.shape[2]
    b = jnp.repeat(b, heads // groups, axis=2)              # [B, T, H, N]
    c = jnp.repeat(c, heads // groups, axis=2)

    def step(state, at):                                    # [B, H, P, N]
        x_t, dt_t, b_t, c_t = at
        state = jnp.exp(dt_t * a)[..., None, None] * state \
            + dt_t[..., None, None] * _r(x_t)[..., :, None] \
            * _r(b_t)[..., None, :]
        y_t = jnp.sum(_r(state) * _r(c_t)[..., None, :], axis=-1)
        return state, y_t + d[:, None] * x_t

    first = jnp.zeros(x.shape[:1] + x.shape[2:] + b.shape[-1:], jnp.float32)
    _, y = jax.lax.scan(step, first, tuple(
        v.swapaxes(0, 1) for v in (x, dt, b, c)))
    return y.swapaxes(0, 1)


def mamba(p, u, cfg):
    """A Mamba-2 mixer on the normed input u [B, T, D] with the parameters of
    one layer's ``ssm``."""
    bsz, t, _ = u.shape
    heads, width = cfg["mamba_num_heads"], cfg["mamba_head_dim"]
    groups, n = cfg["n_groups"], cfg["ssm_state_size"]
    inner, bc = heads * width, groups * n
    proj = _mm(u, _f32(p["in_proj"]["kernel"]))
    z, xbc, dt = (proj[..., :inner], proj[..., inner:2 * inner + 2 * bc],
                  proj[..., 2 * inner + 2 * bc:])
    xbc = jax.nn.silu(convolution(xbc, p["conv"], p["conv_bias"]))
    x = xbc[..., :inner].reshape(bsz, t, heads, width)
    b = xbc[..., inner:inner + bc].reshape(bsz, t, groups, n)
    c = xbc[..., inner + bc:].reshape(bsz, t, groups, n)
    dt = jax.nn.softplus(dt + _f32(p["dt_bias"]))
    y = recurrence(x, dt, -jnp.exp(_f32(p["A_log"])), b, c, _f32(p["D"]))
    y = y.reshape(bsz, t, inner) * jax.nn.silu(z)
    grouped = y.reshape(bsz, t, groups, inner // groups)
    grouped = grouped * jax.lax.rsqrt(
        jnp.mean(grouped * grouped, axis=-1, keepdims=True)
        + cfg["layer_norm_epsilon"])
    y = grouped.reshape(bsz, t, inner) * _f32(p["norm"])
    return _mm(y, _f32(p["out_proj"]["kernel"]))


def attention(p, u, cfg):
    """Full causal grouped-query attention, no position embedding, on the
    normed input u [B, T, D] with the parameters of one layer's ``attn``."""
    b, t, d = u.shape
    heads, kv_heads, hd = (cfg["num_attention_heads"],
                           cfg["num_key_value_heads"], cfg["head_dim"])
    proj = lambda name, n: _mm(  # noqa: E731
        u, _f32(p[name]["kernel"]).reshape(d, n * hd)).reshape(b, t, n, hd)
    q = proj("q", heads)
    k = jnp.repeat(proj("k", kv_heads), heads // kv_heads, axis=2)
    v = jnp.repeat(proj("v", kv_heads), heads // kv_heads, axis=2)
    key_at = np.arange(t)[None, :]
    out = []
    for at in range(0, t, QUERY_BLOCK):
        rows = slice(at, at + QUERY_BLOCK)
        seen = key_at <= np.arange(at, min(at + QUERY_BLOCK, t))[:, None]
        scores = jnp.einsum("bqhd,bkhd->bhqk", _r(q[:, rows]),
                            _r(k)) / np.sqrt(hd)
        scores = jnp.where(seen[None, None], scores, -jnp.inf)
        out.append(jnp.einsum("bhqk,bkhd->bqhd",
                              _r(jax.nn.softmax(scores, -1)), _r(v)))
    out = jnp.concatenate(out, axis=1).reshape(b, t, heads * hd)
    return _mm(out, _f32(p["o"]["kernel"]).reshape(heads * hd, d))


def _relu2_mlp(m, up, down):
    return _mm(jnp.square(jax.nn.relu(_mm(m, up))), down)


def _experts(p, m, bias, cfg):
    """Dense expert layer on tokens m [N, D] with the layer's bias [E] ->
    (the shared expert's output plus the held experts' part of the routed sum
    [N, D], the top-k ids [N, k])."""
    e, k = cfg["n_routed_experts"], cfg["num_experts_per_tok"]
    first, held = cfg["first_expert"], cfg["experts_held"]
    scores = jax.nn.sigmoid(m @ _f32(p["router"]))          # float32 always
    _, ids = jax.lax.top_k(scores + jax.lax.stop_gradient(_f32(bias)), k)
    top = jnp.take_along_axis(scores, ids, axis=-1)
    if cfg["norm_topk_prob"]:
        top = top / (jnp.sum(top, axis=-1, keepdims=True) + 1e-20)
    top = top * cfg["routed_scaling_factor"]
    onehot = jax.nn.one_hot(ids, e, dtype=jnp.float32)      # [N, k, E]
    gates = jnp.sum(onehot * top[..., None], axis=1)        # [N, E]

    def one(carry, w):
        wu, wd, g = w
        return carry + g[:, None] * _relu2_mlp(m, wu, wd), None

    y, _ = jax.lax.scan(one, jnp.zeros_like(m), (
        _f32(p["experts_up"]), _f32(p["experts_down"]),
        gates.T[first:first + held]))
    if cfg["n_shared_experts"]:
        y = y + _relu2_mlp(m, _f32(p["shared_up"]["kernel"]),
                           _f32(p["shared_down"]["kernel"]))
    return y, ids


def _bias_of(state, layer, cfg):
    """The ``layer``-th layer's bias in the program's collection; zeros
    where none is handed in (a fresh model's)."""
    if state is None:
        return jnp.zeros((cfg["n_routed_experts"],), jnp.float32)
    return state[f"block_{layer}"]["moe"]["bias"]


def trunk(params, state, tokens, cfg):
    """tokens [B, T] -> (final normed hidden [B, T, D], the top-k ids of
    every expert layer)."""
    eps = cfg["layer_norm_epsilon"]
    x = _f32(params["embed"]["embedding"])[jnp.asarray(tokens)]
    b, t, d = x.shape
    ids = []
    for i, letter in enumerate(cfg["layer_pattern_held"]):
        p = params[f"block_{i}"]
        u = _rms_norm(x, p["norm"]["scale"], eps)
        if letter == "M":
            x = x + mamba(p["ssm"], u, cfg)
        elif letter == "*":
            x = x + attention(p["attn"], u, cfg)
        elif letter == "E":
            f, top = _experts(p["moe"], u.reshape(b * t, d),
                              _bias_of(state, i, cfg), cfg)
            ids.append(top)
            x = x + f.reshape(b, t, d)
        else:
            raise ValueError(f"layer {i} is {letter!r}: 'M', '*' or 'E'")
    return _rms_norm(x, params["ln_f"]["scale"], eps), ids


def forward(variables: dict, inputs, cfg: dict) -> jnp.ndarray:
    """Logits at the last ``compared_positions`` positions of each sequence
    over the rows held, [B, positions, rows]: what the pipeline's
    ``compared`` keeps."""
    with jax.default_matmul_precision("highest"):
        x, _ = trunk(variables["params"], variables.get(STATE), inputs, cfg)
        keep = min(cfg["compared_positions"], x.shape[1])
        return _mm(x[:, -keep:],
                   _f32(variables["params"]["lm_head"]["kernel"]))


def loss(params: dict, state, tokens, cfg: dict) -> jnp.ndarray:
    """The training loss of one batch: next-token cross entropy over the rows
    held, with the biases of ``state`` (the program's collection, or None for
    zeros). No auxiliary loss."""
    with jax.default_matmul_precision("highest"):
        x, _ = trunk(params, state, tokens, cfg)
        logits = _mm(x[:, :-1], _f32(params["lm_head"]["kernel"]))
        logp = jax.nn.log_softmax(logits, axis=-1)
        picked = jnp.take_along_axis(
            logp, jnp.asarray(tokens)[:, 1:, None], axis=-1)[..., 0]
        return -jnp.mean(picked)


def mixer(p: dict, u, cfg: dict) -> jnp.ndarray:
    """One state-space mixer alone on a normed input: what the CPU test
    compares ``Mamba2Mixer`` against."""
    with jax.default_matmul_precision("highest"):
        return mamba(p, _f32(u), cfg)


def expert_layer(p: dict, m, bias, cfg: dict) -> jnp.ndarray:
    """One expert layer alone: the shared expert's output (where the
    configuration has one) plus the part of the routed sum that the experts
    ``[first_expert, first_expert + experts_held)`` give: what the share test
    adds up over the sixteen chips."""
    with jax.default_matmul_precision("highest"):
        return _experts(p, _f32(m), bias, cfg)[0]


def top_k_ids(params: dict, state, tokens, cfg: dict):
    """The reference's expert choices, [expert layers][N, k]."""
    with jax.default_matmul_precision("highest"):
        return trunk(params, state, tokens, cfg)[1]


def slot_counts(params: dict, state, tokens, cfg: dict):
    """The slots each of ALL the experts was picked for in a batch's tokens,
    [expert layers][E] float32."""
    return [jnp.sum(jax.nn.one_hot(ids.reshape(-1), cfg["n_routed_experts"],
                                   dtype=jnp.float32), axis=0)
            for ids in top_k_ids(params, state, tokens, cfg)]


def next_bias(bias, counts, cfg: dict):
    """The bias after a step in which the experts were picked for ``counts``
    slots (all micro-batches together)."""
    delta = cfg["bias_update_rate"] * jnp.sign(jnp.mean(counts) - counts)
    return _f32(bias) + delta - jnp.mean(delta)
