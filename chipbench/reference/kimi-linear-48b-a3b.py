"""Plain reference of Kimi-Linear-48B-A3B-Instruct (moonshotai,
``kimi_linear``), as one chip of a deployment in which 32 chips share each
layer holds it: forward pass, training loss and, through ``jax.grad`` of that
loss, gradients; the slots each expert was picked for, and the balancing
bias's update from them.

float32 ``jax.numpy`` under ``highest`` matmul precision; no flax, no kernel,
no sharding, and nothing shared with ``raydp_tpu``. The Kimi Delta Attention
state runs **a position at a time** (a ``lax.scan`` over the row: no chunks,
no triangular solve, so it has nothing in common with the program's chunked
form); q, k and v are three slices of the fused matrix; the convolution is
four shifted products of a padded array. Attention is dense with an explicit
mask, computed a block of queries at a time so that a 16,384-token sequence
fits beside a fit's state. The expert layer is computed **densely**: every
held expert on every token, multiplied by the top-k mask times the weight, so
it has nothing in common with the program's sort / gather / grouped-GEMM walk.
``x`` is a layer's input ``[T, 2304]``, ``RMSNorm`` has eps 1e-5 and a weight,
no linear layer has a bias, there is NO position embedding anywhere, and every
layer is a PAIR, an operator and then a feed-forward part:

    x0 = E[tokens]                                  no embedding scale
    u  = RMSNorm_op(x)
    KDA layer (32 heads of 128 for keys and values alike, W = 4096):
        q~ | k~ | v~ = u W_in                       2304 -> 3 x 4096, this order
        q^, k^, v = silu(conv4(q~)), silu(conv4(k~)), silu(conv4(v~))
                                depthwise, causal, 4 taps, zeros before the
                                sequence, no bias
        q_h = q^_h / |q^_h|_2 * 128^-1/2,  k_h = k^_h / |k^_h|_2   eps 1e-6
        g_t = -exp(A_log_h) * softplus((u W_fa) W_fb + dt_bias)    [128] a head
        b_t = sigmoid(u W_b)                                       one a head
        a head, S in R^{128 x 128} (keys x values), S_0 = 0:
            S' = diag(exp(g_t)) S_{t-1}
            S_t = S' + b_t k_t (v_t - S'^T k_t)^T
            o_t = S_t^T q_t
        a = (RMSNorm_128(o_h) * weight * sigmoid((u W_ga) W_gb)_h) W_o
    attention layer (32 heads, latent attention with nothing rotated):
        q = u Wq -> 32 x 192;  [c | k_r] = u W_kva -> 512 + 64;  c = RMSNorm(c)
        [k_nope | v]_h = c W_kvb -> 32 x (128 + 128);  k_h = [k_nope_h | k_r]
        a = concat_h softmax_{j<=i}(q_h[i] k_h[j] * 192^-1/2) v_h[j] W_o
    x' = x + a
    m  = RMSNorm_ffn(x')
    dense layer:   f = (silu(m W1) * (m W3)) W2                    width 9216
    expert layer:  s = sigmoid(m Wr)                [256], float32
                   S = top-8 of s + b               b: the bias, no gradient
                   w_e = 2.446 * s_e / (sum_{e' in S} s_e' + 1e-20)
                   f = sum_{e in S, e held here} w_e (silu(m W1_e) * (m W3_e)) W2_e
                       + (silu(m W1_s) * (m W3_s)) W2_s     one shared expert
    out = x' + f
    after the last layer: RMSNorm, then logits = h W_head    untied
    loss = CE(next token, over the rows held)                no auxiliary loss
    after a step, each expert layer:  c_e = slots expert e was picked for
        (all 256); delta = 0.001 * sign(mean(c) - c); b += delta - mean(delta)

What the absent experts would have added is left out, here as in the program,
and the partial result goes on to the next layer; the weights are normalised
over all eight choices and the counts are over all 256 experts, whatever is
held. ``experts_held`` equal to the expert count gives the uncut layer (the
CPU test of the shares adds them up against it, the shared expert once).

Departures from the published model, each one the program's too: a document
boundary is not masked (the state, the convolution's window and attention run
across the end-of-text id); what ``config.json`` does not state is from
memory of the release's code (``configs/kimi-linear-48b-a3b.json``,
``assumed``).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

# bfloat16 activations (one ulp = 2**-8 relative) through five pairs and the
# 2304-wide head, against float32 at ``highest``: the relative RMS error of
# the compared logits (``harness.relative_rms_error``). Set between two
# readings on the chip at the published widths with a fit's own parameters
# and bias (PERF.md section 6, PR 64): the program reads 0.0207-0.0270 over
# its first twelve seeds, and this reference with 8-bit float operands
# (``at_precision``), the nearest precision below the configuration's, reads
# 0.407 (e5m2) and 0.265 (e4m3): not correct. 0.05 is 1.85 times the first
# and a fifth of the second (their geometric middle is 0.085). The window
# lies inside the optimizer's warm-up, so the parameters are near their
# seeded initialisation, where what dominates the error is not rounding but
# the router: bfloat16 inputs flip near-tied top-8 choices of 256; this
# reference with bfloat16 operands reads 0.0216 itself (the program 0.0213
# beside it). Planted in this reference, the program's output against it
# (``benchmarks/kda_control.py --control`` plants each): beta held at 1
# reads 0.695, a missing tap 1.105, the decay applied after the update
# 0.130: over. A decay rounded to bfloat16 (0.02135), the shared key and the
# queries' last 64 rotated (0.0237) and a pick by the bare scores (0.034) do
# NOT read over it at these weights: the decay's steps are 1e-3 to 1e-1, the
# attention's logits are near zero and the bias has moved 0.003.
TOLERANCE = 0.05
# What check (a) compares: the logits at the last 256 positions of each of 2
# seeded 16,384-token sequences over the 20,480 rows held, pulled one
# sequence a batch.
SAMPLE = {"rows": 2, "batch": 1}
QUERY_BLOCK = 256       # queries whose scores against every key exist at once
STATE = "batch_stats"   # the collection the program keeps the bias in
ROUTE_EPS = 1e-20       # beside the sum of the chosen scores
L2_EPS = 1e-6           # beside a head's sum of squares


# None: plain float32. A dtype: every product's operands (activations and
# weights alike; the convolution's taps and the scan's q, k, v among them)
# are rounded to it first and the product still accumulates in float32,
# which is what computing "in that precision" means on this chip. Only
# ``at_precision`` sets it, to show what TOLERANCE separates.
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


def _is_kda(cfg, layer):
    """Whether the ``layer``-th of the layers held (the source's numbers,
    from 1) has a Kimi Delta Attention operator."""
    return cfg["layers_held"][layer] in cfg["linear_attn_config"]["kda_layers"]


def _conv_silu(x, taps):
    """silu of the depthwise causal convolution of x [B, T, C] with taps
    [K, C]: K shifted products of an array padded with zeros in front."""
    k, t = taps.shape[0], x.shape[1]
    padded = jnp.pad(x, ((0, 0), (k - 1, 0), (0, 0)))
    return jax.nn.silu(sum(_r(taps[j]) * _r(padded[:, j:j + t])
                           for j in range(k)))


def _decay(p, u, heads, width):
    """g [B, T, H, P] float32, never positive."""
    step = _mm(_mm(u, _f32(p["gate_a"])), _f32(p["gate_b"])) \
        + _f32(p["dt_bias"])
    return -jnp.exp(_f32(p["A_log"]))[:, None] * jax.nn.softplus(
        step).reshape(u.shape[:2] + (heads, width))


def _beta(p, u):
    return jax.nn.sigmoid(_mm(u, _f32(p["beta"])))          # [B, T, H]


def _delta_rule(q, k, v, g, beta):
    """The state a position at a time: q, k, g [B, T, H, P], v [B, T, H, P],
    beta [B, T, H] -> o [B, T, H, P]."""
    def step(state, at):
        qt, kt, vt, gt, bt = at
        state = jnp.exp(gt)[..., None] * state
        seen = jnp.einsum("bhkv,bhk->bhv", state, kt)
        state = state + (bt[..., None] * kt)[..., :, None] \
            * (vt - seen)[..., None, :]
        return state, jnp.einsum("bhkv,bhk->bhv", state, qt)

    first = jnp.zeros(k.shape[:1] + k.shape[2:] + v.shape[3:], jnp.float32)
    _, out = jax.lax.scan(step, first, tuple(
        jnp.moveaxis(a, 1, 0) for a in (_r(q), _r(k), _r(v), g, beta)))
    return jnp.moveaxis(out, 0, 1)


def _kda(p, u, cfg):
    """The Kimi Delta Attention operator on u [B, T, D]."""
    b, t, _ = u.shape
    lin = cfg["linear_attn_config"]
    heads, width = lin["num_heads"], lin["head_dim"]
    inner = heads * width
    kernel, taps = _f32(p["in_proj"]["kernel"]), _f32(p["conv"])
    q, k, v = (
        _conv_silu(_mm(u, kernel[:, at:at + inner]), taps[:, at:at + inner])
        .reshape(b, t, heads, width) for at in (0, inner, 2 * inner))
    unit = lambda a: a * jax.lax.rsqrt(  # noqa: E731
        jnp.sum(a * a, axis=-1, keepdims=True) + L2_EPS)
    o = _delta_rule(unit(q) * width ** -0.5, unit(k), v,
                    _decay(p, u, heads, width), _beta(p, u))
    gate = jax.nn.sigmoid(_mm(_mm(u, _f32(p["out_gate_a"])),
                              _f32(p["out_gate_b"]))).reshape(o.shape)
    o = _rms_norm(o, p["norm"], cfg["rms_norm_eps"]) * gate
    return _mm(o.reshape(b, t, inner), _f32(p["out_proj"]["kernel"]))


def _attention(p, u, cfg):
    """Latent attention without positions on the normed input u [B, T, D]."""
    b, t, d = u.shape
    heads, rank = cfg["num_attention_heads"], cfg["kv_lora_rank"]
    nope, rope, dv = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                      cfg["v_head_dim"])
    eps = cfg["rms_norm_eps"]
    q = _mm(u, _f32(p["q"]["kernel"]).reshape(d, heads * (nope + rope)))
    q = q.reshape(b, t, heads, nope + rope)
    down = _mm(u, _f32(p["kv_a"]["kernel"]))                # [B, T, 576]
    latent = _rms_norm(down[..., :rank], p["kv_norm"]["scale"], eps)
    k_shared = down[..., rank:]                 # [B, T, 64]: ONE, not rotated
    up = _mm(latent, _f32(p["kv_b"]["kernel"]).reshape(
        rank, heads * (nope + dv))).reshape(b, t, heads, nope + dv)
    k_nope, v = up[..., :nope], up[..., nope:]
    key_at = np.arange(t)[None, :]
    out = []
    for at in range(0, t, QUERY_BLOCK):
        rows = slice(at, at + QUERY_BLOCK)
        seen = key_at <= np.arange(at, min(at + QUERY_BLOCK, t))[:, None]
        scores = (jnp.einsum("bqhd,bkhd->bhqk", _r(q[:, rows, :, :nope]),
                             _r(k_nope))
                  + jnp.einsum("bqhd,bkd->bhqk", _r(q[:, rows, :, nope:]),
                               _r(k_shared))) / np.sqrt(nope + rope)
        scores = jnp.where(seen[None, None], scores, -jnp.inf)
        out.append(jnp.einsum("bhqk,bkhd->bqhd",
                              _r(jax.nn.softmax(scores, -1)), _r(v)))
    out = jnp.concatenate(out, axis=1).reshape(b, t, heads * dv)
    return _mm(out, _f32(p["o"]["kernel"]).reshape(heads * dv, d))


def _gated_mlp(m, gate, up, down):
    return _mm(jax.nn.silu(_mm(m, gate)) * _mm(m, up), down)


def _experts(p, m, bias, cfg, shared=True):
    """Dense expert layer on tokens m [N, D] with the layer's bias [E] ->
    (the held experts' part of the routed sum plus, ``shared``, the shared
    expert's output [N, D]; the top-k ids [N, k])."""
    e, k = cfg["num_experts"], cfg["num_experts_per_token"]
    first, held = cfg["first_expert"], cfg["experts_held"]
    scores = jax.nn.sigmoid(m @ _f32(p["router"]))          # float32 always
    _, ids = jax.lax.top_k(scores + jax.lax.stop_gradient(_f32(bias)), k)
    top = jnp.take_along_axis(scores, ids, axis=-1)
    if cfg["moe_renormalize"]:
        top = top / (jnp.sum(top, axis=-1, keepdims=True) + ROUTE_EPS)
    top = top * cfg["routed_scaling_factor"]
    onehot = jax.nn.one_hot(ids, e, dtype=jnp.float32)      # [N, k, E]
    gates = jnp.sum(onehot * top[..., None], axis=1)        # [N, E]

    def one(carry, w):
        wg, wu, wd, g = w
        return carry + g[:, None] * _gated_mlp(m, wg, wu, wd), None

    y, _ = jax.lax.scan(one, jnp.zeros_like(m), (
        _f32(p["experts_gate"]), _f32(p["experts_up"]),
        _f32(p["experts_down"]), gates.T[first:first + held]))
    if shared and cfg["num_shared_experts"]:
        y = y + _gated_mlp(m, *(_f32(p[f"shared_{n}"]["kernel"])
                                for n in ("gate", "up", "down")))
    return y, ids


def _bias_of(state, layer, cfg):
    """The ``layer``-th block's bias in the program's collection; zeros
    where none is handed in (a fresh model's)."""
    if state is None:
        return jnp.zeros((cfg["num_experts"],), jnp.float32)
    return state[f"block_{layer}"]["moe"]["bias"]


def trunk(params, state, tokens, cfg):
    """tokens [B, T] -> (final normed hidden [B, T, D], the top-k ids of
    every expert layer)."""
    eps = cfg["rms_norm_eps"]
    x = _f32(params["embed"]["embedding"])[jnp.asarray(tokens)]
    b, t, d = x.shape
    ids = []
    for i in range(cfg["layers"]):
        p = params[f"block_{i}"]
        u = _rms_norm(x, p["ln1"]["scale"], eps)
        x = x + (_kda(p["kda"], u, cfg) if _is_kda(cfg, i)
                 else _attention(p["attn"], u, cfg))
        m = _rms_norm(x, p["ln2"]["scale"], eps).reshape(b * t, d)
        if i < cfg["dense_layers"]:
            f = _gated_mlp(m, *(_f32(p[n]["kernel"])
                                for n in ("gate", "up", "down")))
        else:
            f, top = _experts(p["moe"], m, _bias_of(state, i, cfg), cfg)
            ids.append(top)
        x = x + f.reshape(b, t, d)
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


def kda(p: dict, u, cfg: dict) -> jnp.ndarray:
    """One Kimi Delta Attention operator alone, on a normed input."""
    with jax.default_matmul_precision("highest"):
        return _kda(p, _f32(u), cfg)


def latent_attention(p: dict, u, cfg: dict) -> jnp.ndarray:
    """One attention operator alone, on a normed input."""
    with jax.default_matmul_precision("highest"):
        return _attention(p, _f32(u), cfg)


def expert_layer(p: dict, m, bias, cfg: dict, shared: bool = True):
    """One expert layer alone: the part of the routed sum that the experts
    ``[first_expert, first_expert + experts_held)`` give and, ``shared``, the
    shared expert's output: what the share test adds up over the chips (the
    shared expert once)."""
    with jax.default_matmul_precision("highest"):
        return _experts(p, _f32(m), bias, cfg, shared)[0]


def top_k_ids(params: dict, state, tokens, cfg: dict):
    """The reference's expert choices, [expert layers][N, k]: what a test or
    a builder compares the program's router against."""
    with jax.default_matmul_precision("highest"):
        return trunk(params, state, tokens, cfg)[1]


def slot_counts(params: dict, state, tokens, cfg: dict):
    """The slots each of ALL the experts was picked for in a batch's tokens,
    [expert layers][E] float32."""
    return [jnp.sum(jax.nn.one_hot(ids.reshape(-1), cfg["num_experts"],
                                   dtype=jnp.float32), axis=0)
            for ids in top_k_ids(params, state, tokens, cfg)]


def next_bias(bias, counts, cfg: dict):
    """The bias after a step in which the experts were picked for ``counts``
    slots (all micro-batches together)."""
    delta = cfg["bias_update_rate"] * jnp.sign(jnp.mean(counts) - counts)
    return _f32(bias) + delta - jnp.mean(delta)
