"""Plain reference of Ouro-2.6B (ByteDance, ``ouro``), a looped language
model, as one pipeline stage of ``layers`` layers holds it: forward pass, the
exit distribution, the training loss and, through ``jax.grad`` of that loss,
gradients.

float32 ``jax.numpy`` under ``highest`` matmul precision; no flax, no kernel,
no scan, and nothing shared with ``raydp_tpu/models``: the passes and the
layers are Python loops over ONE set of weights. Attention is dense with an
explicit mask, computed a block of queries at a time so that an 8,192-token
sequence fits beside a fit's state (16 heads x 256 queries x 8,192 keys of
float32 scores are 0.13 GB). ``x`` is a layer's input ``[T, 2048]``,
``RMSNorm`` has eps 1e-6 and a weight, and no linear layer has a bias but the
gate:

    h_0 = E[tokens]
    one layer, x -> out:
        u  = RMSNorm_in(x);  q, k, v = u Wq, u Wk, u Wv      16 heads of 128
        q, k = RoPE(q, k; theta 1e6, rotate-half)            every layer
        a_h[i] = softmax_{j <= i}(q_h[i] k_h[j] / sqrt(128)) v_h[j]
        x' = x + RMSNorm_post_attn(concat_h(a_h) Wo)
        m  = RMSNorm_pre_mlp(x')
        out = x' + RMSNorm_post_mlp((silu(m Wgate) * (m Wup)) Wdown)
    one pass:  Stack(x) = layer_{N-1}( ... layer_0(x))   the same weights
    for t = 1..P (P = total_ut_steps = 4):
        h_t = RMSNorm_f(Stack(h_{t-1}))
        lambda_t = sigmoid(h_t w_g + b_g)                    a position
    S_0 = 1;  p_t = lambda_t S_{t-1},  S_t = S_{t-1} (1 - lambda_t)  (t < P)
    p_P = S_{P-1}                                            the P sum to 1
    l_t(i) = CE(h_t[i] W_head, tokens[i+1])
    loss = mean over rows of mean_{i < T-1} [ sum_t p_t(i) l_t(i)
                                              - beta H(p(i)) ]
    H(p) = - sum_t p_t log p_t,   beta = exit_entropy_weight = 0.1
    logits (no labels) = h_P W_head

Departures from the published model, each one the program's too: a document
boundary is not masked (tokens attend across the end-of-text id); the forms
of the four norms, of the loop (the final norm ends every pass), of the gate,
of the exit distribution and of the objective with its beta are from memory
of the family's code and report (``configs/ouro-2.6b.json``, ``assumed``).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

# bfloat16 activations (one ulp = 2**-8 relative) through FOUR passes of eight
# blocks, each pass ending in a norm, and the 2048-wide head, against float32
# at ``highest``: the relative RMS error (``harness.relative_rms_error``) of
# what check (a) compares, the last pass's logits over 512 rows of the
# vocabulary and the four exit probabilities times sqrt(512 / 4). Set between
# two readings on the chip at the published widths with a fit's own
# parameters (PERF.md, PR 57): the program's bfloat16 path, and this
# reference with every product's operands rounded to an 8-bit float
# (``at_precision``), the nearest precision below, which must read above it:
# not correct. The readings and the room on both sides are in PERF.md §6;
# 32 block applications compound rounding, so this is looser than a model
# that runs its layers once would need.
TOLERANCE = 0.05
# What check (a) compares: the last 256 positions of each of 2 seeded
# 8,192-token sequences, pulled one sequence a batch.
SAMPLE = {"rows": 2, "batch": 1}
QUERY_BLOCK = 256       # queries whose scores against every key exist at once


# None: plain float32. A dtype: every product's operands (activations and
# weights alike) are rounded to it first and the product still accumulates in
# float32, which is what computing "in that precision" means on this chip.
# Only ``at_precision`` sets it, to show what TOLERANCE separates.
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


def _rope(x, theta):
    """x [B, T, H, D]: rotate-half rotary embedding at positions 0..T-1."""
    half = x.shape[-1] // 2
    freqs = theta ** (-np.arange(half) / half)
    angles = np.arange(x.shape[1])[:, None] * freqs[None, :]
    cos = _f32(np.cos(angles))[None, :, None, :]
    sin = _f32(np.sin(angles))[None, :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _attention(p, u, cfg):
    b, t, d = u.shape
    heads, width = cfg["num_attention_heads"], cfg["head_dim"]
    w = lambda n: _f32(p[n]["kernel"]).reshape(d, heads * width)  # noqa: E731
    theta = float(cfg["rope_theta"])
    q = _rope(_mm(u, w("q")).reshape(b, t, heads, width), theta)
    k = _rope(_mm(u, w("k")).reshape(b, t, heads, width), theta)
    v = _mm(u, w("v")).reshape(b, t, heads, width)
    key_at = np.arange(t)[None, :]
    out = []
    for at in range(0, t, QUERY_BLOCK):
        seen = key_at <= np.arange(at, min(at + QUERY_BLOCK, t))[:, None]
        scores = jnp.einsum("bqhd,bkhd->bhqk", _r(q[:, at:at + QUERY_BLOCK]),
                            _r(k)) / np.sqrt(width)
        scores = jnp.where(seen[None, None], scores, -jnp.inf)
        out.append(jnp.einsum("bhqk,bkhd->bqhd",
                              _r(jax.nn.softmax(scores, -1)), _r(v)))
    out = jnp.concatenate(out, axis=1).reshape(b, t, heads * width)
    return _mm(out, _f32(p["o"]["kernel"]).reshape(heads * width, d))


def _layer(p, x, cfg):
    eps = cfg["rms_norm_eps"]
    u = _rms_norm(x, p["ln1"]["scale"], eps)
    x = x + _rms_norm(_attention(p["attn"], u, cfg), p["ln1_post"]["scale"],
                      eps)
    m = _rms_norm(x, p["ln2"]["scale"], eps)
    gate, up, down = (_f32(p[n]["kernel"]) for n in ("gate", "up", "down"))
    f = _mm(jax.nn.silu(_mm(m, gate)) * _mm(m, up), down)
    return x + _rms_norm(f, p["ln2_post"]["scale"], eps)


def passes(params, tokens, cfg, untied=None):
    """tokens [B, T] -> the final norm's output of every pass, a list of
    ``total_ut_steps`` arrays [B, T, D]. ``untied`` (a test's: a list of
    ``total_ut_steps`` trees) gives pass t blocks and a final norm of its
    own, ``untied[t]``, in the shared ones' place: the sum of the copies'
    gradients is what a shared weight's gradient has to be."""
    h = _f32(params["embed"]["embedding"])[jnp.asarray(tokens)]
    out = []
    for t in range(cfg["total_ut_steps"]):
        own = params if untied is None else untied[t]
        for i in range(cfg["layers"]):
            h = _layer(own[f"block_{i}"], h, cfg)
        h = _rms_norm(h, own["ln_f"]["scale"], cfg["rms_norm_eps"])
        out.append(h)
    return out


def exit_probabilities(params, hidden):
    """The passes' hidden states (a list of [B, T, D]) -> the exit
    distribution, a list of as many [B, T]: ``p_t = lambda_t S_{t-1}``, the
    last what is left."""
    gate = params["exit_gate"]
    left, out = 1.0, []
    for h in hidden[:-1]:
        lam = jax.nn.sigmoid(
            h @ _f32(gate["kernel"])[:, 0] + _f32(gate["bias"])[0])
        out.append(lam * left)
        left = left * (1.0 - lam)
    return out + [left * jnp.ones(hidden[-1].shape[:-1], jnp.float32)]


def forward(variables: dict, inputs, cfg: dict) -> jnp.ndarray:
    """What the pipeline's ``compared`` keeps: at the last
    ``compared_positions`` positions of each sequence the last pass's logits
    over the first ``compared_vocab`` rows of the vocabulary and, beside
    them, the exit probabilities times ``sqrt(compared_vocab / passes)``:
    [B, positions, compared_vocab + passes]."""
    with jax.default_matmul_precision("highest"):
        params = variables["params"]
        hidden = passes(params, inputs, cfg)
        keep = min(cfg["compared_positions"], hidden[-1].shape[1])
        rows = cfg["compared_vocab"]
        logits = _mm(hidden[-1][:, -keep:],
                     _f32(params["lm_head"]["kernel"])[:, :rows])
        probs = jnp.stack([p[:, -keep:] for p in exit_probabilities(
            params, hidden)], axis=-1)
        return jnp.concatenate([logits, probs * np.float32(np.sqrt(
            rows / cfg["total_ut_steps"]))], axis=-1)


def loss(params: dict, tokens, cfg: dict, untied=None) -> jnp.ndarray:
    """The training loss of one batch: the expected next-token cross entropy
    over the exit distribution less ``exit_entropy_weight`` times its
    entropy, a mean over rows of a mean over the positions 0..T-2
    (``untied``: :func:`passes`)."""
    with jax.default_matmul_precision("highest"):
        hidden = passes(params, tokens, cfg, untied)
        probs = exit_probabilities(params, hidden)
        labels = jnp.asarray(tokens)[:, 1:, None]
        total = 0.0
        for h, p in zip(hidden, probs):
            logp = jax.nn.log_softmax(
                _mm(h[:, :-1], _f32(params["lm_head"]["kernel"])), axis=-1)
            ce = -jnp.take_along_axis(logp, labels, axis=-1)[..., 0]
            p = p[:, :-1]
            total = total + p * ce + cfg["exit_entropy_weight"] * p * jnp.log(p)
        return jnp.mean(total)
