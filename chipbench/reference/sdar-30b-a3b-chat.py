"""Plain reference of SDAR-30B-A3B-Chat (JetLM, ``sdar_moe``) under its
block-diffusion training objective, as one chip of an eight-way expert- and
vocabulary-parallel deployment holds it: forward pass, training loss and,
through ``jax.grad`` of that loss, gradients.

float32 ``jax.numpy`` under ``highest`` matmul precision; no flax, no kernel,
no sharding, and nothing shared with ``raydp_tpu/models``. It is handed the
row ``x0`` [B, L], its noised copy ``x_t`` [B, L] (some tokens replaced by the
mask id) and the noise level ``t`` a block [B, L / 4]: the draw is input
generation (``pipelines/sdar-30b-a3b-chat.py``), everything from there is
written out here. The model sees ``[x0 ; x_t]``, 2L positions; ``p(i) = i mod
L`` is a position's place in its copy and ``b(i) = p(i) // 4`` its block:

    x  = E[[x0 ; x_t]]
    u  = RMSNorm(x)                                    eps 1e-6, a weight
    q  = u Wq (32 heads of 128)  k = u Wk (4 of 128)  v = u Wv (4 of 128)
    q, k = RMSNorm_128(q), RMSNorm_128(k)       head by head, one weight each
    q, k = RoPE(q, k; theta 1e6, rotate-half) at the place p(i)
    a_h[i] = softmax_j(q_h[i] k_{h // 8}[j] / sqrt(128)) v_{h // 8}[j] over
             i clean:  j clean and b(j) <= b(i)
             i noised: j clean and b(j) < b(i), or j noised and b(j) = b(i)
    x' = x + concat_h(a_h) Wo
    m  = RMSNorm(x')
    s  = softmax(m Wr) over all 128 experts, float32
    S  = top-8 of s;  w_e = s_e / sum_{e' in S} s_e'
    out = x' + sum_{e in S, e held here} w_e (silu(m Wgate_e) * (m Wup_e)) Wdown_e
    after the last layer: RMSNorm, then the head over the rows held
    loss = (1 / L) sum_b (1 / t_b) sum_{i in b, x_t[i] = mask} CE(logits at
           the noised position of i, x0[i])            same position, no shift

Attention is dense with the explicit three-region mask, a block of 256
queries at a time so that 16,384 positions fit beside a fit's state (32 heads
x 256 queries x 16,384 keys of float32 scores are 0.54 GB). The expert layer
is computed **densely**: every held expert on every position, multiplied by
the top-k mask times the weight, so it has nothing in common with the
program's sort / gather / grouped-product walk. What the absent experts would
have added is left out, here as in the program; the weights are normalised
over all eight choices whatever is held. ``experts_held`` equal to the expert
count gives the uncut layer (the CPU test of the eight shares adds them up
against it; there is no shared expert to count once).

Departures from the published model, each one the program's too: a document
boundary is not masked; a token counts as masked where ``x_t`` holds the mask
id (the generator draws no clean token with that id); the block length, the
schedule and the layer's exact forms are from memory of the family's code and
papers (``configs/sdar-30b-a3b-chat.json``, ``assumed``).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

# bfloat16 activations (one ulp = 2**-8 relative) through six blocks of 16,384
# positions and the 2048-wide head, against float32 at ``highest``: the
# relative RMS error of the compared logits (``harness.relative_rms_error``).
# Set between two readings on the chip at the published widths with a fit's
# own parameters (``benchmarks/blockdiff_control.py --control``; PERF.md
# section 6, PR 50): the program reads 0.0039-0.0040 over sixteen seeds, and
# this reference with 8-bit float operands (``at_precision``), the nearest
# precision below, reads 0.0774-0.0777 (e5m2) and 0.135-0.142 (e4m3): not
# correct. 0.018 is four and a half times the first and less than a quarter
# of the second (their geometric middle is 0.0176). The window lies inside
# the optimizer's warm-up, so the parameters are near their seeded
# initialisation; this reference with bfloat16 operands reads 0.0024 itself:
# what the program's error is made of is rounding, with a near-tied top-8
# choice flipped here and there. Both readings belong to the configuration's
# initialisation (an embedding of std 4.0: at the family's 0.02 the same three
# read 0.0058, 0.154 and 0.97) and are to be taken again with it.
# What the limit sees of a wrong program, planted in this file's pieces on
# the chip: position ids that run on into the noised copy read 0.053-0.055, a
# mask with a whole region missing (a noised query without its clean keys)
# 0.50; another noised copy than the program's read 0.9 on the CPU at a small
# size. What it does NOT see at near-initial weights: a mask edge off by one
# block, either way (4 keys more of some 8,190 under attention that is still
# diffuse: 0.0039-0.0040, the program's own reading) and one held expert of
# sixteen dropped (0.0056-0.0058; an expert's output is small beside a std-4
# stream, and 0.028 at the 0.02 embedding). Those are held off the chip,
# exactly: the mask's edges through the kernels against a brute-force table
# and the eight shares against the uncut layer
# (``tests/test_blockdiff_moe_lm.py``), and the CPU rehearsal's check (a).
TOLERANCE = 0.018
# What check (a) compares: the logits at the last 256 NOISED positions of each
# of 2 seeded 8,192-token rows over the 18,992 rows held, one row a batch.
SAMPLE = {"rows": 2, "batch": 1}
QUERY_BLOCK = 256       # queries whose scores against every key exist at once

# None: plain float32. A dtype: every product's operands (activations and
# weights alike) are rounded to it first and the product still accumulates in
# float32. Only ``at_precision`` sets it, to show what TOLERANCE separates.
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


def _rope(x, places, theta):
    """x [B, T, H, D]: rotate-half rotary embedding at ``places`` [T]."""
    half = x.shape[-1] // 2
    freqs = theta ** (-np.arange(half) / half)
    angles = np.asarray(places)[:, None] * freqs[None, :]
    cos = _f32(np.cos(angles))[None, :, None, :]
    sin = _f32(np.sin(angles))[None, :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def visible(queries, length: int, block: int) -> np.ndarray:
    """[len(queries), 2 * length] bool: which keys of ``[x0 ; x_t]`` the
    queries at the positions ``queries`` see."""
    i = np.asarray(queries)[:, None]
    j = np.arange(2 * length)[None, :]
    noised_i, noised_j = i >= length, j >= length
    bi, bj = (i % length) // block, (j % length) // block
    return np.where(noised_i,
                    np.where(noised_j, bj == bi, bj < bi),
                    ~noised_j & (bj <= bi))


def _attention(p, u, cfg):
    b, t, d = u.shape
    heads, kv_heads = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    width, group, eps = cfg["head_dim"], heads // kv_heads, cfg["rms_norm_eps"]
    length, block = t // 2, cfg["diffusion"]["block_length"]
    w = lambda n, h: _f32(p[n]["kernel"]).reshape(d, h * width)  # noqa: E731
    q = _mm(u, w("q", heads)).reshape(b, t, heads, width)
    k = _mm(u, w("k", kv_heads)).reshape(b, t, kv_heads, width)
    v = _mm(u, w("v", kv_heads)).reshape(b, t, kv_heads, width)
    q = _rms_norm(q, p["q_norm"]["scale"], eps)
    k = _rms_norm(k, p["k_norm"]["scale"], eps)
    places, theta = np.arange(t) % length, float(cfg["rope_theta"])
    q, k = _rope(q, places, theta), _rope(k, places, theta)
    # query head h reads K/V head h // group
    k, v = jnp.repeat(k, group, axis=2), jnp.repeat(v, group, axis=2)
    out = []
    for at in range(0, t, QUERY_BLOCK):
        seen = visible(np.arange(at, min(at + QUERY_BLOCK, t)), length, block)
        scores = jnp.einsum("bqhd,bkhd->bhqk", _r(q[:, at:at + QUERY_BLOCK]),
                            _r(k)) / np.sqrt(width)
        scores = jnp.where(seen[None, None], scores, -jnp.inf)
        out.append(jnp.einsum("bhqk,bkhd->bqhd",
                              _r(jax.nn.softmax(scores, -1)), _r(v)))
    out = jnp.concatenate(out, axis=1).reshape(b, t, heads * width)
    return _mm(out, _f32(p["o"]["kernel"]).reshape(heads * width, d))


def _experts(p, m, cfg):
    """Dense expert layer on positions m [N, D] -> (the held experts' part
    of the routed sum [N, D], the top-k ids [N, k])."""
    e, k = cfg["num_experts"], cfg["num_experts_per_tok"]
    first, held = cfg["first_expert"], cfg["experts_held"]
    scores = jax.nn.softmax(m @ _f32(p["router"]), axis=-1)  # float32 always
    top, ids = jax.lax.top_k(scores, k)
    if cfg["norm_topk_prob"]:
        top = top / jnp.sum(top, axis=-1, keepdims=True)
    onehot = jax.nn.one_hot(ids, e, dtype=jnp.float32)      # [N, k, E]
    gates = jnp.sum(onehot * top[..., None], axis=1)        # [N, E]

    def one(carry, w):
        wg, wu, wd, g = w
        return carry + g[:, None] * _mm(
            jax.nn.silu(_mm(m, wg)) * _mm(m, wu), wd), None

    y, _ = jax.lax.scan(one, jnp.zeros_like(m), (
        _f32(p["experts_gate"]), _f32(p["experts_up"]),
        _f32(p["experts_down"]), gates.T[first:first + held]))
    return y, ids


def trunk(params, x0, x_t, cfg):
    """(x0, x_t) [B, L] each -> (final normed hidden [B, 2L, D] of
    ``[x0 ; x_t]``, the top-k ids of every expert layer)."""
    eps = cfg["rms_norm_eps"]
    tokens = jnp.concatenate([jnp.asarray(x0), jnp.asarray(x_t)], axis=1)
    x = _f32(params["embed"]["embedding"])[tokens]
    b, t, d = x.shape
    ids = []
    for i in range(cfg["layers"]):
        p = params[f"block_{i}"]
        x = x + _attention(p["attn"], _rms_norm(x, p["ln1"]["scale"], eps),
                           cfg)
        m = _rms_norm(x, p["ln2"]["scale"], eps).reshape(b * t, d)
        f, top = _experts(p["moe"], m, cfg)
        ids.append(top)
        x = x + f.reshape(b, t, d)
    return _rms_norm(x, params["ln_f"]["scale"], eps), ids


def forward(variables: dict, inputs, cfg: dict) -> jnp.ndarray:
    """Logits at the last ``compared_positions`` NOISED positions of each
    row over the rows held, [B, positions, rows]: what the pipeline's
    ``compared`` keeps. ``inputs`` = (x0, x_t, t)."""
    x0, x_t, _ = inputs
    with jax.default_matmul_precision("highest"):
        x, _ = trunk(variables["params"], x0, x_t, cfg)
        keep = min(cfg["compared_positions"], x.shape[1] // 2)
        return _mm(x[:, -keep:],
                   _f32(variables["params"]["lm_head"]["kernel"]))


def loss(params: dict, x0, x_t, t, cfg: dict) -> jnp.ndarray:
    """The training loss of one batch, the mean over its rows of
    ``(1 / L) sum_b (1 / t_b) sum_{i in b, masked} CE(logits_i, x0_i)`` with
    ``logits_i`` the output at the noised position of token ``i``. No
    auxiliary loss."""
    block = cfg["diffusion"]["block_length"]
    x0, x_t = jnp.asarray(x0), jnp.asarray(x_t)
    with jax.default_matmul_precision("highest"):
        x, _ = trunk(params, x0, x_t, cfg)
        length = x0.shape[1]
        logits = _mm(x[:, length:], _f32(params["lm_head"]["kernel"]))
        logp = jax.nn.log_softmax(logits, axis=-1)
        ce = -jnp.take_along_axis(logp, x0[..., None], axis=-1)[..., 0]
        masked = x_t == cfg["diffusion"]["mask_id"]
        weight = masked / jnp.repeat(_f32(t), block, axis=1)
        return jnp.mean(jnp.sum(weight * ce, axis=1) / length)


def expert_layer(p: dict, m, cfg: dict) -> jnp.ndarray:
    """One expert layer alone: the part of the routed sum that the experts
    ``[first_expert, first_expert + experts_held)`` give: what the share test
    adds up over the eight chips."""
    with jax.default_matmul_precision("highest"):
        return _experts(p, _f32(m), cfg)[0]


def top_k_ids(params: dict, x0, x_t, cfg: dict):
    """The reference's expert choices, [layers][N, k]: what a test or a
    builder compares the program's router against."""
    with jax.default_matmul_precision("highest"):
        return trunk(params, x0, x_t, cfg)[1]
