"""Plain reference of kanana-2-30b-a3b-instruct-2601 (kakaocorp,
``deepseek_v3``), as one chip of an eight-way expert- and vocabulary-parallel
deployment holds it: forward pass, training loss and, through ``jax.grad`` of
that loss, gradients; the slots each expert was picked for, and the balancing
bias's update from them.

float32 ``jax.numpy`` under ``highest`` matmul precision; no flax, no kernel,
no sharding, and nothing shared with ``raydp_tpu/models`` or
``raydp_tpu/ops``. Written from the equations below. Attention is dense with
an explicit mask, a block of 256 queries at a time, so that a 16,384-token
sequence fits beside a fit's state (32 heads x 256 queries x 16,384 keys of
float32 scores are 0.54 GB); a score is the SUM of two products, the
position-free parts' and the rotary parts' against the one rotary key every
head shares, so no key of 192 is ever laid out. The rotation is written out
pair by pair. The expert layer is computed **densely**: every held expert on
every token, multiplied by the top-k mask times the weight. ``x`` is a
layer's input ``[T, 2048]``, ``RMSNorm`` has eps 1e-6 and a weight, and no
linear layer has a bias:

    x0 = E[tokens]
    u  = RMSNorm_in(x)
    q  = u Wq                     32 heads of 192: qn (first 128), qr (last 64)
         (with a query latent:  q = RMSNorm(u Wqa) Wqb)
    c, kr = split(u Wkva)         the latent [512], ONE rotary key [64]
    kv = RMSNorm_512(c) Wkvb      32 heads of 256: kn (first 128), v (last 128)
    qr, kr = R(qr), R(kr)         position p turns pair (2i, 2i + 1) of the 64
                                  by the angle p * 1e6^(-i / 32)
    s_h[p, j] = (qn_h[p] . kn_h[j] + qr_h[p] . kr[j]) / sqrt(192),  j <= p
    a_h[p] = sum_j softmax_j(s_h[p, j]) v_h[j]
    x' = x + concat_h(a_h) Wo
    m  = RMSNorm_post(x')
    dense layer:   f = (silu(m Wgate) * (m Wup)) Wdown            width 6144
    expert layer:  s = sigmoid(m Wr)                              [128]
                   S = top-6 of s + b          b: the layer's bias, no gradient
                   w_e = 2.448 * s_e / (sum_{e' in S} s_e' + 1e-20)
                   f = shared(m) + sum_{e in S, e held here} w_e expert_e(m)
                   shared: one gated MLP of width 2 x 768
    out = x' + f
    after the last layer: RMSNorm, then the head over the rows held
    loss = CE(next token, over the rows held)          no auxiliary loss
    after a step, each expert layer:  c_e = slots expert e was picked for
        (all 128); delta = 0.001 * sign(mean(c) - c); b += delta - mean(delta)

Departures from the published model, each one the program's too. *The share*:
what the absent experts would have added is left out and the partial result
goes on to the next layer; the weights are normalised over all six choices
and the counts are over all 128 experts, whatever is held; the shared experts
are whole on every chip (``experts_held`` equal to the expert count gives the
uncut layer: the CPU test of the eight shares adds them up against it, the
shared MLP counted once). *The slice*: logits and loss are over the
vocabulary rows held. *The assumed forms* (``configs/kanana-2-30b-a3b.json``,
``assumed``): where the latent's norm sits, the interleaved rotation's
convention (each pair turned in place; the family's code moves the pairs to
the half-split layout, q and k alike, so scores agree), the scale 192^-1/2
with no ``mscale``, the bias's update and its rate, the two shared experts as
one MLP, and that a document boundary is not masked (tokens attend across
the end-of-text id). The group-limited pick is not written: ``n_group`` and
``topk_group`` are 1.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

# bfloat16 activations (one ulp = 2**-8 relative) through six blocks and the
# 2048-wide head, against float32 at ``highest``: the relative RMS error of
# the compared logits (``harness.relative_rms_error``). Set between two
# readings on the chip at the published widths with a fit's own parameters
# and bias (PERF.md, PR 40): the program reads 0.0121-0.0179 over ten seeds,
# and this reference with 8-bit float operands
# (``at_precision``), the nearest precision below, reads 0.216 (e5m2) and
# 1.42 (e4m3): not correct. 0.05 is 2.8 times the first and under a quarter
# of the second (their geometric middle is 0.061). The window lies inside the
# optimizer's warm-up, so the parameters are near their seeded
# initialisation, where what dominates the error is not rounding but the
# router: bfloat16 inputs flip near-tied top-6 choices; this reference with
# bfloat16 operands reads 0.0122 itself. A router, a sigmoid or a loss
# computed in bfloat16, a pick by the bare scores or a weight that carries
# the bias, a rotation over the wrong pairs, a rotary key that is not shared
# or a latent that is not normed would read far above the tolerance.
TOLERANCE = 0.05
# What check (a) compares: the logits at the last 256 positions of each of 2
# seeded 16,384-token sequences over the 16,032 rows held, pulled one
# sequence a batch.
SAMPLE = {"rows": 2, "batch": 1}
QUERY_BLOCK = 256       # queries whose scores against every key exist at once
STATE = "batch_stats"   # the collection the program keeps the bias in


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


def rotate_pairs(x, theta):
    """x [B, T, ..., D] at positions 0..T-1: dimensions (2i, 2i + 1) turned by
    the angle ``position * theta^(-2i / D)``, each pair in its place."""
    t, d = x.shape[1], x.shape[-1]
    out = []
    for i in range(d // 2):
        angle = np.arange(t) * float(theta) ** (-2.0 * i / d)
        shape = (1, t) + (1,) * (x.ndim - 3)
        cos = _f32(np.cos(angle)).reshape(shape)
        sin = _f32(np.sin(angle)).reshape(shape)
        a, b = x[..., 2 * i], x[..., 2 * i + 1]
        out += [a * cos - b * sin, a * sin + b * cos]
    return jnp.stack(out, axis=-1)


def attention(p, u, cfg):
    """Latent attention on the normed input u [B, T, D] with the parameters of
    one block's ``attn``."""
    b, t, d = u.shape
    heads, rank = cfg["num_attention_heads"], cfg["kv_lora_rank"]
    nope, rope, dv = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                      cfg["v_head_dim"])
    eps, theta = cfg["rms_norm_eps"], cfg["rope_theta"]
    if cfg["q_lora_rank"] is None:
        q = _mm(u, _f32(p["q"]["kernel"]).reshape(d, heads * (nope + rope)))
    else:
        low = _rms_norm(_mm(u, _f32(p["q_a"]["kernel"])),
                        p["q_a_norm"]["scale"], eps)
        q = _mm(low, _f32(p["q_b"]["kernel"]).reshape(
            cfg["q_lora_rank"], heads * (nope + rope)))
    q = q.reshape(b, t, heads, nope + rope)
    q_nope, q_rope = q[..., :nope], q[..., nope:]
    down = _mm(u, _f32(p["kv_a"]["kernel"]))                # [B, T, 576]
    latent = _rms_norm(down[..., :rank], p["kv_norm"]["scale"], eps)
    k_rope = down[..., rank:]                               # [B, T, 64]: ONE
    up = _mm(latent, _f32(p["kv_b"]["kernel"]).reshape(
        rank, heads * (nope + dv))).reshape(b, t, heads, nope + dv)
    k_nope, v = up[..., :nope], up[..., nope:]
    if not cfg["rope_interleave"]:
        raise NotImplementedError("the reference rotates interleaved pairs")
    q_rope, k_rope = rotate_pairs(q_rope, theta), rotate_pairs(k_rope, theta)
    key_at = np.arange(t)[None, :]
    out = []
    for at in range(0, t, QUERY_BLOCK):
        rows = slice(at, at + QUERY_BLOCK)
        seen = key_at <= np.arange(at, min(at + QUERY_BLOCK, t))[:, None]
        scores = (jnp.einsum("bqhd,bkhd->bhqk", _r(q_nope[:, rows]),
                             _r(k_nope))
                  + jnp.einsum("bqhd,bkd->bhqk", _r(q_rope[:, rows]),
                               _r(k_rope))) / np.sqrt(nope + rope)
        scores = jnp.where(seen[None, None], scores, -jnp.inf)
        out.append(jnp.einsum("bhqk,bkhd->bqhd",
                              _r(jax.nn.softmax(scores, -1)), _r(v)))
    out = jnp.concatenate(out, axis=1).reshape(b, t, heads * dv)
    return _mm(out, _f32(p["o"]["kernel"]).reshape(heads * dv, d))


def _gated_mlp(m, gate, up, down):
    return _mm(jax.nn.silu(_mm(m, gate)) * _mm(m, up), down)


def _experts(p, m, bias, cfg):
    """Dense expert layer on tokens m [N, D] with the layer's bias [E] ->
    (the shared MLP's output plus the held experts' part of the routed sum
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
        wg, wu, wd, g = w
        return carry + g[:, None] * _gated_mlp(m, wg, wu, wd), None

    y, _ = jax.lax.scan(one, jnp.zeros_like(m), (
        _f32(p["experts_gate"]), _f32(p["experts_up"]),
        _f32(p["experts_down"]), gates.T[first:first + held]))
    if cfg["n_shared_experts"]:
        y = y + _gated_mlp(m, *(_f32(p[f"shared_{n}"]["kernel"])
                                for n in ("gate", "up", "down")))
    return y, ids


def _bias_of(state, layer, cfg):
    """The ``layer``-th block's bias in the program's collection; zeros
    where none is handed in (a fresh model's)."""
    if state is None:
        return jnp.zeros((cfg["n_routed_experts"],), jnp.float32)
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
        x = x + attention(p["attn"], _rms_norm(x, p["ln1"]["scale"], eps),
                          cfg)
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


def latent_attention(p: dict, u, cfg: dict) -> jnp.ndarray:
    """One attention sub-layer alone on a normed input: what the CPU test
    compares ``LatentAttention`` against."""
    with jax.default_matmul_precision("highest"):
        return attention(p, _f32(u), cfg)


def expert_layer(p: dict, m, bias, cfg: dict) -> jnp.ndarray:
    """One expert layer alone: the shared MLP's output (where the
    configuration has one) plus the part of the routed sum that the experts
    ``[first_expert, first_expert + experts_held)`` give: what the share test
    adds up over the eight chips."""
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
