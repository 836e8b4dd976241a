"""Operations the training of a delta-rule / latent-attention / sparse-expert
language model (the ``kimi_linear`` family: kimi-linear-48b-a3b) needs, as one
chip of an expert- and vocabulary-parallel deployment runs it: one token's,
each attention kernel's and each Kimi Delta Attention scan's.

Matrix multiplications only, 2 operations per multiply-add. Every layer held
is a PAIR, by its letter in ``layer_pattern_held``:

- ``K``, a Kimi Delta Attention operator: the fused input projection (``3 x
  heads x head_dim`` outputs), the decay's and the output gate's two low-rank
  products each, ``beta``'s projection and the output projection; and the
  scan, from the chunked equations at ``kda_chunk`` rows a chunk
  (:func:`scan_flops_per_token`);
- ``A``, latent attention: the q projection, the K/V down- and up-projections
  and the output projection; QK^T at the keys' width and PV at the values'
  over the ``(T + 1) / 2`` keys a causal query sees;

and under either the feed-forward part: the first ``dense_layers`` of the
layers held keep a dense SwiGLU of width ``intermediate_size``; the others
hold the router over all experts, the shared expert and the expected share
of the ``num_experts_per_token`` choices that falls on an expert held here
(``experts_held / num_experts`` under even routing, which is what the
balancing bias steers to: three products each). The head over the rows held,
once (the embedding's gather moves bytes). The backward pass costs twice the
forward, so a trained token is 3x the forward. **No recompute is counted**
towards a token's operations: a recomputed layer (a ``K`` pair's second
projections, convolution and scan, and the third forming of the chunked form
in the scan's backward among it), the fused head loss's second product and
the flash kernels' re-formed scores lower ``model_flops_util``, they do not
count towards it. The convolution's taps, the L2 and RMS norms, softplus,
sigmoids, exponentials, the sort, gathers, the bias update and the optimizer
count zero towards a token: they move bytes.

The kernels' functions answer to the one contract every family keeps
(``trace/executions.py``): ``(operations, bytes)`` of ONE execution of the
kernels of one layer of ``kind`` over ``sequences`` sequences, both the least
the algorithm needs. Flash (kind ``full``): keys of 192 beside values of 128.
The scan (kind ``kda``), whatever implements it, lists its products beside
each function.
"""

from __future__ import annotations


def visible_pairs(seq_len: int) -> float:
    """(query, key) pairs a causal mask leaves visible in one sequence."""
    return seq_len * (seq_len + 1) / 2


def layers_of(cfg: dict) -> dict:
    """How many of the layers held have each operator."""
    pattern = cfg["layer_pattern_held"]
    if len(pattern) != cfg["layers"] or set(pattern) - set("KA"):
        raise ValueError(f"layer_pattern_held {pattern!r}: {cfg['layers']} "
                         f"letters of 'K', 'A'")
    return {letter: pattern.count(letter) for letter in "KA"}


def _widths(cfg: dict):
    """(a key's and a query's width, a value's) of one attention head."""
    return (cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"],
            cfg["v_head_dim"])


def _kda(cfg: dict):
    """(heads, a head's width, the inner width, the gates' rank, a chunk's
    rows) of a Kimi Delta Attention operator."""
    lin = cfg["linear_attn_config"]
    return (lin["num_heads"], lin["head_dim"],
            lin["num_heads"] * lin["head_dim"], lin["head_dim"],
            cfg["kda_chunk"])


def attention_projection_weights(cfg: dict) -> int:
    """The matrix entries of one layer's attention (the latent's norm is no
    matrix; no query latent in this family)."""
    d, heads = cfg["hidden_size"], cfg["num_attention_heads"]
    d_qk, d_v = _widths(cfg)
    return (d * heads * d_qk
            + d * (cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"])
            + cfg["kv_lora_rank"] * heads * (cfg["qk_nope_head_dim"] + d_v)
            + heads * d_v * d)


def kda_projection_weights(cfg: dict) -> int:
    """The matrix entries of one Kimi Delta Attention operator: the fused
    q | k | v projection, two low-rank pairs, ``beta`` and the output."""
    d = cfg["hidden_size"]
    heads, _, inner, rank, _ = _kda(cfg)
    return (d * 3 * inner + 2 * (d * rank + rank * inner) + d * heads
            + inner * d)


def scan_flops_per_token(cfg: dict) -> float:
    """One token's forward operations in ONE layer's scan, all heads, from
    the chunked equations (keys and values ``P`` wide, ``C`` rows a chunk;
    a product over the pairs of a chunk counts the ``(C + 1) / 2`` a row
    sees): the two score products ``K K^T`` and ``Q K^T`` (``P`` deep), the
    unit-triangular solve as a forward substitution on ``[K e^G | V]`` (``C^2
    x 2P`` operations a chunk), ``W S_0``, ``(Q e^G) S_0`` and the state's
    update ``(K e^{G_C - G})^T U`` (``2 P^2`` each) and ``P U``."""
    heads, p, _, _, c = _kda(cfg)
    seen = (c + 1) / 2
    return heads * (2 * 2 * p * seen + c * 2 * p + 3 * 2 * p * p
                    + 2 * p * seen)


def forward_flops_per_token(cfg: dict) -> dict:
    """One token's forward operations by part."""
    d, count = cfg["hidden_size"], layers_of(cfg)
    dense, sparse = cfg["dense_layers"], cfg["layers"] - cfg["dense_layers"]
    d_qk, d_v = _widths(cfg)
    keys = visible_pairs(cfg["seq_len"]) / cfg["seq_len"]   # a query sees
    expert = 3 * 2 * d * cfg["moe_intermediate_size"]
    held = cfg["experts_held"] / cfg["num_experts"]
    return {
        "kda_projections": count["K"] * 2 * kda_projection_weights(cfg),
        "kda_scan": count["K"] * scan_flops_per_token(cfg),
        "attention_projections": count["A"] * 2
        * attention_projection_weights(cfg),
        # QK^T at the keys' width, PV at the values'
        "attention_scores": count["A"] * 2 * cfg["num_attention_heads"] * (
            d_qk + d_v) * keys,
        "dense_ffn": dense * 3 * 2 * d * cfg["intermediate_size"],
        "router": sparse * 2 * d * cfg["num_experts"],
        "shared_experts": sparse * cfg["num_shared_experts"] * expert,
        "experts": sparse * cfg["num_experts_per_token"] * held * expert,
        "head": 2 * d * cfg["vocab_rows_held"],
    }


def train_flops_per_item(cfg: dict, wl: dict, info: dict) -> float:
    return 3.0 * sum(forward_flops_per_token(cfg).values())


def parameters(cfg: dict) -> dict:
    """The parameters this chip holds, by part (no bias but ``dt_bias``; the
    routing bias and its counts are state, not parameters)."""
    d, count = cfg["hidden_size"], layers_of(cfg)
    dense, sparse = cfg["dense_layers"], cfg["layers"] - cfg["dense_layers"]
    expert = 3 * d * cfg["moe_intermediate_size"]
    heads, p, inner, _, _ = _kda(cfg)
    taps = cfg["linear_attn_config"]["short_conv_kernel_size"]
    return {
        # the matrices, the taps, A_log, dt_bias and the head norm's weight
        "kda": count["K"] * (kda_projection_weights(cfg) + taps * 3 * inner
                             + heads + inner + p),
        "attention": count["A"] * (attention_projection_weights(cfg)
                                   + cfg["kv_lora_rank"]),
        "norms": cfg["layers"] * 2 * d,
        "dense_ffn": dense * 3 * d * cfg["intermediate_size"],
        "router": sparse * d * cfg["num_experts"],
        "shared_experts": sparse * cfg["num_shared_experts"] * expert,
        "experts": sparse * cfg["experts_held"] * expert,
        "embedding_head_final_norm": 2 * cfg["vocab_rows_held"] * d + d,
    }


def _width(cfg: dict) -> int:
    return 2 if cfg["compute_dtype"] == "bfloat16" else 4


def num_experts(cfg: dict) -> int:
    """The experts a layer's router chooses among, whatever a chip holds."""
    return cfg["num_experts"]


def _pairs(cfg: dict, kind: str) -> float:
    if kind != "full":
        raise ValueError(f"kind {kind!r}: this family's attention layers are "
                         f"full causal attention")
    return visible_pairs(cfg["seq_len"])


def flash_forward(cfg: dict, wl: dict, kind: str, sequences: float):
    """One execution of the forward attention kernel of ONE layer over
    ``sequences`` sequences: QK^T at the keys' width and PV at the values'
    over the visible pairs; reads q and k at the keys' width and v at the
    values', writes the output at the values' width and a float32
    log-sum-exp a row."""
    heads = cfg["num_attention_heads"]
    d_qk, d_v = _widths(cfg)
    rows = sequences * cfg["seq_len"]
    flops = sequences * 2 * heads * (d_qk + d_v) * _pairs(cfg, kind)
    moved = rows * heads * (2 * d_qk + 2 * d_v) * _width(cfg) \
        + rows * heads * 4
    return flops, moved


def flash_backward(cfg: dict, wl: dict, kind: str, sequences: float):
    """The backward attention kernels (dK/dV and dQ together) of ONE layer:
    the five products the gradient needs over the visible pairs: the scores
    again, dK and dQ at the keys' width, dP and dV at the values'. Reads q
    and k (keys' width), v, the output and its gradient (values' width) and
    two float32 rows; writes dq and dk at the keys' width and dv at the
    values'."""
    heads = cfg["num_attention_heads"]
    d_qk, d_v = _widths(cfg)
    rows = sequences * cfg["seq_len"]
    flops = sequences * 2 * heads * (3 * d_qk + 2 * d_v) * _pairs(cfg, kind)
    moved = rows * heads * (4 * d_qk + 4 * d_v) * _width(cfg) \
        + 2 * rows * heads * 4
    return flops, moved


def _scan_kind(kind: str) -> None:
    if kind != "kda":
        raise ValueError(f"kind {kind!r}: a delta-rule scan's is 'kda'")


def kda_forward(cfg: dict, wl: dict, kind: str, sequences: float):
    """One execution of the forward scan of ONE Kimi Delta Attention layer
    over ``sequences`` sequences: :func:`scan_flops_per_token`'s products;
    reads q, k, v (at the activations' width), g and beta (float32), writes
    o."""
    _scan_kind(kind)
    heads, _, inner, _, _ = _kda(cfg)
    rows = sequences * cfg["seq_len"]
    moved = rows * (4 * inner * _width(cfg) + (inner + heads) * 4)
    return rows * scan_flops_per_token(cfg), moved


def kda_backward(cfg: dict, wl: dict, kind: str, sequences: float):
    """The backward scan of ONE Kimi Delta Attention layer, a chunk at a
    time from the last to the first with the state's gradient carried. A
    head's products (``P`` wide, ``C`` rows a chunk, pairs counted as in
    :func:`scan_flops_per_token`):

    - formed again: the two score products, the solve, ``W S_0`` (for ``U``);
    - with the state (``2 P^2`` a row each): ``do S_0^T`` (for ``Q e^G``),
      ``(Q e^G)^T do`` and ``W^T dU`` (for ``dS_0``), ``(K e^{G_C - G}) dS``
      (for ``dU``), ``U dS^T`` (for ``K e^{G_C - G}``), ``dU S_0^T`` (for
      ``dW``);
    - over a chunk's pairs: ``P^T do`` and ``do U^T`` (``P`` deep each), the
      solve's transpose (a second substitution on ``[dW | dU~]``) and its
      matrix's gradient (``2P`` deep), and the four products that take
      ``dA`` and ``dP`` back to q and k (``P`` deep each).

    Reads q, k, v and the output's gradient (activations' width), g and beta
    (float32); writes dq, dk, dv, and float32 dg and dbeta."""
    _scan_kind(kind)
    heads, p, inner, _, c = _kda(cfg)
    rows = sequences * cfg["seq_len"]
    seen = (c + 1) / 2
    a_head = (2 * 2 * p * seen + c * 2 * p + 2 * p * p      # formed again
              + 6 * 2 * p * p                               # with the state
              + 2 * 2 * p * seen + c * 2 * p + 2 * 2 * p * seen
              + 4 * 2 * p * seen)
    moved = rows * (7 * inner * _width(cfg) + 2 * (inner + heads) * 4)
    return rows * heads * a_head, moved
