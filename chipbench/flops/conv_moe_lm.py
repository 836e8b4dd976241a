"""Operations the training of a convolution / attention / sparse-expert
language model (the ``lfm2_moe`` family: lfm2-8b-a1b) needs, as one chip of an
expert- and vocabulary-parallel deployment runs it: one token's, each
attention kernel's and each gated-convolution kernel's.

Matrix multiplications only, 2 operations per multiply-add. Every layer held
is a PAIR, by its letter in ``layer_pattern_held``:

- ``C``, a gated short convolution operator: the input projection to ``B``,
  ``C`` and ``z`` (``3 x hidden`` outputs) and the output projection;
- ``A``, full causal attention: q and o at ``heads x head_dim``, k and v at
  the (fewer) K/V heads' width; QK^T and PV over the ``(T + 1) / 2`` keys a
  causal query sees;

and under either the feed-forward part: the first ``dense_layers`` of the
layers held keep a dense SwiGLU of width ``intermediate_size``; the others
hold the router over all experts and the expected share of the
``num_experts_per_tok`` choices that falls on an expert held here
(``experts_held / num_experts`` under even routing, which is what the
balancing bias steers to: three products each; no shared expert). The head
over the rows held, once (the embedding's gather moves bytes). The backward
pass costs twice the forward, so a trained token is 3x the forward. **No
recompute is counted** towards a token's operations: a recomputed layer (its
second ``W_in u`` and gated convolution among it), the fused head loss's
second product and the flash kernels' re-formed scores lower
``model_flops_util``, they do not count towards it. The gated convolution
itself (two gates and three taps: 8 operations a channel), norms, sigmoids,
RoPE, the sort, gathers, the bias update and the optimizer count zero towards
a token: they move bytes.

The kernels' functions answer to the one contract every family keeps
(``trace/executions.py``): ``(operations, bytes)`` of ONE execution of the
kernels of one layer of ``kind`` over ``sequences`` sequences, both the least
the algorithm needs. Flash (kind ``full``): K and V are read once a group of
four query heads. Gated convolution (kind ``conv``), whatever implements it:
forward reads ``B``, ``C``, ``z`` and writes the stage's output once;
backward reads those and the output's gradient and writes the three
gradients once; a tile's halo rows, read twice by a tiled kernel, are not
counted (they lower the share).
"""

from __future__ import annotations


def visible_pairs(seq_len: int) -> float:
    """(query, key) pairs a causal mask leaves visible in one sequence."""
    return seq_len * (seq_len + 1) / 2


def layers_of(cfg: dict) -> dict:
    """How many of the layers held have each operator."""
    pattern = cfg["layer_pattern_held"]
    if len(pattern) != cfg["layers"] or set(pattern) - set("CA"):
        raise ValueError(f"layer_pattern_held {pattern!r}: {cfg['layers']} "
                         f"letters of 'C', 'A'")
    return {letter: pattern.count(letter) for letter in "CA"}


def _heads(cfg: dict):
    """(query width, K/V width): heads times the head's width."""
    return (cfg["num_attention_heads"] * cfg["head_dim"],
            cfg["num_key_value_heads"] * cfg["head_dim"])


def conv_projection_weights(cfg: dict) -> int:
    d = cfg["hidden_size"]
    return d * 3 * d + d * d


def forward_flops_per_token(cfg: dict) -> dict:
    """One token's forward operations by part."""
    d, count = cfg["hidden_size"], layers_of(cfg)
    dense, sparse = cfg["dense_layers"], cfg["layers"] - cfg["dense_layers"]
    q, kv = _heads(cfg)
    keys = visible_pairs(cfg["seq_len"]) / cfg["seq_len"]   # a query sees
    expert = 3 * 2 * d * cfg["moe_intermediate_size"]
    held = cfg["experts_held"] / cfg["num_experts"]
    return {
        "conv_projections": count["C"] * 2 * conv_projection_weights(cfg),
        "attention_projections": count["A"] * 2 * d * (2 * q + 2 * kv),
        "attention_scores": count["A"] * 2 * 2 * q * keys,
        "dense_ffn": dense * 3 * 2 * d * cfg["intermediate_size"],
        "router": sparse * 2 * d * cfg["num_experts"],
        "experts": sparse * cfg["num_experts_per_tok"] * held * expert,
        "head": 2 * d * cfg["vocab_rows_held"],
    }


def train_flops_per_item(cfg: dict, wl: dict, info: dict) -> float:
    return 3.0 * sum(forward_flops_per_token(cfg).values())


def parameters(cfg: dict) -> dict:
    """The parameters this chip holds, by part (no bias anywhere; the
    routing bias and its counts are state, not parameters; embedding and
    head are ONE array)."""
    d, count = cfg["hidden_size"], layers_of(cfg)
    dense, sparse = cfg["dense_layers"], cfg["layers"] - cfg["dense_layers"]
    q, kv = _heads(cfg)
    return {
        # both projections and the taps
        "conv": count["C"] * (conv_projection_weights(cfg)
                              + cfg["conv_L_cache"] * d),
        "attention": count["A"] * (d * (2 * q + 2 * kv)
                                   + 2 * cfg["head_dim"]),
        "norms": cfg["layers"] * 2 * d,
        "dense_ffn": dense * 3 * d * cfg["intermediate_size"],
        "router": sparse * d * cfg["num_experts"],
        "experts": sparse * cfg["experts_held"] * 3 * d
        * cfg["moe_intermediate_size"],
        "embedding_final_norm": cfg["vocab_rows_held"] * d + d,
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
    ``sequences`` sequences: QK^T and PV over the visible pairs; reads q and,
    once a group of four query heads, k and v; writes the output and a
    float32 log-sum-exp a row."""
    q, kv = _heads(cfg)
    rows = sequences * cfg["seq_len"]
    flops = sequences * 2 * 2 * q * _pairs(cfg, kind)
    moved = rows * (2 * q + 2 * kv) * _width(cfg) \
        + rows * cfg["num_attention_heads"] * 4
    return flops, moved


def flash_backward(cfg: dict, wl: dict, kind: str, sequences: float):
    """The backward attention kernel(s) of ONE layer: the five products the
    gradient needs over the visible pairs (scores again, dP, dV, dK, dQ).
    Reads q, the output and its gradient and, once a group, k and v; writes
    dq and, summed over a group, dk and dv."""
    q, kv = _heads(cfg)
    rows = sequences * cfg["seq_len"]
    flops = sequences * 5 * 2 * q * _pairs(cfg, kind)
    moved = rows * (4 * q + 4 * kv) * _width(cfg) \
        + 2 * rows * cfg["num_attention_heads"] * 4
    return flops, moved


def _conv_kind(kind: str) -> None:
    if kind != "conv":
        raise ValueError(f"kind {kind!r}: a convolution operator's is 'conv'")


def gated_conv_forward(cfg: dict, wl: dict, kind: str, sequences: float):
    """One execution of the forward gated convolution of ONE layer over
    ``sequences`` sequences: a channel of a row is one gate, ``conv_L_cache``
    multiply-adds and the other gate; reads ``B``, ``C``, ``z`` and writes
    the output, each ``hidden`` wide at the activations' width."""
    _conv_kind(kind)
    rows, d = sequences * cfg["seq_len"], cfg["hidden_size"]
    return (rows * d * (2 + 2 * cfg["conv_L_cache"]),
            rows * 4 * d * _width(cfg))


def gated_conv_backward(cfg: dict, wl: dict, kind: str, sequences: float):
    """The backward gated convolution of ONE layer: ``g`` and ``c`` formed
    again, ``dC``, ``d c``, the taps the other way, ``dB``, ``dz`` and the
    taps' own gradient; reads ``B``, ``C``, ``z`` and the output's gradient,
    writes the three gradients."""
    _conv_kind(kind)
    rows, d = sequences * cfg["seq_len"], cfg["hidden_size"]
    return (rows * d * (5 + 3 * 2 * cfg["conv_L_cache"]),
            rows * 7 * d * _width(cfg))
