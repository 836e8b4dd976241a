"""Operations the training of an ``afmoe`` language model (Trinity) needs, as
one chip of an expert- and vocabulary-parallel deployment runs it: one
token's, and each attention kernel's.

Matrix multiplications only, 2 operations per multiply-add. A token's forward
pass over the layers held (``layers_held``: published layer indices; the
first ``dense_layers`` of them keep a dense SwiGLU): the five attention
projections at the heads' own width (q, k, v, o and the output gate), QK^T
and PV over the pairs a layer's mask leaves visible (a full causal layer:
``(T + 1) / 2`` keys a query; a windowed one: ``visible_pairs / T``), the
dense layers' feed-forward of width ``intermediate_size``, and in each expert
layer the router over all experts, the shared expert (every token takes it,
whole on every chip) and the expected share of the ``num_experts_per_tok``
choices that falls on an expert held here (``experts_held / num_experts``
under even routing, which is what the balancing bias steers to: three
products each); the head over the rows held, once. The backward pass costs
twice the forward, so a trained token is 3x the forward. **No recompute is
counted** towards a token's operations: a recomputed block, the fused head
loss's second product and the flash kernels' re-formed scores lower
``model_flops_util``, they do not count towards it. Norms, sigmoids, RoPE,
the sort, gathers, the bias update and the optimizer count zero: they move
bytes.

The sequence length is the configuration's ``seq_len`` (the cell's workload
has to repeat it: ``pipelines/trinity-mini.py``); ``max_position_embeddings``
is the published 131,072 and sizes nothing.

The flash kernels' functions answer to the one contract every family keeps
(``trace/executions.py``): ``(operations, bytes)`` of ONE execution of the
kernels of one layer of ``kind`` over ``sequences`` sequences, both the least
the algorithm needs: a reader multiplies by the executions it finds in the
trace (a block that ran its forward kernel a second time would have that
execution's operations counted with its seconds), and
``trace/roofline.share`` divides by the peaks. Bytes are each operand read
once and each result written once at the activations' width; a K/V head is
read once a group of query heads, not once a query head.
"""

from __future__ import annotations


def visible_pairs(seq_len: int, window=None) -> float:
    """(query, key) pairs a causal mask leaves visible in one sequence: all
    ``j <= i``, or with a window those with ``i - j < window``."""
    if not window or window >= seq_len:
        return seq_len * (seq_len + 1) / 2
    return window * (window + 1) / 2 + (seq_len - window) * window


def layer_kinds(cfg: dict) -> dict:
    """How many of the layers held are windowed, and how many full."""
    windowed = sum(cfg["layer_types"][i] == "sliding_attention"
                   for i in cfg["layers_held"])
    return {"window": windowed, "full": len(cfg["layers_held"]) - windowed}


def pairs_by_kind(cfg: dict) -> dict:
    """Visible pairs of one sequence in ONE layer of each kind."""
    return {"window": visible_pairs(cfg["seq_len"], cfg["sliding_window"]),
            "full": visible_pairs(cfg["seq_len"])}


def _heads(cfg: dict):
    """(query width, K/V width): heads times the head's width."""
    return (cfg["num_attention_heads"] * cfg["head_dim"],
            cfg["num_key_value_heads"] * cfg["head_dim"])


def forward_flops_per_token(cfg: dict) -> dict:
    """One token's forward operations by part."""
    d, layers, seq_len = cfg["hidden_size"], cfg["layers"], cfg["seq_len"]
    dense, sparse = cfg["dense_layers"], layers - cfg["dense_layers"]
    q, kv = _heads(cfg)
    kinds, pairs = layer_kinds(cfg), pairs_by_kind(cfg)
    expert = 3 * 2 * d * cfg["moe_intermediate_size"]
    held = cfg["experts_held"] / cfg["num_experts"]
    return {
        # q, o and the gate at the query width, k and v at the K/V width
        "attention_projections": layers * 2 * d * (3 * q + 2 * kv),
        "attention_scores": 2 * 2 * q * sum(
            kinds[k] * pairs[k] for k in kinds) / seq_len,
        "dense_ffn": dense * 3 * 2 * d * cfg["intermediate_size"],
        "router": sparse * 2 * d * cfg["num_experts"],
        "shared_expert": sparse * cfg["num_shared_experts"] * expert,
        "experts": sparse * cfg["num_experts_per_tok"] * held * expert,
        "head": 2 * d * cfg["vocab_rows_held"],
    }


def train_flops_per_item(cfg: dict, wl: dict, info: dict) -> float:
    return 3.0 * sum(forward_flops_per_token(cfg).values())


def parameters(cfg: dict) -> dict:
    """The parameters this chip holds, by part (no bias anywhere; the
    routing bias and its counts are state, not parameters)."""
    d, layers = cfg["hidden_size"], cfg["layers"]
    dense, sparse = cfg["dense_layers"], layers - cfg["dense_layers"]
    q, kv = _heads(cfg)
    expert = 3 * d * cfg["moe_intermediate_size"]
    return {
        "attention": layers * (d * (3 * q + 2 * kv) + 2 * cfg["head_dim"]),
        "norms": layers * 4 * d,
        "dense_ffn": dense * 3 * d * cfg["intermediate_size"],
        "router": sparse * d * cfg["num_experts"],
        "shared_expert": sparse * cfg["num_shared_experts"] * expert,
        "experts": sparse * cfg["experts_held"] * expert,
        "embedding_head_final_norm": 2 * cfg["vocab_rows_held"] * d + d,
    }


def _width(cfg: dict) -> int:
    return 2 if cfg["compute_dtype"] == "bfloat16" else 4


def num_experts(cfg: dict) -> int:
    """The experts a layer's router chooses among, whatever a chip holds."""
    return cfg["num_experts"]


def flash_forward(cfg: dict, wl: dict, kind: str, sequences: float):
    """One execution of the forward attention kernel of ONE layer of
    ``kind`` (``window`` or ``full``) over ``sequences`` sequences: QK^T and
    PV over the layer's visible pairs; reads q and, once a group, k and v;
    writes the output and a float32 log-sum-exp a row."""
    q, kv = _heads(cfg)
    rows = sequences * cfg["seq_len"]
    flops = sequences * 2 * 2 * q * pairs_by_kind(cfg)[kind]
    moved = rows * (2 * q + 2 * kv) * _width(cfg) \
        + rows * cfg["num_attention_heads"] * 4
    return flops, moved


def flash_backward(cfg: dict, wl: dict, kind: str, sequences: float):
    """The backward attention kernels (dK/dV and dQ together) of ONE layer
    of ``kind``: the five products the gradient needs over the visible pairs
    (scores again, dP, dV, dK, dQ). The program's two kernels form the scores
    and dP twice (seven products): the two extra are recompute, not counted.
    Reads q, the output and its gradient and, once a group, k and v; writes
    dq and, summed over a group, dk and dv."""
    q, kv = _heads(cfg)
    rows = sequences * cfg["seq_len"]
    flops = sequences * 5 * 2 * q * pairs_by_kind(cfg)[kind]
    moved = rows * (4 * q + 4 * kv) * _width(cfg) \
        + 2 * rows * cfg["num_attention_heads"] * 4
    return flops, moved
