"""Operations the training of a sparse-expert language model with mixed
window and full attention needs, as one chip of an expert- and vocabulary-
parallel deployment runs it: one token's, and each kernel's.

Matrix multiplications only, 2 operations per multiply-add. A token's forward
pass over the ``layers`` held: the four attention projections at the heads'
own width (``num_attention_heads`` and ``num_key_value_heads`` of
``head_dim``), QK^T and PV over the pairs a layer's mask leaves visible (a
full causal layer: ``(T + 1) / 2`` keys a query; a windowed one:
``visible_pairs / T``), the router over all experts, the expected share of
the ``moe_num_active_primary_experts`` choices that falls on an expert held
here (``experts_held / moe_num_primary_experts`` under even routing: three
products each), and the head over the rows held, once. The backward pass
costs twice the forward, so a trained token is 3x the forward. **No recompute
is counted**: a recomputed block, the fused head loss's second product and
the flash kernels' re-formed scores lower ``model_flops_util``, they do not
count towards it. Norms, softmax, RoPE, the sort, gathers and the optimizer
count zero: they move bytes.

The flash kernels' functions answer to the one contract every family keeps
(``trace/executions.py``): ``(operations, bytes)`` of ONE execution of the
kernels of one layer of ``kind`` (``window`` or ``full``) over ``sequences``
sequences of the workload's ``seq_len``, both the least the algorithm needs:
a reader multiplies by the executions it finds in the trace, and
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
    layout = cfg["sliding_window_layout"]
    windowed = sum(bool(layout[i % len(layout)])
                   for i in range(cfg["layers"]))
    return {"window": windowed, "full": cfg["layers"] - windowed}


def pairs_by_kind(cfg: dict, seq_len: int) -> dict:
    """Visible pairs of one sequence in ONE layer of each kind."""
    return {"window": visible_pairs(seq_len, cfg["sliding_window_size"]),
            "full": visible_pairs(seq_len)}


def _all_pairs(cfg: dict, seq_len: int) -> float:
    """Visible pairs of one sequence summed over the layers held."""
    kinds, pairs = layer_kinds(cfg), pairs_by_kind(cfg, seq_len)
    return sum(kinds[k] * pairs[k] for k in kinds)


def _heads(cfg: dict):
    """(query width, K/V width): heads times the head's width."""
    return (cfg["num_attention_heads"] * cfg["head_dim"],
            cfg["num_key_value_heads"] * cfg["head_dim"])


def forward_flops_per_token(cfg: dict, seq_len: int) -> dict:
    """One token's forward operations by part."""
    d, layers = cfg["hidden_size"], cfg["layers"]
    q, kv = _heads(cfg)
    held = cfg["experts_held"] / cfg["moe_num_primary_experts"]
    return {
        "attention_projections": layers * 2 * d * (2 * q + 2 * kv),
        "attention_scores": 2 * 2 * q * _all_pairs(cfg, seq_len) / seq_len,
        "router": layers * 2 * d * cfg["moe_num_primary_experts"],
        "experts": layers * cfg["moe_num_active_primary_experts"] * held
        * 3 * 2 * d * cfg["moe_ffn_hidden_size"],
        "head": 2 * d * cfg["vocab_rows_held"],
    }


def train_flops_per_item(cfg: dict, wl: dict, info: dict) -> float:
    return 3.0 * sum(forward_flops_per_token(cfg, int(wl["seq_len"])).values())


def parameters(cfg: dict) -> dict:
    """The parameters this chip holds, by part (no bias anywhere)."""
    d, layers = cfg["hidden_size"], cfg["layers"]
    q, kv = _heads(cfg)
    return {
        "attention": layers * d * (2 * q + 2 * kv),
        "router_and_norms": layers * (d * cfg["moe_num_primary_experts"]
                                      + 2 * d),
        "experts": layers * cfg["experts_held"] * 3 * d
        * cfg["moe_ffn_hidden_size"],
        "embedding_head_final_norm": 2 * cfg["vocab_rows_held"] * d + d,
    }


def _width(cfg: dict) -> int:
    return 2 if cfg["compute_dtype"] == "bfloat16" else 4


def num_experts(cfg: dict) -> int:
    """The experts a layer's router chooses among, whatever a chip holds."""
    return cfg["moe_num_primary_experts"]


def flash_forward(cfg: dict, wl: dict, kind: str, sequences: float):
    """One execution of the forward attention kernel of ONE layer of ``kind``
    over ``sequences`` sequences: QK^T and PV over the layer's visible pairs;
    reads q and, once a group, k and v; writes the output and a float32
    log-sum-exp a row."""
    q, kv = _heads(cfg)
    seq_len = int(wl["seq_len"])
    rows = sequences * seq_len
    flops = sequences * 2 * 2 * q * pairs_by_kind(cfg, seq_len)[kind]
    moved = rows * (2 * q + 2 * kv) * _width(cfg) \
        + rows * cfg["num_attention_heads"] * 4
    return flops, moved


def flash_backward(cfg: dict, wl: dict, kind: str, sequences: float):
    """The backward attention kernels (dK/dV and dQ together) of ONE layer of
    ``kind``: the five products the gradient needs over the visible pairs
    (scores again, dP, dV, dK, dQ). The program's two kernels form the scores
    and dP twice (seven products): the two extra are recompute, not counted.
    Reads q, the output and its gradient and, once a group, k and v; writes
    dq and, summed over a group, dk and dv."""
    q, kv = _heads(cfg)
    seq_len = int(wl["seq_len"])
    rows = sequences * seq_len
    flops = sequences * 5 * 2 * q * pairs_by_kind(cfg, seq_len)[kind]
    moved = rows * (4 * q + 4 * kv) * _width(cfg) \
        + 2 * rows * cfg["num_attention_heads"] * 4
    return flops, moved
