"""Operations the training of a latent-attention sparse-expert language model
(the ``deepseek_v3`` family: kanana-2-30b-a3b) needs, as one chip of an
expert- and vocabulary-parallel deployment runs it: one token's, and each
attention kernel's.

Matrix multiplications only, 2 operations per multiply-add. A token's forward
pass over the layers held (the first ``dense_layers`` of them keep a dense
SwiGLU): latent attention's projections (the queries at ``heads x (nope +
rope)``, or their two low-rank halves where ``q_lora_rank`` is set; the down
projection to the K/V latent and the one rotary key; the up projection to
``heads x (nope + v)``; the output projection from ``heads x v``), QK^T at
the keys' width ``nope + rope`` and PV at the values' width ``v`` over the
``(T + 1) / 2`` keys a causal query sees (every layer is full attention), the
dense layers' feed-forward of width ``intermediate_size``, and in each expert
layer the router over all experts, the shared experts (one MLP of
``n_shared_experts`` expert widths that every token takes, whole on every
chip) and the expected share of the ``num_experts_per_tok`` choices that
falls on an expert held here (``experts_held / n_routed_experts`` under even
routing, which is what the balancing bias steers to: three products each);
the head over the rows held, once. The backward pass costs twice the
forward, so a trained token is 3x the forward. **No recompute is counted**
towards a token's operations: a recomputed block, the fused head loss's
second product and the flash kernels' re-formed scores lower
``model_flops_util``, they do not count towards it. Norms, sigmoids, RoPE,
the broadcast of the rotary key, the sort, gathers, the bias update and the
optimizer count zero: they move bytes.

The sequence length is the configuration's ``seq_len`` (the cell's workload
has to repeat it); ``max_position_embeddings`` is the published 32,768 and
sizes nothing.

The flash kernels' functions answer to the one contract every family keeps
(``trace/executions.py``): ``(operations, bytes)`` of ONE execution of the
kernels of one layer of ``kind`` over ``sequences`` sequences, both the least
the algorithm needs, at the TWO widths this family's kernels run: a key and a
query are ``nope + rope`` wide, a value, an output and an output's gradient
``v`` wide. Bytes are each operand read once and each result written once at
the activations' width; no heads group here, so K and V are read once a head
(the one rotary key all heads share is counted as the program lays it out:
once a head, inside each head's key).
"""

from __future__ import annotations


def visible_pairs(seq_len: int) -> float:
    """(query, key) pairs a causal mask leaves visible in one sequence."""
    return seq_len * (seq_len + 1) / 2


def _widths(cfg: dict):
    """(a key's and a query's width, a value's) of one head."""
    return (cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"],
            cfg["v_head_dim"])


def attention_projection_weights(cfg: dict) -> int:
    """The matrix entries of one layer's attention (its norms' weights are
    no matrix)."""
    d, heads = cfg["hidden_size"], cfg["num_attention_heads"]
    d_qk, d_v = _widths(cfg)
    rank = cfg["q_lora_rank"]
    q = d * heads * d_qk if rank is None else rank * (d + heads * d_qk)
    return (q + d * (cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"])
            + cfg["kv_lora_rank"] * heads * (cfg["qk_nope_head_dim"] + d_v)
            + heads * d_v * d)


def forward_flops_per_token(cfg: dict) -> dict:
    """One token's forward operations by part."""
    d, layers = cfg["hidden_size"], cfg["layers"]
    dense, sparse = cfg["dense_layers"], layers - cfg["dense_layers"]
    d_qk, d_v = _widths(cfg)
    keys = visible_pairs(cfg["seq_len"]) / cfg["seq_len"]   # a query sees
    expert = 3 * 2 * d * cfg["moe_intermediate_size"]
    held = cfg["experts_held"] / cfg["n_routed_experts"]
    return {
        "attention_projections": layers * 2 * attention_projection_weights(
            cfg),
        # QK^T at the keys' width, PV at the values'
        "attention_scores": layers * 2 * cfg["num_attention_heads"] * (
            d_qk + d_v) * keys,
        "dense_ffn": dense * 3 * 2 * d * cfg["intermediate_size"],
        "router": sparse * 2 * d * cfg["n_routed_experts"],
        "shared_experts": sparse * cfg["n_shared_experts"] * expert,
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
    expert = 3 * d * cfg["moe_intermediate_size"]
    latent_norms = cfg["kv_lora_rank"] + (cfg["q_lora_rank"] or 0)
    return {
        "attention": layers * (attention_projection_weights(cfg)
                               + latent_norms),
        "norms": layers * 2 * d,
        "dense_ffn": dense * 3 * d * cfg["intermediate_size"],
        "router": sparse * d * cfg["n_routed_experts"],
        "shared_experts": sparse * cfg["n_shared_experts"] * expert,
        "experts": sparse * cfg["experts_held"] * expert,
        "embedding_head_final_norm": 2 * cfg["vocab_rows_held"] * d + d,
    }


def _width(cfg: dict) -> int:
    return 2 if cfg["compute_dtype"] == "bfloat16" else 4


def num_experts(cfg: dict) -> int:
    """The experts a layer's router chooses among, whatever a chip holds."""
    return cfg["n_routed_experts"]


def _pairs(cfg: dict, kind: str) -> float:
    if kind != "full":
        raise ValueError(f"kind {kind!r}: every layer of this family is "
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
    again, dK and dQ at the keys' width, dP and dV at the values'. The
    program's two kernels form the scores and dP twice (seven products): the
    two extra are recompute, not counted. Reads q and k (keys' width), v, the
    output and its gradient (values' width) and two float32 rows; writes dq
    and dk at the keys' width and dv at the values'."""
    heads = cfg["num_attention_heads"]
    d_qk, d_v = _widths(cfg)
    rows = sequences * cfg["seq_len"]
    flops = sequences * 2 * heads * (3 * d_qk + 2 * d_v) * _pairs(cfg, kind)
    moved = rows * heads * (4 * d_qk + 4 * d_v) * _width(cfg) \
        + 2 * rows * heads * 4
    return flops, moved
