"""Operations a sparse-expert language model's training needs, from the
configuration's shapes: one token's, and each kernel's.

Matrix multiplications only, 2 operations per multiply-add. A token's forward
pass: the four attention projections, the causal half of the scores and of
the weighted sum (a query attends to itself and what precedes it), the
router, ``num_experts_per_tok`` of the ``num_experts`` experts (three
products each), and the head once. The backward pass costs twice the forward,
so a trained token is 3x the forward. **No recompute is counted**: the fused
head loss computes the head's product a second time in the backward pass and
the flash kernels re-form the scores; that work lowers ``model_flops_util``,
it does not count towards it. Norms, softmax, RoPE, the sort, gathers and the
optimizer count zero: they move bytes.

The flash kernels' functions answer to the one contract every family keeps
(``trace/executions.py``): ``(operations, bytes)`` of ONE execution of the
kernels of one layer of ``kind`` over ``sequences`` sequences of the
workload's ``seq_len`` (every layer here is ``full``: causal, no window);
``expert_gemms`` returns them for one optimizer step of one chip. Both are
the least the algorithm needs: what ``trace/roofline.share`` divides by the
peaks. Bytes are each operand read once and each result written once at the
activations' width.
"""

from __future__ import annotations


def _shape(cfg: dict):
    d = cfg["hidden_size"]
    return (d, cfg["num_attention_heads"], cfg["intermediate_size"],
            cfg["num_experts"], cfg["num_experts_per_tok"], cfg["vocab_size"],
            cfg["layers"])


def forward_flops_per_token(cfg: dict, seq_len: int) -> dict:
    """One token's forward operations by part (a sequence's mean token sees
    ``(seq_len + 1) / 2`` keys)."""
    d, _, f, e, k, vocab, layers = _shape(cfg)
    return {
        "attention_projections": layers * 4 * 2 * d * d,
        "attention_scores": layers * 2 * 2 * d * (seq_len + 1) / 2,
        "router": layers * 2 * d * e,
        "experts": layers * k * 3 * 2 * d * f,
        "head": 2 * d * vocab,
    }


def train_flops_per_item(cfg: dict, wl: dict, info: dict) -> float:
    return 3.0 * sum(forward_flops_per_token(cfg, int(wl["seq_len"])).values())


def _width(cfg: dict) -> int:
    return 2 if cfg["compute_dtype"] == "bfloat16" else 4


def num_experts(cfg: dict) -> int:
    """The experts a layer's router chooses among."""
    return cfg["num_experts"]


def _pairs(wl: dict, kind: str) -> float:
    """Visible (query, key) pairs of one sequence in one layer."""
    if kind != "full":
        raise ValueError(f"a moe_lm layer is full causal attention, not {kind!r}")
    seq_len = int(wl["seq_len"])
    return seq_len * (seq_len + 1) / 2


def flash_forward(cfg: dict, wl: dict, kind: str, sequences: float):
    """One execution of the forward attention kernel of ONE layer over
    ``sequences`` sequences: QK^T and PV over the causal pairs; reads q, k,
    v, writes the output and a float32 log-sum-exp a row."""
    d, heads, *_ = _shape(cfg)
    rows = sequences * int(wl["seq_len"])
    flops = sequences * 2 * 2 * d * _pairs(wl, kind)
    return flops, 4 * rows * d * _width(cfg) + rows * heads * 4


def flash_backward(cfg: dict, wl: dict, kind: str, sequences: float):
    """The backward attention kernels (dK/dV and dQ together) of ONE layer:
    the five products the gradient needs over the causal pairs (scores again,
    dP, dV, dK, dQ). The program's two kernels form the scores and dP twice
    (seven products): the two extra are recompute, not counted. Reads q, k,
    v, the output and its gradient, writes three gradients."""
    d, heads, *_ = _shape(cfg)
    rows = sequences * int(wl["seq_len"])
    flops = sequences * 5 * 2 * d * _pairs(wl, kind)
    return flops, 8 * rows * d * _width(cfg) + 2 * rows * heads * 4


def expert_gemms(cfg: dict, tokens: int):
    """The grouped expert products of one step, forward and backward: three
    forward, and for each its input's and its weight's gradient: nine
    products of ``top_k * tokens`` rows, dropless. Each reads its two
    operands and writes its result once."""
    d, _, f, e, k, _, layers = _shape(cfg)
    slots = k * tokens
    rows_in, rows_mid, weights = slots * d, slots * f, e * d * f
    flops = layers * 9 * 2 * slots * d * f
    forward = 2 * (rows_in + weights + rows_mid) + (rows_mid + weights
                                                    + rows_in)
    # a product's two gradients read the same three arrays in other roles
    moved = layers * 3 * forward * _width(cfg)
    return flops, moved
