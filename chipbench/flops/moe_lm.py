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

The kernels' functions return ``(operations, bytes)`` for one optimizer step
of one chip, both the least the algorithm needs: what
``trace/roofline.share`` divides by the peaks. Bytes are each operand read
once and each result written once at the activations' width.
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


def flash_forward(cfg: dict, sequences: int, seq_len: int):
    """The forward attention kernel over ``sequences`` sequences in every
    layer: QK^T and PV over the causal pairs; reads q, k, v, writes the
    output and a float32 log-sum-exp a row."""
    d, heads, *_, layers = _shape(cfg)
    pairs = seq_len * (seq_len + 1) / 2
    rows = layers * sequences * seq_len
    flops = layers * sequences * 2 * 2 * d * pairs
    return flops, 4 * rows * d * _width(cfg) + rows * heads * 4


def flash_backward(cfg: dict, sequences: int, seq_len: int):
    """The backward attention kernels: the five products the gradient needs
    over the causal pairs (scores again, dP, dV, dK, dQ). The program's two
    kernels form the scores and dP twice (seven products): the two extra are
    recompute, not counted. Reads q, k, v, the output and its gradient,
    writes three gradients."""
    d, heads, *_, layers = _shape(cfg)
    pairs = seq_len * (seq_len + 1) / 2
    rows = layers * sequences * seq_len
    flops = layers * sequences * 5 * 2 * d * pairs
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
