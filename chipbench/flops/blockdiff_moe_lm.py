"""Operations the block-diffusion training of a sparse-expert language model
(``sdar_moe``) needs, as one chip of an expert- and vocabulary-parallel
deployment runs it: one TRAINED token's, and each attention kernel's.

An item is a trained token: a row of ``L = seq_len`` tokens is ``2 L``
positions in the model, the row and its noised copy, so a trained token is
**two positions** through every layer (projections, router, the expected
share of the ``num_experts_per_tok`` choices that falls on an expert held
here: three products each) and **one** through the head (the loss reads the
noised half alone). Attention: with blocks of ``Bd = block_length`` a clean
query in block ``b`` sees ``(b + 1) Bd`` clean keys, a noised one ``b Bd``
clean keys and the ``Bd`` noised keys of its own block, so a row has
``L (L + Bd) / 2 + L (L - Bd) / 2 + L Bd = L^2 + L Bd`` visible pairs a head
a layer (:func:`visible_pairs`), ``L + Bd`` a trained token. Matrix
multiplications only, 2 operations per multiply-add. The backward pass costs
twice the forward, so a trained token is 3x the forward. **No recompute is
counted**: a recomputed block, the fused head loss's second product and the
flash kernels' re-formed scores lower ``model_flops_util``, they do not count
towards it. Norms, softmax, RoPE, the noise, the sort, gathers and the
optimizer count zero: they move bytes.

The kernels' functions answer to the one contract every family keeps
(``trace/executions.py``): ``(operations, bytes)`` of ONE execution of the
kernels of one layer over ``sequences`` rows (``traced_items / seq_len``:
rows of ``L`` trained tokens, each ``2 L`` positions in the kernel), both the
least the algorithm needs. Bytes are each operand read once and each result
written once at the activations' width; a K/V head is read (and its gradient
written) once a group of query heads, not once a query head.
"""

from __future__ import annotations


def visible_pairs(seq_len: int, block: int) -> float:
    """(query, key) pairs the block-diffusion mask leaves visible in one row
    of ``seq_len`` tokens (``2 * seq_len`` positions): clean on clean
    ``L (L + Bd) / 2``, noised on clean ``L (L - Bd) / 2``, noised on its own
    block ``L Bd``."""
    return float(seq_len) * (seq_len + block)


def _heads(cfg: dict):
    """(query width, K/V width): heads times the head's width."""
    return (cfg["num_attention_heads"] * cfg["head_dim"],
            cfg["num_key_value_heads"] * cfg["head_dim"])


def _pairs(cfg: dict) -> float:
    return visible_pairs(cfg["seq_len"], cfg["diffusion"]["block_length"])


def forward_flops_per_token(cfg: dict) -> dict:
    """One trained token's forward operations by part."""
    d, layers = cfg["hidden_size"], cfg["layers"]
    q, kv = _heads(cfg)
    expert = 3 * 2 * d * cfg["moe_intermediate_size"]
    held = cfg["experts_held"] / cfg["num_experts"]
    positions = 2       # the token and its noised copy
    return {
        # q and o at the query width, k and v at the K/V width
        "attention_projections": positions * layers * 2 * d * (2 * q + 2 * kv),
        "attention_scores": layers * 2 * 2 * q * _pairs(cfg) / cfg["seq_len"],
        "router": positions * layers * 2 * d * cfg["num_experts"],
        "experts": positions * layers * cfg["num_experts_per_tok"] * held
        * expert,
        "head": 2 * d * cfg["vocab_rows_held"],
    }


def train_flops_per_item(cfg: dict, wl: dict, info: dict) -> float:
    return 3.0 * sum(forward_flops_per_token(cfg).values())


def parameters(cfg: dict) -> dict:
    """The parameters this chip holds, by part (no bias anywhere)."""
    d, layers = cfg["hidden_size"], cfg["layers"]
    q, kv = _heads(cfg)
    return {
        "attention": layers * (d * (2 * q + 2 * kv) + 2 * cfg["head_dim"]),
        "norms": layers * 2 * d,
        "router": layers * d * cfg["num_experts"],
        "experts": layers * cfg["experts_held"] * 3 * d
        * cfg["moe_intermediate_size"],
        "embedding_head_final_norm": 2 * cfg["vocab_rows_held"] * d + d,
    }


def _width(cfg: dict) -> int:
    return 2 if cfg["compute_dtype"] == "bfloat16" else 4


def num_experts(cfg: dict) -> int:
    """The experts a layer's router chooses among, whatever a chip holds."""
    return cfg["num_experts"]


def bd_flash_forward(cfg: dict, wl: dict, kind: str, sequences: float):
    """One execution of the forward attention kernel of ONE layer under the
    block-diffusion mask over ``sequences`` rows: QK^T and PV over the
    visible pairs; reads q and, once a group, k and v at all ``2 L``
    positions; writes the output and a float32 log-sum-exp a row."""
    q, kv = _heads(cfg)
    positions = sequences * 2 * cfg["seq_len"]
    flops = sequences * 2 * 2 * q * _pairs(cfg)
    moved = positions * (2 * q + 2 * kv) * _width(cfg) \
        + positions * cfg["num_attention_heads"] * 4
    return flops, moved


def bd_flash_backward(cfg: dict, wl: dict, kind: str, sequences: float):
    """The backward attention kernel(s) of ONE layer under the
    block-diffusion mask: the five products the gradient needs over the
    visible pairs (scores again, dP, dV, dK, dQ; where the program takes two
    kernels they form the scores and dP twice, which is recompute and not
    counted). Reads q, the output's gradient and, once a group, k and v;
    writes dq and, summed over a group, dk and dv; reads two float32 a row
    (log-sum-exp and delta)."""
    q, kv = _heads(cfg)
    positions = sequences * 2 * cfg["seq_len"]
    flops = sequences * 5 * 2 * q * _pairs(cfg)
    moved = positions * (3 * q + 4 * kv) * _width(cfg) \
        + 2 * positions * cfg["num_attention_heads"] * 4
    return flops, moved
