"""Operations the training of a looped language model (the ``ouro`` family)
needs, as one pipeline stage of ``layers`` dense layers runs it: one token's,
and each attention kernel instruction's.

Matrix multiplications only, 2 operations per multiply-add. A token's forward
pass is ``total_ut_steps`` passes through the layers held and as many heads
(the training objective weighs EVERY pass's cross entropy, so the head's
product runs once a pass): a layer is the four attention projections at the
heads' own width, QK^T and PV over the pairs a full causal mask leaves
visible (``(T + 1) / 2`` keys a query) and a SwiGLU of width
``intermediate_size``; the exit gate's ``hidden_size`` multiply-adds a pass
count too. The backward pass costs twice the forward, so a trained token is
3x the forward. **No recompute is counted** towards a token's operations: a
recomputed block, the fused head loss's second product and the flash
kernels' re-formed scores lower ``model_flops_util``, they do not count
towards it. Norms, sigmoids, RoPE, the exit distribution, the entropy and the
optimizer count zero: they move bytes.

The sequence length is the configuration's ``seq_len`` (the cell's workload
has to repeat it: ``pipelines/ouro-2.6b.py``); ``max_position_embeddings`` is
the published 65,536 and sizes nothing.

The flash kernels' functions answer to the contract every family keeps
(``trace/executions.py``) with one difference that this family's program
forces, and that is said here and not in the shared readers: a reader counts
a kernel's executions as the INSTRUCTION NAMES the trace holds, "each runs
once over every sequence". This family's passes are a loop in the program
(one ``lax.scan`` over ``total_ut_steps``: ``raydp_tpu/models/transformer.py``,
``_looped``), so the program holds one forward and one backward kernel
instruction a layer, inside the loop's body and its transpose's, and each
runs ``total_ut_steps`` times a sequence under its one name
(``trace/reduce.py`` keeps seconds a name, no event counts). So
``flash_forward`` / ``flash_backward`` answer for ONE INSTRUCTION of this
family's program: ``(operations, bytes)`` of the kernels of one layer over
``sequences`` sequences, times ``total_ut_steps`` executions a sequence. A
contract test holds the factor to the lowered step itself
(``tests/chipbench_contract/test_chipbench_ouro.py``: one forward flash
custom call a layer, inside a loop of ``total_ut_steps`` trips): a program
that unrolled its passes would show ``layers * total_ut_steps`` of them and
fail it, before a share read four times too high. Bytes are each operand read
once and each result written once an execution at the activations' width.
"""

from __future__ import annotations


def visible_pairs(seq_len: int) -> float:
    """(query, key) pairs a full causal mask leaves visible in one sequence:
    all ``j <= i``."""
    return seq_len * (seq_len + 1) / 2


def _heads(cfg: dict):
    """(query width, K/V width): heads times the head's width."""
    return (cfg["num_attention_heads"] * cfg["head_dim"],
            cfg["num_key_value_heads"] * cfg["head_dim"])


def forward_flops_per_token(cfg: dict) -> dict:
    """One token's forward operations by part, all passes together."""
    d, passes = cfg["hidden_size"], cfg["total_ut_steps"]
    executions = passes * cfg["layers"]         # layer executions a token
    q, kv = _heads(cfg)
    return {
        # q and o at the query width, k and v at the K/V width
        "attention_projections": executions * 2 * d * (2 * q + 2 * kv),
        "attention_scores": executions * 2 * 2 * q * visible_pairs(
            cfg["seq_len"]) / cfg["seq_len"],
        "dense_ffn": executions * 3 * 2 * d * cfg["intermediate_size"],
        "exit_gate": passes * 2 * d,
        "head": passes * 2 * d * cfg["vocab_size"],
    }


def train_flops_per_item(cfg: dict, wl: dict, info: dict) -> float:
    """An item is a token of the row, counted once however many passes it
    takes."""
    return 3.0 * sum(forward_flops_per_token(cfg).values())


def parameters(cfg: dict) -> dict:
    """The parameters this chip holds, by part (no bias but the gate's; the
    passes share every one of them)."""
    d, layers = cfg["hidden_size"], cfg["layers"]
    q, kv = _heads(cfg)
    return {
        "attention": layers * d * (2 * q + 2 * kv),
        "norms": layers * 4 * d,
        "dense_ffn": layers * 3 * d * cfg["intermediate_size"],
        "embedding_head_final_norm": 2 * cfg["vocab_size"] * d + d,
        "exit_gate": d + 1,
    }


def _width(cfg: dict) -> int:
    return 2 if cfg["compute_dtype"] == "bfloat16" else 4


def num_experts(cfg: dict) -> int:
    """The experts a layer's router chooses among: none, the family is dense
    (every family with the flash kernels answers; no reader of a dense cell
    asks)."""
    return 0


def flash_forward(cfg: dict, wl: dict, kind: str, sequences: float):
    """ONE INSTRUCTION of the program's forward attention kernel (a layer's,
    ``kind`` is ``full``: the family has no window) over ``sequences``
    sequences: ``total_ut_steps`` executions a sequence (the instruction lies
    in the loop over the passes), each QK^T and PV over the visible pairs,
    reading q, k and v and writing the output and a float32 log-sum-exp a
    row."""
    q, kv = _heads(cfg)
    runs = sequences * cfg["total_ut_steps"]
    rows = runs * cfg["seq_len"]
    flops = runs * 2 * 2 * q * visible_pairs(cfg["seq_len"])
    moved = rows * (2 * q + 2 * kv) * _width(cfg) \
        + rows * cfg["num_attention_heads"] * 4
    return flops, moved


def flash_backward(cfg: dict, wl: dict, kind: str, sequences: float):
    """ONE INSTRUCTION of the backward attention kernel of a layer (in the
    transposed loop: ``total_ut_steps`` executions a sequence): the five
    products the gradient needs over the visible pairs (scores again, dP, dV,
    dK, dQ). Reads q, k, v, the output and its gradient; writes dq, dk and
    dv."""
    q, kv = _heads(cfg)
    runs = sequences * cfg["total_ut_steps"]
    rows = runs * cfg["seq_len"]
    flops = runs * 5 * 2 * q * visible_pairs(cfg["seq_len"])
    moved = rows * (4 * q + 4 * kv) * _width(cfg) \
        + 2 * rows * cfg["num_attention_heads"] * 4
    return flops, moved
