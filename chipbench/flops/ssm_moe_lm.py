"""Operations the training of a state-space / attention / sparse-expert
hybrid language model (the ``nemotron_h`` family: nemotron-3-nano-30b-a3b)
needs, as one chip of an expert- and vocabulary-parallel deployment runs it:
one token's, each attention kernel's and each scan kernel's.

Matrix multiplications only, 2 operations per multiply-add. Every layer held
is ONE sub-layer, by its letter in ``layer_pattern_held``:

- ``M``, a state-space mixer: the input projection to the gate, ``xBC`` and
  ``dt`` (``2 inner + 2 G N + H`` outputs) and the output projection from
  ``inner``; and the scan in chunks of ``Q``: inside a chunk ``C B^T`` once a
  group and the decayed product with ``x`` once a head over the ``(Q + 1) /
  2`` positions a position sees (as the flash counts count visible pairs),
  plus a token's two products with its head's state (read ``C . S``, write
  ``B (x) x``: ``N x P`` each).
- ``*``, full causal attention: q and o at ``heads x head_dim``, k and v at
  the (fewer) K/V heads' width; QK^T and PV over the ``(T + 1) / 2`` keys a
  causal query sees.
- ``E``, experts alone: the router over all experts, the shared expert (TWO
  products of its width, every token, whole on every chip) and the expected
  share of the ``num_experts_per_tok`` choices that falls on an expert held
  here (``experts_held / n_routed_experts`` under even routing, which is what
  the balancing bias steers to: two products each, the experts are not gated).

The head over the rows held, once. The backward pass costs twice the forward,
so a trained token is 3x the forward. **No recompute is counted** towards a
token's operations: a recomputed layer (its second forward scan among it),
the fused head loss's second product and the kernels' re-formed scores lower
``model_flops_util``, they do not count towards it. The convolution (4
multiply-adds a channel), norms, SiLU, softplus, the decays' exponentials,
the sort, gathers, the bias update and the optimizer count zero: they move
bytes.

The kernels' functions answer to the one contract every family keeps
(``trace/executions.py``): ``(operations, bytes)`` of ONE execution of the
kernels of one layer of ``kind`` over ``sequences`` sequences, both the least
the algorithm needs. Flash (kind ``full``): K and V are read once a group of
16 query heads. Scan (kind ``scan``): ``x``, ``B``, ``C`` and ``dt`` read and
``y`` written once; the backward reads those and ``dy`` and writes ``dx``,
``dB``, ``dC`` and ``d dt``. The float32 states a chunk starts from, which
the program's forward writes and its backward reads (``N x P x 4 / Q`` bytes
a head and token), are NOT counted: an algorithm could re-form them, so
their traffic lowers the share, it does not count towards it.
"""

from __future__ import annotations


def visible_pairs(seq_len: int) -> float:
    """(query, key) pairs a causal mask leaves visible in one sequence."""
    return seq_len * (seq_len + 1) / 2


def layers_of(cfg: dict) -> dict:
    """How many of the layers held are of each letter."""
    pattern = cfg["layer_pattern_held"]
    if len(pattern) != cfg["layers"] or set(pattern) - set("M*E"):
        raise ValueError(f"layer_pattern_held {pattern!r}: {cfg['layers']} "
                         f"letters of 'M', '*', 'E'")
    return {letter: pattern.count(letter) for letter in "M*E"}


def _ssm(cfg: dict):
    """(heads, a head's width, groups, state, chunk, inner, xBC's width)."""
    h, p = cfg["mamba_num_heads"], cfg["mamba_head_dim"]
    g, n = cfg["n_groups"], cfg["ssm_state_size"]
    return h, p, g, n, cfg["chunk_size"], h * p, h * p + 2 * g * n


def _heads(cfg: dict):
    """(query width, K/V width): heads times the head's width."""
    return (cfg["num_attention_heads"] * cfg["head_dim"],
            cfg["num_key_value_heads"] * cfg["head_dim"])


def ssm_projection_weights(cfg: dict) -> int:
    h, _, _, _, _, inner, xbc = _ssm(cfg)
    return cfg["hidden_size"] * (inner + xbc + h) + inner * cfg["hidden_size"]


def scan_flops_per_token(cfg: dict) -> float:
    """One layer's scan, forward, a token: the intra-chunk products over the
    positions a position sees and the two products with the state."""
    h, p, g, n, q, _, _ = _ssm(cfg)
    return 2 * (q + 1) / 2 * (g * n + h * p) + 2 * 2 * h * n * p


def forward_flops_per_token(cfg: dict) -> dict:
    """One token's forward operations by part."""
    d, count = cfg["hidden_size"], layers_of(cfg)
    q, kv = _heads(cfg)
    keys = visible_pairs(cfg["seq_len"]) / cfg["seq_len"]   # a query sees
    expert = 2 * 2 * d * cfg["moe_intermediate_size"]
    shared = 2 * 2 * d * cfg["moe_shared_expert_intermediate_size"]
    held = cfg["experts_held"] / cfg["n_routed_experts"]
    return {
        "ssm_projections": count["M"] * 2 * ssm_projection_weights(cfg),
        "ssm_scan": count["M"] * scan_flops_per_token(cfg),
        "attention_projections": count["*"] * 2 * d * (2 * q + 2 * kv),
        "attention_scores": count["*"] * 2 * 2 * q * keys,
        "router": count["E"] * 2 * d * cfg["n_routed_experts"],
        "shared_expert": count["E"] * cfg["n_shared_experts"] * shared,
        "experts": count["E"] * cfg["num_experts_per_tok"] * held * expert,
        "head": 2 * d * cfg["vocab_rows_held"],
    }


def train_flops_per_item(cfg: dict, wl: dict, info: dict) -> float:
    return 3.0 * sum(forward_flops_per_token(cfg).values())


def parameters(cfg: dict) -> dict:
    """The parameters this chip holds, by part (no bias but the
    convolution's; the routing bias and its counts are state, not
    parameters)."""
    d, count = cfg["hidden_size"], layers_of(cfg)
    h, _, _, _, _, inner, xbc = _ssm(cfg)
    q, kv = _heads(cfg)
    return {
        # projections, the convolution's taps and bias, dt_bias, A_log and D,
        # the gated norm's weight
        "ssm": count["M"] * (ssm_projection_weights(cfg)
                             + (cfg["conv_kernel"] + 1) * xbc + 3 * h + inner),
        "attention": count["*"] * d * (2 * q + 2 * kv),
        "norms": cfg["layers"] * d,
        "router": count["E"] * d * cfg["n_routed_experts"],
        "shared_expert": count["E"] * cfg["n_shared_experts"] * 2 * d
        * cfg["moe_shared_expert_intermediate_size"],
        "experts": count["E"] * cfg["experts_held"] * 2 * d
        * cfg["moe_intermediate_size"],
        "embedding_head_final_norm": 2 * cfg["vocab_rows_held"] * d + d,
    }


def _width(cfg: dict) -> int:
    return 2 if cfg["compute_dtype"] == "bfloat16" else 4


def num_experts(cfg: dict) -> int:
    """The experts a layer's router chooses among, whatever a chip holds."""
    return cfg["n_routed_experts"]


def _pairs(cfg: dict, kind: str) -> float:
    if kind != "full":
        raise ValueError(f"kind {kind!r}: this family's attention layers are "
                         f"full causal attention")
    return visible_pairs(cfg["seq_len"])


def flash_forward(cfg: dict, wl: dict, kind: str, sequences: float):
    """One execution of the forward attention kernel of ONE layer over
    ``sequences`` sequences: QK^T and PV over the visible pairs; reads q and,
    once a group of 16 query heads, k and v; writes the output and a float32
    log-sum-exp a row."""
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


def _scan_kind(kind: str) -> None:
    if kind != "scan":
        raise ValueError(f"kind {kind!r}: a state-space layer's is 'scan'")


def ssd_forward(cfg: dict, wl: dict, kind: str, sequences: float):
    """One execution of the forward scan kernel of ONE state-space layer over
    ``sequences`` sequences: ``C B^T`` once a group and the decayed product
    with ``x`` once a head over the positions a position sees in its chunk,
    and the two products with the carried state; reads ``x``, ``B``, ``C`` (at
    the activations' width) and ``dt`` (float32), writes ``y``."""
    _scan_kind(kind)
    h, _, g, n, _, inner, _ = _ssm(cfg)
    rows = sequences * cfg["seq_len"]
    moved = rows * ((2 * inner + 2 * g * n) * _width(cfg) + h * 4)
    return rows * scan_flops_per_token(cfg), moved


def ssd_backward(cfg: dict, wl: dict, kind: str, sequences: float):
    """The backward scan kernel of ONE state-space layer: over the positions
    a position sees, ``C B^T`` again, ``dC`` and ``dB`` once a group and
    ``dy x^T`` and ``dx`` once a head; and five products with a head's state
    (``C . S`` and ``B . dS`` again, ``dC``'s and ``dB``'s state terms, the
    state's own gradient). Reads ``x``, ``dy``, ``B``, ``C`` and ``dt``;
    writes ``dx``, ``dB``, ``dC`` and a float32 ``d dt``."""
    _scan_kind(kind)
    h, p, g, n, q, inner, _ = _ssm(cfg)
    rows = sequences * cfg["seq_len"]
    flops = rows * (2 * (q + 1) / 2 * (3 * g * n + 2 * h * p)
                    + 5 * 2 * h * n * p)
    moved = rows * ((3 * inner + 4 * g * n) * _width(cfg) + 2 * h * 4)
    return flops, moved
