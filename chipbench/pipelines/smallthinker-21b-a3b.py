"""smallthinker-21b-a3b: SmallThinker-21BA3B-Instruct (PowerInfer) trained on
packed 16,384-token sequences, through ETL -> ``FlaxEstimator.fit_on_frame``,
as one chip of a deployment in which four chips share each layer.

One row of the raw input is one packed sequence: ``tokens``, a fixed-size
list of ``max_position_embeddings`` int32 ids, and ``n_tokens``, how many of
them are real (the generator's are all full). This chip holds a quarter of
the vocabulary's rows (``vocab_rows_held``), and a sliced vocabulary is a
smaller vocabulary: the ids are drawn from the slice, a seeded Zipf over it,
hashed so that frequent ids are spread over the embedding's rows; documents
of geometric length are joined by the end-of-text id and attended across.

The ETL plan keeps the full sequences and the token column. The model is
``raydp_tpu.models.TransformerLM`` at the configuration's widths: grouped-
query heads of the published width, the layer pattern (``rope_layout``,
``sliding_window_layout``: full attention without a position embedding, then
three windowed layers with RoPE), the router on the attention's input, the
ReLU-gated expert layer told which experts it holds. The estimator takes the
loss from the model (fused head over the rows held, float32, both auxiliary
losses), so no ``[B, T, vocab]`` logits exist in the train step.
"""

from __future__ import annotations

import numpy as np
import pyarrow as pa

TOKENS, LENGTH = "tokens", "n_tokens"
HASH = 2654435761       # Knuth's multiplicative hash: spreads ranks over ids


def seq_len(cfg: dict, wl: dict) -> int:
    """The positions a sequence holds: the configuration's, which the
    workload's ``seq_len`` (the harness's unit of work) has to repeat."""
    n = int(cfg["max_position_embeddings"])
    if int(wl.get("seq_len", n)) != n:
        raise ValueError(f"workload seq_len {wl['seq_len']} != the "
                         f"configuration's positions {n}")
    return n


def generate(rows: int, seed: int, cfg: dict) -> pa.Table:
    rng = np.random.default_rng(seed)
    t, vocab = int(cfg["max_position_embeddings"]), int(cfg["vocab_rows_held"])
    inp = cfg["input"]
    ranks = rng.zipf(inp["zipf_a"], size=rows * t).astype(np.uint64)
    ids = ((ranks * np.uint64(HASH)) % np.uint64(vocab)).astype(np.int32)
    ends = rng.random(rows * t) < 1.0 / inp["mean_document_tokens"]
    ids[ends] = min(int(inp["eos_id"]), vocab - 1)
    return pa.table({
        TOKENS: pa.FixedSizeListArray.from_arrays(pa.array(ids), t),
        LENGTH: np.full(rows, t, np.int32)})


def describe(cfg: dict, wl: dict) -> dict:
    """What ``etl`` says of its frame, without a frame."""
    return {"tokens": TOKENS, "seq_len": seq_len(cfg, wl)}


def etl(raw_df, cfg: dict, wl: dict):
    from raydp_tpu.etl.expressions import col

    info = describe(cfg, wl)
    df = raw_df.filter(col(LENGTH) == info["seq_len"]).select(TOKENS)
    return df, info


def batch_leaves(cfg: dict, wl: dict, info: dict, batch: int) -> dict:
    """A global batch as the train step is handed it: leaf -> (shape, dtype)."""
    return {"tokens": ((batch, info["seq_len"]), "int32")}


def cpu_cut(cfg: dict, wl: dict, chips: int) -> int:
    """The cell cut for a CPU rehearsal, counts only: the four layers of the
    period stay, 8 experts of which 2 are held and 2 a token, 512 of 2048
    vocabulary rows, 256 positions with a window of 64 (four windows a
    sequence, as 16,384 positions hold four of 4096), 7 query heads on 1 K/V
    head (one group of the published seven; 28 + 4 heads make the rehearsal's
    state 1.6 GB and its two checkpoints most of its time), 1 sequence a step
    and 4 steps an epoch, a warm-up of 64 steps (inside 2,000 the rehearsal's
    16 steps move no bfloat16 weight). Hidden 2560, heads of 128 and the expert width 768
    stay."""
    cfg["layers"] = 4
    cfg["moe_num_primary_experts"], cfg["experts_held"] = 8, 2
    cfg["moe_num_active_primary_experts"] = 2
    cfg["vocab_size"], cfg["vocab_rows_held"] = 2048, 512
    cfg["input"]["eos_id"] = 511
    cfg["max_position_embeddings"] = wl["seq_len"] = 256
    cfg["sliding_window_size"] = 64
    cfg["num_attention_heads"], cfg["num_key_value_heads"] = 7, 1
    cfg["compared_positions"] = 32
    cfg["optimizer"]["warmup_steps"] = 64
    wl["batch_per_replica"] = 1
    return 4 * chips


def build_model(cfg: dict, mesh=None):
    import jax.numpy as jnp

    from raydp_tpu.models import TransformerLM

    aux = cfg["aux_loss"]
    return TransformerLM(
        vocab_size=cfg["vocab_rows_held"], dim=cfg["hidden_size"],
        num_heads=cfg["num_attention_heads"], num_layers=cfg["layers"],
        ffn_dim=cfg["moe_ffn_hidden_size"], attention=cfg["attention"],
        mesh=mesh, dtype=jnp.dtype(cfg["compute_dtype"]),
        rms_norm_eps=cfg["rms_norm_eps"], rope_theta=float(cfg["rope_theta"]),
        num_experts=cfg["moe_num_primary_experts"],
        experts_per_token=cfg["moe_num_active_primary_experts"],
        balance_loss_weight=aux["balance_weight"],
        z_loss_weight=aux["z_weight"], init_std=cfg["init_std"],
        head_dim=cfg["head_dim"], num_kv_heads=cfg["num_key_value_heads"],
        sliding_window=cfg["sliding_window_size"],
        window_layers=tuple(cfg["sliding_window_layout"]),
        rope_layers=tuple(cfg["rope_layout"]),
        first_expert=cfg["first_expert"], experts_held=cfg["experts_held"],
        expert_activation="relu", normalize_top_k=cfg["norm_topk_prob"],
        router_input="attention", remat_blocks=cfg["remat_blocks"])


def build_optimizer(cfg: dict):
    import optax

    o = cfg["optimizer"]
    rate = optax.linear_schedule(0.0, o["learning_rate"], o["warmup_steps"])
    return optax.chain(
        optax.clip_by_global_norm(o["clip_global_norm"]),
        optax.adamw(rate, b1=o["b1"], b2=o["b2"], eps=o["eps"],
                    weight_decay=o["weight_decay"]))


def build_estimator(cfg: dict, wl: dict, info: dict, **fit_args):
    from raydp_tpu.train import FlaxEstimator

    return FlaxEstimator(
        model=build_model(cfg, fit_args["mesh"]),
        optimizer=build_optimizer(cfg), loss=None,
        columns_spec={"tokens": (info["tokens"], np.int32)},
        batch_preprocessor=lambda batch: (batch["tokens"], batch["tokens"]),
        shuffle=cfg["shuffle"], **fit_args)


def compared(outputs, cfg: dict):
    """Inside the jit: of a batch's logits [B, T, rows held], the last
    ``compared_positions`` positions, so only those leave the device."""
    return outputs[:, -min(cfg["compared_positions"], outputs.shape[1]):]


def reference_inputs(table: pa.Table, info: dict):
    col = table[info["tokens"]].combine_chunks()
    return col.flatten().to_numpy().reshape(len(col), info["seq_len"])
