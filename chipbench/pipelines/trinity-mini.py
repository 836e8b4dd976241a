"""trinity-mini: Trinity-Mini (arcee-ai, ``afmoe``) trained on packed
8,192-token sequences, through ETL -> ``FlaxEstimator.fit_on_frame``, as one
chip of a deployment in which eight chips share each layer.

One row of the raw input is one packed sequence: ``tokens``, a fixed-size
list of ``seq_len`` int32 ids, and ``n_tokens``, how many of them are real
(the generator's are all full). This chip holds an eighth of the
vocabulary's rows (``vocab_rows_held``), and a sliced vocabulary is a smaller
vocabulary: the ids are drawn from the slice, a seeded Zipf over it, hashed
so that frequent ids are spread over the embedding's rows; documents of
geometric length are joined by the end-of-text id and attended across.

The ETL plan keeps the full sequences and the token column. The model is
``raydp_tpu.models.TransformerLM`` at the configuration's widths: the layers
held (``layers_held``: published layer 0, dense, and one period of expert
layers) with each one's attention kind from ``layer_types``, grouped-query
heads normed head by head, the attention gate, four norms a block, scaled
embeddings, sigmoid routing with its balancing bias (state the estimator
carries beside the parameters; ``load_balance_coeff`` moves it once an
optimizer step), the shared expert, and the expert layer told which experts
it holds. The estimator takes the loss from the model (fused head over the
rows held, float32, no auxiliary loss), so no ``[B, T, vocab]`` logits exist
in the train step.
"""

from __future__ import annotations

import numpy as np
import pyarrow as pa

TOKENS, LENGTH = "tokens", "n_tokens"
HASH = 2654435761       # Knuth's multiplicative hash: spreads ranks over ids


def seq_len(cfg: dict, wl: dict) -> int:
    """The positions a sequence holds: the configuration's ``seq_len``, which
    the workload's (the harness's unit of work) has to repeat."""
    n = int(cfg["seq_len"])
    if int(wl.get("seq_len", n)) != n:
        raise ValueError(f"workload seq_len {wl['seq_len']} != the "
                         f"configuration's seq_len {n}")
    return n


def generate(rows: int, seed: int, cfg: dict) -> pa.Table:
    rng = np.random.default_rng(seed)
    t, vocab = int(cfg["seq_len"]), int(cfg["vocab_rows_held"])
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
    """The cell cut for a CPU rehearsal, counts only: two of the five layers
    held (published layer 0: dense, sliding window, RoPE; published layer 7:
    experts, full attention, no position embedding; the dense layer alone is
    44.5 M parameters at the published widths, and all five made the
    rehearsal's state 1.8 GB and its checkpoints most of its time), 16
    experts of which 2 are held (an eighth) with the published 8 a token
    (with 4 of 16 one near-tie that bfloat16 flips swaps a fifth of a token's
    feed-forward, which the norm after it scales up to the stream's size: a
    rehearsal that overfits its sequences then read 0.04-0.07 where 8 of 16
    reads 0.016-0.021), 512 of 4096 vocabulary rows, 256 positions with a
    window of 64 (four windows a sequence, as 8,192 positions hold four of
    2048), 8 query heads on 1 K/V head (one group of the published eight), 1
    sequence a step and 2 steps an epoch, a warm-up of 64 steps (inside 2,000
    the rehearsal's steps move no bfloat16 weight). Hidden 2048, heads of
    128, the dense width 6144, the expert width 1024, the experts a token and
    the bias's step stay."""
    cfg["layers"], cfg["layers_held"] = 2, [0, 7]
    cfg["num_experts"], cfg["experts_held"] = 16, 2
    cfg["vocab_size"], cfg["vocab_rows_held"] = 4096, 512
    cfg["input"]["eos_id"] = 511
    cfg["seq_len"] = wl["seq_len"] = 256
    cfg["sliding_window"] = 64
    cfg["num_attention_heads"], cfg["num_key_value_heads"] = 8, 1
    cfg["compared_positions"] = 32
    cfg["optimizer"]["warmup_steps"] = 64
    wl["batch_per_replica"] = 1
    return 2 * chips


def build_model(cfg: dict, mesh=None):
    import jax.numpy as jnp

    from raydp_tpu.models import TransformerLM

    aux = cfg["aux_loss"]
    windowed = tuple(int(cfg["layer_types"][i] == "sliding_attention")
                     for i in cfg["layers_held"])
    if len(windowed) != cfg["layers"]:
        raise ValueError(f"layers_held {cfg['layers_held']} are not the "
                         f"{cfg['layers']} layers")
    return TransformerLM(
        vocab_size=cfg["vocab_rows_held"], dim=cfg["hidden_size"],
        num_heads=cfg["num_attention_heads"], num_layers=cfg["layers"],
        ffn_dim=cfg["moe_intermediate_size"], attention=cfg["attention"],
        mesh=mesh, dtype=jnp.dtype(cfg["compute_dtype"]),
        rms_norm_eps=cfg["rms_norm_eps"], rope_theta=float(cfg["rope_theta"]),
        qk_norm="head", num_experts=cfg["num_experts"],
        experts_per_token=cfg["num_experts_per_tok"],
        balance_loss_weight=aux["balance_weight"],
        z_loss_weight=aux["z_weight"], init_std=cfg["init_std"],
        head_dim=cfg["head_dim"], num_kv_heads=cfg["num_key_value_heads"],
        sliding_window=cfg["sliding_window"],
        # a sliding-window layer applies RoPE, a full one has no position
        # embedding: one pattern for both
        window_layers=windowed, rope_layers=windowed,
        first_expert=cfg["first_expert"], experts_held=cfg["experts_held"],
        expert_activation=cfg["hidden_act"],
        normalize_top_k=cfg["route_norm"], remat_blocks=cfg["remat_blocks"],
        attention_gate=True, sandwich_norms=True,
        embed_scale=cfg["mup_enabled"], dense_layers=cfg["dense_layers"],
        dense_ffn_dim=cfg["intermediate_size"], routing=cfg["score_func"],
        route_scale=cfg["route_scale"],
        shared_expert_dim=cfg["moe_intermediate_size"]
        * cfg["num_shared_experts"],
        bias_update_rate=cfg["load_balance_coeff"])


def build_optimizer(cfg: dict):
    import jax
    import optax

    o = cfg["optimizer"]
    rate = optax.linear_schedule(0.0, o["learning_rate"], o["warmup_steps"])
    return optax.chain(
        optax.clip_by_global_norm(o["clip_global_norm"]),
        # decay on the matrices (and the stacked expert kernels) alone: a
        # norm's weight has one dimension
        optax.adamw(rate, b1=o["b1"], b2=o["b2"], eps=o["eps"],
                    weight_decay=o["weight_decay"],
                    mask=lambda params: jax.tree.map(
                        lambda p: p.ndim >= 2, params)))


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
