"""olmoe-1b-7b: OLMoE-1B-7B-0125-Instruct (allenai) pre-trained on packed
sequences, through ETL -> ``FlaxEstimator.fit_on_frame``.

One row of the raw input is one packed sequence: ``tokens``, a fixed-size
list of ``max_position_embeddings`` int32 ids, and ``n_tokens``, how many of
them are real (a tokeniser's packer writes a short last sequence of a shard;
the generator's are all full). Token ids follow a seeded Zipf over the
vocabulary, hashed so that frequent ids are spread over the embedding's
rows; documents of geometric length are joined by the end-of-text token and
attended across. The unigram frequencies are what a correct gradient learns
first, so a broken one cannot lower the loss.

The ETL plan keeps the full sequences and the token column. The model is
``raydp_tpu.models.TransformerLM`` at the configuration's widths with its
expert layer; the estimator takes the loss from the model (fused head,
float32, both auxiliary losses), so no ``[B, T, vocab]`` logits exist in the
train step.
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
    t, vocab = int(cfg["max_position_embeddings"]), int(cfg["vocab_size"])
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
    """The cell cut for a CPU rehearsal, counts only: one layer, 8 experts
    and 4 a token (8 a token would be every expert; with 2 of 8 one near-tie
    that bfloat16 flips swaps half of a token's experts, and a rehearsal that
    overfits its eight sequences then reads 0.018 against the 0.0156
    tolerance), 512 vocabulary rows, 128 positions, 2 sequences a step and 4
    steps an epoch. Hidden 2048, 16 heads of 128 and the expert width 1024
    stay."""
    cfg["layers"] = 1
    cfg["num_experts"], cfg["num_experts_per_tok"] = 8, 4
    cfg["vocab_size"] = 512
    cfg["input"]["eos_id"] = 511
    cfg["max_position_embeddings"] = wl["seq_len"] = 128
    cfg["compared_positions"] = 32
    wl["batch_per_replica"] = 2
    wl["estimator_args"] = {k: v for k, v in wl["estimator_args"].items()
                            if k != "accum_steps"}
    return 2 * 4 * chips


def build_model(cfg: dict, mesh=None):
    import jax.numpy as jnp

    from raydp_tpu.models import TransformerLM

    aux = cfg["aux_loss"]
    return TransformerLM(
        vocab_size=cfg["vocab_size"], dim=cfg["hidden_size"],
        num_heads=cfg["num_attention_heads"], num_layers=cfg["layers"],
        ffn_dim=cfg["intermediate_size"], attention=cfg["attention"],
        mesh=mesh, dtype=jnp.dtype(cfg["compute_dtype"]),
        rms_norm_eps=cfg["rms_norm_eps"], rope_theta=float(cfg["rope_theta"]),
        qk_norm=cfg["model_type"] == "olmoe", num_experts=cfg["num_experts"],
        experts_per_token=cfg["num_experts_per_tok"],
        balance_loss_weight=aux["balance_weight"],
        z_loss_weight=aux["z_weight"], init_std=cfg["init_std"])


def build_optimizer(cfg: dict):
    import optax

    o = cfg["optimizer"]
    return optax.chain(
        optax.clip_by_global_norm(o["clip_global_norm"]),
        optax.adamw(o["learning_rate"], b1=o["b1"], b2=o["b2"], eps=o["eps"],
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
    """Inside the jit: of a batch's logits [B, T, vocab], the last
    ``compared_positions`` positions, so only those leave the device."""
    return outputs[:, -min(cfg["compared_positions"], outputs.shape[1]):]


def reference_inputs(table: pa.Table, info: dict):
    col = table[info["tokens"]].combine_chunks()
    return col.flatten().to_numpy().reshape(len(col), info["seq_len"])
