"""sdar-30b-a3b-chat: SDAR-30B-A3B-Chat (JetLM, ``sdar_moe``) trained with the
block-diffusion objective on packed 8,192-token rows, through ETL ->
``FlaxEstimator.fit_on_frame``, as one chip of a deployment in which eight
chips share each layer.

One row of the raw input is one packed row: ``tokens``, a fixed-size list of
``seq_len`` int32 ids, and ``n_tokens``, how many of them are real (the
generator's are all full). This chip holds an eighth of the vocabulary's rows
(``vocab_rows_held``), and a sliced vocabulary is a smaller vocabulary: the
ids are drawn from the slice's rows below the mask id, a seeded Zipf over
them, hashed so that frequent ids are spread over the embedding's rows;
documents of geometric length are joined by the end-of-text id (the slice's
last row) and attended across. The mask id (the last row but one) is never a
clean token.

The ETL plan keeps the full rows and the token column. The model is
``raydp_tpu.models.TransformerLM`` at the configuration's widths with
``diffusion=BlockDiffusionSpec(...)``: the model itself lays out ``[row ;
noised row]`` (2 x ``seq_len`` positions, position ids 0..L-1 twice), draws
the noise (in training from the key the estimator folds from the fit's seed
and the optimizer step; in a plain call from the configuration's fixed
``eval_noise_seed``), runs every layer under the block-diffusion mask and
takes its loss over the noised half (fused head over the rows held, float32,
one weight a position, no auxiliary loss), so no ``[B, T, vocab]`` logits
exist in the train step.

Check (a): ``reference_inputs`` calls the program's sampler
(``block_diffusion_noise``: input generation, like ``generate``) with the
fixed key and hands the reference ``(x0, x_t, t)``; everything after it in the
reference is its own. ``etl`` puts the sampler's settings into ``info``, which
is all ``reference_inputs`` is handed beside the table.
"""

from __future__ import annotations

import numpy as np
import pyarrow as pa

TOKENS, LENGTH = "tokens", "n_tokens"
HASH = 2654435761       # Knuth's multiplicative hash: spreads ranks over ids


def seq_len(cfg: dict, wl: dict) -> int:
    """The tokens a row holds: the configuration's ``seq_len``, which the
    workload's (the harness's unit of work: a TRAINED token, not a position)
    has to repeat."""
    n = int(cfg["seq_len"])
    if int(wl.get("seq_len", n)) != n:
        raise ValueError(f"workload seq_len {wl['seq_len']} != the "
                         f"configuration's seq_len {n}")
    return n


def generate(rows: int, seed: int, cfg: dict) -> pa.Table:
    rng = np.random.default_rng(seed)
    t, inp, noise = int(cfg["seq_len"]), cfg["input"], cfg["diffusion"]
    # clean ids lie below the mask id and the end-of-text id
    clean = min(int(noise["mask_id"]), int(inp["eos_id"]))
    ranks = rng.zipf(inp["zipf_a"], size=rows * t).astype(np.uint64)
    ids = ((ranks * np.uint64(HASH)) % np.uint64(clean)).astype(np.int32)
    ends = rng.random(rows * t) < 1.0 / inp["mean_document_tokens"]
    ids[ends] = int(inp["eos_id"])
    return pa.table({
        TOKENS: pa.FixedSizeListArray.from_arrays(pa.array(ids), t),
        LENGTH: np.full(rows, t, np.int32)})


def describe(cfg: dict, wl: dict) -> dict:
    """What ``etl`` says of its frame, without a frame."""
    return {"tokens": TOKENS, "seq_len": seq_len(cfg, wl),
            "diffusion": dict(cfg["diffusion"])}


def etl(raw_df, cfg: dict, wl: dict):
    from raydp_tpu.etl.expressions import col

    info = describe(cfg, wl)
    df = raw_df.filter(col(LENGTH) == info["seq_len"]).select(TOKENS)
    return df, info


def batch_leaves(cfg: dict, wl: dict, info: dict, batch: int) -> dict:
    """A global batch as the train step is handed it: leaf -> (shape, dtype)."""
    return {"tokens": ((batch, info["seq_len"]), "int32")}


def cpu_cut(cfg: dict, wl: dict, chips: int) -> int:
    """The cell cut for a CPU rehearsal, counts only: one of the six layers,
    16 experts of which 2 are held (an eighth) with the published 8 a token,
    512 vocabulary rows (the mask id 510, the end-of-text id 511), 128
    tokens a row = 256 positions in blocks of the published 4, 8 query heads
    on 1 K/V head (one group of the published eight), 1 row a step and 4
    steps an epoch, a warm-up of 64 steps. Hidden 2048, heads of 128, the
    expert width 768, the experts a token and the block length stay. And one
    thing that is no count: the noise level's lower clip goes from 1e-3 to
    0.5. A block's loss term has variance ``Bd (E[1/t] - 1)``: 4 x 5.9 under
    U(1e-3, 1], which 2,048 blocks a row average out on the chip and the 32
    of a rehearsal's row do not (an epoch's loss would read +-0.45 and check
    (b) would be a coin); under U(0.5, 1] it is 4 x 0.39. A rehearsal finds
    wrong paths and shapes; the objective's constants are the chip's."""
    cfg["layers"] = 1
    cfg["num_experts"], cfg["experts_held"] = 16, 2
    cfg["vocab_size"], cfg["vocab_rows_held"] = 4096, 512
    cfg["input"]["eos_id"], cfg["diffusion"]["mask_id"] = 511, 510
    cfg["diffusion"]["t_min"] = 0.5
    cfg["seq_len"] = wl["seq_len"] = 128
    cfg["num_attention_heads"], cfg["num_key_value_heads"] = 8, 1
    cfg["compared_positions"] = 32
    cfg["optimizer"]["warmup_steps"] = 64
    wl["batch_per_replica"] = 1
    return 4 * chips


def diffusion_spec(noise: dict):
    """The configuration's ``diffusion`` group as the program's spec."""
    from raydp_tpu.models.transformer import BlockDiffusionSpec

    return BlockDiffusionSpec(
        block=int(noise["block_length"]), mask_id=int(noise["mask_id"]),
        t_min=float(noise["t_min"]), eval_seed=int(noise["eval_noise_seed"]))


def build_model(cfg: dict, mesh=None):
    import jax.numpy as jnp

    from raydp_tpu.models import TransformerLM

    aux = cfg["aux_loss"]
    if cfg["mlp_only_layers"] or cfg["decoder_sparse_step"] != 1:
        raise ValueError("every layer of this family holds the expert layer")
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
        first_expert=cfg["first_expert"], experts_held=cfg["experts_held"],
        expert_activation=cfg["hidden_act"],
        normalize_top_k=cfg["norm_topk_prob"],
        remat_blocks=cfg["remat_blocks"],
        embed_init_std=cfg["embed_init_std"],
        diffusion=diffusion_spec(cfg["diffusion"]))


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
    """Inside the jit: of a batch's logits at the NOISED positions [B, L,
    rows held], the last ``compared_positions`` of them, so only those leave
    the device."""
    return outputs[:, -min(cfg["compared_positions"], outputs.shape[1]):]


def reference_inputs(table: pa.Table, info: dict):
    """``(x0, x_t, t)`` of a batch: the rows [B, L], their noised copy as the
    program's plain call noises them (the fixed key, the program's sampler:
    input generation) and the noise level a block [B, L / block]."""
    import jax

    from raydp_tpu.models.transformer import block_diffusion_noise

    col = table[info["tokens"]].combine_chunks()
    tokens = col.flatten().to_numpy().reshape(len(col), info["seq_len"])
    spec = diffusion_spec(info["diffusion"])
    noised, level, _ = jax.jit(block_diffusion_noise, static_argnums=2)(
        jax.random.PRNGKey(spec.eval_seed), tokens, spec)
    return tokens, np.asarray(noised), np.asarray(level)
