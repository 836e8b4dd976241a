"""kimi-linear-48b-a3b: Kimi-Linear-48B-A3B-Instruct (moonshotai,
``kimi_linear``) trained on packed 16,384-token sequences, through ETL ->
``FlaxEstimator.fit_on_frame``, as one chip of a deployment in which 32 chips
share each layer.

One row of the raw input is one packed sequence: ``tokens``, a fixed-size
list of ``seq_len`` int32 ids, and ``n_tokens``, how many of them are real
(the generator's are all full). This chip holds an eighth of the vocabulary's
rows (``vocab_rows_held``), and a sliced vocabulary is a smaller vocabulary:
the ids are drawn from the slice, a seeded Zipf over it, hashed so that
frequent ids are spread over the embedding's rows; documents of geometric
length are joined by the end-of-text id and carried across (neither the
delta rule's state, nor the convolution's window, nor attention is reset at
it).

The ETL plan keeps the full sequences and the token column. The model is
``raydp_tpu.models.TransformerLM`` at the configuration's widths: the layers
held (``layers_held``, the source's own numbers from 1: published layer 1, a
Kimi Delta Attention operator over the dense SwiGLU, and published layers
2-5) each a PAIR whose operator is what ``linear_attn_config`` says
(``kda_layers``: Kimi Delta Attention, letter ``K``; ``full_attn_layers``:
latent attention without positions, 32 heads of 192 / 128, the model's letter
``B`` under ``rope_layers=(0,)``), sigmoid routing with its balancing bias
(state the estimator carries beside the parameters), a shared expert, and the
expert layer told which experts it holds. The estimator takes the loss from
the model (fused head over the rows held, float32, no auxiliary loss), so no
``[B, T, vocab]`` logits exist in the train step.
"""

from __future__ import annotations

import numpy as np
import pyarrow as pa

TOKENS, LENGTH = "tokens", "n_tokens"
HASH = 2654435761       # Knuth's multiplicative hash: spreads ranks over ids
# a letter of ``layer_pattern_held`` (``K``: Kimi Delta Attention, ``A``:
# attention) -> the model's ``layer_kinds`` (``B``: the attention pair)
KINDS = {"K": "K", "A": "B"}


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
    """The cell cut for a CPU rehearsal, counts only: one layer of each kind
    (published layer 1: a KDA operator over the dense SwiGLU; published layer
    2: a KDA operator over experts; published layer 4: attention over
    experts), 16 experts of which 4 are held (a quarter) with the published 8
    a token, 512 of 2048 vocabulary rows, 256 positions, 2 heads in both
    operators, 1 sequence a step and 2 steps an epoch, a warm-up of 64 steps.
    Hidden 2304, heads of 128 (KDA) and 192 / 128 (attention), the K/V latent
    512, the gates' rank 128, the four taps, the dense width 9216, the expert
    width 1024, the experts a token and the bias's step stay."""
    cfg["layers"], cfg["layers_held"] = 3, [1, 2, 4]
    cfg["layer_pattern_held"] = "KKA"
    cfg["num_experts"], cfg["experts_held"] = 16, 4
    cfg["vocab_size"], cfg["vocab_rows_held"] = 2048, 512
    cfg["input"]["eos_id"] = 511
    cfg["seq_len"] = wl["seq_len"] = 256
    cfg["num_attention_heads"] = cfg["num_key_value_heads"] = 2
    cfg["linear_attn_config"] = dict(cfg["linear_attn_config"], num_heads=2)
    cfg["compared_positions"] = 32
    cfg["optimizer"]["warmup_steps"] = 64
    wl["batch_per_replica"] = 1
    return 2 * chips


def layer_kinds(cfg: dict) -> str:
    """The model's ``layer_kinds`` of the layers held. Refuses a
    ``layers_held`` whose letters by the published ``kda_layers`` /
    ``full_attn_layers`` (both counted from 1) are not
    ``layer_pattern_held``, and a ``dense_layers`` that is not the held
    layers at or under ``first_k_dense_replace``, leading."""
    held, lin = cfg["layers_held"], cfg["linear_attn_config"]
    letters = {**{i: "K" for i in lin["kda_layers"]},
               **{i: "A" for i in lin["full_attn_layers"]}}
    pattern = "".join(letters.get(i, "?") for i in held)
    if pattern != cfg["layer_pattern_held"] or len(held) != cfg["layers"]:
        raise ValueError(
            f"layers_held {held} are {pattern!r} by the published "
            f"kda_layers / full_attn_layers, not the {cfg['layers']} layers "
            f"of layer_pattern_held {cfg['layer_pattern_held']!r}")
    dense = [i <= cfg["first_k_dense_replace"] for i in held]
    if dense != sorted(dense, reverse=True) \
            or sum(dense) != cfg["dense_layers"]:
        raise ValueError(
            f"dense_layers {cfg['dense_layers']}: of layers_held {held} "
            f"{sum(dense)} lie at or under first_k_dense_replace "
            f"{cfg['first_k_dense_replace']}, and they lead")
    return "".join(KINDS[letter] for letter in pattern)


def build_model(cfg: dict, mesh=None):
    import jax.numpy as jnp

    from raydp_tpu.models import TransformerLM
    from raydp_tpu.models.transformer import KDASpec

    aux, lin = cfg["aux_loss"], cfg["linear_attn_config"]
    if cfg["moe_router_activation_func"] != "sigmoid" \
            or cfg["num_expert_group"] != 1 or cfg["topk_group"] != 1 \
            or not cfg["mla_use_nope"] or cfg["q_lora_rank"] is not None:
        raise ValueError("this pipeline builds sigmoid routing without a "
                         "group limit and latent attention without positions "
                         "or a query latent")
    return TransformerLM(
        vocab_size=cfg["vocab_rows_held"], dim=cfg["hidden_size"],
        num_heads=cfg["num_attention_heads"], num_layers=cfg["layers"],
        ffn_dim=cfg["moe_intermediate_size"], attention=cfg["attention"],
        mesh=mesh, dtype=jnp.dtype(cfg["compute_dtype"]),
        rms_norm_eps=cfg["rms_norm_eps"], num_experts=cfg["num_experts"],
        experts_per_token=cfg["num_experts_per_token"],
        balance_loss_weight=aux["balance_weight"],
        z_loss_weight=aux["z_weight"], init_std=cfg["init_std"],
        first_expert=cfg["first_expert"], experts_held=cfg["experts_held"],
        expert_activation=cfg["hidden_act"],
        normalize_top_k=cfg["moe_renormalize"],
        remat_blocks=cfg["remat_blocks"], dense_layers=cfg["dense_layers"],
        dense_ffn_dim=cfg["intermediate_size"], routing="sigmoid",
        route_scale=cfg["routed_scaling_factor"],
        shared_expert_dim=cfg["moe_intermediate_size"]
        * cfg["num_shared_experts"],
        bias_update_rate=cfg["bias_update_rate"],
        kv_lora_rank=cfg["kv_lora_rank"], q_lora_rank=cfg["q_lora_rank"],
        qk_nope_head_dim=cfg["qk_nope_head_dim"],
        qk_rope_head_dim=cfg["qk_rope_head_dim"],
        v_head_dim=cfg["v_head_dim"], rope_layers=(0,),
        layer_kinds=layer_kinds(cfg),
        kda=KDASpec(lin["num_heads"], lin["head_dim"],
                    lin["short_conv_kernel_size"], lin["head_dim"],
                    cfg["kda_chunk"]))


def build_optimizer(cfg: dict):
    import jax
    import optax

    o = cfg["optimizer"]
    rate = optax.linear_schedule(0.0, o["learning_rate"], o["warmup_steps"])
    return optax.chain(
        optax.clip_by_global_norm(o["clip_global_norm"]),
        # decay on the matrices (the stacked expert kernels and the taps
        # among them) alone: a norm's weight, ``A_log`` and ``dt_bias`` have
        # one dimension
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
