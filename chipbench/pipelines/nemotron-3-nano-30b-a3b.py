"""nemotron-3-nano-30b-a3b: NVIDIA-Nemotron-3-Nano-30B-A3B-BF16 (nvidia,
``nemotron_h``) trained on packed 16,384-token sequences, through ETL ->
``FlaxEstimator.fit_on_frame``, as one chip of a deployment in which sixteen
chips share each layer.

One row of the raw input is one packed sequence: ``tokens``, a fixed-size
list of ``seq_len`` int32 ids, and ``n_tokens``, how many of them are real
(the generator's are all full). This chip holds an eighth of the
vocabulary's rows (``vocab_rows_held``), and a sliced vocabulary is a smaller
vocabulary: the ids are drawn from the slice, a seeded Zipf over it, hashed
so that frequent ids are spread over the embedding's rows; documents of
geometric length are joined by the end-of-text id and carried across (the
state-space state is not reset at a document's end, as attention attends
across).

The ETL plan keeps the full sequences and the token column. The model is
``raydp_tpu.models.TransformerLM`` at the configuration's widths with the
layers held (``layers_held``: published layers 0-8) each ONE sub-layer by its
letter in ``layer_pattern_held`` (``M`` a Mamba-2 mixer of the configuration's
heads, groups, state, taps and chunk; ``*`` full grouped-query attention with
no position embedding; ``E`` the expert layer alone: sigmoid routing with its
balancing bias, which is state the estimator carries beside the parameters
and ``bias_update_rate`` moves once an optimizer step, non-gated ReLU^2
experts beside a shared expert, told which experts it holds). The estimator
takes the loss from the model (fused head over the rows held, float32, no
auxiliary loss), so no ``[B, T, vocab]`` logits exist in the train step.
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
    """A global batch as the train step takes it: leaf -> (shape, dtype)."""
    return {"tokens": ((batch, info["seq_len"]), "int32")}


def cpu_cut(cfg: dict, wl: dict, chips: int) -> int:
    """The cell cut for a CPU rehearsal, counts only: three of the nine
    layers held, one of each kind (published layers 0, 1 and 5: ``M E *``),
    16 experts of which 1 is held (a sixteenth) with the published 6 a token,
    512 of 4096 vocabulary rows, 256 positions (two chunks of the scan), 16
    of the 64 state-space heads in 2 of the 8 groups (8 heads a group, as
    published), 8 of the 32 query heads on the 2 K/V heads, 1 sequence a step
    and 2 steps an epoch, a warm-up of 64 steps (inside 2,000 the rehearsal's
    steps move no bfloat16 weight). Hidden 2688, a state-space head's width
    64, the state 128, the chunk 128, the 4 taps, an attention head's 128,
    the expert width 1856, the shared expert's 3712, the experts a token and
    the bias's step stay."""
    cfg["layers"], cfg["layers_held"] = 3, [0, 1, 5]
    cfg["layer_pattern_held"] = "ME*"
    cfg["n_routed_experts"], cfg["experts_held"] = 16, 1
    cfg["vocab_size"], cfg["vocab_rows_held"] = 4096, 512
    cfg["input"]["eos_id"] = 511
    cfg["seq_len"] = wl["seq_len"] = 256
    cfg["mamba_num_heads"], cfg["n_groups"] = 16, 2
    cfg["num_attention_heads"] = 8
    cfg["compared_positions"] = 32
    cfg["optimizer"]["warmup_steps"] = 64
    wl["batch_per_replica"] = 1
    return 2 * chips


def build_model(cfg: dict, mesh=None):
    import jax.numpy as jnp

    from raydp_tpu.models import TransformerLM
    from raydp_tpu.models.transformer import SSMSpec

    aux = cfg["aux_loss"]
    pattern = "".join(cfg["hybrid_override_pattern"][i]
                      for i in cfg["layers_held"])
    if len(pattern) != cfg["layers"] or pattern != cfg["layer_pattern_held"]:
        raise ValueError(f"layers_held {cfg['layers_held']} are {pattern!r} "
                         f"of the published pattern, not {cfg['layers']} "
                         f"layers {cfg['layer_pattern_held']!r}")
    return TransformerLM(
        vocab_size=cfg["vocab_rows_held"], dim=cfg["hidden_size"],
        num_heads=cfg["num_attention_heads"], num_layers=cfg["layers"],
        ffn_dim=cfg["moe_intermediate_size"], attention=cfg["attention"],
        mesh=mesh, dtype=jnp.dtype(cfg["compute_dtype"]),
        rms_norm_eps=cfg["layer_norm_epsilon"],
        num_experts=cfg["n_routed_experts"],
        experts_per_token=cfg["num_experts_per_tok"],
        balance_loss_weight=aux["balance_weight"],
        z_loss_weight=aux["z_weight"], init_std=cfg["init_std"],
        head_dim=cfg["head_dim"], num_kv_heads=cfg["num_key_value_heads"],
        rope_layers=(0,),               # no layer's attention has a position
        first_expert=cfg["first_expert"], experts_held=cfg["experts_held"],
        expert_activation=cfg["mlp_hidden_act"],
        normalize_top_k=cfg["norm_topk_prob"],
        remat_blocks=cfg["remat_blocks"], routing="sigmoid",
        route_scale=cfg["routed_scaling_factor"],
        shared_expert_dim=cfg["moe_shared_expert_intermediate_size"]
        * cfg["n_shared_experts"],
        bias_update_rate=cfg["bias_update_rate"],
        layer_kinds=cfg["layer_pattern_held"], expert_gated=False,
        ssm=SSMSpec(
            num_heads=cfg["mamba_num_heads"], head_dim=cfg["mamba_head_dim"],
            n_groups=cfg["n_groups"], state_size=cfg["ssm_state_size"],
            conv_kernel=cfg["conv_kernel"], chunk_size=cfg["chunk_size"],
            dt_min=cfg["time_step_min"], dt_max=cfg["time_step_max"],
            dt_floor=cfg["time_step_floor"]))


def build_optimizer(cfg: dict):
    import jax
    import optax

    o = cfg["optimizer"]
    rate = optax.linear_schedule(0.0, o["learning_rate"], o["warmup_steps"])
    return optax.chain(
        optax.clip_by_global_norm(o["clip_global_norm"]),
        # decay on the matrices (the stacked expert kernels and the
        # convolution's taps among them) alone: a norm's weight, the
        # convolution's bias, dt_bias, A_log and D have one dimension
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
