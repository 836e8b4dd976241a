"""ouro-2.6b: Ouro-2.6B (ByteDance, ``ouro``, a looped language model)
trained on packed 8,192-token sequences, through ETL ->
``FlaxEstimator.fit_on_frame``, as one pipeline stage of eight layers that
every one of the four passes crosses.

One row of the raw input is one packed sequence: ``tokens``, a fixed-size
list of ``seq_len`` int32 ids, and ``n_tokens``, how many of them are real
(the generator's are all full). The ids are a seeded Zipf over the whole
vocabulary (nothing is sliced: the model is dense and this chip holds the
embedding and the head whole), hashed so that frequent ids are spread over
the embedding's rows; documents of geometric length are joined by the
end-of-text id and attended across.

The ETL plan keeps the full sequences and the token column. The model is
``raydp_tpu.models.TransformerLM`` at the configuration's widths with what a
looped model needs switched on: ``total_ut_steps`` passes of the ``layers``
layers held on shared weights (one ``lax.scan`` in the program: each layer is
traced once), four norms a block, the final norm after every pass, the exit
gate and the expected loss over the passes less ``exit_entropy_weight`` times
the exit distribution's entropy. The estimator takes the loss from the model
(the four passes' hidden states through ONE fused head scan, float32), so no
``[B, T, vocab]`` logits exist in the train step.
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
    t, vocab = int(cfg["seq_len"]), int(cfg["vocab_size"])
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
    """The cell cut for a CPU rehearsal, counts only: two of the eight layers
    held, all four passes, 4 query heads on 4 K/V heads (plain multi-head, as
    published), 1,024 of the 49,152 vocabulary rows, 128 positions, 1
    sequence a step and 2 steps an epoch, a warm-up of 64 steps (inside 2,000
    the rehearsal's steps move no bfloat16 weight). Hidden 2048, heads of
    128, the feed-forward's 5632, the norms' eps, RoPE's theta and the
    entropy's weight stay."""
    cfg["layers"] = 2
    cfg["vocab_size"] = 1024
    cfg["input"]["eos_id"] = 1023
    cfg["seq_len"] = wl["seq_len"] = 128
    cfg["num_attention_heads"] = cfg["num_key_value_heads"] = 4
    cfg["compared_positions"] = 32
    cfg["optimizer"]["warmup_steps"] = 64
    wl["batch_per_replica"] = 1
    return 2 * chips


def build_model(cfg: dict, mesh=None):
    import jax.numpy as jnp

    from raydp_tpu.models import TransformerLM

    if (cfg["num_key_value_heads"] != cfg["num_attention_heads"]
            or cfg["sliding_window"] is not None
            or set(cfg["layer_types"]) != {"full_attention"}):
        raise ValueError("the ouro family: plain multi-head, full attention")
    return TransformerLM(
        vocab_size=cfg["vocab_size"], dim=cfg["hidden_size"],
        num_heads=cfg["num_attention_heads"], num_layers=cfg["layers"],
        ffn_dim=cfg["intermediate_size"], attention=cfg["attention"],
        mesh=mesh, dtype=jnp.dtype(cfg["compute_dtype"]),
        rms_norm_eps=cfg["rms_norm_eps"], rope_theta=float(cfg["rope_theta"]),
        init_std=cfg["init_std"], head_dim=cfg["head_dim"],
        remat_blocks=cfg["remat_blocks"], sandwich_norms=True,
        total_ut_steps=cfg["total_ut_steps"],
        exit_entropy_weight=cfg["exit_entropy_weight"],
        # a plain call's logits end in the four exit probabilities: check
        # (a) compares them beside the logits
        exit_probs_out=True)


def build_optimizer(cfg: dict):
    import jax
    import optax

    o = cfg["optimizer"]
    rate = optax.linear_schedule(0.0, o["learning_rate"], o["warmup_steps"])
    return optax.chain(
        optax.clip_by_global_norm(o["clip_global_norm"]),
        # decay on the matrices alone (the gate's [2048, 1] kernel is one): a
        # norm's weight and the gate's bias have one dimension
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


def exit_scale(cfg: dict) -> float:
    """What check (a) multiplies the exit probabilities by: the harness
    compares ONE relative RMS error over all the numbers of a position, and
    four probabilities of 1/8 to 1/2 beside ``compared_vocab`` logits of unit
    size would be a hundredth of its sum of squares: a gate wrong by 0.3
    would move the error by 0.03. Times ``sqrt(compared_vocab / passes)``
    their differences weigh what the logits' do, number for number."""
    return float(np.sqrt(cfg["compared_vocab"] / cfg["total_ut_steps"]))


def compared(outputs, cfg: dict):
    """Inside the jit: of a batch's output ``[B, T, vocab + passes]`` (the
    last pass's logits, then the exit probabilities), the last
    ``compared_positions`` positions over the first ``compared_vocab`` rows
    of the vocabulary and, beside them, the ``total_ut_steps`` exit
    probabilities times :func:`exit_scale`: ``[B, positions, compared_vocab
    + passes]``, so only those leave the device."""
    import jax.numpy as jnp

    out = outputs[:, -min(cfg["compared_positions"], outputs.shape[1]):]
    return jnp.concatenate([
        out[..., :cfg["compared_vocab"]],
        out[..., -cfg["total_ut_steps"]:] * exit_scale(cfg)], axis=-1)


def reference_inputs(table: pa.Table, info: dict):
    col = table[info["tokens"]].combine_chunks()
    return col.flatten().to_numpy().reshape(len(col), info["seq_len"])
