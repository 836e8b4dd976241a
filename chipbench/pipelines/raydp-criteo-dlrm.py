"""raydp-criteo-dlrm: pang-wu/raydp ``examples/pytorch_dlrm.ipynb`` at its
widths, on the Criteo Kaggle display-advertising schema (label, 13 integer
dense columns with nulls, 26 categorical columns), table rows from the Kaggle
set's per-column cardinalities.

The ETL plan is expressions only: cast / fill_null / log1p on the dense
columns, modulo the table's row count on the categorical ones (what
facebookresearch/dlrm's ``--max-ind-range`` does to hashed ids). The notebook's
dictionary ``pre_process`` is too slow to be a run's set-up (PERF.md, Open
questions). Raw categorical values are hashed 32-bit ids whose rank follows
Zipf(1.2); the label is drawn from a seeded logistic function of three dense
columns and one categorical column, so a broken gradient cannot lower the loss.
"""

from __future__ import annotations

import numpy as np
import pyarrow as pa

NUM_DENSE, NUM_CAT = 13, 26
LABEL = "_c0"
DENSE = [f"_c{i}" for i in range(1, NUM_DENSE + 1)]
CAT = [f"_c{i}" for i in range(NUM_DENSE + 1, NUM_DENSE + 1 + NUM_CAT)]
HASH = 2654435761       # Knuth's multiplicative hash: spreads ranks over ids


def table_rows(cfg: dict, wl: dict) -> list:
    """Rows of each table as this cell holds them. The program's rule shards
    a table only if the ``expert`` extent divides its rows, so every table is
    padded up to a multiple of the deployment's extent (at most one row);
    ``table_row_divisor`` then takes one chip's share of it."""
    e = int(cfg["deployment_mesh"]["expert"])
    d = int(wl.get("table_row_divisor", 1))
    return [-(-int(n) // e) * e // d for n in cfg["model"]["table_rows"]]


def generate(rows: int, seed: int, cfg: dict) -> pa.Table:
    rng = np.random.default_rng(seed)
    zipf_a = cfg["input"]["zipf_a"]
    cols = {}
    dense = rng.poisson(8, size=(NUM_DENSE, rows)).astype(np.int64)
    null = rng.random((NUM_DENSE, rows)) < cfg["input"]["dense_null_share"]
    ranks = np.minimum(rng.zipf(zipf_a, size=(NUM_CAT, rows)), 2 ** 31)
    ids = (ranks.astype(np.uint64) * np.uint64(HASH)) % np.uint64(2 ** 32)
    logit = (0.25 * (dense[0] - 8) - 0.2 * (dense[1] - 8)
             + 0.15 * (dense[2] - 8) + 1.5 * (ranks[0] == 1) - 1.6)
    logit = np.where(null[0], -1.6, logit)
    cols[LABEL] = (rng.random(rows) < 1 / (1 + np.exp(-logit))).astype(np.int64)
    for j, name in enumerate(DENSE):
        cols[name] = pa.array(dense[j], mask=null[j])
    for j, name in enumerate(CAT):
        cols[name] = ids[j].astype(np.int64)
    return pa.table(cols)


def etl(raw_df, cfg: dict, wl: dict):
    from raydp_tpu.etl import functions as F
    from raydp_tpu.etl.expressions import col

    df = raw_df
    sizes = table_rows(cfg, wl)
    for c in DENSE:
        df = df.withColumn(c, F.log1p(col(c).cast("double").fill_null(0.0)))
    for c, n in zip(CAT, sizes):
        df = df.withColumn(c, col(c) % n)
    return df, {"features": DENSE + CAT, "label": LABEL, "table_rows": sizes}


def build_estimator(cfg: dict, wl: dict, info: dict, **fit_args):
    import jax.numpy as jnp
    import optax

    from raydp_tpu.models import (DLRM, criteo_batch_preprocessor,
                                  dlrm_param_rules)
    from raydp_tpu.train import FlaxEstimator

    m = cfg["model"]
    mesh = fit_args["mesh"]
    sharded = mesh.shape.get("expert", 1) > 1
    return FlaxEstimator(
        model=DLRM(categorical_sizes=tuple(info["table_rows"]),
                   num_dense=NUM_DENSE, embedding_dim=m["embedding_dim"],
                   bottom_mlp=tuple(m["bottom_mlp"]),
                   top_mlp=tuple(m["top_mlp"]),
                   dtype=jnp.dtype(m["compute_dtype"])),
        optimizer=optax.adagrad(cfg["optimizer"]["learning_rate"]),
        loss=cfg["loss"], feature_columns=info["features"],
        label_column=info["label"], shuffle=cfg["shuffle"],
        feature_dtype=np.dtype(cfg["feature_dtype"]), label_dtype=np.float32,
        param_rules=dlrm_param_rules("expert") if sharded else None,
        batch_preprocessor=criteo_batch_preprocessor(NUM_DENSE), **fit_args)


def reference_inputs(table: pa.Table, info: dict):
    dense = np.stack([table[c].to_numpy().astype(np.float32) for c in DENSE], 1)
    sparse = np.stack([table[c].to_numpy().astype(np.int64) for c in CAT], 1)
    return dense, sparse
