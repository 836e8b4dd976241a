"""Criteo DLRM end-to-end — the port of the reference's heaviest workload
(examples/pytorch_dlrm.ipynb): Criteo-format TSV → distributed preprocessing
(frequency-limited categorical dictionaries via groupBy counts, log-transform
on numerics — the notebook's ``pre_process``) → DLRM with sharded embedding
tables trained under pjit.

Synthetic Criteo-shaped data is generated when no ``--tsv`` is given: 1 int
label, 13 int dense features with missing values, 26 categorical string
columns with a skewed (zipf) distribution — the reference's schema
(pytorch_dlrm.ipynb: LABEL_COL=0, INT_COLS=1..13, CAT_COLS=14..39).

Run: python examples/dlrm_criteo.py [--rows 200000] [--epochs 2]
     [--scale small|full]   # full = reference model dims (512-128-32 bottom,
                            # 1024-1024-512-256-1 top, 26×embedding_dim=32)
"""

from __future__ import annotations

import argparse
import os
import sys
import tempfile
import time

import numpy as np

sys.path.insert(0, os.path.dirname(__file__))
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

NUM_DENSE = 13
NUM_CAT = 26
LABEL = "_c0"
DENSE_COLS = [f"_c{i}" for i in range(1, NUM_DENSE + 1)]
CAT_COLS = [f"_c{i}" for i in range(NUM_DENSE + 1, NUM_DENSE + 1 + NUM_CAT)]


def generate_criteo(rows: int, path: str, seed: int = 0,
                    cat_cardinality: int = 1000) -> None:
    """Criteo-format TSV: label \\t 13 ints (w/ blanks) \\t 26 cat tokens."""
    rng = np.random.RandomState(seed)
    label = (rng.random_sample(rows) < 0.25).astype(np.int64)
    dense = rng.poisson(8, size=(rows, NUM_DENSE)).astype(object)
    dense[rng.random_sample(dense.shape) < 0.1] = ""  # missing values
    cats = np.empty((rows, NUM_CAT), dtype=object)
    for j in range(NUM_CAT):
        ids = rng.zipf(1.3, size=rows) % cat_cardinality
        cats[:, j] = np.char.add(f"t{j}_", ids.astype(str))
    with open(path, "w") as f:
        for i in range(rows):
            f.write("\t".join([str(label[i])]
                              + [str(v) for v in dense[i]]
                              + list(cats[i])) + "\n")


def pre_process(session, df, frequency_limit: int = 3):
    """The notebook's ``pre_process``: per-column frequency-limited dictionary
    (rank by count, ids dense from 1; rare/null → 0) built with distributed
    groupBy counts, then log(x+1) on the numeric columns."""
    from raydp_tpu.etl import functions as F
    from raydp_tpu.etl.expressions import col, udf

    sizes = []
    for c in CAT_COLS:
        counts = (df.groupBy(c).agg(F.count(c).alias("n"))
                  .to_pandas())
        counts = counts[counts["n"] >= frequency_limit]
        counts = counts.sort_values("n", ascending=False)
        mapping = {v: i + 1 for i, v in enumerate(counts[c])}
        sizes.append(len(mapping) + 1)  # 0 = rare/unseen
        to_id = udf("int64")(lambda v, m=mapping: m.get(v, 0))
        df = df.withColumn(c, to_id(col(c)))
    for c in DENSE_COLS:
        v = col(c).cast("double").fill_null(0.0)
        df = df.withColumn(c, F.log1p(v))
    return df, sizes


def main():
    import optax

    import raydp_tpu
    from raydp_tpu.models import DLRM, criteo_batch_preprocessor, \
        dlrm_param_rules
    from raydp_tpu.parallel import MeshSpec, make_mesh
    from raydp_tpu.train import FlaxEstimator

    ap = argparse.ArgumentParser()
    ap.add_argument("--rows", type=int, default=200_000)
    ap.add_argument("--epochs", type=int, default=2)
    ap.add_argument("--batch-size", type=int, default=4096)
    ap.add_argument("--num-executors", type=int, default=2)
    ap.add_argument("--scale", choices=["small", "full"], default="full")
    ap.add_argument("--tsv", default=None, help="real Criteo TSV path")
    args = ap.parse_args()

    tsv = args.tsv
    if tsv is None:
        tsv = os.path.join(tempfile.mkdtemp(), "criteo.tsv")
        print(f"generating {args.rows} Criteo-format rows ...")
        generate_criteo(args.rows, tsv)

    session = raydp_tpu.init("dlrm", num_executors=args.num_executors,
                             executor_cores=1, executor_memory="2GB")
    try:
        names = [LABEL] + DENSE_COLS + CAT_COLS
        df = session.read.csv(
            tsv, num_partitions=args.num_executors * 2,
            options={"delimiter": "\t", "column_names": names})
        t0 = time.perf_counter()
        df, cat_sizes = pre_process(session, df)
        print(f"pre_process: {time.perf_counter() - t0:.1f}s; "
              f"category sizes: min={min(cat_sizes)} max={max(cat_sizes)}")

        if args.scale == "full":
            # reference dims (pytorch_dlrm.ipynb)
            model_kw = dict(embedding_dim=32, bottom_mlp=(512, 128, 32),
                            top_mlp=(1024, 1024, 512, 256, 1))
        else:
            model_kw = dict(embedding_dim=8, bottom_mlp=(64, 8),
                            top_mlp=(64, 32, 1))

        import jax
        n_dev = len(jax.devices())
        expert = 2 if n_dev % 2 == 0 else 1
        mesh = make_mesh(MeshSpec(expert=expert))
        # a table shards over ``expert`` only if the extent divides its rows:
        # pad each up to a multiple (ids never reach a pad row)
        cat_sizes = [-(-n // expert) * expert for n in cat_sizes]
        import jax.numpy as jnp
        est = FlaxEstimator(
            model=DLRM(categorical_sizes=cat_sizes, num_dense=NUM_DENSE,
                       dtype=jnp.bfloat16, **model_kw),
            optimizer=optax.adagrad(1e-2),
            loss="bce_with_logits",
            feature_columns=DENSE_COLS + CAT_COLS,
            label_column=LABEL,
            feature_dtype=np.float64,
            label_dtype=np.float32,
            batch_size=args.batch_size,
            num_epochs=args.epochs,
            mesh=mesh,
            param_rules=dlrm_param_rules("expert") if expert > 1 else None,
            batch_preprocessor=criteo_batch_preprocessor(NUM_DENSE),
        )
        result = est.fit_on_frame(df)
        for row in result.history:
            print(row)
    finally:
        raydp_tpu.stop()


if __name__ == "__main__":
    main()
