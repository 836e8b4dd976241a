"""Host-feed decode: native staging kernel vs the numpy astype+stack path,
plus the end-to-end PIPELINE OVERLAP leg.

The streaming DeviceFeed's per-epoch host cost is dominated by this decode
for over-cap datasets (VERDICT r4 #3 / SURVEY §7 step 2). Shapes mirror the
bench workloads: NYCTaxi (25 f64 cols -> f32) and Criteo DLRM dense+cats
(13 f64 -> f32 + 26 i64 -> i32).

``--overlap`` runs the async double-buffered device feed (DevicePrefetcher,
raydp_tpu/data/feed.py) against a jitted per-batch compute and records the
per-phase split (decode/h2d vs compute): the pipelined wall-clock
coming in UNDER the sum of the phase walls is the direct evidence that
host staging and H2D placement are hidden behind device compute. The
record is persisted to ``benchmarks/HOST_DECODE_DETAIL.json``
(override: RDT_HOST_DECODE_DETAIL_PATH) so the overlap claim has an
artifact, not a narrative.

Run: python benchmarks/host_decode_bench.py [rows]
     python benchmarks/host_decode_bench.py --overlap [rows]
"""
import json
import os
import sys
import time

import numpy as np
import pyarrow as pa

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
from raydp_tpu.native.stage import native_stage_available, stage_table  # noqa: E402


def numpy_path(table, columns, dtype):
    return np.stack(
        [table.column(c).to_numpy(zero_copy_only=False).astype(dtype,
                                                               copy=False)
         for c in columns], axis=1)


def bench(name, table, columns, dtype, reps=5):
    # warm + correctness
    a = numpy_path(table, columns, dtype)
    b = stage_table(table, columns, np.dtype(dtype))
    assert b is not None, "kernel declined an eligible table"
    np.testing.assert_array_equal(a, b)

    t0 = time.perf_counter()
    for _ in range(reps):
        numpy_path(table, columns, dtype)
    t_np = (time.perf_counter() - t0) / reps

    t0 = time.perf_counter()
    for _ in range(reps):
        stage_table(table, columns, np.dtype(dtype))
    t_nat = (time.perf_counter() - t0) / reps

    rows = table.num_rows
    print(f"{name}: rows={rows} cols={len(columns)} "
          f"numpy={t_np * 1e3:.1f}ms native={t_nat * 1e3:.1f}ms "
          f"speedup={t_np / t_nat:.2f}x "
          f"({rows / t_nat / 1e6:.1f}M rows/s native)")


class _TableDataset:
    """The minimal dataset surface the feed needs (block_sizes / get_block),
    over in-memory Arrow tables — keeps the overlap leg free of the ETL
    runtime so it isolates the feed pipeline itself."""

    def __init__(self, tables):
        self._tables = list(tables)

    def num_blocks(self):
        return len(self._tables)

    def block_sizes(self):
        return [t.num_rows for t in self._tables]

    def get_block(self, i, zero_copy=False):
        return self._tables[i]


def overlap_run(rows=400_000, batch=8192, hidden=256, layers=2,
                prefetch_to_device=2, out_path=None):
    """One epoch of the streaming pipeline against a jitted MLP-shaped
    compute: per-phase walls (decode/h2d from the feed's thread-side
    timers, compute on the consumer clock) vs the pipeline wall-clock.

    ``overlap_hidden_s = sum(phases) - wall`` > 0 means the host phases ran
    WHILE the device computed — the double-buffering win the synchronous
    feed cannot have (its wall is exactly the sum of its phases)."""
    import jax
    import jax.numpy as jnp

    from raydp_tpu.data.feed import DeviceFeed

    n_cols = 25
    rng = np.random.RandomState(0)
    n_blocks = 8
    per = rows // n_blocks
    tables = [pa.table({f"f{i}": rng.randn(per) for i in range(n_cols)})
              for _ in range(n_blocks)]
    ds = _TableDataset(tables)
    columns = {"features": ([f"f{i}" for i in range(n_cols)], np.float32),
               "label": ("f0", np.float32)}
    feed = DeviceFeed(ds, batch, columns, shuffle=False,
                      prefetch_to_device=prefetch_to_device)

    w1 = jnp.asarray(rng.randn(n_cols, hidden).astype(np.float32))
    w2 = jnp.asarray(rng.randn(hidden, hidden).astype(np.float32))

    @jax.jit
    def compute(feats):
        h = jnp.tanh(feats @ w1)
        for _ in range(layers):
            h = jnp.tanh(h @ w2)
        return h.sum()

    # warm the compile outside the timed window
    jax.block_until_ready(compute(jnp.zeros((batch, n_cols), jnp.float32)))

    compute_s = 0.0
    steps = 0
    t_wall = time.perf_counter()
    for item in feed:
        t0 = time.perf_counter()
        jax.block_until_ready(compute(item["features"]))
        compute_s += time.perf_counter() - t0
        steps += 1
    wall = time.perf_counter() - t_wall
    phases = feed.timings.take()
    sum_phases = phases["decode"] + phases["h2d"] + compute_s
    record = {
        "rows": rows, "batch": batch,
        "prefetch_to_device": prefetch_to_device, "steps": steps,
        "platform": jax.devices()[0].platform,
        "wall_s": round(wall, 3),
        "decode_s": round(phases["decode"], 3),
        "h2d_s": round(phases["h2d"], 3),
        "compute_s": round(compute_s, 3),
        "sum_phases_s": round(sum_phases, 3),
        "overlap_hidden_s": round(sum_phases - wall, 3),
        "overlapped": bool(wall < sum_phases),
    }
    # rdtlint: allow[knob-registry] bench output-path plumbing, not a runtime knob
    path = out_path or os.environ.get(
        "RDT_HOST_DECODE_DETAIL_PATH",
        os.path.join(os.path.dirname(os.path.abspath(__file__)),
                     "HOST_DECODE_DETAIL.json"))
    with open(path, "w") as fh:
        json.dump(record, fh, indent=1)
    print(json.dumps(record))
    return record


def main():
    args = [a for a in sys.argv[1:] if a != "--overlap"]
    rows = int(args[0]) if args else 400_000
    if "--overlap" in sys.argv[1:]:
        overlap_run(rows=rows)
        return
    if not native_stage_available():
        raise SystemExit("native staging kernel unavailable")
    rng = np.random.RandomState(0)

    nyctaxi = pa.table({f"f{i}": rng.randn(rows) for i in range(25)})
    bench("nyctaxi-features f64->f32", nyctaxi,
          [f"f{i}" for i in range(25)], np.float32)

    dense = pa.table({f"d{i}": rng.randn(rows) for i in range(13)})
    bench("dlrm-dense f64->f32", dense, [f"d{i}" for i in range(13)],
          np.float32)

    cats = pa.table({f"c{i}": rng.randint(0, 1 << 20, rows)
                     for i in range(26)})
    bench("dlrm-cats i64->i32", cats, [f"c{i}" for i in range(26)], np.int32)


if __name__ == "__main__":
    main()
