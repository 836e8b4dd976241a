"""Benchmark matrix: ETL→train end-to-end plus flagship-kernel throughput.

Prints ONE JSON line (primary metric = the BASELINE.json headline config:
NYCTaxi ETL→train samples/sec/chip) with the other configs under ``extra``:

- ``nyctaxi``      CSV → distributed feature ETL → pjit MLP (FlaxEstimator)
- ``gbdt``         XLA histogram-GBDT on the NYCTaxi shape (xgboost parity)
- ``keras``        the TFEstimator-parity path (Keras 3 on JAX)
- ``gang``         1/2/4-rank jax.distributed DP gang (raytrain-8-worker /
                   horovod BASELINE configs; CPU ranks, labeled as such)
- ``transformer``  TransformerLM fwd+bwd tokens/s + MFU at long context,
                   flash (Pallas) vs dense attention
- ``dlrm``         Criteo-format TSV → dictionary/log preprocess → DLRM
                   (reference examples/pytorch_dlrm.ipynb workload shape)

Each selected config runs in its own subprocess, in order, under a hard wall
cap. The parent never imports jax — one process owns the chip at a time, and
the final JSON line is emitted no matter what any config does. The run needs
a TPU: a default run that finds none exits non-zero, and so does a run in
which any config failed or was killed at its cap (after printing what it
has). ``JAX_PLATFORMS=cpu`` in the caller's environment asks for the CPU
instead: every config then scales itself down to CPU-feasible shapes and the
results are labelled ``cpu``.

``vs_baseline`` compares against the self-measured reference workload: the
reference publishes no numbers (BASELINE.md), so round 2 measured its
examples/pytorch_nyctaxi.py pipeline — same data, same preprocessing, same
5-layer BatchNorm MLP, torch CPU (the reference's own CI hardware class) via
benchmarks/reference_nyctaxi_torch.py. Select configs with e.g.
``BENCH_CONFIGS=nyctaxi,transformer``.
"""

from __future__ import annotations

import json
import os
import signal
import subprocess
import sys
import tempfile
import time
from functools import partial
from typing import Optional, Tuple

# Self-measured reference numbers (benchmarks/reference_nyctaxi_torch.py,
# 400k rows, torch 2.13 CPU, 2026-07-29; see BASELINE.md):
REF_NYCTAXI_B8192 = 69_924.2   # samples/s, batch 8192 (apples-to-apples)
REF_NYCTAXI_B64 = 26_456.9     # samples/s, batch 64 (as the reference ships)

ROWS = int(os.environ.get("BENCH_ROWS", "400000"))
EPOCHS = int(os.environ.get("BENCH_EPOCHS", "5"))
BATCH = int(os.environ.get("BENCH_BATCH", "8192"))
DLRM_ROWS = int(os.environ.get("BENCH_DLRM_ROWS", "120000"))
SEQ_LEN = int(os.environ.get("BENCH_SEQ_LEN", "8192"))

CONFIG_ORDER = ["nyctaxi", "transformer", "gbdt", "dlrm", "dlrm_stream",
                "keras", "gang"]
#: hard per-config wall caps (seconds) — a config that blows its cap is
#: killed and recorded as a timeout; the matrix continues and the run fails
CONFIG_CAPS_S = {"nyctaxi": 300, "gbdt": 300, "keras": 240, "gang": 480,
                 "transformer": 390, "dlrm": 330, "dlrm_stream": 330}

RESULT_MARK = "##BENCH_RESULT## "


def _on_cpu() -> bool:
    """The caller asked for the CPU in the environment."""
    return os.environ.get("JAX_PLATFORMS", "").strip().lower() == "cpu"


def _tabular_dtype():
    """Compute dtype for the MLP/DLRM estimator configs: bf16 feeds the MXU
    on TPU; a CPU run emulates bf16 slowly (the BENCH_LOCAL_R5_CPU.json
    record), and the torch-CPU baseline is f32 anyway, so f32-vs-f32 is the
    fairer comparison there. The transformer keeps bf16 on every platform —
    its CPU run got slower in f32 (twice the bytes through the [B,T,V]
    logits and GEMMs outweigh the emulation cost at that shape)."""
    import jax.numpy as jnp
    return jnp.float32 if _on_cpu() else jnp.bfloat16


def _apply_cpu_scaledown() -> None:
    """Shrink every knob to CPU-feasible shapes (T=8192 is a shape only a
    TPU can finish). Runs only when the caller asked for the CPU."""
    global ROWS, EPOCHS, DLRM_ROWS, SEQ_LEN, BATCH
    ROWS = min(ROWS, 100_000)
    DLRM_ROWS = min(DLRM_ROWS, 30_000)
    SEQ_LEN = min(SEQ_LEN, 1024)
    BATCH = min(BATCH, 4096)
    env = os.environ
    env["BENCH_LM_DIM"] = str(min(int(env.get("BENCH_LM_DIM", "256")), 256))
    env["BENCH_LM_HEAD_DIM"] = "64"
    env["BENCH_LM_LAYERS"] = str(min(int(env.get("BENCH_LM_LAYERS", "2")), 2))
    env["BENCH_LM_STEPS"] = str(min(int(env.get("BENCH_LM_STEPS", "2")), 2))
    env["BENCH_LM_BATCH"] = "1"
    env["BENCH_GBDT_ROUNDS"] = str(
        min(int(env.get("BENCH_GBDT_ROUNDS", "5")), 5))


def _num_chips() -> int:
    import jax
    return max(1, len(jax.devices()))


def _probe_device() -> Optional[Tuple[str, str, int]]:
    """(platform, device_kind, count) as a fresh process sees the default
    backend, or None when it cannot initialise one. A subprocess, so the
    parent stays off jax; it holds the chip only until it exits."""
    proc = subprocess.run(
        [sys.executable, "-c",
         "import jax; d = jax.devices(); "
         "print('ok', d[0].platform, len(d), d[0].device_kind)"],
        stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
        timeout=240)
    for line in proc.stdout.splitlines():
        if line.startswith("ok "):
            _, platform, count, kind = line.split(" ", 3)
            return platform.lower(), kind.strip(), int(count)
    print(proc.stderr[-2000:], file=sys.stderr)
    return None


def _kill_group(proc: subprocess.Popen) -> None:
    """Terminate a config subprocess AND everything it spawned (executor
    actors, gang ranks). No unbounded wait: a child stuck in an
    uninterruptible device ioctl is unreapable, and waiting on it would
    recreate the hang here."""
    for sig in (signal.SIGTERM, signal.SIGKILL):
        try:
            os.killpg(proc.pid, sig)
        except (ProcessLookupError, PermissionError):
            return
        try:
            proc.wait(timeout=5)
            return
        except subprocess.TimeoutExpired:
            continue


def _steady(history):
    """Steady-state samples/s: total samples over total wall across epochs
    after the first (compile epoch). One long window is far more stable than
    averaging per-epoch rates, which swing with host load."""
    rows = history[1:] or history
    wall = sum(r.get("epoch_time_s", 0.0) for r in rows)
    if wall <= 0:
        return sum(r["samples_per_s"] for r in rows) / max(len(rows), 1)
    samples = sum(r["samples_per_s"] * r.get("epoch_time_s", 0.0) for r in rows)
    return samples / wall


def _feed_split(history) -> dict:
    """Aggregate the feed/dispatch/sync wall split the estimator records per
    epoch (host-boundness evidence, round-3 verdict Weak #2), plus the
    pipeline's thread-side decode/h2d phase split (ISSUE 1: the
    measured attribution of host staging vs device time; phase walls overlap
    dispatch by design, so they attribute the epoch, they don't sum to it)."""
    rows = [r for r in history[1:] if "feed_time_s" in r]
    if not rows:
        return {}
    out = {
        "feed_s": round(sum(r["feed_time_s"] for r in rows), 2),
        "dispatch_s": round(sum(r["dispatch_time_s"] for r in rows), 2),
        "device_sync_s": round(sum(r["sync_time_s"] for r in rows), 2),
    }
    if any(r.get("h2d_time_s") is not None for r in rows):
        out.update(
            decode_s=round(sum(r.get("decode_time_s", 0.0) for r in rows), 2),
            h2d_s=round(sum(r.get("h2d_time_s", 0.0) for r in rows), 2),
        )
    return out


# steady-state averages over epochs[1:]: anything fewer than 3 epochs leaves
# a single-epoch window
STEADY_EPOCHS = max(3, EPOCHS // 2 + 1)


# --------------------------------------------------------------------- nyctaxi
def bench_nyctaxi() -> dict:
    import optax

    import raydp_tpu
    from generate_nyctaxi import generate
    from nyctaxi_features import LABEL, feature_columns, nyc_taxi_preprocess
    from raydp_tpu.models import NYCTaxiModel
    from raydp_tpu.train import FlaxEstimator
    import jax.numpy as jnp

    tmp = tempfile.mkdtemp(prefix="rdt-bench-")
    csv_path = os.path.join(tmp, "nyctaxi.csv")
    generate(ROWS).to_csv(csv_path, index=False)

    session = raydp_tpu.init("bench", num_executors=2, executor_cores=2,
                             executor_memory="2GB")
    try:
        data = session.read.csv(csv_path, num_partitions=4)
        data = nyc_taxi_preprocess(data)
        features = feature_columns(data)
        est = FlaxEstimator(
            model=NYCTaxiModel(dtype=_tabular_dtype()),
            optimizer=optax.adam(1e-3),
            loss="smooth_l1",
            feature_columns=features,
            label_column=LABEL,
            batch_size=BATCH,
            num_epochs=EPOCHS,
            shuffle=True,
        )
        t0 = time.perf_counter()
        result = est.fit_on_frame(data)
        wall = time.perf_counter() - t0
        out = {"samples_per_s_per_chip": _steady(result.history) / _num_chips(),
               "wall_s": round(wall, 1), "rows": ROWS, "batch": BATCH}
        out.update(_feed_split(result.history))
        return out
    finally:
        raydp_tpu.stop()


# ----------------------------------------------------------------------- dlrm
def bench_dlrm() -> dict:
    import numpy as np
    import optax

    import raydp_tpu
    from dlrm_criteo import (
        CAT_COLS, DENSE_COLS, LABEL, NUM_DENSE, generate_criteo, pre_process,
    )
    from raydp_tpu.models import DLRM, criteo_batch_preprocessor
    from raydp_tpu.train import FlaxEstimator
    import jax.numpy as jnp

    tsv = os.path.join(tempfile.mkdtemp(prefix="rdt-bench-"), "criteo.tsv")
    generate_criteo(DLRM_ROWS, tsv)
    session = raydp_tpu.init("bench-dlrm", num_executors=2, executor_cores=2,
                             executor_memory="2GB")
    try:
        names = [LABEL] + DENSE_COLS + CAT_COLS
        df = session.read.csv(tsv, num_partitions=4,
                              options={"delimiter": "\t",
                                       "column_names": names})
        t_etl = time.perf_counter()
        df, cat_sizes = pre_process(session, df)
        est = FlaxEstimator(
            model=DLRM(categorical_sizes=cat_sizes, num_dense=NUM_DENSE,
                       embedding_dim=32, bottom_mlp=(512, 128, 32),
                       top_mlp=(1024, 1024, 512, 256, 1),
                       dtype=_tabular_dtype()),
            optimizer=optax.adagrad(1e-2),
            loss="bce_with_logits",
            feature_columns=DENSE_COLS + CAT_COLS,
            label_column=LABEL,
            feature_dtype=np.float64,
            batch_size=min(4096, BATCH),
            num_epochs=max(STEADY_EPOCHS, 4),
            batch_preprocessor=criteo_batch_preprocessor(NUM_DENSE),
        )
        result = est.fit_on_frame(df)
        wall = time.perf_counter() - t_etl
        out = {"samples_per_s_per_chip": _steady(result.history) / _num_chips(),
               "wall_s": round(wall, 1), "rows": DLRM_ROWS}
        out.update(_feed_split(result.history))
        return out
    finally:
        raydp_tpu.stop()


# ---------------------------------------------------------------- dlrm_stream
def bench_dlrm_stream() -> dict:
    """The HBM-overflow regime: the residency gate forced off, so training
    runs through the streaming DeviceFeed (background host decode and
    per-batch transfers) instead of the resident epoch cache — the
    realistic Criteo-at-scale case where the dataset cannot live in HBM
    (reference examples/pytorch_dlrm.ipynb). The
    feed/dispatch/sync split in the entry is the host-boundness evidence."""
    os.environ["RDT_DEVICE_CACHE"] = "0"
    out = bench_dlrm()
    out["streaming_forced"] = True
    return out


# ---------------------------------------------------------------------- keras
def bench_keras() -> dict:
    os.environ.setdefault("KERAS_BACKEND", "jax")
    import raydp_tpu
    from generate_nyctaxi import generate
    from nyctaxi_features import LABEL, feature_columns, nyc_taxi_preprocess
    from raydp_tpu.train import KerasEstimator

    tmp = tempfile.mkdtemp(prefix="rdt-bench-")
    csv_path = os.path.join(tmp, "nyctaxi.csv")
    generate(min(ROWS, 200_000)).to_csv(csv_path, index=False)
    session = raydp_tpu.init("bench-keras", num_executors=2, executor_cores=2,
                             executor_memory="2GB")
    try:
        data = session.read.csv(csv_path, num_partitions=4)
        data = nyc_taxi_preprocess(data)
        features = feature_columns(data)

        def build():
            import keras
            # the NYCTaxiModel shape (256-128-64-32-1 + BatchNorm), so the
            # keras and flax paths train the same model and their numbers
            # isolate estimator overhead, not model size (round-3 Weak #6)
            model = keras.Sequential([keras.layers.Input(shape=(len(features),))])
            for width in (256, 128, 64, 32):
                model.add(keras.layers.Dense(width, activation="relu"))
                model.add(keras.layers.BatchNormalization())
            model.add(keras.layers.Dense(1))
            return model

        epochs = STEADY_EPOCHS
        est = KerasEstimator(
            model_builder=build, optimizer="adam", loss="mse",
            feature_columns=features, label_column=LABEL,
            batch_size=min(BATCH, 4096), num_epochs=epochs,
            data_parallel=_num_chips() > 1)
        t0 = time.perf_counter()
        result = est.fit_on_frame(data)
        wall = time.perf_counter() - t0
        return {"samples_per_s_per_chip": _steady(result.history) / _num_chips(),
                "final_loss": result.history[-1].get("loss"),
                "model": "nyctaxi-mlp-bn", "wall_s": round(wall, 1)}
    finally:
        raydp_tpu.stop()


# ----------------------------------------------------------------------- gbdt
def bench_gbdt() -> dict:
    """GBDT training on the NYCTaxi shape (BASELINE workload
    examples/xgboost_ray_nyctaxi.py:60-75: hist trees, 90/10 split,
    fare_amount label, num_boost_round=10, per-round eval). Throughput =
    training rows × boosting rounds / fit wall — each round is one full
    histogram pass over every row, the hist-method unit of work."""
    import raydp_tpu
    from generate_nyctaxi import generate
    from nyctaxi_features import LABEL, feature_columns, nyc_taxi_preprocess
    from raydp_tpu.train import GBDTEstimator
    from raydp_tpu.utils import random_split

    rows = min(ROWS, 200_000)
    rounds = int(os.environ.get("BENCH_GBDT_ROUNDS", "10"))
    tmp = tempfile.mkdtemp(prefix="rdt-bench-")
    csv_path = os.path.join(tmp, "nyctaxi.csv")
    generate(rows).to_csv(csv_path, index=False)
    session = raydp_tpu.init("bench-gbdt", num_executors=2, executor_cores=2,
                             executor_memory="2GB")
    try:
        data = session.read.csv(csv_path, num_partitions=4)
        data = nyc_taxi_preprocess(data)
        features = feature_columns(data)
        train_df, test_df = random_split(data, [0.9, 0.1], 0)
        est = GBDTEstimator(
            params={"tree_method": "hist", "max_depth": 6},
            feature_columns=features, label_column=LABEL,
            num_boost_round=rounds)
        t_etl = time.perf_counter()
        train_ds, eval_ds = est._convert_frames(train_df, test_df)
        t0 = time.perf_counter()
        result = est.fit(train_ds, eval_ds)
        wall = time.perf_counter() - t0
        n_train = int(rows * 0.9)
        report = result.history[-1]
        return {"samples_per_s_per_chip":
                round(n_train * rounds / wall / _num_chips(), 1),
                "throughput_def": "train_rows*rounds/fit_wall",
                "rows": rows, "rounds": rounds,
                "train_rmse": report.get("train_rmse"),
                "eval_rmse": report.get("eval_rmse"),
                "fit_wall_s": round(wall, 1),
                "wall_s": round(time.perf_counter() - t_etl, 1)}
    finally:
        raydp_tpu.stop()


# ----------------------------------------------------------------------- gang
def bench_gang() -> dict:
    """Multi-worker data-parallel gang (BASELINE.json configs: "NYCTaxi MLP
    via raytrain_nyctaxi.py (Ray Train data-parallel, 8 workers)" and the
    Horovod-allreduce→psum port), swept at 1/2/4 rank processes over a FIXED
    8-virtual-CPU-device global mesh (8/4/2 devices per rank): same global
    batch and model at every width, so the curve isolates gang-orchestration
    cost — process fan-out, per-rank host feed, cross-process collectives —
    from compute. Ranks are pinned to CPU (two processes cannot share the one
    physical TPU chip), labeled cpu-gang; ``scaling`` is throughput relative
    to the 1-worker gang.

    What this sweep can and cannot show: this host exposes ONE schedulable
    CPU core (``os.sched_getaffinity`` = {0}), so every rank process
    timeshares that core and aggregate compute is constant at any width —
    rank scaling >1.0 is physically impossible here. The r4 sweep recorded
    ~0.5 at 2 ranks and the r5 diagnosis isolated ONE mechanism
    (benchmarks/gang_collective_microbench.py): the per-step XLA-inserted
    gradient all-reduces cost ~90 ms/step in-process and ~192 ms/step the
    moment they cross a process boundary on this host's loopback distributed
    backend, amplified by the ranks timesharing one core. The r5 record
    itself showed that mechanism accounts for roughly HALF the observed
    train-loop delta (``collective_mechanism_ratio`` ≈ 1.9-2.0) — so the
    in-run microbench now measures 1/2/4 ranks (the 4-rank leg replaces the
    old extrapolation) and the per-rank histories
    carry the feed pipeline's decode/h2d split, so the residual
    half is attributed by measurement (host-side staging/dispatch
    serialization vs collective latency) instead of narrated away. It is
    NOT duplicated per-rank decode: the steady clock excludes the compile
    epoch, and ``feed_s`` stays ~0.01 s/epoch at every width (the
    decoded-block cache works). On a real multi-host TPU mesh the same
    all-reduces ride ICI at hardware bandwidth and overlap compute, so this
    loopback cost does not transfer. Per-width entries carry
    ``first_epoch_wall_s`` (compile) vs ``steady_epoch_wall_s`` and the
    feed split so the reader can audit the clock.
    """
    import optax

    import raydp_tpu
    from generate_nyctaxi import generate
    from nyctaxi_features import LABEL, feature_columns, nyc_taxi_preprocess
    from raydp_tpu.data import from_frame_recoverable
    from raydp_tpu.models import NYCTaxiModel
    from raydp_tpu.train import FlaxEstimator

    rows = min(ROWS, 120_000)
    host_cpus = len(os.sched_getaffinity(0))
    tmp = tempfile.mkdtemp(prefix="rdt-bench-")
    csv_path = os.path.join(tmp, "nyctaxi.csv")
    generate(rows).to_csv(csv_path, index=False)
    # a wide virtual node: the widest gang's 4 rank bundles must fit beside
    # the 2 executors regardless of the host's advertised core count
    session = raydp_tpu.init("bench-gang", num_executors=2, executor_cores=1,
                             executor_memory="2GB",
                             virtual_nodes=[{"CPU": 16.0,
                                             "memory": float(8 << 30)}])
    try:
        data = session.read.csv(csv_path, num_partitions=4)
        data = nyc_taxi_preprocess(data)
        features = feature_columns(data)
        ds = from_frame_recoverable(data)

        sweep = {}
        for workers in (1, 2, 4):
            est = FlaxEstimator(
                model=NYCTaxiModel(),
                optimizer=optax.adam(1e-3),
                loss="smooth_l1",
                feature_columns=features,
                label_column=LABEL,
                batch_size=min(BATCH, 4096),
                num_epochs=3,
                shuffle=False,
            )
            t0 = time.perf_counter()
            result = est.fit_gang(
                ds, num_workers=workers, run_timeout=1800.0,
                worker_env={
                    "JAX_PLATFORMS": "cpu",
                    "XLA_FLAGS": "--xla_force_host_platform_device_count="
                                 f"{8 // workers}",
                })
            hist = result.history
            steady = hist[1:] or hist
            entry = {
                "samples_per_s": round(_steady(hist), 1),
                "final_loss": hist[-1].get("train_loss"),
                "wall_s": round(time.perf_counter() - t0, 1),
                # compile vs steady separation: the first
                # epoch carries each rank's jit compile; the steady clock
                # never includes it
                "first_epoch_wall_s": round(hist[0]["epoch_time_s"], 2),
                "steady_epoch_wall_s": round(
                    sum(r["epoch_time_s"] for r in steady) / len(steady), 2),
                "steps_per_epoch": hist[-1].get("steps"),
            }
            entry.update(_feed_split(hist))
            sweep[workers] = entry
        base = sweep[1]["samples_per_s"] or 1.0
        steps = float(sweep[1].get("steps_per_epoch") or 1)
        base_step_ms = sweep[1]["steady_epoch_wall_s"] / steps * 1e3
        # per-step cost each width's cross-process all-reduces added to the
        # TRAIN loop (derived from the steady epoch walls) ...
        collective_delta_ms = {
            str(w): round(
                (v["steady_epoch_wall_s"] - sweep[1]["steady_epoch_wall_s"])
                / steps * 1e3, 1)
            for w, v in sweep.items()}
        out = {"samples_per_s_gang": sweep[2]["samples_per_s"],
               "devices": 8, "platform": "cpu-gang", "rows": rows,
               "host_cpus": host_cpus,
               "sweep": {str(w): v for w, v in sweep.items()},
               "scaling": {str(w): round(v["samples_per_s"] / base, 3)
                           for w, v in sweep.items()},
               "collective_delta_ms_per_step": collective_delta_ms}
        # checkpoint the completed sweep before the optional microbench: a
        # microbench stall at the cap must not erase the measured sweep
        print(RESULT_MARK + json.dumps(out), flush=True)
        # ... versus the INDEPENDENT measurement: the same gradient-leaf psum
        # pattern with zero model compute (benchmarks/
        # gang_collective_microbench.py), run fresh here at 1 and 2 ranks.
        # The non-circular criterion: the train loop's 2-rank delta should
        # match the pure-collective delta — overhead beyond it would be real
        # gang-machinery waste (duplicated feed/decode/compile work), which
        # feed_s and the first_epoch/steady split also rule out directly.
        try:
            import importlib.util as _ilu
            spec = _ilu.spec_from_file_location(
                "gang_collective_microbench",
                os.path.join(os.path.dirname(os.path.abspath(__file__)),
                             "benchmarks", "gang_collective_microbench.py"))
            micro = _ilu.module_from_spec(spec)
            spec.loader.exec_module(micro)
            ms1, ms2 = micro.measure(1, timeout=180), \
                micro.measure(2, timeout=180)
            psum_delta = max(ms2 - ms1, 1e-6)
            out["psum_microbench_ms_per_step"] = {
                "1": round(ms1, 1), "2": round(ms2, 1)}
            out["scaling_predicted_by_collective_latency"] = round(
                base_step_ms / (base_step_ms + psum_delta), 3)
            # train-loop delta vs pure-collective delta at 2 ranks: ~1 means
            # the scaling loss IS collective latency; r5 recorded ~2 — half
            # the loss sits OUTSIDE the collective mechanism, which is what
            # the per-phase feed split in the sweep entries now attributes
            out["collective_mechanism_ratio"] = round(
                float(collective_delta_ms["2"]) / psum_delta, 2)
            # checkpoint before the 4-rank leg: it is the longest and a
            # stall there must not erase the 1/2-rank measurements
            print(RESULT_MARK + json.dumps(out), flush=True)
            ms4 = micro.measure(4, timeout=240)
            out["psum_microbench_ms_per_step"]["4"] = round(ms4, 1)
            # the 4-rank mechanism ratio is measured in-run like the 2-rank one
            out["collective_mechanism_ratio_4"] = round(
                float(collective_delta_ms["4"]) / max(ms4 - ms1, 1e-6), 2)
        except Exception as e:  # noqa: BLE001 - the sweep stands alone
            out["psum_microbench_error"] = f"{type(e).__name__}: {e}"[:200]
        out["scaling_note"] = (
            "single-core host: ranks timeshare one CPU, so >1.0 scaling is "
            "impossible. 'collective_mechanism_ratio' (train-loop 2-rank "
            "delta / pure-psum delta, microbench in-run at 1/2/4 ranks — "
            "benchmarks/gang_collective_microbench.py) near 1 means the "
            "loss IS cross-process all-reduce latency; r5 recorded ~2, i.e. "
            "half the loss sits outside the collective mechanism — the "
            "per-width decode/h2d/dispatch/sync split in 'sweep' "
            "attributes that residual (duplicated decode would show in "
            "decode_s, host dispatch serialization in dispatch_s). feed_s "
            "~0 and the first_epoch/steady split rule out re-decode and "
            "compile as causes"
            if host_cpus <= 1 else "")
        return out
    finally:
        raydp_tpu.stop()


# ---------------------------------------------------------------- transformer
#: per-chip peak bf16 FLOP/s by exact ``device_kind``, each with its source.
#: A device that is not in the table is an error, not a default.
_PEAK_BF16 = {
    # Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16 per chip
    "TPU v5 lite": 197e12,
}


def _peak_flops(device) -> float:
    try:
        return _PEAK_BF16[device.device_kind]
    except KeyError:
        raise RuntimeError(
            f"no peak FLOP/s on record for device_kind "
            f"{device.device_kind!r}; add it to _PEAK_BF16 with its source "
            f"(known: {sorted(_PEAK_BF16)})") from None


def _lm_mode_run(mode: str, T: int) -> dict:
    """One TransformerLM fwd+bwd timing at sequence length ``T``."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    from raydp_tpu.models import TransformerLM, lm_loss
    from raydp_tpu.models.transformer import lm_loss_fused

    # flagship shape (ROOFLINE_LM.md): dim=1024 deepens every dense GEMM's
    # contraction (K=1024 = 8 MXU passes) and head_dim=128 feeds the MXU
    # full 128-lanes inside the flash kernel (~60% vs ~51% at head_dim=64)
    dim = int(os.environ.get("BENCH_LM_DIM", "1024"))
    head_dim = int(os.environ.get("BENCH_LM_HEAD_DIM", "128"))
    if dim % head_dim:
        raise SystemExit("BENCH_LM_DIM must be a multiple of "
                         "BENCH_LM_HEAD_DIM")
    layers = int(os.environ.get("BENCH_LM_LAYERS", "8"))
    heads, vocab = dim // head_dim, 32768
    B = int(os.environ.get("BENCH_LM_BATCH", "2"))
    steps = int(os.environ.get("BENCH_LM_STEPS", "8"))
    rng = np.random.RandomState(0)
    tokens = jnp.asarray(rng.randint(0, vocab, size=(B, T)), jnp.int32)

    model = TransformerLM(vocab_size=vocab, dim=dim, num_heads=heads,
                          num_layers=layers, attention=mode,
                          # bf16 on EVERY platform: the CPU completeness run
                          # measured slower in f32 (see _tabular_dtype)
                          dtype=jnp.bfloat16)
    params = model.init(jax.random.PRNGKey(0), tokens)["params"]
    tx = optax.adam(1e-3)
    opt = tx.init(params)

    # all `steps` train steps are CHAINED on device inside one executable and
    # the final loss is fetched as a host float: the timed window is one
    # dispatch that ends in a fetched value
    from jax import lax

    # BENCH_LM_FUSED: 0 = materialized [B,T,V] f32 logits; 1 and 2 both = the
    # one chunked fused head loss (lm_loss_fused: both head gradients taken
    # in the forward scan, no logits kept, nothing recomputed). "2" used to
    # select a scan without remat; that choice is gone and it maps to "1".
    fused = os.environ.get("BENCH_LM_FUSED", "0")

    def step_loss(p, tokens):
        if fused in ("1", "2"):
            hidden = model.apply({"params": p}, tokens, return_hidden=True)
            return lm_loss_fused(hidden, p["lm_head"]["kernel"], tokens)
        return lm_loss(model.apply({"params": p}, tokens), tokens)

    @partial(jax.jit, donate_argnums=(0, 1))
    def run_steps(params, opt, tokens):
        def body(carry, _):
            params, opt = carry
            loss, grads = jax.value_and_grad(
                lambda p: step_loss(p, tokens))(params)
            upd, opt = tx.update(grads, opt, params)
            return (optax.apply_updates(params, upd), opt), loss

        (params, opt), losses = lax.scan(body, (params, opt), None,
                                         length=steps)
        return params, opt, losses[-1]

    params, opt, loss = run_steps(params, opt, tokens)  # compile + warm
    float(loss)
    t0 = time.perf_counter()
    params, opt, loss = run_steps(params, opt, tokens)
    loss = float(loss)
    dt = time.perf_counter() - t0
    tok_s = B * T * steps / dt

    n_params = sum(int(np.prod(p.shape)) for p in jax.tree.leaves(params))
    # train FLOPs/token ≈ 6·(P − embed) + 6·L·d·T: the embedding table is
    # a gather, not a matmul (the lm_head, same size, IS one and stays in
    # P); attention is causal, hence T/2 effective keys per query
    matmul_params = n_params - vocab * dim
    flops_per_tok = 6 * matmul_params + 6 * layers * dim * T
    entry = {"tokens_per_s": round(tok_s, 1), "seq_len": T,
             "loss": round(float(loss), 3), "dim": dim,
             "head_dim": head_dim, "layers": layers,
             "params_m": round(n_params / 1e6, 1)}
    if not _on_cpu():   # a CPU run has no device peak to be a share of
        entry["mfu"] = round(
            tok_s * flops_per_tok / _peak_flops(jax.devices()[0]), 4)
    return entry


def bench_transformer() -> dict:
    """TransformerLM fwd+bwd at long context: tokens/s and MFU, Pallas flash
    vs dense attention.

    Per-mode isolation: dense attention materializes the full T×T score
    matrix and OOMs HBM at long context on a single chip (observed: 20.25G
    needed vs 15.75G on v5e at T=8192) — that failure must not discard the
    flash number, and dense retries at T/2 until it fits, recording where it
    first OOM'd. The gap IS the point: flash runs contexts dense cannot. The
    flash modes get no such backoff: a flash run that fails, fails.
    """
    def _one(mode: str, fused: Optional[str] = None) -> dict:
        t_mode = SEQ_LEN
        prev = os.environ.get("BENCH_LM_FUSED")
        if fused is not None:
            os.environ["BENCH_LM_FUSED"] = fused
        try:
            while True:
                try:
                    entry = _lm_mode_run(mode, t_mode)
                    if fused is not None:
                        entry["fused_ce"] = fused
                    return entry
                except Exception as e:  # noqa: BLE001 - per-mode isolation
                    msg = str(e)
                    oom = ("RESOURCE_EXHAUSTED" in msg or "hbm" in msg
                           or "out of memory" in msg.lower())
                    if mode == "dense" and oom and t_mode > 1024:
                        out.setdefault("dense_oom_at_seq_len", t_mode)
                        t_mode //= 2
                        continue
                    return {"error": f"{type(e).__name__}: {msg[:300]}",
                            "seq_len": t_mode}
        finally:
            if fused is not None:
                if prev is None:
                    os.environ.pop("BENCH_LM_FUSED", None)
                else:
                    os.environ["BENCH_LM_FUSED"] = prev

    out = {}
    for mode in ("flash", "dense"):
        out[mode] = _one(mode)
        # checkpoint the measured-so-far matrix: the parent keeps the LAST
        # marker line, and salvages it from partial stdout on a cap kill —
        # a later mode's compile stall can no longer cost these entries
        print(RESULT_MARK + json.dumps(out), flush=True)
    # the named open item from ROOFLINE_LM.md: the chunked fused head loss
    # with no lm_head recompute. There is one fused path now (both head
    # gradients taken in the forward scan), so "2" runs it; the slot keeps
    # its name. Run last (the checkpoint line above protects flash/dense);
    # skipped on a CPU run (its scaled-down shape says nothing about the
    # HBM/FLOPs trade).
    if not _on_cpu():
        out["flash_fused2"] = _one("flash", fused="2")
    errors = [f"{k}: {v['error']}" for k, v in out.items()
              if isinstance(v, dict) and "error" in v]
    if errors:
        out["error"] = "; ".join(errors)[:500]
    return out


# ------------------------------------------------------------ child execution
CONFIG_FNS = {"nyctaxi": bench_nyctaxi, "dlrm": bench_dlrm,
              "dlrm_stream": bench_dlrm_stream, "keras": bench_keras,
              "transformer": bench_transformer, "gbdt": bench_gbdt,
              "gang": bench_gang}


def _run_config_child(name: str) -> int:
    """Entry point of a per-config subprocess: run one config, print the
    result JSON on the marker line. A body that raised is reported on the
    marker line too, and in the exit code."""
    here = os.path.dirname(os.path.abspath(__file__))
    sys.path.insert(0, os.path.join(here, "examples"))
    sys.path.insert(0, here)
    if _on_cpu():
        _apply_cpu_scaledown()
    try:
        result = CONFIG_FNS[name]()
    except Exception as e:  # noqa: BLE001 - the parent records the failure
        result = {"error": f"{type(e).__name__}: {str(e)[:500]}"}
    print(RESULT_MARK + json.dumps(result), flush=True)
    return 1 if "error" in result else 0


def _spawn_config(name: str, cap_s: float) -> dict:
    """Run one config in its own process group under a hard wall cap."""
    proc = subprocess.Popen(
        [sys.executable, os.path.abspath(__file__), "--config", name],
        stdout=subprocess.PIPE, stderr=None, text=True,
        start_new_session=True)
    timed_out = False
    try:
        out, _ = proc.communicate(timeout=cap_s)
    except subprocess.TimeoutExpired:
        timed_out = True
        _kill_group(proc)
        try:  # collect what the child printed before the kill: configs
            # checkpoint partial results on marker lines as they measure
            out, _ = proc.communicate(timeout=5)
        except Exception:  # noqa: BLE001 - unreapable child
            out = ""
    result = None
    for line in (out or "").splitlines():
        if line.startswith(RESULT_MARK):
            try:  # LAST marker line wins (incremental checkpoints)
                result = json.loads(line[len(RESULT_MARK):])
            except ValueError:
                continue
    if timed_out:
        timeout_info = {"timeout_s": cap_s,
                        "error": f"config exceeded its {cap_s:.0f}s wall cap"}
        if result is not None:
            result.update(timeout_info, partial=True)
            return result
        return timeout_info
    if result is not None:
        if proc.returncode and "error" not in result:
            # the child died AFTER a checkpoint marker (segfault/OOM-kill
            # mid-mode): the salvaged entries are real but the run is NOT
            # complete — tag it so it cannot read as a clean result
            result.update(partial=True,
                          error=f"config subprocess died rc={proc.returncode} "
                                "after a partial result")
        return result
    return {"error": f"config subprocess rc={proc.returncode}, "
                     "no result line"}


# ----------------------------------------------------------------------- main
def main() -> int:
    t_start = time.perf_counter()
    from raydp_tpu.utils import compile_cache_dir
    compile_cache_dir()   # before any child imports jax; children inherit it

    if _on_cpu():
        platform, device = "cpu", None
    else:
        device = _probe_device()
        if device is None or device[0] != "tpu":
            print("bench.py: no TPU — a fresh process found "
                  f"{device[0] if device else 'no backend at all'}. Nothing "
                  "was measured. (JAX_PLATFORMS=cpu runs the scaled-down CPU "
                  "shapes, labelled cpu.)", file=sys.stderr)
            return 2
        platform = device[0]

    selected = [c.strip() for c in os.environ.get(
        "BENCH_CONFIGS", ",".join(CONFIG_ORDER)).split(",") if c.strip()]
    pending = ([c for c in CONFIG_ORDER if c in selected]
               + [c for c in selected if c not in CONFIG_ORDER])

    extra = {}
    for name in pending:
        t0 = time.perf_counter()
        result = _spawn_config(name, float(CONFIG_CAPS_S.get(name, 300)))
        result["config_wall_s"] = round(time.perf_counter() - t0, 1)
        result.setdefault("platform", platform)
        extra[name] = result
        print(f"# {name}: {result}", file=sys.stderr)
    primary = extra.get("nyctaxi")
    failed = sorted(n for n, e in extra.items() if "error" in e)

    out = {
        "metric": "nyctaxi_e2e_train_samples_per_sec_per_chip",
        "unit": "samples/s/chip",
        "platform": platform,
        **({"device": {"platform": device[0], "kind": device[1],
                       "count": device[2]}} if device else {}),
        "total_wall_s": round(time.perf_counter() - t_start, 1),
        "baseline_note": "self-measured reference workload, torch CPU "
                         f"batch 8192 ({REF_NYCTAXI_B8192:.0f} samples/s; "
                         f"batch-64-as-shipped: {REF_NYCTAXI_B64:.0f})",
        **({"failed": failed} if failed else {}),
        "extra": extra,
    }
    if primary is None:
        # headline config not selected: null, not a fake measured 0.0
        out.update(value=None, vs_baseline=None, skipped_primary=True)
    elif "error" in primary:
        out.update(value=None, vs_baseline=None, error=primary["error"])
    else:
        value = round(primary["samples_per_s_per_chip"], 1)
        out.update(value=value,
                   vs_baseline=round(value / REF_NYCTAXI_B8192, 3))
    # The FULL record goes to a file; stdout gets a line a driver that keeps
    # only the tail of stdout can still parse: the contract keys + a
    # one-number-per-config digest.
    detail_path = os.environ.get("RDT_BENCH_DETAIL_PATH") or os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "BENCH_DETAIL.json")
    try:
        with open(detail_path, "w") as fh:
            json.dump(out, fh, indent=1)
    except OSError as e:
        print(f"# could not write {detail_path}: {e}", file=sys.stderr)
    compact = {k: out[k] for k in ("metric", "unit", "platform", "device",
                                   "value", "vs_baseline", "total_wall_s",
                                   "failed")
               if k in out}
    if "error" in out:
        compact["error"] = str(out["error"])[:200]
    compact["detail"] = "BENCH_DETAIL.json"
    compact["extra"] = _digest(extra)
    line = json.dumps(compact)
    if len(line) > 1900:  # belt and braces: the digest must never trip the
        compact.pop("extra", None)  # same truncation the detail file avoids
        line = json.dumps(compact)
    print(line)
    return 1 if failed else 0


def _digest(extra: dict) -> dict:
    """One headline number per config — small enough that a 2000-char
    stdout tail always keeps the whole line. Failure status is
    NEVER masked by a value: a timed-out/partial/crashed entry carries its
    marker alongside whatever was salvaged, because when BENCH_DETAIL.json
    is lost this digest is the round's only surviving record."""
    dig = {}
    for name, e in extra.items():
        if not isinstance(e, dict):
            continue
        if "samples_per_s_per_chip" in e:
            val = round(e["samples_per_s_per_chip"], 1)
        elif name == "transformer":
            t = {}
            for mode in ("flash", "dense", "flash_fused2"):
                m = e.get(mode)
                if isinstance(m, dict) and "tokens_per_s" in m:
                    t[mode] = {"tok_s": m["tokens_per_s"],
                               "seq_len": m.get("seq_len")}
                    if "mfu" in m:
                        t[mode]["mfu"] = m["mfu"]
            val = t or None
        elif name == "gang":
            val = {"scaling": e.get("scaling"),
                   "mechanism_ratio": e.get("collective_mechanism_ratio")}
            if all(v is None for v in val.values()):
                val = None
        else:
            val = None
        status = ("timeout" if "timeout_s" in e
                  else "error" if "error" in e else None)
        if status is None:
            dig[name] = val if val is not None else "no-result"
        elif val is None:
            dig[name] = (status if status == "timeout"
                         else str(e["error"])[:60])
        else:
            marker = "partial" if e.get("partial") else status
            dig[name] = {"status": marker, "salvaged": val}
    return dig


if __name__ == "__main__":
    if len(sys.argv) == 3 and sys.argv[1] == "--config":
        sys.exit(_run_config_child(sys.argv[2]))
    sys.exit(main())
