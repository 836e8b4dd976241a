"""Chip smoke: the system's main path, once, on the TPU, through the entry
points a user calls. Run from the root of the checkout, no arguments:

    python chip_smoke.py

Leg ``dlrm`` — the flow of ``examples/dlrm_criteo.py --scale full`` at the
reference model's full width: seeded Criteo-format TSV → ``raydp_tpu.init``
(two CPU executors) → ``pre_process`` on the executors → ``FlaxEstimator``
``fit_on_frame``, once as it routes by default (the device-resident epoch
cache) and once through the streaming ``DeviceFeed`` → ``get_model`` →
``raydp_tpu.stop``. Rows are cut so the leg is set-up-bound; width is not.

Leg ``lm`` — ``TransformerLM`` at the shape ``bench.py`` pins (dim 1024, 8
heads of 128, 8 layers, vocab 32768, bf16, T=8192, flash attention, two
sequences per chip): a few adam steps with the loss fetched each step, the
compiled step's HLO checked for the three Mosaic kernels and against a T×T
score tensor, and the compiled kernel checked against ``dense_attention`` at
head_dim 64 and 128 where dense fits.

One process owns the chip for the whole run; the ETL executors are CPU
children and the script checks that none of them opened it. It needs a TPU:
on any other platform it exits non-zero, naming what it found, and there is
no flag that changes that. It reports walls for the record of what ran, not
throughput as a result. The last line of stdout is one JSON object,
``{"ok": true, "device": {...}}``; any failed check exits non-zero first.
"""

from __future__ import annotations

import glob
import importlib.metadata
import json
import os
import re
import sys
import tempfile
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(HERE, "examples"))
sys.path.insert(0, HERE)

from raydp_tpu.utils import compile_cache_dir  # noqa: E402

CACHE_DIR = compile_cache_dir()   # before jax is imported; children inherit

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

DLRM_ROWS = 40_000      # 9 steps of 4096 an epoch: set-up-bound on purpose
DLRM_EPOCHS = 3
LM_LAYERS = 8           # bench.py's depth: it fits one chip, so it is not cut
LM_SEQ_LEN = 8192
LM_STEPS = 4
PARITY_SEQ_LEN = 1024   # dense attention fits here
# bf16 keeps 8 significant bits (one ulp = 2**-8 relative): outputs of order
# one within 4 ulps, gradients within 6 ulps of their largest entry
OUT_ATOL = 4 * 2.0 ** -8
GRAD_RTOL = 6 * 2.0 ** -8


class CheckFailed(Exception):
    pass


def check(ok: bool, what: str) -> None:
    print(f"  [{'ok' if ok else 'FAILED'}] {what}", flush=True)
    if not ok:
        raise CheckFailed(what)


def shard_devices(x) -> int:
    """Distinct devices holding a shard of ``x`` — read off the array."""
    return len({s.device for s in x.addressable_shards})


def chip_holders() -> set:
    """Pids with a TPU device node open (``/dev/vfio/<group>``, ``/dev/accel*``)."""
    holders = set()
    for link in glob.glob("/proc/[0-9]*/fd/*"):
        try:
            target = os.readlink(link)
        except OSError:     # the fd (or its process) is gone, or not ours
            continue
        if re.match(r"/dev/(vfio/\d|accel)", target):
            holders.add(int(link.split("/")[2]))
    return holders


# ------------------------------------------------------------------ leg: dlrm
def leg_dlrm(rows: int = DLRM_ROWS, epochs: int = DLRM_EPOCHS) -> dict:
    import optax

    import raydp_tpu
    from dlrm_criteo import (
        CAT_COLS, DENSE_COLS, LABEL, NUM_DENSE, generate_criteo, pre_process)
    from raydp_tpu import metrics
    from raydp_tpu.data import DeviceFeed, from_frame_recoverable
    from raydp_tpu.etl.expressions import col, udf
    from raydp_tpu.models import DLRM, criteo_batch_preprocessor
    from raydp_tpu.native.stage import native_stage_available
    from raydp_tpu.parallel import make_mesh
    from raydp_tpu.runtime import get_runtime
    from raydp_tpu.train import FlaxEstimator

    platform, n_dev = jax.devices()[0].platform, len(jax.devices())
    t_leg = time.perf_counter()
    tsv = os.path.join(tempfile.mkdtemp(prefix="rdt-smoke-"), "criteo.tsv")
    generate_criteo(rows, tsv, seed=0)
    session = raydp_tpu.init("chip-smoke", num_executors=2, executor_cores=1,
                             executor_memory="2GB")
    try:
        df = session.read.csv(
            tsv, num_partitions=4,
            options={"delimiter": "\t",
                     "column_names": [LABEL] + DENSE_COLS + CAT_COLS})
        df, cat_sizes = pre_process(session, df)
        etl_s = time.perf_counter() - t_leg
        store = get_runtime().store_server.arena_info()
        print(f"  etl: {rows} rows, 26 dictionaries on 2 executors, "
              f"{etl_s:.1f}s; store core: "
              f"{'native arena' if store else 'per-object segments'}")

        def fit(streaming: bool):
            est = FlaxEstimator(
                model=DLRM(categorical_sizes=cat_sizes, num_dense=NUM_DENSE,
                           embedding_dim=32, bottom_mlp=(512, 128, 32),
                           top_mlp=(1024, 1024, 512, 256, 1),
                           dtype=jnp.bfloat16),
                optimizer=optax.adagrad(1e-2), loss="bce_with_logits",
                feature_columns=DENSE_COLS + CAT_COLS, label_column=LABEL,
                feature_dtype=np.float64, label_dtype=np.float32,
                batch_size=4096, num_epochs=epochs, shuffle=False,
                batch_preprocessor=criteo_batch_preprocessor(NUM_DENSE))
            if streaming:   # the residency gate's own switch, as bench.py's
                os.environ["RDT_DEVICE_CACHE"] = "0"    # dlrm_stream sets it
            try:
                t0 = time.perf_counter()
                history = est.fit_on_frame(df).history
                wall = time.perf_counter() - t0
            finally:
                if streaming:
                    del os.environ["RDT_DEVICE_CACHE"]
            losses = [h["train_loss"] for h in history]
            walls = [h["epoch_time_s"] for h in history]
            name = "streaming" if streaming else "resident"
            print(f"  fit[{name}]: losses {[round(v, 4) for v in losses]}, "
                  f"fit wall {wall:.1f}s, first epoch (compile) "
                  f"{walls[0]:.2f}s, steady epochs "
                  f"{[round(w, 3) for w in walls[1:]]}s")
            check(all(np.isfinite(losses)) and losses[-1] < losses[0],
                  f"{name} fit: losses finite and falling")
            took_feed = sum(h["feed_time_s"] + h["h2d_time_s"]
                            for h in history) > 0
            check(took_feed == streaming,
                  f"{name} fit ran through the "
                  f"{'DeviceFeed' if streaming else 'DeviceEpochCache'}")
            leaves = [a for a in jax.tree.leaves(est.get_state())
                      if isinstance(a, jax.Array)]
            check(bool(leaves) and all(d.platform == platform
                                       for a in leaves for d in a.devices()),
                  f"{name} fit: all {len(leaves)} leaves of the trained "
                  f"state live on {platform} devices")
            check(all(shard_devices(a) == n_dev for a in leaves),
                  f"{name} fit: every leaf has shards on {n_dev} distinct "
                  f"device(s)")
            return est, losses

        est, resident = fit(streaming=False)

        # the driver holds the chip now, the executors are alive: an executor
        # that imports jax (as serve replicas do) must land on the CPU, and no
        # child may have the chip's device node open
        probe = udf("string")(lambda v: __import__("jax").default_backend())
        backends = set(df.limit(64).withColumn("backend", probe(col(LABEL)))
                       .to_pandas()["backend"])
        check(backends == {"cpu"},
              f"executors that import jax run on {sorted(backends)}")
        holders = chip_holders()
        check(holders == {os.getpid()},
              f"the chip's device node is open in this process only "
              f"(pid {os.getpid()}; holders {sorted(holders)})")

        _, streaming = fit(streaming=True)
        check(np.allclose(resident, streaming, rtol=2e-2),
              "resident and streaming losses agree (rtol 2e-2, bf16 model)")

        # the batch as the feed shards it, read off the arrays
        columns = {"features": (DENSE_COLS + CAT_COLS, np.float64),
                   "label": (LABEL, np.float32)}
        batch = next(iter(DeviceFeed(from_frame_recoverable(df), 4096,
                                     columns, mesh=make_mesh(),
                                     shuffle=False)))
        check(all(shard_devices(a) == n_dev and a.shape[0] == 4096
                  for a in batch.values()),
              f"a DeviceFeed batch of 4096 has shards on {n_dev} distinct "
              f"device(s)")

        params = est.get_model()["params"]
        check(len(jax.tree.leaves(params)) > 0, "get_model() returns params")
        staged = metrics.snapshot()["counters"].get(
            "feed_staged_tables_total", {})
        print(f"  host staging: library "
              f"{'built and loaded' if native_stage_available() else 'UNAVAILABLE'}"
              f"; tables decoded by path: {staged or 'none'}")
        return {"etl_s": round(etl_s, 1), "staged": staged,
                "store": "arena" if store else "segments"}
    finally:
        raydp_tpu.stop()


# -------------------------------------------------------------------- leg: lm
def kernel_calls(hlo: str) -> list:
    """(result dtypes, leading dim of the first result) of every Mosaic
    custom call in compiled HLO text."""
    calls = []
    for line in hlo.splitlines():
        if 'custom_call_target="tpu_custom_call"' not in line:
            continue
        result = line.split(" custom-call(")[0].split("=", 1)[1]
        calls.append((tuple(re.findall(r"(bf16|f32)\[", result)),
                      int(re.search(r"\[(\d+),", result).group(1))))
    return calls


def kernel_parity(head_dim: int) -> None:
    from raydp_tpu.ops.flash_attention import flash_attention
    from raydp_tpu.ops.ring_attention import dense_attention

    rng = np.random.RandomState(head_dim)
    q, k, v = (jnp.asarray(rng.randn(2, PARITY_SEQ_LEN, 2, head_dim) * 0.5,
                           jnp.bfloat16) for _ in range(3))

    def run(fn):
        def loss(q, k, v):
            return (fn(q, k, v, causal=True).astype(jnp.float32) ** 2).sum()
        out = jax.jit(lambda q, k, v: fn(q, k, v, causal=True))(q, k, v)
        grads = jax.jit(jax.grad(loss, argnums=(0, 1, 2)))(q, k, v)
        return [np.asarray(a, np.float32) for a in (out, *grads)]

    (out, *grads), (ref, *ref_grads) = run(flash_attention), \
        run(dense_attention)
    err = float(np.abs(out - ref).max())
    check(err <= OUT_ATOL, f"head_dim {head_dim}: compiled kernel output vs "
          f"dense_attention, max abs err {err:.4f} <= {OUT_ATOL:.4f}")
    for name, g, r in zip("qkv", grads, ref_grads):
        rel = float(np.abs(g - r).max() / np.abs(r).max())
        check(rel <= GRAD_RTOL, f"head_dim {head_dim}: d{name} vs dense, max "
              f"err / max |grad| {rel:.4f} <= {GRAD_RTOL:.4f}")


def leg_lm(seq_len: int = LM_SEQ_LEN, layers: int = LM_LAYERS,
           steps: int = LM_STEPS) -> dict:
    import optax
    from jax.sharding import NamedSharding, PartitionSpec

    from raydp_tpu.models import TransformerLM, lm_loss
    from raydp_tpu.parallel import batch_sharding, make_mesh

    mesh = make_mesh()      # every device on `data`
    n_dev = mesh.size
    vocab, dim, heads = 32768, 1024, 8
    batch = 2 * n_dev       # two sequences per chip
    print(f"  TransformerLM vocab {vocab} dim {dim} heads {heads} "
          f"(head_dim {dim // heads}) bf16 flash, T={seq_len}, B={batch}, "
          f"{layers} layers")
    model = TransformerLM(vocab_size=vocab, dim=dim, num_heads=heads,
                          num_layers=layers, attention="flash", mesh=mesh,
                          dtype=jnp.bfloat16)
    tokens = np.random.RandomState(0).randint(
        0, vocab, size=(batch, seq_len)).astype(np.int32)
    replicated = NamedSharding(mesh, PartitionSpec())
    # shapes do not depend on T: initialise on a short prefix
    params = jax.jit(model.init, out_shardings=replicated)(
        jax.random.PRNGKey(0), tokens[:, :256])["params"]
    tx = optax.adam(1e-3)
    opt = jax.jit(tx.init, out_shardings=replicated)(params)
    tokens = jax.device_put(tokens, batch_sharding(mesh))
    check(shard_devices(tokens) == n_dev,
          f"the token batch has shards on {n_dev} distinct device(s)")

    def train_step(params, opt, tokens):
        loss, grads = jax.value_and_grad(
            lambda p: lm_loss(model.apply({"params": p}, tokens), tokens)
        )(params)
        updates, opt = tx.update(grads, opt, params)
        return optax.apply_updates(params, updates), opt, loss

    step = jax.jit(train_step, donate_argnums=(0, 1))
    t0 = time.perf_counter()
    compiled = step.lower(params, opt, tokens).compile()
    compile_s = time.perf_counter() - t0
    hlo, mem = compiled.as_text(), compiled.memory_analysis()
    print(f"  step compiled in {compile_s:.1f}s; per device: arguments "
          f"{mem.argument_size_in_bytes / 2**30:.2f} GiB, temporaries "
          f"{mem.temp_size_in_bytes / 2**30:.2f} GiB")
    os.makedirs(os.path.join(HERE, "chiprun_out"), exist_ok=True)
    with open(os.path.join(HERE, "chiprun_out", "lm_step_hlo.txt"), "w") as fh:
        fh.write(hlo)

    calls = kernel_calls(hlo)
    kinds = sorted(sig for sig, _ in calls)
    want = sorted([("bf16", "f32"), ("bf16", "bf16"), ("bf16",)] * layers)
    check(kinds == want,
          f"compiled step holds {len(calls)} Mosaic custom calls: forward "
          f"(out, lse), dk/dv and dq for each of {layers} layers")
    local_bh = batch // n_dev * heads
    check({bh for _, bh in calls} == {local_bh},
          f"every kernel runs on its chip's {local_bh} (batch x heads) rows, "
          f"not the global {batch * heads}")
    check(not re.search(rf"\[(\d+,)*{seq_len},{seq_len}[,\]]", hlo),
          f"no [.., {seq_len}, {seq_len}] score tensor in the compiled step")
    # (the compiler may all-gather parameters it updates in shards; what
    # must not happen is an activation gathered ahead of the kernel)
    gather = re.compile(r"\ball-gather(?:-start)?\(")
    gathers = [gather.split(line)[0] for line in hlo.splitlines()
               if gather.search(line)]
    check(not any(re.search(rf"[\[,]{seq_len}[,\]]", g) for g in gathers),
          f"none of the {len(gathers)} all-gathers in the compiled step "
          f"carries the sequence dim: q/k/v stay sharded")

    losses, walls = [], []
    for _ in range(steps):
        t0 = time.perf_counter()
        params, opt, loss = step(params, opt, tokens)
        losses.append(float(loss))      # fetched to the host every step
        walls.append(time.perf_counter() - t0)
    print(f"  losses {[round(v, 3) for v in losses]}; step walls "
          f"{[round(w, 3) for w in walls]}s")
    check(all(np.isfinite(losses)) and losses[-1] < losses[0],
          "losses finite and falling")
    leaves = jax.tree.leaves((params, opt))
    check(shard_devices(loss) == n_dev
          and all(shard_devices(a) == n_dev for a in leaves),
          f"the step's outputs (loss, params, optimizer state) have shards "
          f"on {n_dev} distinct device(s)")

    for head_dim in (64, 128):
        kernel_parity(head_dim)
    return {"compile_s": round(compile_s, 1), "layers": layers}


# ----------------------------------------------------------------------- main
def main() -> int:
    t_start = time.perf_counter()
    if jax.default_backend() != "tpu":
        print(f"chip_smoke: needs a TPU, but JAX's default backend here is "
              f"{jax.default_backend()!r} ({jax.devices()[0].device_kind}); "
              f"nothing was run", file=sys.stderr)
        return 2
    first = jax.devices()[0]
    device = {"platform": first.platform, "kind": first.device_kind,
              "count": len(jax.devices())}
    cache_entries = len(os.listdir(CACHE_DIR))
    print(f"chip_smoke: platform={device['platform']} "
          f"device_kind={device['kind']!r} count={device['count']} "
          f"jax={jax.__version__} "
          f"libtpu={importlib.metadata.version('libtpu')}\n"
          f"  compile cache {CACHE_DIR}: {cache_entries} entries at start "
          f"({'warm' if cache_entries else 'cold'})")
    report = {}
    for name, leg in (("dlrm", leg_dlrm), ("lm", leg_lm)):
        print(f"leg {name}:", flush=True)
        t0 = time.perf_counter()
        try:
            report[name] = leg()
        except CheckFailed as e:
            print(f"chip_smoke: leg {name} FAILED: {e}", file=sys.stderr)
            return 1
        report[name]["wall_s"] = round(time.perf_counter() - t0, 1)
        print(f"  leg {name} passed in {report[name]['wall_s']}s")
    print(f"chip_smoke: all checks passed in "
          f"{time.perf_counter() - t_start:.1f}s; compile cache now holds "
          f"{len(os.listdir(CACHE_DIR))} entries; legs {json.dumps(report)}")
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
