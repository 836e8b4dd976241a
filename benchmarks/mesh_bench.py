"""Multi-axis sharded training bench: the ISSUE 16 acceptance record
(MESH.json).

Two configs on one process with 8 virtual devices (the same topology the
mesh-matrix tests run), each a fresh session:

1. ``memory`` — the FSDP claim, in bytes where it is true: train an
   embedding-dominated regressor once with every parameter replicated
   (dp-only mesh) and once under ``mesh_spec=dict(fsdp=8)`` with the role
   policy choosing the specs, and record the params+optimizer bytes
   resident per process after placement (``addressable_nbytes`` — the
   number behind the ``train_param_bytes_per_process`` gauge; replicated
   leaves count one copy per device, which IS the memory they occupy).
   Against the config's per-process HBM budget the replicated run must NOT
   fit and the sharded run MUST — the adam moments inherit their
   parameter's spec, so the win covers optimizer state too. Both runs must
   land the same final loss (sharding is a layout, not a math change).
2. ``overlap`` — the sharded feed path keeps its prefetch win: streaming
   epochs under ``fsdp=8`` with ``prefetch_to_device=2`` (H2D for batch
   k+1 overlaps the jitted step of batch k) vs synchronous placement
   (``prefetch_to_device=0``). The prefetching epoch must not be slower,
   and the overlap must be visible: the feed-thread phase walls
   (decode/h2d) plus dispatch exceed the epoch wall only when the
   phases actually ran concurrently.

``--smoke`` shrinks the model/rows, writes to /tmp (never the recorded
artifact), and ASSERTS the contract above; the full run records
``benchmarks/MESH.json`` (override with ``--out``).

Run: RDT_FAULTS_SEED=7 python benchmarks/mesh_bench.py [--smoke] [--out P]
"""

import argparse
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# an 8-device mesh before jax imports: real accelerators keep their count,
# a CPU host splits into 8 virtual devices (the test topology)
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8").strip()


def _embed_model(vocab, dim):
    import flax.linen as nn
    import jax.numpy as jnp

    class EmbedRegressor(nn.Module):
        """An embedding-dominated model: the table (and its adam moments)
        carries ~99% of the state bytes, so per-process residency tracks
        the embedding's placement — the shape the role policy shards
        hardest (rows over fsdp×tensor)."""

        @nn.compact
        def __call__(self, x):
            ids = jnp.clip(x.astype(jnp.int32), 0, vocab - 1)
            e = nn.Embed(vocab, dim, name="embed_tokens")(ids)
            h = nn.relu(nn.Dense(dim)(e))
            return nn.Dense(1)(h)

    return EmbedRegressor()


def _ids_frame(session, n, vocab, parts=4):
    import pandas as pd

    rng = np.random.RandomState(0)
    ids = rng.randint(0, vocab, n)
    y = (ids % 7).astype(np.float64) / 7.0
    return session.createDataFrame(pd.DataFrame({"c": ids, "y": y}),
                                   num_partitions=parts)


def _linear_frame(session, n, parts=4):
    import pandas as pd

    rng = np.random.RandomState(0)
    x = rng.random_sample((n, 2))
    y = x @ np.array([2.0, -3.0]) + 1.0
    return session.createDataFrame(
        pd.DataFrame({"x1": x[:, 0], "x2": x[:, 1], "y": y}),
        num_partitions=parts)


def run_memory_config(smoke):
    """Config 1: per-process param+optimizer bytes, replicated vs fsdp."""
    import optax

    import raydp_tpu
    from raydp_tpu.data.dataset import from_frame
    from raydp_tpu.parallel.roles import addressable_nbytes, describe_roles
    from raydp_tpu.train import FlaxEstimator

    vocab = 8_192 if smoke else 65_536
    dim = 32
    n = 1_024 if smoke else 4_096
    # the synthetic per-process budget the claim is judged against: between
    # one sharded copy and eight replicated ones (adam triples the bytes)
    budget = (8 if smoke else 64) * (1 << 20)

    s = raydp_tpu.init("mesh-bench-mem", num_executors=2, executor_cores=1,
                       executor_memory="512MB")
    try:
        ds = from_frame(_ids_frame(s, n, vocab))

        def one_run(mesh_spec):
            est = FlaxEstimator(
                model=_embed_model(vocab, dim),
                optimizer=optax.adam(1e-2), loss="mse",
                feature_columns=["c"], label_column="y",
                feature_dtype=np.int32,
                batch_size=256, num_epochs=1, mesh_spec=mesh_spec,
                shuffle=False)
            r = est.fit(ds)
            state = est.get_state()
            return {
                "bytes_per_process": int(addressable_nbytes(state)),
                "final_loss": round(float(r.history[-1]["train_loss"]), 6),
            }, state

        replicated, _ = one_run(None)            # dp-only: 8 device copies
        sharded, state = one_run(dict(fsdp=8))   # role policy shards
        roles = describe_roles(state.params)
        embed_role = roles.get("embed_tokens/embedding", (None, ()))[0]
        record = {
            "vocab": vocab,
            "embedding_dim": dim,
            "hbm_budget_bytes": budget,
            "replicated_bytes_per_process": replicated["bytes_per_process"],
            "sharded_bytes_per_process": sharded["bytes_per_process"],
            "replicated_over_sharded": round(
                replicated["bytes_per_process"]
                / max(1, sharded["bytes_per_process"]), 2),
            "fits_replicated":
                replicated["bytes_per_process"] <= budget,
            "fits_sharded": sharded["bytes_per_process"] <= budget,
            "embedding_role": embed_role,
            "loss_replicated": replicated["final_loss"],
            "loss_sharded": sharded["final_loss"],
        }
    finally:
        raydp_tpu.stop()
    print(f"[memory] replicated={record['replicated_bytes_per_process']}B "
          f"sharded={record['sharded_bytes_per_process']}B "
          f"ratio={record['replicated_over_sharded']}x "
          f"budget={budget}B")
    return record


def run_overlap_config(smoke):
    """Config 2: sharded streaming feed, prefetch overlap vs synchronous
    placement (the fsdp batch path must keep the prefetch win)."""
    import optax

    import raydp_tpu
    from raydp_tpu.data.dataset import from_frame
    from raydp_tpu.models import MLP
    from raydp_tpu.train import FlaxEstimator

    n = 4_096 if smoke else 32_768
    epochs = 3
    os.environ["RDT_DEVICE_CACHE"] = "0"  # force the streaming feed path
    s = raydp_tpu.init("mesh-bench-ovl", num_executors=2, executor_cores=1,
                       executor_memory="512MB")
    try:
        ds = from_frame(_linear_frame(s, n))

        def one_run(mesh_spec, prefetch):
            est = FlaxEstimator(
                model=MLP(features=(128, 64), use_batch_norm=False),
                optimizer=optax.sgd(5e-2), loss="mse",
                feature_columns=["x1", "x2"], label_column="y",
                batch_size=512, num_epochs=epochs,
                mesh_spec=mesh_spec, shuffle=False,
                prefetch_to_device=prefetch)
            r = est.fit(ds)
            h = r.history[-1]  # steady state: compile paid in epoch 0
            return {
                "epoch_time_s": round(h["epoch_time_s"], 4),
                "dispatch_time_s": round(h["dispatch_time_s"], 4),
                "feed_thread_s": round(h["decode_time_s"]
                                       + h["h2d_time_s"], 4),
                "samples_per_s": round(h["samples_per_s"], 1),
                "train_loss": round(float(h["train_loss"]), 6),
            }

        replicated = one_run(None, 2)          # dp: params replicated
        sharded = one_run(dict(fsdp=8), 2)     # fsdp feed, same prefetch
        sync = one_run(dict(fsdp=8), 0)        # fsdp, synchronous placement
        # phase walls summing past the epoch wall is the overlap signature:
        # serial execution can never exceed 1.0
        overlap = (sharded["feed_thread_s"] + sharded["dispatch_time_s"]) \
            / max(sharded["epoch_time_s"], 1e-9)
        record = {
            "rows": n,
            "replicated": replicated,
            "sharded": sharded,
            "sharded_sync": sync,
            "sharded_over_replicated_epoch": round(
                sharded["epoch_time_s"]
                / max(replicated["epoch_time_s"], 1e-9), 3),
            "overlap_ratio": round(overlap, 3),
            "overlap_visible": overlap > 1.0,
        }
    finally:
        raydp_tpu.stop()
        os.environ.pop("RDT_DEVICE_CACHE", None)
    print(f"[overlap] replicated={replicated['epoch_time_s']}s "
          f"sharded={sharded['epoch_time_s']}s "
          f"ratio={record['sharded_over_replicated_epoch']}x "
          f"overlap_ratio={record['overlap_ratio']}")
    return record


def run_activation_config(smoke):
    """Config 3 (``--activation``, ISSUE 17): peak live activation bytes of
    the train step at a FIXED global batch, full-batch vs accumulated vs
    accumulated×remat vs accumulated×remat×seq-sharded.

    The model is a per-position MLP whose ``[B, T, H]`` hidden activations
    dominate the step's temp allocation — the shape gradient accumulation
    (only one ``B/k`` microbatch's activations ever live, because the
    value_and_grad runs INSIDE the scan body), remat (``jax.checkpoint``
    recomputes the residuals), and seq sharding (dim 1 over the mesh's
    ``seq`` axis) each cut along a different dimension. Peak temp bytes are
    read off XLA's own ``memory_analysis`` of the compiled step — the same
    number the estimator's ``train_activation_bytes_per_process`` gauge
    publishes — so the record is deterministic, not a wall-clock guess.
    Every variant then runs real optimizer steps on the same data: the
    final losses must agree to float-summation tolerance (residency is a
    layout/schedule change, not a math change)."""
    import jax
    import jax.numpy as jnp
    import optax
    from flax import linen as nn
    from jax.sharding import NamedSharding
    from jax.sharding import PartitionSpec as P

    from raydp_tpu.parallel.mesh import make_mesh
    from raydp_tpu.parallel.roles import apply_remat

    B = 1_024 if smoke else 2_048       # fixed global batch for ALL variants
    T = 128 if smoke else 256
    H = 64 if smoke else 128
    accum = 8
    opt_steps = 3

    mesh = make_mesh(dict(data=4, seq=2))
    n_local = mesh.devices.size

    class PerPosMLP(nn.Module):
        """[B, T] → [B]: Dense stack applied per position, so the hidden
        activations are [B, T, H] — big enough that the step's temp bytes
        track activation residency, not parameter scratch."""

        @nn.compact
        def __call__(self, x):
            h = nn.relu(nn.Dense(H)(x[..., None]))
            h = nn.relu(nn.Dense(H)(h))
            return nn.Dense(1)(h).squeeze(-1).mean(axis=-1)

    model = PerPosMLP()
    rng = np.random.RandomState(0)
    xs = rng.random_sample((B, T)).astype(np.float32)
    ys = (xs.mean(axis=1) * 2.0 - 1.0).astype(np.float32)

    import jax.random as jrandom
    params0 = model.init(jrandom.PRNGKey(0), jnp.zeros((1, T)))["params"]
    tx = optax.sgd(5e-2)

    data_sh = NamedSharding(mesh, P("data"))
    seq_sh = NamedSharding(mesh, P("data", "seq"))

    def make_step(k, remat_mode, seq):
        in_sh = seq_sh if seq else data_sh

        def loss_of(p, xb, yb):
            preds = model.apply({"params": p}, xb)
            return jnp.mean((preds - yb) ** 2)

        fwd = apply_remat(loss_of, remat_mode)

        def step(p, opt, x, y):
            x = jax.lax.with_sharding_constraint(x, in_sh)
            y = jax.lax.with_sharding_constraint(y, data_sh)
            if k <= 1:
                lv, g = jax.value_and_grad(fwd)(p, x, y)
            else:
                xm = x.reshape((k, B // k, T))
                ym = y.reshape((k, B // k))

                def body(carry, mb):
                    g_acc, l_acc = carry
                    # re-constrain the microbatch: the [B]→[k, B/k] reshape
                    # breaks sharding propagation and XLA would otherwise
                    # gather every microbatch onto all data shards, erasing
                    # most of the accumulation win (measured: 4× worse)
                    mx = jax.lax.with_sharding_constraint(mb[0], in_sh)
                    my = jax.lax.with_sharding_constraint(mb[1], data_sh)
                    lv, g = jax.value_and_grad(fwd)(p, mx, my)
                    g_acc = jax.tree.map(lambda a, b: a + b, g_acc, g)
                    return (g_acc, l_acc + lv), ()

                g0 = jax.tree.map(jnp.zeros_like, p)
                (g, lv), _ = jax.lax.scan(body, (g0, jnp.float32(0)),
                                          (xm, ym))
                g = jax.tree.map(lambda a: a / k, g)
                lv = lv / k
            upd, opt = tx.update(g, opt, p)
            return optax.apply_updates(p, upd), opt, lv

        return jax.jit(step)

    x_dev = jax.device_put(xs, data_sh)
    y_dev = jax.device_put(ys, data_sh)

    def measure(name, k, remat_mode, seq):
        step = make_step(k, remat_mode, seq)
        p = jax.device_put(params0)
        opt = tx.init(p)
        compiled = step.lower(p, opt, x_dev, y_dev).compile()
        temp = int(compiled.memory_analysis().temp_size_in_bytes) * n_local
        lv = None
        for _ in range(opt_steps):
            p, opt, lv = step(p, opt, x_dev, y_dev)
        lv = float(lv)
        t0 = time.perf_counter()
        for _ in range(opt_steps):
            p, opt, lv2 = step(p, opt, x_dev, y_dev)
        jax.block_until_ready(lv2)
        wall = (time.perf_counter() - t0) / opt_steps
        print(f"[activation] {name}: temp={temp}B loss={lv:.6f} "
              f"step={wall * 1e3:.1f}ms")
        return {"bytes_per_process": temp, "final_loss": lv,
                "step_wall_s": round(wall, 5)}

    full = measure("full-batch", 1, "none", False)
    acc = measure("accum", accum, "none", False)
    acc_remat = measure("accum+remat", accum, "full", False)
    acc_remat_seq = measure("accum+remat+seq", accum, "full", True)

    ratio = round(full["bytes_per_process"]
                  / max(1, acc_remat["bytes_per_process"]), 2)
    ratio_seq = round(full["bytes_per_process"]
                      / max(1, acc_remat_seq["bytes_per_process"]), 2)
    tol = 5e-4 * max(1.0, abs(full["final_loss"]))
    return {
        "global_batch": B,
        "seq_len": T,
        "hidden": H,
        "accum_steps": accum,
        "mesh": {"data": 4, "seq": 2},
        "full_batch": full,
        "accum": acc,
        "accum_remat": acc_remat,
        "accum_remat_seq": acc_remat_seq,
        "full_over_accum_remat": ratio,
        "full_over_accum_remat_seq": ratio_seq,
        "losses_match": (
            abs(full["final_loss"] - acc_remat["final_loss"]) <= tol
            and abs(full["final_loss"] - acc_remat_seq["final_loss"]) <= tol
            and abs(full["final_loss"] - acc["final_loss"]) <= tol),
    }


def run_pipeline_config(smoke):
    """Config 4 (``--pipeline``, ISSUE 20): end-to-end pipeline-parallel
    training through the estimator — the SAME ``FlaxEstimator.fit`` call on
    the same data, once on a ``stage=1`` mesh (every layer replicated over
    the data axis) and once on ``stage=2`` (the layer stack split across
    the mesh's stage axis, accum microbatches marching through the GPipe
    scan as pipeline microbatches).

    Three numbers make the claim: per-process params+optimizer bytes after
    placement (``addressable_nbytes`` — stage-sharding the stack must cut
    resident state, the adam moments inherit their parameter's stage
    spec), steady-state step wall (the staged step may pay at most the
    pipeline bubble, ``(stages-1)/n_micro``, plus scheduling noise), and
    the final loss (staging is a placement change, not a math change — the
    losses must agree to float tolerance)."""
    import flax.linen as nn
    import optax

    import raydp_tpu
    from raydp_tpu.data.dataset import from_frame
    from raydp_tpu.parallel import make_mesh
    from raydp_tpu.parallel.roles import addressable_nbytes
    from raydp_tpu.train import FlaxEstimator, PipelineModel

    dim = 64 if smoke else 128
    n_layers = 4
    n = 2_048 if smoke else 8_192
    accum = 4
    stages = 2
    epochs = 3

    class Block(nn.Module):
        """Residual MLP block: the 4×dim expansion puts the state bytes in
        the stacked layers, where the stage axis can shard them."""

        @nn.compact
        def __call__(self, x):
            h = nn.relu(nn.Dense(4 * dim)(x))
            return x + nn.Dense(dim)(h)

    s = raydp_tpu.init("mesh-bench-pipe", num_executors=2, executor_cores=1,
                       executor_memory="512MB")
    try:
        import pandas as pd

        rng = np.random.RandomState(0)
        x = rng.normal(size=(n, dim))
        w = rng.normal(size=(dim,))
        pdf = pd.DataFrame({f"f{i}": x[:, i] for i in range(dim)})
        pdf["y"] = x @ w / np.sqrt(dim)
        ds = from_frame(s.createDataFrame(pdf, num_partitions=4))

        def one_run(stage):
            est = FlaxEstimator(
                model=PipelineModel(
                    layers=[Block() for _ in range(n_layers)],
                    head=nn.Dense(1)),
                optimizer=optax.adam(1e-3), loss="mse",
                feature_columns=[f"f{i}" for i in range(dim)],
                label_column="y", batch_size=256, num_epochs=epochs,
                mesh=make_mesh(dict(stage=stage, data=8 // stage)),
                accum_steps=accum, seed=0, shuffle=False)
            r = est.fit(ds)
            h = r.history[-1]  # steady state: compile paid in epoch 0
            return {
                "bytes_per_process": int(addressable_nbytes(est.get_state())),
                "step_wall_s": round(
                    h["epoch_time_s"] / max(1, h["steps"]), 5),
                "final_loss": round(float(h["train_loss"]), 6),
            }

        unstaged = one_run(1)
        staged = one_run(stages)
    finally:
        raydp_tpu.stop()

    bubble = (stages - 1) / accum
    # CPU walls are noisy (8 virtual devices share the host's cores): the
    # bound is the pipeline-bubble model with measurement slack, the same
    # spirit as the overlap config's "not slower" bar
    wall_bound = round(unstaged["step_wall_s"] * (1.0 + bubble) * 1.5, 5)
    tol = 5e-4 * max(1.0, abs(unstaged["final_loss"]))
    record = {
        "layers": n_layers,
        "hidden": dim,
        "rows": n,
        "stages": stages,
        "accum_steps": accum,
        "unstaged": unstaged,
        "staged": staged,
        "unstaged_over_staged_bytes": round(
            unstaged["bytes_per_process"]
            / max(1, staged["bytes_per_process"]), 2),
        "bubble_fraction": bubble,
        "step_wall_bound_s": wall_bound,
        "step_wall_bounded": staged["step_wall_s"] <= wall_bound,
        "losses_match":
            abs(staged["final_loss"] - unstaged["final_loss"]) <= tol,
    }
    print(f"[pipeline] unstaged={unstaged['bytes_per_process']}B "
          f"staged={staged['bytes_per_process']}B "
          f"ratio={record['unstaged_over_staged_bytes']}x "
          f"step {unstaged['step_wall_s']}s -> {staged['step_wall_s']}s "
          f"(bound {wall_bound}s)")
    return record


def _assert_contract(record):
    configs = record["configs"]
    if "memory" in configs:
        mem = configs["memory"]
        assert mem["embedding_role"] == "embedding", mem
        assert not mem["fits_replicated"], mem
        assert mem["fits_sharded"], mem
        assert mem["replicated_over_sharded"] >= 4.0, mem
        assert abs(mem["loss_replicated"] - mem["loss_sharded"]) \
            <= 5e-4 * max(1.0, abs(mem["loss_replicated"])), mem
    if "overlap" in configs:
        ovl = configs["overlap"]
        assert ovl["overlap_visible"], ovl
        # CPU walls are noisy: "not slower" with slack, not a strict ≤
        assert ovl["sharded"]["epoch_time_s"] \
            <= ovl["replicated"]["epoch_time_s"] * 1.5, ovl
        assert ovl["sharded"]["train_loss"] \
            == ovl["sharded_sync"]["train_loss"], ovl
    if "activation" in configs:
        act = configs["activation"]
        # the ISSUE 17 acceptance bar: accumulation×remat at least HALVES
        # peak live activation bytes at the same global batch, seq sharding
        # cuts further, and every variant lands the same loss — strictly
        # decreasing residency, identical math
        assert act["full_batch"]["bytes_per_process"] \
            > act["accum"]["bytes_per_process"], act
        assert act["accum"]["bytes_per_process"] \
            >= act["accum_remat"]["bytes_per_process"], act
        assert act["accum_remat"]["bytes_per_process"] \
            > act["accum_remat_seq"]["bytes_per_process"], act
        assert act["full_over_accum_remat"] >= 2.0, act
        assert act["full_over_accum_remat_seq"] \
            > act["full_over_accum_remat"], act
        assert act["losses_match"], act
    if "pipeline" in configs:
        pipe = configs["pipeline"]
        # the ISSUE 20 acceptance bar: stage-stacked placement cuts resident
        # state (layers + adam moments live on HALF the devices at stage=2),
        # the staged step wall stays inside the bubble bound, and the staged
        # fit lands the unstaged loss — cheaper residency, identical math
        assert pipe["unstaged_over_staged_bytes"] >= 1.5, pipe
        assert pipe["step_wall_bounded"], pipe
        assert pipe["losses_match"], pipe


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n", 1)[0])
    ap.add_argument("--smoke", action="store_true",
                    help="CI contract: small load, asserts, writes to /tmp")
    ap.add_argument("--activation", action="store_true",
                    help="run ONLY the activation-residency config (accum × "
                         "remat × seq); a full run merges configs.activation "
                         "into the existing MESH.json record so the "
                         "memory/overlap numbers stay as measured")
    ap.add_argument("--pipeline", action="store_true",
                    help="run ONLY the pipeline-parallel config (stage-"
                         "stacked estimator placement vs unstaged); a full "
                         "run merges configs.pipeline into the existing "
                         "MESH.json record")
    ap.add_argument("--out", default=None, help="record path override")
    args = ap.parse_args()
    here = os.path.dirname(os.path.abspath(__file__))
    smoke_out = ("/tmp/MESH_ACTIVATION_SMOKE.json" if args.activation
                 else "/tmp/MESH_PIPELINE_SMOKE.json" if args.pipeline
                 else "/tmp/MESH_SMOKE.json")
    out = args.out or (smoke_out if args.smoke
                       else os.path.join(here, "MESH.json"))
    if args.activation:
        configs = {"activation": run_activation_config(args.smoke)}
    elif args.pipeline:
        configs = {"pipeline": run_pipeline_config(args.smoke)}
    else:
        configs = {
            "memory": run_memory_config(args.smoke),
            "overlap": run_overlap_config(args.smoke),
        }
    if not args.smoke and os.path.exists(out):
        # merge with the prior record: each config's numbers (and the claims
        # pinned to them) survive a run that didn't re-measure them
        with open(out) as fh:
            prior = json.load(fh)
        merged = dict(prior.get("configs", {}))
        merged.update(configs)
        configs = merged
    record = {
        "bench": "mesh_bench",
        "metric": "fsdp_state_bytes_reduction",
        "value": (configs["memory"]["replicated_over_sharded"]
                  if "memory" in configs
                  else configs["activation"]["full_over_accum_remat"]
                  if "activation" in configs
                  else configs["pipeline"]["unstaged_over_staged_bytes"]),
        "smoke": args.smoke,
        "ts": time.strftime("%Y-%m-%dT%H:%M:%S"),
        "configs": configs,
    }
    with open(out, "w") as fh:
        json.dump(record, fh, indent=2, sort_keys=True)
    print(f"record written to {out}")
    _assert_contract(record)
    print("mesh bench contract: OK")


if __name__ == "__main__":
    main()
