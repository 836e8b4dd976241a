"""The block-diffusion cell's controls, alone on the chip: what set the limits
of ``sdar_30ba3b_8k_blockdiff_train`` and what its configuration's one
sourceless value (``embed_init_std``) does. Run by no cell; every reading of
``PERF.md`` section 6, PR 50, that no cell's result line holds names the mode
and the arguments that gave it.

``--control`` fits the cell for four epochs through the normal path
(``harness.fit_once``, the cell's own pipeline, ``--seed``'s rows) and then
runs check (a) as the harness does (``program_outputs`` against
``reference_outputs`` on the reference's ``SAMPLE``, ``relative_rms_error``
beside the reference's ``TOLERANCE``), followed by the same comparison with
one thing wrong at a time. Each prints ``compared <name>: <error> limit
<TOLERANCE> correct <true|false>``:

- ``program``: check (a) itself, which has to read correct;
- ``reference_at_<dtype>``: the reference with every product's operands
  rounded to bfloat16, float8_e5m2 and float8_e4m3fn, against the float32
  reference. The 8-bit ones are the nearest precision below the
  configuration's and have to read NOT correct;
- planted faults, the program's outputs against a reference with one piece
  changed (what check (a) would read if the program differed from the
  reference in that piece). Three of the mask: an edge off by one block
  (``mask_noised_sees_own_clean_block``: ``b(j) <= b(i)`` for ``<``, the
  answer leaks through 4 keys of some 8,190), a region missing
  (``mask_noised_sees_no_clean_key``) and a region added
  (``mask_clean_sees_own_noised_block``); ``positions_run_on`` (position ids
  0..2L-1 for 0..L-1 twice); ``held_expert_dropped`` (the first held expert
  of every layer adds nothing). Which of them check (a) can see at near-
  initial weights, and which only the CPU tests against the brute-force table
  and the uncut layer hold, is ``PERF.md`` section 6, PR 50.

``--kernels`` times ``rdt_flash_bd_fwd`` and the one-kernel backward alone at
the cell's shape (one row of 2 x 8,192 positions, 32 query heads on 4 K/V
heads of 128, bfloat16) by the wall clock of 20 calls, beside
``chipbench/flops/blockdiff_moe_lm.py``'s roofline of one execution, and the
causal forward kernel on a 16,384-token row for scale.

``--steps`` times the jitted train step alone (the estimator's own
``_make_train_step`` on the cell's model and optimizer, 8 rows of
``--token-seeds``' tokens each, ``--steps-a-seed`` steps of which the first 8
are left out) for each ``--embed-init-std``: a step's mean ms a token seed,
the held experts' share of the routed slots, and the spread over the token
seeds. It is what showed that the cell's run-to-run spread comes from the
routing at initialisation and not from the loss's noise.

``--by-scope <trace dir>`` lists a traced run's busy seconds by scope and
kernel (same checkout, same call as the ``--trace 1`` run: the machine is
thrown away).

``--embed-init-std`` (one value for ``--control``, a comma list for
``--steps``) overrides the configuration's; left out, the committed value
runs. Needs a TPU: everything runs at the cell's size. Tier-1 plants the
three faults at a tiny size (``tests/test_blockdiff_moe_lm.py``).

Run: python benchmarks/blockdiff_control.py --control [--seed N]
     python benchmarks/blockdiff_control.py --kernels
     python benchmarks/blockdiff_control.py --steps --embed-init-std 0.02,1,4
"""

from __future__ import annotations

import argparse
import contextlib
import json
import os
import re
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

CELL = "sdar_30ba3b_8k_blockdiff_train"
FIT_EPOCHS = 4
LOW_PRECISIONS = ("bfloat16", "float8_e5m2", "float8_e4m3fn")


# ------------------------------------------------------------- --control
def _faults(ref, variables):
    """name -> (what to set on the reference's module, the variables it is
    handed): one piece of the reference changed at a time."""
    import jax
    import numpy as np

    def region(change):
        """The reference's mask with ``change(seen, noised query [q, 1],
        noised key [1, k], same block [q, k])`` laid over it."""
        def visible(queries, length, block):
            i = np.asarray(queries)[:, None]
            j = np.arange(2 * length)[None, :]
            return change(ref_visible(queries, length, block), i >= length,
                          j >= length,
                          (j % length) // block == (i % length) // block)
        return {"visible": visible}

    ref_visible, ref_rope = ref.visible, ref._rope
    dropped = jax.tree_util.tree_map_with_path(
        lambda path, leaf: leaf if "experts_down" not in jax.tree_util.keystr(
            path) else np.concatenate([np.zeros_like(leaf[:1]), leaf[1:]]),
        variables)
    return {
        "mask_noised_sees_own_clean_block": (region(
            lambda seen, qn, kn, same: seen | (qn & ~kn & same)), variables),
        "mask_noised_sees_no_clean_key": (region(
            lambda seen, qn, kn, same: seen & ~(qn & ~kn)), variables),
        "mask_clean_sees_own_noised_block": (region(
            lambda seen, qn, kn, same: seen | (~qn & kn & same)), variables),
        "positions_run_on": ({"_rope": lambda x, places, theta: ref_rope(
            x, np.arange(len(places)), theta)}, variables),
        "held_expert_dropped": ({}, dropped)}


@contextlib.contextmanager
def patched(module, patch: dict):
    """``module`` with the attributes of ``patch`` set, and then put back."""
    kept = {k: getattr(module, k) for k in patch}
    for k, v in patch.items():
        setattr(module, k, v)
    try:
        yield
    finally:
        for k, v in kept.items():
            setattr(module, k, v)


def control(cell, seed: int) -> dict:
    """Fit, then check (a) and its controls; returns name -> error."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    import raydp_tpu
    from chipbench import harness
    from raydp_tpu.data import from_frame_recoverable
    from raydp_tpu.parallel import make_mesh

    ref, pipeline, cfg = cell.reference, cell.pipeline, cell.cfg
    out_dir = os.path.join(ROOT, "chipbench", "out", "blockdiff_control")
    os.makedirs(out_dir, exist_ok=True)
    rows = int(cell.wl["rows"])
    path = harness.write_input(cell, rows, seed, out_dir)
    os.environ.update(harness.residency_env(cell, rows))
    session = raydp_tpu.init("blockdiff_control", num_executors=2,
                             executor_cores=2, executor_memory="2GB")
    errors = {}

    def said(name, err):
        errors[name] = err
        print(f"compared {name}: {err} limit {ref.TOLERANCE} correct "
              f"{str(err <= ref.TOLERANCE).lower()}", flush=True)

    try:
        df, info = pipeline.etl(session.read.parquet(path), cfg, cell.wl)
        mesh = make_mesh(None, devices=jax.devices()[:1])
        est, result, _, _ = harness.fit_once(cell, df.persist(), info, mesh,
                                             FIT_EPOCHS, [])
        print("LOSSES", [e["train_loss"] for e in result.history], flush=True)
        sample = ref.SAMPLE
        df, _ = pipeline.etl(session.read.parquet(harness.write_input(
            cell, sample["rows"], seed + 1, out_dir, parts=1)), cfg, cell.wl)
        df = df.persist()
        table = df.to_arrow()
        got = harness.program_outputs(
            est, from_frame_recoverable(df), sample["batch"],
            lambda out: pipeline.compared(out, cfg))
        variables = jax.device_get(est.get_model())

        def reference(variables=variables):
            return harness.reference_outputs(cell, variables, table, info,
                                             sample["batch"])

        want = reference()
        said("program", harness.relative_rms_error(got, want))
        for name in LOW_PRECISIONS:
            said("reference_at_" + name, harness.relative_rms_error(
                ref.at_precision(jnp.dtype(name), reference), want))
        for name, (patch, handed) in _faults(ref, variables).items():
            with patched(ref, patch):
                said(name, harness.relative_rms_error(got, reference(handed)))
    finally:
        raydp_tpu.stop()
        harness.reap_children()
    print("CONTROL " + json.dumps({
        "seed": seed, "embed_init_std": cfg["embed_init_std"],
        "tolerance": ref.TOLERANCE, "shape": list(np.shape(got)), **errors}),
        flush=True)
    return errors


# ------------------------------------------------------------- --kernels
def kernels_alone(cell) -> dict:
    import jax
    import jax.numpy as jnp
    from chipbench import manifest
    from chipbench.trace import roofline
    from raydp_tpu.ops.flash_attention import flash_attention

    cfg = cell.cfg
    t, bd = 2 * cfg["seq_len"], cfg["diffusion"]["block_length"]
    h, hk, d = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                cfg["head_dim"])
    key = jax.random.PRNGKey(0)
    q, k, v, g = (jax.random.normal(jax.random.fold_in(key, i),
                                    (1, t, heads, d), jnp.bfloat16)
                  for i, heads in enumerate((h, hk, hk, h)))

    def ms(fn, *a, n=20):
        jax.block_until_ready(fn(*a))
        jax.block_until_ready(fn(*a))
        t0 = time.perf_counter()
        for _ in range(n):
            out = fn(*a)
        jax.block_until_ready(out)
        return (time.perf_counter() - t0) / n * 1e3

    f = lambda q, k, v: flash_attention(q, k, v, blockdiff=bd)  # noqa: E731
    fwd = ms(jax.jit(f), q, k, v)
    both = ms(jax.jit(lambda q, k, v, g: jax.vjp(f, q, k, v)[1](g)),
              q, k, v, g)
    out = {"fwd_ms": fwd, "fwd_plus_bwd_ms": both, "bwd_ms": both - fwd,
           "causal_16k_fwd_ms": ms(jax.jit(flash_attention), q, k, v)}
    peak = manifest.peak_of(jax.devices()[0].device_kind)
    for name, count in (("fwd", cell.flops.bd_flash_forward),
                        ("bwd", cell.flops.bd_flash_backward)):
        ops, moved = count(cfg, cell.wl, "blockdiff", 1.0)
        out[name + "_roofline_wall_clock"] = roofline.share(
            out[name + "_ms"] / 1e3, ops, moved, peak)
    print("KERNELS_ALONE " + json.dumps(out), flush=True)
    return out


# --------------------------------------------------------------- --steps
def steps_alone(cell, stds, token_seeds, init_seed: int, steps: int) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np
    from flax.training import train_state
    from raydp_tpu.train.flax_estimator import _make_apply, _make_train_step
    from raydp_tpu.train.metrics import model_counters

    class State(train_state.TrainState):
        batch_stats: object = None

    cfg, pipeline, rows = cell.cfg, cell.pipeline, 8
    for std in stds:
        cfg["embed_init_std"] = std
        model, tx = pipeline.build_model(cfg), pipeline.build_optimizer(cfg)
        metrics = model_counters(model)
        names = [f"{a}.{b}" for a, b in model.loss_counters]
        slots = (2 * cfg["seq_len"] * cfg["num_experts_per_tok"]
                 * cfg["layers"])
        apply_fn = _make_apply(model, False,
                               lambda b: (b["tokens"], b["tokens"]), None)
        step = jax.jit(_make_train_step(apply_fn, None, metrics, 1, "none",
                                        seed=init_seed), donate_argnums=(0, 3))
        create = jax.jit(lambda key: State.create(
            apply_fn=model.apply, tx=tx, batch_stats=None, params=model.init(
                key, jnp.zeros((1, 8), jnp.int32))["params"]))
        means = []
        for token_seed in token_seeds:
            col = pipeline.generate(rows, token_seed, cfg)[
                "tokens"].combine_chunks()
            tokens = col.flatten().to_numpy().reshape(rows, cfg["seq_len"])
            state = create(jax.random.PRNGKey(init_seed))
            order = np.random.default_rng(0)
            stats, loss = tuple(m.init() for m in metrics), jnp.float32(0)
            times, held = [], []
            for s in range(steps):
                if s % rows == 0:
                    perm = order.permutation(rows)
                batch = {"tokens": jnp.asarray(tokens[perm[s % rows]][None])}
                jax.block_until_ready(batch)
                t0 = time.perf_counter()
                state, loss, new = step(state, batch, stats, jnp.float32(0))
                jax.block_until_ready(loss)
                times.append(time.perf_counter() - t0)
                held.append(float(np.asarray(new[0])[
                    names.index("moe_slots_total.held")]))
            del state
            means.append(1e3 * float(np.mean(times[rows:])))
            print("STEPS " + json.dumps({
                "embed_init_std": std, "token_seed": token_seed,
                "mean_ms": means[-1],
                "ms_by_epoch": [round(1e3 * float(np.mean(times[i:i + rows])),
                                      2) for i in range(0, steps, rows)],
                "held_share": float(np.mean(held[rows:])) / slots,
                "last_loss": float(loss)}), flush=True)
        print("STEPS_SUMMARY " + json.dumps({
            "embed_init_std": std, "init_seed": init_seed,
            "token_seeds": token_seeds, "mean_ms": float(np.mean(means)),
            "range_pct": 100 * (max(means) - min(means)) / np.mean(means),
            "std_pct": 100 * float(np.std(means, ddof=1)) / np.mean(means)}),
            flush=True)


# ------------------------------------------------------------ --by-scope
def by_scope(trace_dir: str) -> None:
    from chipbench.trace import reduce, scopes

    plane = reduce.find_xplane(trace_dir)
    red, names, buckets = reduce.reduce(plane), scopes.op_names(plane), {}
    named = re.compile(
        r"/(diffusion|attn_blockdiff|attn|moe/router|moe/dispatch|moe/combine"
        r"|moe/experts|moe|lm_head_loss|embed|ln1|ln2|ln_f)(/|$)")
    for op, seconds in red["op_seconds"].items():
        found = named.search(names.get(op, ""))
        key = ("kernel fwd" if op.startswith("rdt_flash_bd_fwd") else
               "kernel bwd" if op.startswith("rdt_flash_bd_bwd") else
               "ragged-dot" if op.startswith("ragged-dot") else
               found.group(1) if found else "other")
        buckets[key] = buckets.get(key, 0.0) + seconds
    print("BY_SCOPE busy", red["busy_s"], "window", red["window_s"],
          json.dumps({k: round(v, 4) for k, v in sorted(
              buckets.items(), key=lambda kv: -kv[1])}), flush=True)


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--control", action="store_true")
    ap.add_argument("--kernels", action="store_true")
    ap.add_argument("--steps", action="store_true")
    ap.add_argument("--by-scope", metavar="TRACE_DIR")
    ap.add_argument("--seed", type=int, default=2147484001)
    ap.add_argument("--embed-init-std", default="")
    ap.add_argument("--token-seeds", default="201,202,203,204,205,206,207,208")
    ap.add_argument("--init-seed", type=int, default=0)
    ap.add_argument("--steps-a-seed", type=int, default=24)
    args = ap.parse_args(argv)
    if args.by_scope:
        return by_scope(args.by_scope)
    from raydp_tpu.utils import compile_cache_dir

    # as chipbench/run.py: the checkout's compile cache, every program in it
    compile_cache_dir()
    os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "0")
    os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES", "-1")
    os.environ.setdefault("TPU_LOG_DIR", os.path.join(
        ROOT, "chipbench", "out", "tpu_logs"))
    os.makedirs(os.environ["TPU_LOG_DIR"], exist_ok=True)
    from chipbench import manifest

    cell = manifest.resolve(manifest.load_manifest(), CELL)
    stds = [float(s) for s in args.embed_init_std.split(",") if s]
    if args.kernels:
        kernels_alone(cell)
    if args.steps:
        steps_alone(cell, stds or [cell.cfg["embed_init_std"]],
                    [int(s) for s in args.token_seeds.split(",")],
                    args.init_seed, args.steps_a_seed)
    if args.control:
        if stds:
            cell.cfg["embed_init_std"] = stds[0]
        control(cell, args.seed)


if __name__ == "__main__":
    main()
