"""What a recomputed attention keeps of its inputs, set by set, on the chip.

Run by no cell. It is how ``PERF.md`` section 6, PR 62, read the candidate
sets of names before ONE rule was committed
(``raydp_tpu.models.transformer._kept_inputs``), and how a later writer reads
them again after a change to the attention, the policy or the compiler.

One (cell, set) a process, so that the allocator's peak is the set's own: the
cell's own train step, built as a fit builds it (the pipeline's estimator,
model and optimizer, the state's shardings, ``_make_train_step``), with the
rule replaced by the set's names for this process alone; ``--steps`` steps of
the cell's generated rows, then four traced steps reduced by scope.

The sets (``--set``; ``rule`` is the committed rule, untouched):

- ``S0``: nothing of the inputs (the flash kernel's pair and the
  feed-forward's output: what a block kept before PR 62);
- ``S1``: + k and v as the kernel takes them, and the raw ``W_k u`` where a
  head norm reads it;
- ``S2``: S1 + q as the kernel takes it; ``S3``: S1 + the raw ``W_q u`` in its
  place; ``S4``: S1 + both (S3 and S4 differ from S2 only under a head norm);
- a trailing ``g``: + the gate's raw projection (gated attention only).

Prints, a set: ``SWEEP`` (the step's mean, median and least ms after two
warm steps, the allocator's ``peak_bytes_in_use`` and ``peak_bytes_reserved``:
a step program's temporaries may be counted as reserved), ``BY_SCOPE`` (busy
ms a step by scope and pass: ``attn`` is the attention outside the scopes
round its kernels, ``attn_full`` / ``attn_window`` / ``attn_blockdiff`` what
lies round the kernels themselves, ``kernel`` the ``rdt_flash*`` kernels) and
``ATTN_OPS`` (the longest ops under ``attn``).

``--aot`` needs no chip: it compiles the step for a described v5e and prints
the compiler's temporaries, the matrix products in the program's text and how
often ``remat`` stands in it (PR 58's first build lost its gain to a
rematerialisation the compiler chose when memory tightened).

Run: python benchmarks/attention_inputs_sweep.py --cell <cell> --set S4
     python benchmarks/attention_inputs_sweep.py --cell <cell> --set S4 --aot
"""

from __future__ import annotations

import argparse
import json
import os
import re
import shutil
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

TRACED_STEPS = 4
INNER = re.compile(r"/(attn_blockdiff|attn_full|attn_window)(/|$)")
SCOPE = re.compile(
    r"/(attn|moe|mlp|lm_head_loss|embed|ln1_post|ln2_post|ln1|ln2|ln_f"
    r"|short_conv|ssm|diffusion)(/|$)")


def names_of(which: str, model):
    """The names set ``which`` keeps in ``model``'s recomputed layers."""
    from raydp_tpu.models.transformer import RAW_NAMES as raw
    from raydp_tpu.ops.flash_attention import INPUT_NAMES

    q, k, v = INPUT_NAMES
    gate = (raw["gate"],) if which.endswith("g") else ()
    s1 = (k, v) + ((raw["k"],) if model.qk_norm else ())
    return {"S0": (), "S1": s1, "S2": s1 + (q,), "S3": s1 + (raw["q"],),
            "S4": s1 + (q, raw["q"])}[which.rstrip("g")] + gate


def build(cell, devices):
    """(the jitted train step, a function key -> state, the state's
    shardings, the batch's sharding, a global batch's leaves, the rows a
    step takes) of ``cell`` on ``devices``, as a fit builds them."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from flax.training import train_state
    from jax.sharding import Mesh

    from chipbench import harness
    from raydp_tpu.parallel import batch_sharding, param_sharding_rules
    from raydp_tpu.parallel.mesh import AXES, MeshSpec
    from raydp_tpu.train.flax_estimator import (_make_apply, _make_train_step,
                                                _resolve_loss)

    sizes = MeshSpec(**cell.wl["mesh_spec"]).sizes(len(devices))
    mesh = Mesh(np.array(devices).reshape([sizes[a] for a in AXES]), AXES)
    info = cell.pipeline.describe(cell.cfg, cell.wl)
    batch = harness.global_batch(cell, mesh)
    est = cell.pipeline.build_estimator(cell.cfg, cell.wl, info, mesh=mesh,
                                        batch_size=batch, num_epochs=1)
    model, tx = est._build_model(), est._build_optimizer()
    leaves = cell.pipeline.batch_leaves(cell.cfg, cell.wl, info, batch)
    inputs0, _ = est._split_batch({
        k: jnp.zeros((1,) + tuple(shape[1:]), dtype)
        for k, (shape, dtype) in leaves.items()})

    class State(train_state.TrainState):
        batch_stats: object = None

    def create(key):
        v = model.init(key, inputs0)
        return State.create(apply_fn=model.apply, params=v["params"],
                            tx=tx, batch_stats=v.get("batch_stats"))

    shardings = param_sharding_rules(mesh, est.param_rules)(
        jax.eval_shape(create, jax.random.PRNGKey(0)))
    b_sh = batch_sharding(mesh)
    step = jax.jit(_make_train_step(
        _make_apply(model, False, est._split_batch, est.compute_dtype),
        _resolve_loss(est._loss), [], 1, "none",
        mb_shardings=(b_sh, None), state_shardings=shardings),
        donate_argnums=(0, 3))
    return model, step, create, shardings, b_sh, leaves, batch


def compiled_off_the_chip(head, step, create, shardings, b_sh, leaves):
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec

    state = jax.tree.map(
        lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh),
        jax.eval_shape(create, jax.random.PRNGKey(0)), shardings)
    batch = {k: jax.ShapeDtypeStruct(tuple(shape), jnp.dtype(dtype),
                                     sharding=b_sh)
             for k, (shape, dtype) in leaves.items()}
    t0 = time.perf_counter()
    compiled = step.lower(state, batch, (), jax.ShapeDtypeStruct(
        (), jnp.float32, sharding=NamedSharding(
            b_sh.mesh, PartitionSpec()))).compile()
    mem, hlo = compiled.memory_analysis(), compiled.as_text()
    print("AOT " + json.dumps(dict(
        head, compile_s=round(time.perf_counter() - t0, 1),
        arguments_gib=round(mem.argument_size_in_bytes / 2 ** 30, 3),
        temporaries_gib=round(mem.temp_size_in_bytes / 2 ** 30, 3),
        remat_in_text=len(re.findall(r"\.remat", hlo)),
        matrix_products=len(re.findall(r" (?:dot|convolution)\(", hlo)),
        fusions=len(re.findall(r" fusion\(", hlo)))), flush=True)


def by_scope(head, trace_dir):
    from chipbench.trace import reduce, scopes

    plane = reduce.find_xplane(trace_dir)
    red, names = reduce.reduce(plane), scopes.op_names(plane)
    buckets, attn_ops = {}, {}
    for op, seconds in red["op_seconds"].items():
        name = names.get(op, "")
        found = INNER.search(name) or SCOPE.search(name)
        where = "kernel" if op.startswith("rdt_flash") else \
            found.group(1) if found else "no scope of the model's"
        # a recomputed forward op lies in the backward's part of the program
        key = f"{where}.{'backward' if 'transpose(' in name else 'forward'}"
        buckets[key] = buckets.get(key, 0.0) + seconds / TRACED_STEPS
        if where == "attn":
            attn_ops[f"{op} {name[-60:]}"] = seconds / TRACED_STEPS
    print("BY_SCOPE " + json.dumps(dict(
        head, busy_ms_a_step=round(1e3 * red["busy_s"] / TRACED_STEPS, 2),
        attn_outside_kernels_ms=round(1e3 * sum(
            v for k, v in buckets.items() if k.startswith("attn.")), 2),
        ms={k: round(1e3 * v, 2) for k, v in sorted(
            buckets.items(), key=lambda kv: -kv[1]) if v > 2e-4})),
        flush=True)
    print("ATTN_OPS " + json.dumps(dict(head, ops=[
        [k, round(1e3 * v, 3)] for k, v in sorted(
            attn_ops.items(), key=lambda kv: -kv[1])[:14]])), flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--cell", required=True)
    ap.add_argument("--set", default="rule")
    ap.add_argument("--steps", type=int, default=14)
    ap.add_argument("--seed", type=int, default=2147484001)
    ap.add_argument("--aot", action="store_true")
    args = ap.parse_args(argv)
    if args.aot:
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ.setdefault("TPU_LOG_DIR", "disabled")
    else:
        os.environ.setdefault("TPU_LOG_DIR", os.path.join(
            ROOT, "chipbench", "out", "tpu_logs"))
        os.makedirs(os.environ["TPU_LOG_DIR"], exist_ok=True)

    import jax
    import jax.numpy as jnp
    import numpy as np

    from chipbench import manifest
    from raydp_tpu.models import transformer

    if args.set != "rule":
        transformer._kept_inputs = lambda model: names_of(args.set, model)
    cell = manifest.resolve(manifest.load_manifest(), args.cell)
    if args.aot:
        from jax.experimental import topologies
        jax.config.update("jax_enable_compilation_cache", False)
        devices = list(topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2").devices)[:1]
    else:
        devices = jax.devices()[:1]
    model, step, create, shardings, b_sh, leaves, rows_a_step = build(
        cell, devices)
    head = {"cell": args.cell, "set": args.set,
            "kept": list(transformer._kept(model) or ())}
    if args.aot:
        compiled_off_the_chip(head, step, create, shardings, b_sh, leaves)
        return 0

    rows = 8
    tokens = cell.pipeline.generate(rows, args.seed, cell.cfg)[
        "tokens"].combine_chunks().flatten().to_numpy().reshape(rows, -1)
    state = jax.jit(create, out_shardings=shardings)(jax.random.PRNGKey(0))
    loss = jnp.float32(0)

    def one(s):
        nonlocal state, loss
        at = [(s * rows_a_step + i) % rows for i in range(rows_a_step)]
        batch = {"tokens": jax.device_put(jnp.asarray(tokens[at]), b_sh)}
        jax.block_until_ready(batch)
        t0 = time.perf_counter()
        state, loss, _ = step(state, batch, (), jnp.float32(0))
        jax.block_until_ready(loss)
        return time.perf_counter() - t0

    first = one(0)
    steady = [one(s) for s in range(1, args.steps)][2:]
    stats = devices[0].memory_stats() or {}
    print("SWEEP " + json.dumps(dict(
        head, first_step_s=round(first, 1),
        mean_ms=round(1e3 * float(np.mean(steady)), 2),
        median_ms=round(1e3 * float(np.median(steady)), 2),
        min_ms=round(1e3 * float(np.min(steady)), 2),
        peak_in_use_gib=round(stats.get("peak_bytes_in_use", 0) / 2 ** 30, 3),
        peak_reserved_gib=round(
            stats.get("peak_bytes_reserved", 0) / 2 ** 30, 3),
        limit_gib=round(stats.get("bytes_limit", 0) / 2 ** 30, 3),
        last_loss=float(loss))), flush=True)
    trace_dir = os.path.join(ROOT, "chipbench", "out", "sweep",
                             f"{args.cell}.{args.set}")
    jax.profiler.start_trace(trace_dir)
    for s in range(TRACED_STEPS):
        one(args.steps + s)
    jax.profiler.stop_trace()
    by_scope(head, trace_dir)
    shutil.rmtree(trace_dir, ignore_errors=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
