"""The three chip-free rehearsals (``on-chip-measurement`` guide, section 2).
Run them before chip time is spent; what they print are rehearsals, never
results:

    python3 chipbench/rehearse.py cells [cell ...]    # 1 and 2: each cell end
                        # to end on the CPU, cut as its pipeline's cpu_cut says,
                        # a four-chip cell on four virtual devices
    python3 chipbench/rehearse.py compile [cell ...]  # 3: each cell's train
                        # step at its real sizes, compiled for a described
                        # v5e:2x2

No cell is named here: both take every cell of BENCHMARK.json, or those named.
"""

from __future__ import annotations

import os
import sys
import time

os.environ["JAX_PLATFORMS"] = "cpu"
os.environ.setdefault("TPU_LOG_DIR", "disabled")
if "xla_force_host_platform_device_count" not in os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                               + " --xla_force_host_platform_device_count=4")

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

def rehearse_cells(names, out_dir: str) -> int:
    import json

    from chipbench import harness, manifest

    m = manifest.load_manifest()
    failed = 0
    for entry in m["workloads"]:
        if names and entry["name"] not in names:
            continue
        cell = manifest.resolve(m, entry["name"])
        rehearsal = harness.cut_for_cpu(cell, out_dir)
        for trace in (False, True):
            t0 = time.perf_counter()
            result = harness.run_cell(cell, seed=1, seconds=0.5, trace=trace,
                                      t_start=t0, rehearsal=rehearsal)
            detail = result.pop("detail")
            print(f"REHEARSAL {cell.name} trace={int(trace)} "
                  f"({time.perf_counter() - t0:.1f}s): "
                  f"checks {detail['found']['checks']}, reference error "
                  f"{detail['found']['reference_error']:.5f} <= "
                  f"{detail['found']['reference_tolerance']:.5f} over "
                  f"{detail['found']['compared_shape']}")
            print(json.dumps(result))
            failed += not result["correct"]
    return failed


def rehearse_compile(names) -> int:
    """The program's own train step (``_make_train_step``) for each cell at
    its real sizes, compiled by the TPU's compiler for a described
    ``v5e:2x2``, on the cell's mesh over as many of its chips as the cell
    takes. The cell's pipeline says what the step is handed (``describe``:
    what its ETL says of the frame; ``batch_leaves``: a global batch's leaves,
    shapes and dtypes). Prints the bytes on each device and the collectives
    in the HLO. Nothing runs."""
    import re

    import jax
    import jax.numpy as jnp
    import numpy as np
    from flax.training import train_state
    from jax.experimental import topologies
    from jax.sharding import Mesh, NamedSharding, PartitionSpec

    from chipbench import harness, manifest
    from raydp_tpu.parallel import batch_sharding, param_sharding_rules
    from raydp_tpu.parallel.mesh import AXES, MeshSpec
    from raydp_tpu.train.flax_estimator import (_make_apply, _make_train_step,
                                                _resolve_loss, _takes_train)

    jax.config.update("jax_enable_compilation_cache", False)
    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    m = manifest.load_manifest()
    for name in names or [w["name"] for w in m["workloads"]]:
        cell = manifest.resolve(m, name)
        devices = list(topo.devices)[:cell.chips]
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
        takes_train = _takes_train(model)

        class State(train_state.TrainState):
            batch_stats: object = None

        def create():
            v = model.init(jax.random.PRNGKey(0), inputs0,
                           **({"train": False} if takes_train else {}))
            return State.create(apply_fn=model.apply, params=v["params"],
                                tx=tx, batch_stats=v.get("batch_stats"))

        shapes = jax.eval_shape(create)
        shardings = param_sharding_rules(mesh, est.param_rules)(shapes)
        state = jax.tree.map(
            lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh),
            shapes, shardings)
        b_sh = batch_sharding(mesh)
        rep = NamedSharding(mesh, PartitionSpec())
        jbatch = {k: jax.ShapeDtypeStruct(tuple(shape), jnp.dtype(dtype),
                                          sharding=b_sh)
                  for k, (shape, dtype) in leaves.items()}
        loss_sum = jax.ShapeDtypeStruct((), jnp.float32, sharding=rep)
        step = _make_train_step(
            _make_apply(model, takes_train, est._split_batch,
                        est.compute_dtype),
            _resolve_loss(est._loss), [], 1, "none",
            mb_shardings=(b_sh, None), state_shardings=shardings)
        t0 = time.perf_counter()
        compiled = jax.jit(step, donate_argnums=(0, 3)).lower(
            state, jbatch, (), loss_sum).compile()
        mem = compiled.memory_analysis()
        hlo = compiled.as_text()
        found = sorted(set(re.findall(
            r"(all-reduce|all-gather|reduce-scatter|all-to-all"
            r"|collective-permute)(?:-start)?\(", hlo)))
        counts = {c: len(re.findall(rf" {c}(?:-start)?\(", hlo)) for c in found}
        params = sum(int(np.prod(p.shape))
                     for p in jax.tree.leaves(shapes.params))
        gib = 2.0 ** 30
        print(f"REHEARSAL compile {name}: mesh {dict(mesh.shape)}, "
              f"{params} parameters, global batch {batch}, compiled in "
              f"{time.perf_counter() - t0:.1f}s; per device: arguments "
              f"{mem.argument_size_in_bytes / gib:.2f} GiB, outputs "
              f"{mem.output_size_in_bytes / gib:.2f} GiB (aliased "
              f"{mem.alias_size_in_bytes / gib:.2f}), temporaries "
              f"{mem.temp_size_in_bytes / gib:.2f} GiB; collectives {counts}")
    return 0


if __name__ == "__main__":
    what = sys.argv[1] if len(sys.argv) > 1 else ""
    if what == "cells":
        sys.exit(rehearse_cells(sys.argv[2:], os.path.join(
            ROOT, "chipbench", "out", "rehearsal")))
    if what == "compile":
        sys.exit(rehearse_compile(sys.argv[2:]))
    sys.exit(__doc__)
