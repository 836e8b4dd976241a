"""One chip's share of one expert layer, alone on the chip.

    chiprun -- python3 benchmarks/moe_to_tokens_probe.py [--trace DIR]
        [--beside <another checkout's raydp_tpu/models/moe.py>]

For each shape of ``SHAPES`` (``top_k``, held of experts, ``D``, ``F``, ``N``)
one bfloat16 layer of :class:`raydp_tpu.models.moe.MoE` with seeded logits is
run forward and backward (output and the five gradients), eight executions a
reading, three readings; with ``--beside`` the same layer of another
checkout's ``moe.py`` too (how PR 58 read the ``top_k`` gathers of its parent
beside the runs, doc/long_context.md), the two interleaved. A line a shape:
the least reading of each in ms an execution, the slots a token held and the
largest difference between the two modules' outputs and gradients; with
``--trace``, one traced execution each: the device's busy time and its longest
ops (``chipbench/trace/reduce.py``). A side script: no cell runs it and it
fails off the chip.
"""

from __future__ import annotations

import argparse
import importlib.util
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# (top_k, held, experts, D, F, N): even shares of 0.5, 1.0, 1.5 and 2.0 slots
# a token at the widths of four cells, and smallthinker's own
SHAPES = [(8, 8, 128, 2048, 1024, 16384), (8, 16, 128, 2048, 1024, 16384),
          (8, 24, 128, 2048, 1024, 16384), (8, 32, 128, 2048, 1024, 16384),
          (6, 16, 64, 2560, 768, 16384)]


def main() -> int:
    parser = argparse.ArgumentParser()
    parser.add_argument("--trace", default=None,
                        help="a directory for one traced execution a form")
    parser.add_argument("--beside", default=None,
                        help="another checkout's raydp_tpu/models/moe.py")
    parser.add_argument("--seed", type=int, default=58)
    args = parser.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np

    from raydp_tpu.models import moe

    if jax.devices()[0].platform != "tpu":
        print("no chip: a probe's readings are the chip's or nothing")
        return 1
    modules = {"tree": moe}
    if args.beside:
        spec = importlib.util.spec_from_file_location("moe_beside",
                                                      args.beside)
        modules["beside"] = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(modules["beside"])
    for k, held, e, d, f, n in SHAPES:
        rng = np.random.default_rng(args.seed)
        layers = {name: module.MoE(e, k, f, dtype=jnp.bfloat16,
                                   experts_held=held, normalize_top_k=True)
                  for name, module in modules.items()}
        layer = layers["tree"]
        h = jnp.asarray(rng.normal(size=(n, d)), jnp.bfloat16)
        logits = jnp.asarray(rng.normal(size=(n, e)), jnp.float32)
        w = jnp.asarray(rng.normal(size=(n, d)), jnp.bfloat16)
        params = jax.jit(lambda: layer.init(
            jax.random.PRNGKey(args.seed), h, logits)["params"])()

        steps = {name: jax.jit(jax.value_and_grad(
            lambda p, h, lg, one=one: jnp.sum(
                one.apply({"params": p}, h, lg)[0].astype(jnp.float32) * w),
            (0, 1, 2))).lower(params, h, logits).compile()
            for name, one in layers.items()}
        outs = [jax.block_until_ready(step(params, h, logits))
                for step in steps.values()]
        apart = max(float(jnp.max(jnp.abs(
            a.astype(jnp.float32) - b.astype(jnp.float32))))
            for a, b in zip(jax.tree.leaves(outs[0]),
                            jax.tree.leaves(outs[-1])))
        _, aux = layer.apply({"params": params}, h, logits)
        least = {name: float("inf") for name in steps}
        for _ in range(3):
            for name, step in steps.items():
                t0 = time.perf_counter()
                for _ in range(8):
                    out = step(params, h, logits)
                jax.block_until_ready(out)
                least[name] = min(least[name],
                                  (time.perf_counter() - t0) / 8 * 1e3)
        if args.trace:
            from chipbench.trace import reduce as trace_reduce
            for name, step in steps.items():
                where = os.path.join(args.trace,
                                     f"k{k}_held{held}_of{e}_{name}")
                with jax.profiler.trace(where):
                    jax.block_until_ready(step(params, h, logits))
                found = trace_reduce.reduce(trace_reduce.find_xplane(where))
                print(f"TRACE k={k} held={held}/{e} {name}: busy "
                      f"{found['busy_s'] * 1e3:.3f} ms; " + ", ".join(
                          f"{op} {sec * 1e3:.3f}" for op, sec
                          in trace_reduce.most_first(found["op_seconds"], 12)),
                      flush=True)
        slots = {kind: float(aux[f"slots_{kind}"]) / n
                 for kind in ("held", "moved")}
        print(f"PROBE k={k} held={held}/{e} D={d} F={f} N={n}: even share "
              f"{k * held / e:.2f} a token, held {slots['held']:.3f}, "
              f"moved {slots['moved']:.3f}; " + ", ".join(
                  f"{name} {ms:.3f} ms" for name, ms in least.items())
              + f"; apart by at most {apart:.3g}", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
