"""Long-context LM training demo: sequence parallelism over the mesh's seq axis.

Runs a small decoder-only transformer over sequences sharded across devices:
ring attention rotates K/V blocks over ICI while each device attends for its
local queries, so per-device memory stays O(T / seq_devices) and contexts can
exceed single-chip HBM. On CPU, run with:

    JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \\
        python examples/longcontext_lm.py --seq-len 512 --steps 20

(The reference has no long-context support at all — SURVEY.md §2.4 — this is
TPU-native added capability.)
"""

from __future__ import annotations

import argparse
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--seq-len", type=int, default=512)
    p.add_argument("--batch", type=int, default=8)
    p.add_argument("--dim", type=int, default=128)
    p.add_argument("--heads", type=int, default=4)
    p.add_argument("--layers", type=int, default=2)
    p.add_argument("--vocab", type=int, default=256)
    p.add_argument("--steps", type=int, default=20)
    p.add_argument("--seq-parallel", type=int, default=0,
                   help="devices on the seq axis (0 = all devices)")
    p.add_argument("--tensor-parallel", type=int, default=1,
                   help="devices on the tensor axis (Megatron param split)")
    args = p.parse_args()

    import jax
    import jax.numpy as jnp
    import optax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from raydp_tpu.models import TransformerLM, lm_loss, \
        transformer_param_rules
    from raydp_tpu.parallel import MeshSpec, make_mesh, shard_params

    n_dev = len(jax.devices())
    tp = args.tensor_parallel
    if tp < 1 or n_dev % tp:
        raise SystemExit(f"--tensor-parallel must be >= 1 and divide the "
                         f"device count ({n_dev})")
    seq_par = args.seq_parallel or n_dev // tp
    mesh = make_mesh(MeshSpec(data=n_dev // (seq_par * tp), seq=seq_par,
                              tensor=tp))
    print(f"devices={n_dev} mesh={dict(mesh.shape)}")

    model = TransformerLM(vocab_size=args.vocab, dim=args.dim,
                          num_heads=args.heads, num_layers=args.layers,
                          attention="ring" if seq_par > 1 else "auto",
                          mesh=mesh)

    rng = np.random.RandomState(0)
    start = rng.randint(0, args.vocab, size=(args.batch, 1))
    tokens = jnp.asarray((start + np.arange(args.seq_len)[None]) % args.vocab,
                         dtype=jnp.int32)
    tokens = jax.device_put(tokens, NamedSharding(mesh, P("data", "seq")))

    variables = model.init(jax.random.PRNGKey(0), tokens)
    tx = optax.adamw(3e-4)
    params = variables["params"]
    opt_state = tx.init(params)
    if tp > 1:
        # Megatron split: q/k/v + gate/up column-parallel, o/down row-parallel
        rules = transformer_param_rules("tensor")
        params = shard_params(params, mesh, rules)
        opt_state = shard_params(opt_state, mesh, rules)

    @jax.jit
    def step(params, opt_state, batch):
        loss, grads = jax.value_and_grad(
            lambda p: lm_loss(model.apply({"params": p}, batch), batch)
        )(params)
        updates, opt_state = tx.update(grads, opt_state, params)
        return optax.apply_updates(params, updates), opt_state, loss

    with mesh:
        t0 = time.perf_counter()
        for i in range(args.steps):
            params, opt_state, loss = step(params, opt_state, tokens)
            if i % 5 == 0 or i == args.steps - 1:
                print(f"step {i}: loss {float(loss):.4f}")
        dt = time.perf_counter() - t0
    toks = args.batch * args.seq_len * args.steps
    print(f"{toks / dt:.0f} tokens/s over {n_dev} devices "
          f"(seq_parallel={seq_par}, T={args.seq_len})")


if __name__ == "__main__":
    main()
