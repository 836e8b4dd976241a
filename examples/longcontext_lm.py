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
    import optax
    import pyarrow as pa

    import raydp_tpu
    from raydp_tpu.models import TransformerLM, lm_loss, \
        transformer_param_rules
    from raydp_tpu.parallel import MeshSpec, make_mesh
    from raydp_tpu.train import FlaxEstimator

    n_dev = len(jax.devices())
    tp = args.tensor_parallel
    if tp < 1 or n_dev % tp:
        raise SystemExit(f"--tensor-parallel must be >= 1 and divide the "
                         f"device count ({n_dev})")
    seq_par = args.seq_parallel or n_dev // tp
    mesh = make_mesh(MeshSpec(data=n_dev // (seq_par * tp), seq=seq_par,
                              tensor=tp))
    print(f"devices={n_dev} mesh={dict(mesh.shape)}")

    rng = np.random.RandomState(0)
    start = rng.randint(0, args.vocab, size=(args.batch, 1))
    tokens = ((start + np.arange(args.seq_len)[None]) % args.vocab
              ).astype(np.int32)

    session = raydp_tpu.init("longcontext", num_executors=1, executor_cores=1,
                             executor_memory="1GB")
    try:
        # one batch of token rows as a list column; the feed places each row's
        # positions over the mesh's seq axis, and an epoch is one step
        df = session.createDataFrame(pa.table({
            "tokens": pa.FixedSizeListArray.from_arrays(tokens.ravel(),
                                                        args.seq_len)}))
        est = FlaxEstimator(
            model=TransformerLM(vocab_size=args.vocab, dim=args.dim,
                                num_heads=args.heads, num_layers=args.layers,
                                attention="ring" if seq_par > 1 else "auto",
                                mesh=mesh),
            optimizer=optax.adamw(3e-4), loss=lm_loss, mesh=mesh,
            # Megatron split: q/k/v + gate/up column-parallel, o/down row-parallel
            param_rules=transformer_param_rules("tensor") if tp > 1 else None,
            columns_spec={"tokens": ("tokens", np.int32)},
            batch_preprocessor=lambda batch: (batch["tokens"], batch["tokens"]),
            batch_size=args.batch, num_epochs=args.steps, shuffle=False)
        for report in est.fit_on_frame(df).history:
            if report["epoch"] % 5 == 0 or report["epoch"] == args.steps - 1:
                print(f"step {report['epoch']}: loss {report['train_loss']:.4f}")
    finally:
        raydp_tpu.stop()


if __name__ == "__main__":
    main()
