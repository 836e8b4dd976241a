"""The Kimi Delta Attention scan alone on the chip, at the published shape
(``[1, 16384, 32, 128]``, bfloat16 q, k, v; float32 g and beta): the chunked
``jax.numpy`` form of ``raydp_tpu/ops/kda_scan.py`` forward, and forward with
its backward (the ``custom_vjp``: the chunked form formed again and
transposed), by the host's clock round ``block_until_ready`` (median of
``--repeats`` calls after a warm-up), with the allocator's peak; and, at
``--check-len`` positions, the form against the recurrence a position at a
time. Run by no cell; ``PERF.md`` section 6 (PR 64) holds its readings.

Run: python benchmarks/kda_scan_sweep.py [--chunks 64,128] [--seq-len 16384]
"""

from __future__ import annotations

import argparse
import os
import statistics
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def inputs(key, batch, t, heads, width, dtype):
    import jax
    import jax.numpy as jnp

    ks = jax.random.split(key, 6)
    unit = lambda a: a / jnp.linalg.norm(a, axis=-1, keepdims=True)  # noqa: E731
    shape = (batch, t, heads, width)
    q = unit(jax.random.normal(ks[0], shape)) * width ** -0.5
    k = unit(jax.random.normal(ks[1], shape))
    v = jax.random.normal(ks[2], shape)
    # what a fresh layer gives: A in [1, 16], a step of softplus(~N(0,1) - 4)
    a = jnp.exp(jax.random.uniform(ks[5], (heads, 1), minval=0.0,
                                   maxval=jnp.log(16.0)))
    g = -a * jax.nn.softplus(jax.random.normal(ks[3], shape) - 4.0)
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], shape[:3]))
    return (q.astype(dtype), k.astype(dtype), v.astype(dtype),
            g.astype(jnp.float32), beta.astype(jnp.float32))


def timed(fn, args, repeats):
    import jax

    jax.block_until_ready(fn(*args))
    seconds = []
    for _ in range(repeats):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        seconds.append(time.perf_counter() - t0)
    return 1e3 * statistics.median(seconds)


def main(argv=None) -> int:
    import jax
    import jax.numpy as jnp

    from raydp_tpu.ops.kda_scan import kda_recurrent_jnp, kda_scan

    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chunks", default="64")
    ap.add_argument("--seq-len", type=int, default=16384)
    ap.add_argument("--heads", type=int, default=32)
    ap.add_argument("--width", type=int, default=128)
    ap.add_argument("--repeats", type=int, default=5)
    ap.add_argument("--check-len", type=int, default=2048)
    args = ap.parse_args(argv)
    device = jax.devices()[0]
    print(f"device {device.device_kind}", flush=True)
    key = jax.random.PRNGKey(64)
    small = inputs(key, 1, args.check_len, args.heads, args.width,
                   jnp.bfloat16)
    want = jax.jit(kda_recurrent_jnp)(*small)
    big = inputs(key, 1, args.seq_len, args.heads, args.width, jnp.bfloat16)
    for chunk in (int(c) for c in args.chunks.split(",")):
        got = jax.jit(lambda *a: kda_scan(*a, chunk=chunk))(*small)
        err = float(jnp.sqrt(jnp.mean(jnp.square(got.astype(jnp.float32)
                                                  - want))
                             / jnp.mean(jnp.square(want))))
        forward = jax.jit(lambda *a: kda_scan(*a, chunk=chunk))
        both = jax.jit(jax.grad(
            lambda *a: jnp.sum(kda_scan(*a, chunk=chunk).astype(jnp.float32)
                               ** 2), argnums=(0, 1, 2, 3, 4)))
        fwd_ms = timed(forward, big, args.repeats)
        both_ms = timed(both, big, args.repeats)
        stats = device.memory_stats() or {}
        print(f"chunk {chunk}: T {args.seq_len} forward {fwd_ms:.2f} ms, "
              f"forward+backward {both_ms:.2f} ms, peak "
              f"{stats.get('peak_bytes_in_use', 0) / 2**30:.2f} GiB; "
              f"against the recurrence at T {args.check_len}: relative RMS "
              f"{err:.5f}", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
