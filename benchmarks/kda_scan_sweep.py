"""The Kimi Delta Attention scan alone on the chip, at the published shape
(``[1, 16384, 32, 128]``, bfloat16 q, k, v; float32 g and beta): a row for the
kernel pair of ``raydp_tpu/ops/kda_scan.py`` (``rdt_kda_fwd`` / ``rdt_kda_bwd``,
what a program lowered for a TPU runs) beside a row for the chunked
``jax.numpy`` form (every other platform's, here held in place by refusing
every shape while it is traced): forward, and forward with its backward (the
``custom_vjp``), by the host's clock round ``block_until_ready`` (median of
``--repeats`` calls after a warm-up), the allocator's peak since the process
began, the relative RMS against the recurrence a position at a time at
``--check-len`` positions, and each time divided into the least time
``chipbench/flops/kda_moe_lm.py``'s ``kda_forward`` / ``kda_backward`` give
for the shape: a HOST-CLOCK ESTIMATE of a roofline share, no trace reading
and no metric of the benchmark. Run by no cell; ``PERF.md`` section 6 (PR 64,
PR 65) holds its readings.

Run: python benchmarks/kda_scan_sweep.py [--chunks 64,128] [--seq-len 16384]
"""

from __future__ import annotations

import argparse
import contextlib
import os
import statistics
import sys
import time
from unittest import mock

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


def least_ms(args, chunk, device_kind):
    """(forward, backward) least milliseconds of one layer's scan at the
    sweep's shape by the benchmark's own counts and the device's peaks; None
    off the chip (a rehearsal's times are no device's)."""
    from chipbench import manifest

    flops = manifest.load_module(ROOT, "flops", "kda_moe_lm.py")
    try:
        peak = manifest.peak_of(device_kind)
    except KeyError:
        return None
    cfg = {"seq_len": args.seq_len, "compute_dtype": "bfloat16",
           "linear_attn_config": {"num_heads": args.heads,
                                  "head_dim": args.width},
           "kda_chunk": chunk}
    return tuple(1e3 * max(ops / peak["bf16_flops_per_s"],
                           moved / peak["hbm_bytes_per_s"])
                 for ops, moved in (fn(cfg, {}, "kda", 1.0) for fn in (
                     flops.kda_forward, flops.kda_backward)))


def main(argv=None) -> int:
    import jax
    import jax.numpy as jnp

    from raydp_tpu.ops import kda_scan as op
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
    refuse = lambda *shape: "the sweep's jax.numpy row"  # noqa: E731
    for chunk in (int(c) for c in args.chunks.split(",")):
        least = least_ms(args, chunk, device.device_kind)
        why = op.kernel_ineligible(args.seq_len, chunk, args.width,
                                   args.width)
        for path in ("kernel", "jnp"):
            if path == "kernel" and why is not None:
                print(f"chunk {chunk}: no kernel row: {why}", flush=True)
                continue
            # the jax.numpy row: every shape refused while its programs trace
            with (mock.patch.object(op, "kernel_ineligible", refuse)
                  if path == "jnp" else contextlib.nullcontext()):
                forward = jax.jit(lambda *a: kda_scan(*a, chunk=chunk))
                both = jax.jit(jax.grad(
                    lambda *a: jnp.sum(kda_scan(*a, chunk=chunk).astype(
                        jnp.float32) ** 2), argnums=(0, 1, 2, 3, 4)))
                got = forward(*small)
                fwd_ms = timed(forward, big, args.repeats)
                both_ms = timed(both, big, args.repeats)
            err = float(jnp.sqrt(jnp.mean(jnp.square(
                got.astype(jnp.float32) - want)) / jnp.mean(jnp.square(want))))
            stats = device.memory_stats() or {}
            print(f"chunk {chunk} {path}: T {args.seq_len} forward "
                  f"{fwd_ms:.2f} ms, forward+backward {both_ms:.2f} ms, peak "
                  f"since the start {stats.get('peak_bytes_in_use', 0) / 2**30:.2f}"
                  f" GiB; against the recurrence at T {args.check_len}: "
                  f"relative RMS {err:.5f}; host-clock ESTIMATE of the "
                  f"roofline share: " + (
                      "not measured (no chip)" if least is None else
                      f"forward {100 * least[0] / fwd_ms:.1f}% of "
                      f"{least[0]:.2f} ms least, backward "
                      f"{100 * least[1] / max(both_ms - fwd_ms, 1e-9):.1f}"
                      f"% of {least[1]:.2f} ms least"), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
