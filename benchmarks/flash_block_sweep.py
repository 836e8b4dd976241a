"""Flash-attention block-size sweep on the chip.

Times forward and forward+backward through ``flash_attention`` for a grid of
(block_q, block_k) at long context — the evidence behind the default block
choices. A kernel application is a few milliseconds, the same order as one
dispatch's host cost, so every measurement chains ``--iters`` applications
on device inside ONE executable (``lax.scan`` feeding the output back as q)
and fetches a scalar once; per-iter time = (wall - one dispatch+fetch) /
iters, with that round trip itself measured on a trivial op. ``--grad``
differentiates w.r.t. q, k and v so both backward kernels run (w.r.t. q alone
the dk/dv kernel is dead code and the compiler drops it). A block shape the
compiler refuses is listed as REFUSED with the compiler's message.

Needs a TPU (off the chip ``flash_attention`` runs its jnp path and the
blocks mean nothing).

``--window`` and ``--kv-heads`` give a cell's geometry. ``--edges`` leaves the
blocks at their default and times instead the kernels' paths by the mask's
two edges, one line each: as built (interior blocks unmasked, edge blocks
walked in tiles), the interior unmasked alone, the tiles alone, neither (every
computed block whole and masked), and the tile walk at the other width. It
sets the op's module-level rules aside for a measurement (``_tile``, ``_walk``,
``_TILES_A_SIDE``); the op has no argument for any of them.

``--v-dim`` gives v and the output a width of their own (latent attention:
``--dim 192 --v-dim 128``); ``--blocks`` names the shapes to time.

``--backward`` times a layer's backward alone at the default blocks
(``_bwd_pallas`` on the residuals of one forward, chained on its cotangent),
one line each for the one kernel that holds a K/V head's dk and dv in VMEM and
for the pair of kernels, whichever of them the shape rule
(``_fused_backward_fits``) would take: the rule is set aside for a
measurement, the op has no argument for it. A form the compiler refuses is a
line that says so.

``--forward`` times a layer's forward alone at the default blocks, one line
for each unit of an unmasked block's online-softmax update: the whole block
(one product for all its scores, then the softmax, then PV), the chunk of q
rows the op takes (``_ROW_CHUNK``: a chunk's QK^T is issued before the
softmax and PV of the chunk before it), and chunks of 128, 256 and 512 rows.
The constant is set aside for a measurement; the op has no argument for it.

Run: python benchmarks/flash_block_sweep.py [--seq-len 8192] [--dim 128]
     python benchmarks/flash_block_sweep.py --edges --grad --batch 2 \
         --seq-len 8192 --heads 32 --kv-heads 4 --window 2048   # a Trinity layer
     python benchmarks/flash_block_sweep.py --backward --seq-len 16384 \
         --heads 32 --dim 192 --v-dim 128                       # a kanana-2 layer
     python benchmarks/flash_block_sweep.py --forward --seq-len 16384 \
         --heads 28 --kv-heads 4 --window 4096          # a SmallThinker layer
"""

from __future__ import annotations

import argparse
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--seq-len", type=int, default=8192)
    ap.add_argument("--heads", type=int, default=8)
    ap.add_argument("--dim", type=int, default=128, help="head dim")
    ap.add_argument("--v-dim", type=int, default=None,
                    help="width of v and the output (default: --dim)")
    ap.add_argument("--blocks", default=None,
                    help="the shapes to time, e.g. 1024x1024,512x1024")
    ap.add_argument("--batch", type=int, default=1)
    ap.add_argument("--iters", type=int, default=16)
    ap.add_argument("--grad", action="store_true",
                    help="time fwd+bwd instead of fwd")
    ap.add_argument("--kv-heads", type=int, default=None,
                    help="K/V heads (default: as many as query heads)")
    ap.add_argument("--window", type=int, default=None)
    ap.add_argument("--edges", action="store_true",
                    help="time the paths by the mask's edges, not the blocks")
    ap.add_argument("--backward", action="store_true",
                    help="time the backward alone: one kernel, and the pair")
    ap.add_argument("--forward", action="store_true",
                    help="time the forward's update whole and by row chunks")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax import lax

    from raydp_tpu.ops import flash_attention as fa
    flash_attention = fa.flash_attention

    if jax.default_backend() != "tpu":
        raise SystemExit(f"flash_block_sweep needs a TPU, found platform "
                         f"{jax.default_backend()!r}")
    B, T, H, D = args.batch, args.seq_len, args.heads, args.dim
    iters = args.iters
    rng = np.random.RandomState(0)
    Hk = args.kv_heads or H
    Dv = args.v_dim or D
    mk = lambda h, d=D: jnp.asarray(  # noqa: E731
        rng.randn(B, T, h, d).astype(np.float32) * 0.3).astype(jnp.bfloat16)
    q, k, v = mk(H), mk(Hk), mk(Hk, Dv)

    def rtt_ms() -> float:
        x = jnp.ones((8, 8))
        f = jax.jit(lambda a, c: (a * c).sum())
        float(f(x, 1.0))
        t0 = time.perf_counter()
        float(f(x, 2.0))
        return (time.perf_counter() - t0) * 1e3

    rtt = min(rtt_ms() for _ in range(3))
    print(f"dispatch+fetch RTT: {rtt:.1f} ms (subtracted)", file=sys.stderr)

    def timed(bq: int, bk: int) -> float:
        if args.grad:
            def one(x):
                dq, dk, dv = jax.grad(lambda qq, kk, vv: flash_attention(
                    qq, kk, vv, causal=True, block_q=bq, block_k=bk,
                    window=args.window)
                    .astype(jnp.float32).sum(), argnums=(0, 1, 2))(x, k, v)
                # a K/V head's gradients enter as one number each: they are
                # a group's, not a query head's shape
                kept = dk.astype(jnp.float32).mean() + dv.astype(
                    jnp.float32).mean()
                return (dq + kept.astype(dq.dtype)).astype(x.dtype)
        else:
            def one(x):
                out = flash_attention(x, k, v, causal=True, block_q=bq,
                                      block_k=bk, window=args.window)
                # fed back as q: a narrower output keeps q's other columns
                # (one more copy of q an application)
                return out if Dv == D else jnp.concatenate(
                    [out, x[..., Dv:]], axis=-1)

        @jax.jit
        def chained(x):
            out = lax.scan(lambda c, _: (one(c), ()), x, None,
                           length=iters)[0]
            return out.astype(jnp.float32).sum()

        return per_iter_ms(lambda: chained(q), rtt, iters)

    if args.backward:
        return backward(fa, (q, k, v), rtt, args)
    what = "fwd+bwd" if args.grad else "fwd"
    if args.edges:
        return edges(fa, timed, what, args)
    if args.forward:
        return forward(fa, timed, args)

    results, refused = [], []
    grid = [(128, 128), (128, 256), (256, 256), (256, 512), (512, 512),
            (512, 1024), (1024, 1024)]
    if args.blocks:
        grid = [tuple(int(n) for n in shape.split("x"))
                for shape in args.blocks.split(",")]
    for bq, bk in grid:
        if bq > T or bk > T:
            continue
        try:
            us = timed(bq, bk) * 1e3
        except jax.errors.JaxRuntimeError as e:   # the compiler said no
            refused.append((bq, bk))
            print(f"blk_q={bq:5d} blk_k={bk:5d}  REFUSED "
                  f"({type(e).__name__}: {str(e)[:600]})", file=sys.stderr)
            continue
        results.append((us, bq, bk))
        print(f"blk_q={bq:5d} blk_k={bk:5d}  {us:9.1f} us/{what}",
              file=sys.stderr)
    if not results:
        raise SystemExit("the compiler refused every configuration")
    best = min(results)
    # causal flash fwd FLOPs: QK^T at D and PV at Dv, B*H*(T^2/2) pairs, x 2
    flops = 2.0 * B * H * (T * T / 2) * (D + Dv) * (
        3.5 if args.grad else 1.0)
    tflops = flops / (best[0] * 1e-6) / 1e12
    print(f"best: blk_q={best[1]} blk_k={best[2]} ({best[0]:.1f} us/{what}, "
          f"~{tflops:.1f} TFLOP/s) at B={B} T={T} H={H} D={D} on "
          f"{jax.devices()[0].device_kind}; refused: {refused or 'none'}")


def per_iter_ms(chain, rtt: float, iters: int) -> float:
    """Milliseconds an application of ``chain()``, which runs ``iters`` of
    them and returns a scalar: the first call compiles and warms, the least
    wall of three more, less one round trip, is the measurement."""
    float(chain())
    walls = []
    for _ in range(3):
        t0 = time.perf_counter()
        float(chain())
        walls.append((time.perf_counter() - t0) * 1e3)
    wall = min(walls)
    per_iter = (wall - rtt) / iters
    if per_iter <= 0:
        raise RuntimeError(
            f"measurement below timing noise (wall {wall:.1f} ms <= RTT "
            f"{rtt:.1f} ms) — raise --iters or --seq-len")
    return per_iter


def edges(fa, timed, what, args):
    """The kernels' paths by the mask's edges at the default blocks, each
    timed under the op's rules set aside for it."""
    import jax

    if not hasattr(fa, "_tile"):       # a checkout from before the paths
        us = timed(fa.DEFAULT_BLOCK_Q, fa.DEFAULT_BLOCK_K) * 1e3
        print(f"every block whole and masked  {us:9.1f} us/{what}")
        return
    tile, walk, a_side = fa._tile, fa._walk, fa._TILES_A_SIDE

    def masked_interior(edge, *rest):
        return walk("whole" if edge is None else edge, *rest)

    other = 4 if a_side == 2 else 2
    for name, rules in (
            ("as built: interior unmasked, edges in tiles", {}),
            ("interior unmasked, edges whole", {"_tile": lambda *a: None}),
            ("interior masked, edges in tiles", {"_walk": masked_interior}),
            ("every block whole and masked",
             {"_tile": lambda *a: None, "_walk": masked_interior}),
            (f"as built at {other} tiles a side", {"_TILES_A_SIDE": other})):
        for rule, value in rules.items():
            setattr(fa, rule, value)
        jax.clear_caches()
        try:
            us = timed(fa.DEFAULT_BLOCK_Q, fa.DEFAULT_BLOCK_K) * 1e3
        finally:
            fa._tile, fa._walk, fa._TILES_A_SIDE = tile, walk, a_side
        print(f"{name:46s} {us:9.1f} us/{what}  (B={args.batch} "
              f"T={args.seq_len} H={args.heads}/{args.kv_heads or args.heads}"
              f" D={args.dim} window={args.window})", flush=True)


def forward(fa, timed, args):
    """A layer's forward at the default blocks by the unit of a block's
    online-softmax update: the whole block, the chunk the op takes, and each
    other chunk of q rows."""
    import jax

    built = fa._ROW_CHUNK
    for name, rows in [("the whole block", fa.DEFAULT_BLOCK_Q),
                       (f"as built: chunks of {built} rows", built)] + [
            (f"chunks of {c} rows", c) for c in (128, 256, 512) if c != built]:
        fa._ROW_CHUNK = rows
        jax.clear_caches()
        try:
            ms = timed(fa.DEFAULT_BLOCK_Q, fa.DEFAULT_BLOCK_K)
            said = f"{ms:9.3f} ms/fwd"
        except jax.errors.JaxRuntimeError as e:   # the compiler said no
            said = f"REFUSED ({str(e)[:400]})"
        finally:
            fa._ROW_CHUNK = built
        print(f"{name:32s} {said}  (B={args.batch} T={args.seq_len} "
              f"H={args.heads}/{args.kv_heads or args.heads} "
              f"D={args.dim}/{args.v_dim or args.dim} window={args.window})",
              flush=True)


def backward(fa, qkv, rtt, args):
    """A layer's backward alone, as one kernel and as the pair."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    b, t, h, d = qkv[0].shape
    to3 = lambda x: x.transpose(0, 2, 1, 3).reshape(  # noqa: E731
        b * x.shape[2], t, x.shape[3])
    q3, k3, v3 = map(to3, qkv)
    rules = dict(scale=d ** -0.5, causal=True, blk_q=fa.DEFAULT_BLOCK_Q,
                 blk_k=fa.DEFAULT_BLOCK_K, interpret=False,
                 window=args.window)
    res = (q3, k3, v3) + tuple(jax.jit(
        lambda *a: fa._fwd_pallas(*a, **rules))(q3, k3, v3))
    fits = fa._fused_backward_fits
    held = fa._fused_resident_bytes(t, d, v3.shape[2], k3.dtype)

    def one(res, g):
        dq, dk, dv = fa._bwd_pallas(res, g, **rules)
        # every gradient enters the next cotangent as one number
        kept = sum(x.astype(jnp.float32).mean() for x in (dq, dk, dv))
        return g + (kept * 1e-3).astype(g.dtype)

    @jax.jit
    def chained(res, g):
        out = lax.scan(lambda c, _: (one(res, c), ()), g, None,
                       length=args.iters)[0]
        return out.astype(jnp.float32).sum()

    g = jnp.ones_like(res[3])
    for name, rule in (("one kernel (dk, dv held in VMEM)", lambda *a: True),
                       ("the pair of kernels", lambda *a: False)):
        fa._fused_backward_fits = rule
        jax.clear_caches()
        try:
            ms = per_iter_ms(lambda: chained(res, g), rtt, args.iters)
            said = f"{ms:9.3f} ms/bwd"
        except jax.errors.JaxRuntimeError as e:   # the compiler said no
            said = f"REFUSED ({str(e)[:400]})"
        finally:
            fa._fused_backward_fits = fits
        print(f"{name:34s} {said}  (B={b} T={t} H={h}/{k3.shape[0] // b} "
              f"D={d}/{v3.shape[2]} window={args.window}; a head holds "
              f"{held / 2**20:.0f} MiB, the rule takes "
              f"{'one' if fits(t, d, v3.shape[2], k3.dtype) else 'the pair'})",
              flush=True)


if __name__ == "__main__":
    main()
