"""The state-space scan kernels, and the kernels round them, alone on the chip.

Times ``rdt_ssd_fwd`` (with and without the chunk states written) and
``rdt_ssd_bwd`` of ``raydp_tpu/ops/ssd_scan.py`` at one layer's shape — by
default nemotron-3-nano-30b-a3b's: one sequence of 16,384 positions, 64 heads
of 64 in 8 groups, a state of 128, chunks of 128, bfloat16 — and prints, a
kernel, milliseconds an execution: the kernel's own seconds on the device
(a profiler trace of ``--iters`` executions, the events named after the
kernel) and the wall of the jitted call round it (the packing of ``l`` and
``dt`` and, for the backward, the three passes after the kernel). It also
prints the forward's relative RMS against the float32 ``jax.numpy`` form
(``_ssd_jnp``) of the same inputs.

``--beside <path>`` times another checkout's ``ssd_scan.py`` (the parent's)
in the same process, before this one's: one chip, one call, two numbers.

``--forms`` times the forms of a group's work that were tried, one line each,
with the op's module-level rules set aside for a measurement (``_tiles``,
``_over_lanes``, ``_head_sums``; the op has no argument for any of them):

- as built: a group's lanes a 128-lane piece at a time; a ``[Q, hg]`` factor
  laid over a piece as three bfloat16 pieces' one-pass products with the 0/1
  ``[hg, piece]`` matrix, added in float32 (exact); a head's sum over its
  lanes a masked lane reduction;
- the whole group's width in one piece (``[128, 512]`` float32 arrays, 64
  vregs each: the register file holds one);
- the factors laid over the lanes by a lane broadcast a head and a select
  (as exact; the forward is faster by it and the backward, whose XLU also
  carries the lane sums, slower by more);
- by one lane gather a piece (``take_along_axis``: half the lane permutes
  of the broadcasts);
- by a product with the 0/1 matrix at ``Precision.HIGHEST`` (exact, and six
  float32 passes of the MXU a factor);
- the heads' sums by the same product the other way.

Every form's forward and gradients are printed as digests (sums of absolute
values): forms that lay the factors out exactly print the same digits.

The form before them all (a head at a time: ``[Q, 1]`` columns, 64-lane
slices) is the parent's file: ``--beside``.

``--glue`` times what stands round the scan in place of it
(``raydp_tpu/ops/ssm_glue.py``: the causal convolution with its SiLU and the
gated grouped norm, two kernels each) at the same layer's shape (``xBC [1,
16384, 6144]``, ``y`` and ``z`` ``[1, 16384, 4096]``), the ``jax.numpy`` forms
XLA runs beside the kernels in one process: each op's own ms from a trace
(the kernels' events; every op of a ``jax.numpy`` call), the bytes a pass has
to move at 819 GB/s, and a layer's forward + recomputed forward + backward
both ways (the gate a stage was built against: the kernels' at most half
XLA's). ``--rows``, ``--lanes`` and ``--walk`` set the module's tile rules
aside for a measurement, ``--approx-sigmoid`` the sigmoid's exact division (a
form tried: faster, and not the same arithmetic).

Needs a TPU; ``--interpret`` runs the kernels through the Pallas interpreter
instead (any platform, toy shapes: the tier-1 smoke test), where a time means
nothing.

``--gated`` times the gated short convolution of a convolution operator
(``raydp_tpu/ops/short_conv.py``: ``rdt_gated_conv_fwd|bwd``) beside its
``jax.numpy`` form the same way (``--batch 2 --seq-len 8192`` is
lfm2-8b-a1b's step: ``W_in u [2, 8192, 6144]``).

Run: python benchmarks/ssd_scan_sweep.py [--forms] [--beside <ssd_scan.py>]
     python benchmarks/ssd_scan_sweep.py --glue [--rows R --lanes L --walk W]
     python benchmarks/ssd_scan_sweep.py --gated --batch 2 --seq-len 8192
"""

from __future__ import annotations

import argparse
import glob
import importlib.util
import os
import sys
import tempfile
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _inputs(args):
    import jax.numpy as jnp
    import numpy as np

    b, t, h, p, g, n = (args.batch, args.seq_len, args.heads, args.head_dim,
                        args.groups, args.state)
    r = np.random.default_rng(0)
    dtype = jnp.dtype(args.dtype)
    x = jnp.asarray(r.normal(size=(b, t, h, p)), dtype)
    dt = jnp.asarray(0.05 * np.log1p(np.exp(r.normal(size=(b, t, h)))),
                     jnp.float32)
    a = jnp.asarray(-np.exp(r.uniform(0, 2.7, h)), jnp.float32)
    bm = jnp.asarray(r.normal(size=(b, t, g, n)) * 0.3, dtype)
    cm = jnp.asarray(r.normal(size=(b, t, g, n)) * 0.3, dtype)
    d = jnp.ones((h,), jnp.float32)
    dy = jnp.asarray(r.normal(size=(b, t, h, p)), dtype)
    return (x, dt, a, bm, cm, d), dy


def _kernel_ms(trace_dir: str, kernel: str, iters: int):
    """Milliseconds an execution (of ``iters``) of the device events named
    after ``kernel`` in the trace under ``trace_dir``, every event of a call
    that runs the kernel several times summed (``""``: every op of the
    call; None: no device plane)."""
    import jax

    found = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    if not found:
        return None
    data = jax.profiler.ProfileData.from_file(max(found, key=os.path.getmtime))
    spent = [e.duration_ns for plane in data.planes
             if plane.name.startswith("/device:TPU:")
             for line in plane.lines if line.name == "XLA Ops"
             for e in line.events if e.name.lstrip("%").startswith(kernel)]
    return sum(spent) / iters / 1e6 if spent else None


def _timed(name: str, kernel: str, fn, operands, iters: int, traced: bool):
    """``fn(*operands)``: compiled and warmed, then ``iters`` executions
    under the profiler: (its result, wall ms an execution, the kernel's own
    ms an execution or None)."""
    import jax

    out = jax.block_until_ready(fn(*operands))
    with tempfile.TemporaryDirectory() as trace_dir:
        if traced:
            jax.profiler.start_trace(trace_dir)
        t0 = time.perf_counter()
        for _ in range(iters):
            out = fn(*operands)
        jax.block_until_ready(out)
        wall = 1e3 * (time.perf_counter() - t0) / iters
        if traced:
            jax.profiler.stop_trace()
        on_device = _kernel_ms(trace_dir, kernel, iters) if traced else None
    said = "not measured" if on_device is None else f"{on_device:8.3f} ms"
    print(f"  {name:28s} {'kernel' if kernel else 'ops   '} {said}   call "
          f"{wall:8.3f} ms", flush=True)
    return out, wall, on_device


def measure(ssd, label: str, args) -> dict:
    """The three kernels of ``ssd`` (a module) at ``args``' shape."""
    import jax
    import jax.numpy as jnp

    operands, dy = _inputs(args)
    rules = dict(chunk=args.chunk, interpret=args.interpret)
    traced = not args.interpret
    print(f"{label}:", flush=True)
    out = {}
    fwd = jax.jit(lambda *a: ssd._fwd_pallas(*a, **rules,
                                             emit_states=False)[0])
    fwd_states = jax.jit(lambda *a: ssd._fwd_pallas(*a, **rules,
                                                    emit_states=True))
    bwd = jax.jit(lambda *a: ssd._bwd_pallas(*a, **rules))
    y, *out["forward"] = _timed("forward, no states written", "rdt_ssd_fwd",
                                fwd, operands, args.iters, traced)
    (_, states), *out["forward_states"] = _timed(
        "forward, states written", "rdt_ssd_fwd", fwd_states, operands,
        args.iters, traced)
    grads, *out["backward"] = _timed("backward", "rdt_ssd_bwd", bwd,
                                     operands + (states, dy), args.iters,
                                     traced)
    f32 = lambda v: v.astype(jnp.float32)  # noqa: E731
    x, dt, a, bm, cm, d = operands
    want = jax.jit(lambda *a: ssd._ssd_jnp(*a, args.chunk)[0])(
        f32(x), dt, a, f32(bm), f32(cm), d)
    out["forward_rel_rms"] = float(jnp.sqrt(
        jnp.mean((f32(y) - want) ** 2) / jnp.mean(want ** 2)))
    out["gradients_finite"] = all(
        bool(jnp.isfinite(f32(v)).all()) for v in grads)
    out["digests"] = [float(jnp.sum(jnp.abs(f32(v)))) for v in (y, *grads)]
    print(f"  forward against the float32 jax.numpy form: relative RMS "
          f"{out['forward_rel_rms']:.3e}; gradients finite: "
          f"{out['gradients_finite']}\n  digests (y, dx, d dt, dA, dB, dC, dD): "
          + " ".join(f"{v:.9g}" for v in out["digests"]), flush=True)
    return out


HBM_BYTES_A_SECOND = 819e9      # chipbench/peaks.json, TPU v5 lite


def glue(sg, args) -> dict:
    """The two stages of ``sg`` (``raydp_tpu/ops/ssm_glue.py``) round the
    scan at ``args``' shape, kernels alone beside their ``jax.numpy`` forms
    in one process: a stage's forward and its backward as the kernels run
    them (each op's own ms from a trace: the kernels' events, and every
    op's of the call) and as XLA runs the ``jax.numpy`` form (forward; forward
    and backward by autodiff: every op's ms), the bytes a pass has to move
    at 819 GB/s, and a layer's forward + recomputed forward + backward both
    ways (the gate: the kernels' at most half XLA's)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    b, t = args.batch, args.seq_len
    inner, bc = args.heads * args.head_dim, args.groups * args.state
    widths, channels = (inner, bc, bc), inner + 2 * bc
    dtype, f32 = jnp.dtype(args.dtype), jnp.float32
    r = np.random.default_rng(0)
    normal = lambda *shape: jnp.asarray(r.normal(size=shape), dtype)  # noqa: E731
    xbc, kernel, bias = (normal(b, t, channels),
                         jnp.asarray(0.5 * r.normal(size=(4, channels)), f32),
                         jnp.asarray(0.3 * r.normal(size=channels), f32))
    grads = tuple(normal(b, t, w) for w in widths)
    y, z, dout = normal(b, t, inner), normal(b, t, inner), normal(b, t, inner)
    weight = jnp.asarray(1 + 0.3 * r.normal(size=inner), f32)
    rules = dict(tile=sg._row_tile(t, args.rows or sg.ROW_TILE),
                 interpret=args.interpret)
    conv = dict(offset=0, widths=widths)
    norm = dict(groups=args.groups, eps=1e-5, offset=0)
    nbytes = lambda columns: columns * b * t * dtype.itemsize  # noqa: E731
    stages = {
        "conv": dict(
            operands=(xbc, kernel, bias), grads=grads,
            forward=lambda *a: sg._conv_fwd_pallas(*a, **conv, **rules),
            backward=lambda *a: sg._conv_bwd_pallas(*a, **conv, **rules),
            jnp=lambda *a: sg._conv_jnp(*a, **conv),
            bytes=(nbytes(2 * channels), nbytes(3 * channels))),
        "norm": dict(
            operands=(y, z, weight), grads=dout,
            forward=lambda *a: sg._norm_fwd_pallas(*a, **norm, **rules),
            backward=lambda *a: sg._norm_bwd_pallas(*a, **norm, **rules),
            jnp=lambda *a: sg._norm_jnp(*a, **norm),
            bytes=(nbytes(3 * inner), nbytes(5 * inner)))}
    out = {}
    for stage, of in stages.items():
        print(f"{stage} (tiles: {rules['tile']} rows, {sg.LANE_TILE} lanes, "
              f"walked {sg.CONV_WALK} / {sg.NORM_WALK} rows at a time):",
              flush=True)
        out[stage] = _stage(of, f"rdt_ssm_{stage}", args,
                            "the gate: the kernels' at most half")
    return out


def _stage(of: dict, kernel: str, args, gate: str) -> dict:
    """One stage both ways: ``of["forward"]`` and ``of["backward"]`` (the
    kernels ``<kernel>_fwd`` and ``<kernel>_bwd``: their own ms from a trace)
    on ``of["operands"]`` (and ``of["grads"]``), beside ``of["jnp"]`` as XLA
    runs it (forward; forward and backward by autodiff: every op's ms), the
    worst difference between the two, ``of["bytes"]`` (forward, backward) at
    819 GB/s, and a recomputed layer's forward + forward + backward both
    ways against ``gate``."""
    import jax
    import jax.numpy as jnp

    operands, g = of["operands"], of["grads"]
    n, traced, f32 = len(operands), not args.interpret, jnp.float32
    both = lambda *a: jax.vjp(of["jnp"], *a[:n])[1](a[n])  # noqa: E731
    read = {}
    got, *read["forward"] = _timed(
        "forward, kernels", f"{kernel}_fwd", jax.jit(of["forward"]),
        operands, args.iters, traced)
    d_got, *read["backward"] = _timed(
        "backward, kernels", f"{kernel}_bwd", jax.jit(of["backward"]),
        operands + (g,), args.iters, traced)
    want, *read["forward_jnp"] = _timed(
        "forward, jax.numpy", "", jax.jit(of["jnp"]), operands, args.iters,
        traced)
    d_want, *read["both_jnp"] = _timed(
        "forward + backward, jax.numpy", "", jax.jit(both), operands + (g,),
        args.iters, traced)
    flat = lambda v: [a.astype(f32) for a in jax.tree.leaves(v)]  # noqa: E731
    read["worst"] = max(
        float(jnp.abs(a - w).max() / jnp.maximum(jnp.abs(w).max(), 1e-6))
        for a, w in zip(flat((got, d_got)), flat((want, d_want))))
    floors = [1e3 * n / HBM_BYTES_A_SECOND for n in of["bytes"]]
    print(f"  against the jax.numpy form, value and gradients: worst "
          f"|difference| / max {read['worst']:.3e}; bytes at 819 GB/s: "
          f"forward {floors[0]:.3f} ms, backward {floors[1]:.3f} ms",
          flush=True)
    if traced:
        ours = 2 * read["forward"][1] + read["backward"][1]
        xla = read["forward_jnp"][1] + read["both_jnp"][1]
        read["layer_ms"] = (ours, xla)
        print(f"  a layer (forward + forward + backward): kernels "
              f"{ours:.3f} ms, jax.numpy {xla:.3f} ms: "
              f"{100 * ours / xla:.1f}%, jax.numpy {xla / ours:.2f}x the "
              f"kernels ({gate})", flush=True)
    return read


def gated(sc, args) -> dict:
    """The gated short convolution of ``sc`` (``raydp_tpu/ops/
    short_conv.py``) at one layer's shape (``W_in u [B, T, 3 width]``),
    kernels alone beside the ``jax.numpy`` form in one process, as
    :func:`glue` times a state-space mixer's stages (the gate this stage
    was built against: XLA's within 1.3x of the kernels' and the simpler
    form is kept)."""
    import jax.numpy as jnp
    import numpy as np

    b, t, width = args.batch, args.seq_len, args.width
    dtype = jnp.dtype(args.dtype)
    r = np.random.default_rng(0)
    src = jnp.asarray(r.normal(size=(b, t, 3 * width)), dtype)
    taps = jnp.asarray(0.5 * r.normal(size=(3, width)), jnp.float32)
    rules = dict(width=width, interpret=args.interpret,
                 tile=sc._row_tile(t, args.rows or sc.ROW_TILE))
    size = b * t * width * dtype.itemsize
    print(f"gated conv (tiles: {rules['tile']} rows, walked {sc.WALK} rows "
          f"at a time):", flush=True)
    return _stage(dict(
        operands=(src, taps),
        grads=jnp.asarray(r.normal(size=(b, t, width)), dtype),
        forward=lambda *a: sc._fwd_pallas(*a, **rules),
        backward=lambda *a: sc._bwd_pallas(*a, **rules),
        jnp=lambda *a: sc.gated_conv_jnp(*a, width),
        bytes=(4 * size, 7 * size)), "rdt_gated_conv", args,
        "the gate: jax.numpy within 1.3x and the kernels go")


def _whole_width(hg: int, p: int):
    return [(range(hg), slice(0, hg * p))]


def _spread(heads, p: int, hg: int):
    """``[hg, piece]`` 0/1 float32: a head of the piece over its own lanes."""
    import jax.numpy as jnp
    from jax import lax

    lane = lax.broadcasted_iota(jnp.int32, (hg, len(heads) * p), 1)
    first = (lax.broadcasted_iota(jnp.int32, (hg, len(heads) * p), 0)
             - heads[0]) * p
    return ((lane >= first) & (lane < first + p)).astype(jnp.float32)


def _over_lanes_by_product(per_head, heads, p: int):
    import jax.numpy as jnp
    from jax import lax

    return lax.dot_general(
        per_head, _spread(heads, p, per_head.shape[1]),
        (((1,), (0,)), ((), ())), precision=lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32)


def _head_sums_by_product(piece, heads, p: int, into):
    import jax.numpy as jnp
    from jax import lax

    return into + lax.dot_general(
        piece, _spread(heads, p, into.shape[1]), (((1,), (1,)), ((), ())),
        precision=lax.Precision.HIGHEST, preferred_element_type=jnp.float32)


def _over_lanes_by_gather(per_head, heads, p: int):
    import jax.numpy as jnp
    from jax import lax

    rows, hg = per_head.shape
    width = len(heads) * p
    lane = lax.broadcasted_iota(jnp.int32, (1, width), 1)
    index = heads[0] + sum((lane >= k * p).astype(jnp.int32)
                           for k in range(1, len(heads)))
    padded = jnp.concatenate(
        [per_head, jnp.zeros((rows, width - hg), per_head.dtype)], axis=1)
    return jnp.take_along_axis(
        padded, jnp.broadcast_to(index, (rows, width)), axis=1)


def _over_lanes_by_broadcasts(per_head, heads, p: int):
    import jax.numpy as jnp
    from jax import lax

    if len(heads) == 1:
        return per_head[:, heads[0]:heads[0] + 1]
    shape = (per_head.shape[0], len(heads) * p)
    lane = lax.broadcasted_iota(jnp.int32, (1, shape[1]), 1)
    out = jnp.broadcast_to(per_head[:, heads[0]:heads[0] + 1], shape)
    for k, head in enumerate(heads[1:], 1):
        out = jnp.where(lane >= k * p, jnp.broadcast_to(
            per_head[:, head:head + 1], shape), out)
    return out


FORMS = (
    ("as built: 128-lane pieces, factors by three bfloat16 pieces' products",
     {}),
    ("the whole group's width in one piece", {"_tiles": _whole_width}),
    ("factors over the lanes by a lane broadcast a head and a select",
     {"_over_lanes": _over_lanes_by_broadcasts}),
    ("factors over the lanes by one lane gather a piece",
     {"_over_lanes": _over_lanes_by_gather}),
    ("factors over the lanes by a HIGHEST product",
     {"_over_lanes": _over_lanes_by_product}),
    ("heads' sums by a HIGHEST product",
     {"_head_sums": _head_sums_by_product}),
)


def forms(ssd, args) -> dict:
    """Each kept form of a group's work, the module's rules set aside."""
    import jax

    out = {}
    for name, rules in FORMS:
        built = {rule: getattr(ssd, rule) for rule in rules}
        for rule, value in rules.items():
            setattr(ssd, rule, value)
        jax.clear_caches()
        try:
            out[name] = measure(ssd, name, args)
        except Exception as e:  # noqa: BLE001  the lowering or the compiler said no
            out[name] = None
            print(f"{name}: REFUSED ({type(e).__name__}: {str(e)[:400]})",
                  flush=True)
        finally:
            for rule, value in built.items():
                setattr(ssd, rule, value)
    jax.clear_caches()
    return out


def main(argv=None) -> dict:
    ap = argparse.ArgumentParser()
    ap.add_argument("--batch", type=int, default=1)
    ap.add_argument("--seq-len", type=int, default=16384)
    ap.add_argument("--heads", type=int, default=64)
    ap.add_argument("--head-dim", type=int, default=64)
    ap.add_argument("--groups", type=int, default=8)
    ap.add_argument("--state", type=int, default=128)
    ap.add_argument("--chunk", type=int, default=128)
    ap.add_argument("--dtype", default="bfloat16")
    ap.add_argument("--iters", type=int, default=10)
    ap.add_argument("--interpret", action="store_true",
                    help="the Pallas interpreter (any platform, toy shapes)")
    ap.add_argument("--beside", default=None,
                    help="another checkout's ssd_scan.py, timed first")
    ap.add_argument("--forms", action="store_true",
                    help="time each kept form of a group's work")
    ap.add_argument("--glue", action="store_true",
                    help="the convolution and the gated norm round the scan "
                         "(ops/ssm_glue.py) beside their jax.numpy forms, "
                         "and not the scan")
    ap.add_argument("--gated", action="store_true",
                    help="the gated short convolution (ops/short_conv.py) "
                         "beside its jax.numpy form, and not the scan; "
                         "--batch, --seq-len, --width, --rows")
    ap.add_argument("--width", type=int, default=2048,
                    help="--gated: the channels of each of B, C and z")
    ap.add_argument("--rows", type=int, default=0,
                    help="--glue: rows a grid step (default: the op's)")
    ap.add_argument("--lanes", type=int, default=0,
                    help="--glue: the convolution's lanes a grid step")
    ap.add_argument("--walk", type=int, default=0,
                    help="--glue: rows a kernel works on at a time")
    ap.add_argument("--approx-sigmoid", action="store_true",
                    help="--glue: the sigmoid's division as the EUP's "
                         "approximate reciprocal (a form tried)")
    args = ap.parse_args(argv)

    import jax

    from raydp_tpu.ops import ssd_scan

    if not args.interpret and jax.default_backend() != "tpu":
        raise SystemExit(f"ssd_scan_sweep needs a TPU, found platform "
                         f"{jax.default_backend()!r} (--interpret runs the "
                         f"kernels interpreted)")
    if args.gated:
        from raydp_tpu.ops import short_conv

        print(f"B={args.batch} T={args.seq_len} 3 x {args.width} channels "
              f"{args.dtype} on {jax.devices()[0].device_kind}"
              + (" (interpreted)" if args.interpret else ""), flush=True)
        return {"gated": gated(short_conv, args)}
    if args.glue:
        from raydp_tpu.ops import ssm_glue

        print(f"B={args.batch} T={args.seq_len} channels "
              f"{args.heads * args.head_dim} + 2 x {args.groups * args.state} "
              f"{args.dtype} on {jax.devices()[0].device_kind}"
              + (" (interpreted)" if args.interpret else ""), flush=True)
        # the module's tile rules set aside for a measurement, as --forms does
        rules = {"LANE_TILE": args.lanes, "CONV_WALK": args.walk,
                 "NORM_WALK": args.walk}
        if args.approx_sigmoid:
            from jax.experimental import pallas as pl

            rules["_sigmoid"] = lambda x: pl.reciprocal(
                1.0 + jax.numpy.exp(-x), approx=True)
        built = {rule: getattr(ssm_glue, rule) for rule in rules}
        for rule, value in rules.items():
            setattr(ssm_glue, rule, value or built[rule])
        try:
            return {"glue": glue(ssm_glue, args)}
        finally:
            for rule, value in built.items():
                setattr(ssm_glue, rule, value)
    why_not = ssd_scan.kernel_ineligible(
        args.seq_len, args.chunk, args.heads // args.groups, args.head_dim,
        args.state)
    if why_not and not args.interpret:
        raise SystemExit(f"the compiled kernels do not take this shape: "
                         f"{why_not}")
    print(f"B={args.batch} T={args.seq_len} H={args.heads}x{args.head_dim} "
          f"G={args.groups} N={args.state} Q={args.chunk} {args.dtype} on "
          f"{jax.devices()[0].device_kind}"
          + (" (interpreted)" if args.interpret else ""), flush=True)
    out = {}
    if args.beside:
        spec = importlib.util.spec_from_file_location("ssd_scan_beside",
                                                      args.beside)
        beside = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(beside)
        out["beside"] = measure(beside, f"beside ({args.beside})", args)
        jax.clear_caches()
    if args.forms:
        out["forms"] = forms(ssd_scan, args)
    else:
        out["built"] = measure(ssd_scan, "as built", args)
    return out


if __name__ == "__main__":
    main()
