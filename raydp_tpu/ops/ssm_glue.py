"""What a state-space mixer does round its scan, each stage ONE pass over HBM
in each direction: the causal convolution with its SiLU, and the gated
grouped RMSNorm, as four Pallas kernels with a ``custom_vjp`` each.

Both stages are elementwise along the channels but for a halo of three rows
(the convolution) or a sum over a group's lanes (the norm), so a kernel reads
a tile of rows once, does the stage's arithmetic in float32 in VMEM, and
writes the result once in the activations' dtype. Written in ``jax.numpy`` the
same stages are 28 and 88 XLA ops a layer that re-read float32 ``[T, 4096 to
6144]`` intermediates many times over.

**The convolution** (:func:`conv_silu`): ``silu(bias + sum_j kernel[j] *
x_{t - (K - 1) + j})`` with zeros before position 0 of every sequence, read
straight out of the projection's output by block index (``offset``: the
channels' first column there; nothing is sliced in HBM) and written as one
array a width asked for (the mixer's ``x``, ``B`` and ``C``: the scan kernels
take them as they are). A call is one kernel a width, ``rdt_ssm_conv_fwd``,
on a grid (sequence, lane tile, row tile). A step loads a tile of rows and
the 16 rows before it (a second view of the same array; at a sequence's first
tile they count as zeros, so a halo never crosses a sequence), lays both in
float32 in VMEM, and walks the tile a few rows at a time: a tap is a sublane
roll of the window. Backward, ``rdt_ssm_conv_bwd``: reads the same rows (the
residual is the projection's output itself: nothing float32 is kept), the 16
rows AFTER the tile too and the output's gradient with its 16 rows after (the
taps run the other way: ``dx_t`` takes the pre-activation's gradient of rows
``t`` to ``t + K - 1``; beyond a sequence's end it is zero), re-forms the
pre-activation, applies SiLU's derivative, writes the input's gradient once
and sums ``d kernel`` and ``d bias`` in float32 in an output block that stays
put along the rows.

**The gated norm** (:func:`gated_norm`): ``g = y * silu(z)``, ``g *
rsqrt(mean(g^2) + eps) * weight`` over each group of lanes, ``z`` read out of
the projection's output by block index. ``rdt_ssm_norm_fwd`` on a grid
(sequence, group, row tile); ``rdt_ssm_norm_bwd`` reads ``y``, ``z`` and the
output's gradient, re-forms ``g`` and the row's ``rsqrt``, writes ``dy`` and
``dz`` and sums ``d weight`` as the convolution sums its own.

The kernels take ``T`` a multiple of the row tile (:func:`kernel_ineligible`)
and widths, offsets and a norm group that are multiples of 128 lanes.
Anything else, and every platform but a TPU, takes the ``jax.numpy`` forms
(:func:`causal_conv`, :func:`gated_norm_jnp`: the CPU's path and the tests'
reference), chosen when the program is lowered as
:mod:`raydp_tpu.ops.ssd_scan` chooses. ``interpret`` runs the kernels through
the Pallas interpreter (tests).
"""

from __future__ import annotations

import functools
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from raydp_tpu.ops.flash_attention import _by_platform

KERNEL_NAMES = ("rdt_ssm_conv_fwd", "rdt_ssm_conv_bwd",
                "rdt_ssm_norm_fwd", "rdt_ssm_norm_bwd")
ROW_TILE = 1024         # rows a grid step, fitted down to a divisor of T
LANE_TILE = 512         # lanes a grid step of the convolution, at most
_HALO = 16              # rows of a halo block: one packed bfloat16 tile
# rows a kernel works on at a time: the loop body's size. Measured at the
# published shape (benchmarks/ssd_scan_sweep.py --glue): the convolution's
# kernels are fastest at 32 rows of 512 lanes, the norm's at 64 or more
CONV_WALK = 32
NORM_WALK = 64
_NORM_TILE = 1 << 19    # elements of a norm's block at most (1 MiB bfloat16)


# ---------------------------------------------------------------------------
# jax.numpy forms
# ---------------------------------------------------------------------------
def causal_conv(x, kernel, bias):
    """A depthwise causal convolution as shifted multiply-adds, float32:
    ``y_t = bias + sum_j kernel[j] * x_{t - (K - 1) + j}`` with zeros before
    the sequence. ``x [B, T, C]``, ``kernel [K, C]``, ``bias [C]``. The
    ``jax.numpy`` form: what :func:`conv_silu` runs on every platform but a
    TPU and for shapes its kernels do not take, and the tests' reference."""
    taps, t = kernel.shape[0], x.shape[1]
    padded = jnp.pad(x, ((0, 0), (taps - 1, 0), (0, 0)))
    y = bias.astype(jnp.float32)
    for j in range(taps):
        y = y + kernel[j].astype(jnp.float32) * padded[:, j:j + t].astype(
            jnp.float32)
    return y


def _conv_jnp(src, kernel, bias, offset: int, widths: Sequence[int]):
    xbc = src[..., offset:offset + kernel.shape[1]]
    out = jax.nn.silu(causal_conv(xbc, kernel, bias)).astype(src.dtype)
    return tuple(jnp.split(out, np.cumsum(widths)[:-1].tolist(), axis=-1))


def gated_norm_jnp(y, z, weight, groups: int, eps: float):
    """``RMSNorm_by_group(y * silu(z)) * weight`` in float32, cast to ``y``'s
    dtype: ``y`` and ``z`` ``[B, T, inner]``, ``groups`` groups of lanes each
    normed by its own mean square. The ``jax.numpy`` form (see
    :func:`causal_conv`)."""
    b, t, inner = y.shape
    f32 = jnp.float32
    gated = (y.reshape(b, t, groups, -1).astype(f32)
             * jax.nn.silu(z.astype(f32)).reshape(b, t, groups, -1))
    var = jnp.mean(jnp.square(gated), axis=-1, keepdims=True)
    return ((gated * jax.lax.rsqrt(var + eps)).reshape(b, t, inner)
            * weight).astype(y.dtype)


def _norm_jnp(y, src, weight, groups: int, eps: float, offset: int):
    return gated_norm_jnp(y, src[..., offset:offset + y.shape[-1]], weight,
                          groups, eps)


# ---------------------------------------------------------------------------
# Which shapes the kernels take
# ---------------------------------------------------------------------------
def _row_tile(t: int, rows: int) -> Optional[int]:
    """The rows of a grid step: ``rows`` halved until it divides ``t``; None
    where that leaves less than a halo block or less than 128 of the rows
    asked for."""
    tile = rows
    while tile >= _HALO and t % tile:
        tile //= 2
    return tile if tile >= min(rows, 128) and tile % _HALO == 0 else None


def _lane_tile(*extents: int) -> int:
    """The widest tile of whole 128-lane vregs, at most ``LANE_TILE``, that
    divides every extent (each a multiple of 128)."""
    return next(tile for tile in range(LANE_TILE, 0, -128)
                if not any(e % tile for e in extents))


def kernel_ineligible(t: int, widths: Sequence[int], offset: int = 0,
                      rows: int = ROW_TILE, taps: int = 4) -> Optional[str]:
    """Why the kernels cannot take a stage over ``t`` positions and channels
    of ``widths`` (the convolution's outputs, or the norm's one group) that
    start at column ``offset`` of what they are read from (None where they
    can): whole row tiles, whole 128-lane tiles, a halo of one block."""
    if _row_tile(t, rows) is None:
        return (f"{t} positions are no whole number of row tiles of "
                f"{min(rows, 128)} or more")
    if any(w % 128 for w in widths) or offset % 128:
        return (f"widths {tuple(widths)} from column {offset} have to be "
                f"multiples of 128 lanes")
    if not 1 <= taps - 1 <= 8:
        return f"{taps} taps: a tap reaches at most 8 rows back"
    return None


def _count(path: str) -> None:
    from raydp_tpu import metrics as rdt_metrics

    rdt_metrics.inc("ssm_glue_total", label=path)


# ---------------------------------------------------------------------------
# Kernel bodies
# ---------------------------------------------------------------------------
def _sigmoid(x):
    return 1.0 / (1.0 + jnp.exp(-x))


def _tapped(window, taps_ref, n_taps: int, rows: int):
    """The convolution's pre-activation of ``rows`` rows from a float32
    window that starts 8 rows before them: tap ``j`` is the window rolled
    ``K - 1 - j`` rows down. Also each tap's rows (the parameters' gradient
    takes them)."""
    from jax.experimental.pallas import tpu as pltpu

    pre, shifted = taps_ref[n_taps:n_taps + 1], []
    for j in range(n_taps):
        back = n_taps - 1 - j
        rolled = pltpu.roll(window, back, 0) if back else window
        shifted.append(rolled[8:8 + rows])
        pre = pre + taps_ref[j:j + 1] * shifted[-1]
    return pre, shifted


def _lay_out(ext, x_ref, before_ref, after_ref=None):
    """A tile's rows in float32 behind the 16 before them (zeros at a
    sequence's first tile) and, ``after_ref``, before the 16 after them."""
    from jax.experimental import pallas as pl

    f32, rows = jnp.float32, x_ref.shape[1]
    first = pl.program_id(2) == 0
    ext[:_HALO] = jnp.where(first, 0.0, before_ref[0].astype(f32))
    ext[_HALO:_HALO + rows] = x_ref[0].astype(f32)
    if after_ref is not None:
        ext[_HALO + rows:] = after_ref[0].astype(f32)


def _window(ext, r0, rows: int):
    """Rows ``r0 - 8 .. r0 + rows`` of the tile laid out in ``ext``."""
    from jax.experimental import pallas as pl

    return ext[pl.ds(pl.multiple_of(r0 + _HALO - 8, 8), rows + 8), :]


def _conv_fwd_kernel(x_ref, before_ref, taps_ref, o_ref, ext, *, n_taps: int,
                     walk: int):
    from jax.experimental import pallas as pl

    _lay_out(ext, x_ref, before_ref)

    def step(k, carry):
        r0 = pl.multiple_of(k * walk, walk)
        pre, _ = _tapped(_window(ext, r0, walk), taps_ref, n_taps, walk)
        o_ref[0, pl.ds(r0, walk), :] = (pre * _sigmoid(pre)).astype(
            o_ref.dtype)
        return carry

    lax.fori_loop(0, x_ref.shape[1] // walk, step, None)


def _row_sums(a):
    """``[R, C]`` float32 -> ``[8, C]``: the rows summed eight apart (whole
    vregs added; the eight sublanes are summed outside the kernel)."""
    out = a[:8]
    for first in range(8, a.shape[0], 8):
        out = out + a[first:first + 8]
    return out


def _conv_bwd_kernel(x_ref, before_ref, after_ref, g_ref, g_after_ref,
                     taps_ref, dx_ref, dtaps_ref, ext, d_pre, *, n_taps: int,
                     walk: int):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    f32, rows = jnp.float32, x_ref.shape[1]
    i, tiles = pl.program_id(2), pl.num_programs(2)
    _lay_out(ext, x_ref, before_ref, after_ref)

    @pl.when(i == 0)
    def _start():
        dtaps_ref[...] = jnp.zeros_like(dtaps_ref)

    def pre_gradient(r0, n, g):
        """Rows ``r0 .. r0 + n`` of the tile: the pre-activation's gradient
        and each tap's input rows."""
        pre, shifted = _tapped(_window(ext, r0, n), taps_ref, n_taps, n)
        s = _sigmoid(pre)
        return g.astype(f32) * (s * (1.0 + pre - pre * s)), shifted

    def first_walk(k, sums):
        r0 = pl.multiple_of(k * walk, walk)
        d, shifted = pre_gradient(r0, walk, g_ref[0, pl.ds(r0, walk), :])
        d_pre[pl.ds(r0, walk), :] = d
        return (*(acc + _row_sums(d * of_tap)
                  for acc, of_tap in zip(sums, shifted)),
                sums[n_taps] + _row_sums(d))

    zero = jnp.zeros((8, x_ref.shape[2]), f32)
    sums = lax.fori_loop(0, rows // walk, first_walk,
                         (zero,) * (n_taps + 1))
    for j, acc in enumerate(sums):
        dtaps_ref[0, 8 * j:8 * j + 8] += acc
    # the 8 rows after the tile (beyond a sequence's end: no gradient)
    after, _ = pre_gradient(rows, 8, g_after_ref[0].astype(f32)[:8])
    d_pre[rows:] = jnp.where(i == tiles - 1, 0.0, after)

    def second_walk(k, carry):
        r0 = pl.multiple_of(k * walk, walk)
        window = d_pre[pl.ds(r0, walk + 8), :]
        dx = taps_ref[n_taps - 1:n_taps] * window[:walk]
        for j in range(n_taps - 1):
            ahead = n_taps - 1 - j
            dx = dx + taps_ref[j:j + 1] * pltpu.roll(
                window, walk + 8 - ahead, 0)[:walk]
        dx_ref[0, pl.ds(r0, walk), :] = dx.astype(dx_ref.dtype)
        return carry

    lax.fori_loop(0, rows // walk, second_walk, None)


def _gate(y_ref, z_ref, r0, walk: int, eps: float):
    """A walk's rows of one group: ``y``, ``z``, ``sigmoid(z)``, ``silu(z)``,
    the normed ``g = y silu(z)`` and its row's ``rsqrt``."""
    from jax.experimental import pallas as pl

    f32 = jnp.float32
    y = y_ref[0, pl.ds(r0, walk), :].astype(f32)
    z = z_ref[0, pl.ds(r0, walk), :].astype(f32)
    s = _sigmoid(z)
    silu = z * s
    gated = y * silu
    rstd = lax.rsqrt(jnp.mean(gated * gated, axis=1, keepdims=True) + eps)
    return y, z, s, silu, gated * rstd, rstd


def _norm_fwd_kernel(y_ref, z_ref, w_ref, o_ref, *, eps: float, walk: int):
    from jax.experimental import pallas as pl

    def step(k, carry):
        r0 = pl.multiple_of(k * walk, walk)
        normed = _gate(y_ref, z_ref, r0, walk, eps)[4]
        o_ref[0, pl.ds(r0, walk), :] = (normed * w_ref[...]).astype(
            o_ref.dtype)
        return carry

    lax.fori_loop(0, y_ref.shape[1] // walk, step, None)


def _norm_bwd_kernel(y_ref, z_ref, w_ref, g_ref, dy_ref, dz_ref, dw_ref, *,
                     eps: float, walk: int):
    from jax.experimental import pallas as pl

    f32 = jnp.float32

    @pl.when(pl.program_id(2) == 0)
    def _start():
        dw_ref[...] = jnp.zeros_like(dw_ref)

    def step(k, dw):
        r0 = pl.multiple_of(k * walk, walk)
        y, z, s, silu, normed, rstd = _gate(y_ref, z_ref, r0, walk, eps)
        g = g_ref[0, pl.ds(r0, walk), :].astype(f32)
        h = g * w_ref[...]
        d_gated = rstd * (h - normed * jnp.mean(h * normed, axis=1,
                                                keepdims=True))
        dy_ref[0, pl.ds(r0, walk), :] = (d_gated * silu).astype(dy_ref.dtype)
        dz_ref[0, pl.ds(r0, walk), :] = (
            d_gated * y * (s * (1.0 + z - silu))).astype(dz_ref.dtype)
        return dw + _row_sums(g * normed)

    dw_ref[0] += lax.fori_loop(0, y_ref.shape[1] // walk, step,
                               jnp.zeros(dw_ref.shape[1:], f32))


# ---------------------------------------------------------------------------
# The kernels' calls
# ---------------------------------------------------------------------------
def _once_a_program(kernels_call):
    """``kernels_call`` jitted over its keyword rules: the layers of a model
    call a stage with the same shapes, so one traced call and ONE lowering of
    its kernels serve them all (a call's kernels are lowered where the
    program is, a quarter of a second a layer otherwise; XLA inlines the
    calls)."""
    import inspect

    return jax.jit(kernels_call, static_argnames=[
        name for name, p in inspect.signature(kernels_call).parameters.items()
        if p.kind is p.KEYWORD_ONLY])


def _params():
    from jax.experimental.pallas import tpu as pltpu

    # sequences and lane tiles are independent; the parameters' gradients
    # are summed along the row tiles
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "arbitrary"))


def _taps(kernel, bias):
    """``[K + 1, C]`` float32: the taps over the bias."""
    return jnp.concatenate([kernel.astype(jnp.float32),
                            bias.astype(jnp.float32)[None]])


def _segments(offset: int, widths: Sequence[int]):
    """(first channel of the convolution, first column of the source, width)
    an output."""
    starts = np.cumsum([0, *widths[:-1]]).tolist()
    return [(first, offset + first, w) for first, w in zip(starts, widths)]


def _into(like, pieces, offset: int):
    """``pieces`` side by side as the gradient of ``like``, whose columns
    from ``offset`` they are the gradient of, zeros elsewhere: a sum of
    padded pieces (exact: no two overlap), which XLA fuses into whatever
    reads the gradient, so nothing of ``like``'s width is written for it."""
    out, width = None, like.shape[-1]
    for piece in pieces:
        after = width - offset - piece.shape[-1]
        padded = jnp.pad(piece, ((0, 0), (0, 0), (offset, after)))
        out = padded if out is None else out + padded
        offset += piece.shape[-1]
    return out


@_once_a_program
def _conv_fwd_pallas(src, kernel, bias, *, offset, widths, tile, interpret):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    bsz, t, _ = src.shape
    n_taps, tr = kernel.shape[0], tile
    taps, vma = _taps(kernel, bias), jax.typeof(src).vma
    outs = []
    for channel, column, width in _segments(offset, widths):
        tc = _lane_tile(channel, column, width)
        c0, k0, halos = column // tc, channel // tc, tr // _HALO
        outs.append(pl.pallas_call(
            functools.partial(_conv_fwd_kernel, n_taps=n_taps,
                              walk=min(CONV_WALK, tr)),
            grid=(bsz, width // tc, t // tr),
            in_specs=[
                pl.BlockSpec((1, tr, tc), lambda b, c, i: (b, i, c0 + c)),
                pl.BlockSpec((1, _HALO, tc), lambda b, c, i: (
                    b, jnp.maximum(i * halos - 1, 0), c0 + c)),
                pl.BlockSpec((n_taps + 1, tc), lambda b, c, i: (0, k0 + c))],
            out_specs=pl.BlockSpec((1, tr, tc), lambda b, c, i: (b, i, c)),
            out_shape=jax.ShapeDtypeStruct((bsz, t, width), src.dtype,
                                           vma=vma),
            scratch_shapes=[pltpu.VMEM((_HALO + tr, tc), jnp.float32)],
            compiler_params=_params(), interpret=interpret,
            name=KERNEL_NAMES[0])(src, src, taps))
    return tuple(outs)


@_once_a_program
def _conv_bwd_pallas(src, kernel, bias, grads, *, offset, widths, tile,
                     interpret):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    bsz, t, _ = src.shape
    n_taps, tr = kernel.shape[0], tile
    taps, vma = _taps(kernel, bias), jax.typeof(src).vma
    f32, halos, last = jnp.float32, tr // _HALO, t // _HALO - 1
    dxs, dtaps = [], []
    for (channel, column, width), g in zip(_segments(offset, widths), grads):
        tc = _lane_tile(channel, column, width)
        c0, k0 = column // tc, channel // tc
        tile = lambda first: pl.BlockSpec(  # noqa: E731
            (1, tr, tc), lambda b, c, i: (b, i, first + c))
        after = lambda first: pl.BlockSpec(  # noqa: E731
            (1, _HALO, tc), lambda b, c, i: (
                b, jnp.minimum((i + 1) * halos, last), first + c))
        dx, dt = pl.pallas_call(
            functools.partial(_conv_bwd_kernel, n_taps=n_taps,
                              walk=min(CONV_WALK, tr)),
            grid=(bsz, width // tc, t // tr),
            in_specs=[
                tile(c0),
                pl.BlockSpec((1, _HALO, tc), lambda b, c, i: (
                    b, jnp.maximum(i * halos - 1, 0), c0 + c)),
                after(c0), tile(0), after(0),
                pl.BlockSpec((n_taps + 1, tc), lambda b, c, i: (0, k0 + c))],
            out_specs=[
                tile(0),
                # a sequence's sum over its row tiles: the block stays put
                pl.BlockSpec((1, 8 * (n_taps + 1), tc),
                             lambda b, c, i: (b, 0, c))],
            out_shape=[
                jax.ShapeDtypeStruct((bsz, t, width), src.dtype, vma=vma),
                jax.ShapeDtypeStruct((bsz, 8 * (n_taps + 1), width), f32,
                                     vma=vma)],
            scratch_shapes=[pltpu.VMEM((_HALO + tr + _HALO, tc), f32),
                            pltpu.VMEM((tr + 8, tc), f32)],
            compiler_params=_params(), interpret=interpret,
            name=KERNEL_NAMES[1])(src, src, src, g, g, taps)
        dxs.append(dx)
        dtaps.append(dt.reshape(bsz, n_taps + 1, 8, width).sum(axis=(0, 2)))
    dtaps = jnp.concatenate(dtaps, axis=1)
    return (_into(src, dxs, offset), dtaps[:n_taps].astype(kernel.dtype),
            dtaps[n_taps].astype(bias.dtype))


def _norm_specs(t, tr, gw, z0):
    from jax.experimental import pallas as pl

    return (pl.BlockSpec((1, tr, gw), lambda b, g, i: (b, i, g)),
            pl.BlockSpec((1, tr, gw), lambda b, g, i: (b, i, z0 + g)),
            pl.BlockSpec((1, gw), lambda b, g, i: (0, g)))


@_once_a_program
def _norm_fwd_pallas(y, src, weight, *, groups, eps, offset, tile,
                     interpret):
    from jax.experimental import pallas as pl

    bsz, t, inner = y.shape
    gw, tr = inner // groups, tile
    y_spec, z_spec, w_spec = _norm_specs(t, tr, gw, offset // gw)
    return pl.pallas_call(
        functools.partial(_norm_fwd_kernel, eps=eps, walk=min(NORM_WALK, tr)),
        grid=(bsz, groups, t // tr), in_specs=[y_spec, z_spec, w_spec],
        out_specs=y_spec,
        out_shape=jax.ShapeDtypeStruct(y.shape, y.dtype,
                                       vma=jax.typeof(y).vma),
        compiler_params=_params(), interpret=interpret,
        name=KERNEL_NAMES[2])(y, src, weight.astype(jnp.float32)[None])


@_once_a_program
def _norm_bwd_pallas(y, src, weight, g, *, groups, eps, offset, tile,
                     interpret):
    from jax.experimental import pallas as pl

    bsz, t, inner = y.shape
    gw, tr = inner // groups, tile
    y_spec, z_spec, w_spec = _norm_specs(t, tr, gw, offset // gw)
    vma = jax.typeof(y).vma
    dy, dz, dw = pl.pallas_call(
        functools.partial(_norm_bwd_kernel, eps=eps, walk=min(NORM_WALK, tr)),
        grid=(bsz, groups, t // tr),
        in_specs=[y_spec, z_spec, w_spec, y_spec],
        out_specs=[y_spec, y_spec,
                   pl.BlockSpec((1, 8, gw), lambda b, g, i: (b, 0, g))],
        out_shape=[jax.ShapeDtypeStruct(y.shape, y.dtype, vma=vma),
                   jax.ShapeDtypeStruct(y.shape, src.dtype, vma=vma),
                   jax.ShapeDtypeStruct((bsz, 8, inner), jnp.float32,
                                        vma=vma)],
        compiler_params=_params(), interpret=interpret,
        name=KERNEL_NAMES[3])(y, src, weight.astype(jnp.float32)[None], g)
    return (dy, _into(src, [dz], offset),
            dw.sum(axis=(0, 1)).astype(weight.dtype))


# ---------------------------------------------------------------------------
# The ops
# ---------------------------------------------------------------------------
def _choose(tile, interpret: bool, kernel_fn, jnp_fn, *args):
    """The kernels (``tile``: their row tile) where the program is lowered
    for a TPU, the ``jax.numpy`` form elsewhere and for a shape the kernels
    do not take (``tile`` None)."""
    if tile is None:
        return jnp_fn(*args)
    return _by_platform(
        functools.partial(kernel_fn, tile=tile, interpret=interpret), jnp_fn,
        interpret, *args)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _conv(src, kernel, bias, offset, widths, tile, interpret):
    return _conv_fwd(src, kernel, bias, offset, widths, tile, interpret)[0]


def _conv_fwd(src, kernel, bias, offset, widths, tile, interpret):
    rules = dict(offset=offset, widths=widths)
    out = _choose(tile, interpret,
                  functools.partial(_conv_fwd_pallas, **rules),
                  functools.partial(_conv_jnp, **rules), src, kernel, bias)
    return out, (src, kernel, bias)


def _conv_bwd(offset, widths, tile, interpret, residuals, grads):
    def jnp_fn(src, kernel, bias, grads):
        xbc = src[..., offset:offset + kernel.shape[1]]
        d_xbc, d_kernel, d_bias = jax.vjp(
            functools.partial(_conv_jnp, offset=0, widths=widths),
            xbc, kernel, bias)[1](grads)
        return _into(src, [d_xbc], offset), d_kernel, d_bias

    return _choose(tile, interpret, functools.partial(
        _conv_bwd_pallas, offset=offset, widths=widths), jnp_fn,
        *residuals, grads)


_conv.defvjp(_conv_fwd, _conv_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _norm(y, src, weight, groups, eps, offset, tile, interpret):
    return _norm_fwd(y, src, weight, groups, eps, offset, tile, interpret)[0]


def _norm_fwd(y, src, weight, groups, eps, offset, tile, interpret):
    rules = dict(groups=groups, eps=eps, offset=offset)
    out = _choose(tile, interpret,
                  functools.partial(_norm_fwd_pallas, **rules),
                  functools.partial(_norm_jnp, **rules), y, src, weight)
    return out, (y, src, weight)


def _norm_bwd(groups, eps, offset, tile, interpret, residuals, g):
    def jnp_fn(y, src, weight, g):
        z = src[..., offset:offset + y.shape[-1]]
        dy, dz, dw = jax.vjp(functools.partial(
            gated_norm_jnp, groups=groups, eps=eps), y, z, weight)[1](g)
        return dy, _into(src, [dz], offset), dw

    return _choose(tile, interpret, functools.partial(
        _norm_bwd_pallas, groups=groups, eps=eps, offset=offset), jnp_fn,
        *residuals, g)


_norm.defvjp(_norm_fwd, _norm_bwd)


def conv_silu(src, kernel, bias, widths: Sequence[int], offset: int = 0,
              rows: int = ROW_TILE, interpret: bool = False):
    """``silu(causal_conv(src[..., offset:offset + C], kernel, bias))`` in
    ``src``'s dtype, as one array ``[B, T, w]`` a width of ``widths`` (they
    sum to ``C``). ``src [B, T, W]`` (the projection's output: the kernels
    read the ``C`` channels from column ``offset`` by block index), ``kernel
    [K, C]``, ``bias [C]``. Differentiable in all three."""
    widths = tuple(int(w) for w in widths)
    if (src.ndim != 3 or kernel.ndim != 2 or bias.shape != kernel.shape[1:]
            or sum(widths) != kernel.shape[1]
            or offset + kernel.shape[1] > src.shape[2]):
        raise ValueError(
            f"src {src.shape} from column {offset}, kernel {kernel.shape}, "
            f"bias {bias.shape}, widths {widths}: the widths sum to the "
            f"kernel's channels, which lie inside src")
    t = src.shape[1]
    why = kernel_ineligible(t, widths, offset, rows, kernel.shape[0])
    _count("jnp" if why else "kernel")
    return _conv(src, kernel, bias, int(offset), widths,
                 None if why else _row_tile(t, rows), bool(interpret))


def gated_norm(y, src, weight, groups: int, eps: float, offset: int = 0,
               rows: int = ROW_TILE, interpret: bool = False):
    """:func:`gated_norm_jnp` of ``y [B, T, inner]`` and the gate ``z =
    src[..., offset:offset + inner]`` (the kernels read it out of ``src [B,
    T, W]`` by block index). Differentiable in ``y``, ``src``, ``weight``."""
    inner = y.shape[-1]
    if (y.ndim != 3 or src.shape[:2] != y.shape[:2] or inner % groups
            or weight.shape != (inner,) or offset + inner > src.shape[2]):
        raise ValueError(
            f"y {y.shape}, src {src.shape} from column {offset}, weight "
            f"{weight.shape}, {groups} groups: the gate lies inside src, the "
            f"groups divide the width")
    t, width = y.shape[1], inner // groups
    # a block is a whole group wide: five of them (twice over) share VMEM
    rows = min(rows, _NORM_TILE // width)
    why = (f"the gate's column {offset} is no multiple of a group's {width}"
           if offset % width else kernel_ineligible(t, (width,), offset, rows))
    _count("jnp" if why else "kernel")
    return _norm(y, src, weight, int(groups), float(eps), int(offset),
                 None if why else _row_tile(t, rows), bool(interpret))


def _over_batch(fn, mesh, ranks, out_ranks):
    """``fn`` mapped over the mesh's data axes (None or one device: ``fn``
    itself): a stage is independent along the batch (the halo runs along
    ``T``), and the partitioner cannot split a custom call. ``ranks``: an
    argument's rank where it has a batch, 0 where every device holds it
    whole."""
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    from raydp_tpu.parallel.mesh import data_axes

    if mesh is None or mesh.size == 1:
        return fn
    axes = data_axes(mesh)
    batch = axes if len(axes) > 1 else axes[0]
    spec = lambda rank: P(batch, *(None,) * (rank - 1)) if rank else P()  # noqa: E731

    def mapped(*args):
        # a parameter every device holds whole varies from here on, so its
        # gradient (a device's rows' share) is summed over the devices
        return fn(*(a if rank else lax.pcast(a, tuple(axes), to="varying")
                    for a, rank in zip(args, ranks)))

    return shard_map(mapped, mesh=mesh, in_specs=tuple(map(spec, ranks)),
                     out_specs=jax.tree.map(spec, out_ranks))


def conv_silu_sharded(src, kernel, bias, widths, mesh, **kwargs):
    """:func:`conv_silu` mapped over the mesh's data axes."""
    fn = lambda s, k, b: conv_silu(s, k, b, widths, **kwargs)  # noqa: E731
    return _over_batch(fn, mesh, (3, 0, 0), (3,) * len(widths))(
        src, kernel, bias)


def gated_norm_sharded(y, src, weight, groups, eps, mesh, **kwargs):
    """:func:`gated_norm` mapped over the mesh's data axes."""
    fn = lambda a, s, w: gated_norm(a, s, w, groups, eps, **kwargs)  # noqa: E731
    return _over_batch(fn, mesh, (3, 3, 0), 3)(y, src, weight)
