"""The gated short convolution of a convolution operator (the ``lfm2``
family's), ONE pass over HBM in each direction: ``C * conv(B * z)`` between
the operator's two projections, as two Pallas kernels with a ``custom_vjp``.

The operator's input projection gives ``[B | C | z]``, three widths of
``width`` channels side by side; the stage is

    g_t = B_t * z_t                               elementwise
    c_t = sum_j taps[j] * g_{t - (K - 1) + j}     depthwise, causal, K taps,
                                                  zeros before the sequence,
                                                  no bias, no activation
    out_t = C_t * c_t

elementwise along the channels but for a halo of ``K - 1`` rows.
:func:`gated_conv` reads the three widths straight out of the projection's
output by block index (columns 0, ``width``, ``2 width``: nothing is sliced in
HBM), does the arithmetic in float32 in VMEM and writes ``[B, T, width]`` once
at the activations' dtype. The kernels are built from the row-tile / 16-row
halo / lane-tile helpers of :mod:`raydp_tpu.ops.ssm_glue` (a state-space
mixer's convolution, which computes something else: an activation and a bias,
no gate before it and none after).

``rdt_gated_conv_fwd``, on a grid (sequence, lane tile, row tile): a step
loads a tile of rows of ``B`` and ``z`` with the 16 rows before them (zeros
at a sequence's first tile: a halo never crosses a sequence) and the tile's
rows of ``C``, and walks the tile a few rows at a time; a tap is a sublane
roll of the window of ``g``. ``rdt_gated_conv_bwd``, on a grid (sequence,
lane tile, row tile, width): reads the same and, with the 16 rows AFTER the
tile, ``C`` and the output's gradient (``dg_t`` takes ``d c`` of rows ``t`` to
``t + K - 1``; beyond a sequence's end it is zero), re-forms ``g`` and ``c``
(the residual is the projection's output itself: nothing float32 is kept),
and writes the gradient of the projection's output as ONE ``[B, T, 3 width]``
array: the grid's last axis walks the three widths of a tile, whose inputs
stay where they are (a block whose index does not move is not fetched again);
its first step computes all three gradients (``dB`` to its block, ``dC`` and
``dz`` to VMEM) and the other two copy theirs out. ``d taps`` is summed in
float32 in an output block that stays put along the rows.

The kernels take ``T`` a multiple of the row tile and a ``width`` of whole
128-lane tiles (:func:`kernel_ineligible`). Anything else, and every platform
but a TPU, takes the ``jax.numpy`` form (:func:`gated_conv_jnp`: the CPU's
path and the tests' reference), chosen when the program is lowered.
``interpret`` runs the kernels through the Pallas interpreter (tests). The
counter ``short_conv_total{kernel|jnp}`` counts a built call by its path.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax

from raydp_tpu.ops import ssm_glue
from raydp_tpu.ops.ssm_glue import (_HALO, _choose, _lane_tile, _lay_out,
                                    _once_a_program, _over_batch, _row_sums,
                                    _row_tile, _tapped, _window)

KERNEL_NAMES = ("rdt_gated_conv_fwd", "rdt_gated_conv_bwd")
ROW_TILE = 512          # rows a grid step, fitted down to a divisor of T
WALK = 32               # rows a kernel works on at a time


# ---------------------------------------------------------------------------
# jax.numpy form
# ---------------------------------------------------------------------------
def gated_conv_jnp(src, taps, width: int):
    """``C * conv(B * z)`` in float32, cast to ``src``'s dtype: ``src [B, T,
    3 width]`` holds ``B``, ``C``, ``z`` side by side, ``taps [K, width]``.
    The ``jax.numpy`` form: what :func:`gated_conv` runs on every platform
    but a TPU and for shapes its kernels do not take, and the tests'
    reference."""
    f32, t = jnp.float32, src.shape[1]
    b_in, c_in, z = (src[..., i * width:(i + 1) * width].astype(f32)
                     for i in range(3))
    n_taps = taps.shape[0]
    padded = jnp.pad(b_in * z, ((0, 0), (n_taps - 1, 0), (0, 0)))
    conv = sum(taps[j].astype(f32) * padded[:, j:j + t]
               for j in range(n_taps))
    return (c_in * conv).astype(src.dtype)


def kernel_ineligible(t: int, width: int, rows: int = ROW_TILE,
                      taps: int = 3) -> Optional[str]:
    """Why the kernels cannot take the stage over ``t`` positions and three
    widths of ``width`` channels (None where they can)."""
    return ssm_glue.kernel_ineligible(t, (width,), 0, rows, taps)


# ---------------------------------------------------------------------------
# Kernel bodies
# ---------------------------------------------------------------------------
def _rows(ext, r0, n: int):
    """Rows ``r0 .. r0 + n`` of the tile laid out in ``ext``."""
    from jax.experimental import pallas as pl

    return ext[pl.ds(pl.multiple_of(r0 + _HALO, 8), n), :]


def _fwd_kernel(b_ref, b_before, z_ref, z_before, c_ref, taps_ref, o_ref,
                ext_b, ext_z, *, n_taps: int, walk: int):
    from jax.experimental import pallas as pl

    _lay_out(ext_b, b_ref, b_before)
    _lay_out(ext_z, z_ref, z_before)

    def step(k, carry):
        r0 = pl.multiple_of(k * walk, walk)
        gated = _window(ext_b, r0, walk) * _window(ext_z, r0, walk)
        conv, _ = _tapped(gated, taps_ref, n_taps, walk)
        o_ref[0, pl.ds(r0, walk), :] = (
            c_ref[0, pl.ds(r0, walk), :].astype(jnp.float32)
            * conv).astype(o_ref.dtype)
        return carry

    lax.fori_loop(0, b_ref.shape[1] // walk, step, None)


def _bwd_kernel(b_ref, b_before, z_ref, z_before, c_ref, c_after, g_ref,
                g_after, taps_ref, d_ref, dtaps_ref, ext_b, ext_z, d_conv,
                d_c, d_z, *, n_taps: int, walk: int):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    f32, rows = jnp.float32, b_ref.shape[1]
    i, tiles, part = pl.program_id(2), pl.num_programs(2), pl.program_id(3)

    @pl.when((i == 0) & (part == 0))
    def _start():
        dtaps_ref[...] = jnp.zeros_like(dtaps_ref)

    @pl.when(part == 0)
    def _gradients():
        # ``_lay_out`` by hand: the grid's position is read outside a branch
        for ext, ref, before in ((ext_b, b_ref, b_before),
                                 (ext_z, z_ref, z_before)):
            ext[:_HALO] = jnp.where(i == 0, 0.0, before[0].astype(f32))
            ext[_HALO:] = ref[0].astype(f32)

        def first_walk(k, sums):
            r0 = pl.multiple_of(k * walk, walk)
            gated = _window(ext_b, r0, walk) * _window(ext_z, r0, walk)
            conv, shifted = _tapped(gated, taps_ref, n_taps, walk)
            g = g_ref[0, pl.ds(r0, walk), :].astype(f32)
            d_c[pl.ds(r0, walk), :] = (g * conv).astype(d_c.dtype)
            d = g * c_ref[0, pl.ds(r0, walk), :].astype(f32)
            d_conv[pl.ds(r0, walk), :] = d
            return tuple(acc + _row_sums(d * of_tap)
                         for acc, of_tap in zip(sums, shifted))

        zero = jnp.zeros((8, b_ref.shape[2]), f32)
        sums = lax.fori_loop(0, rows // walk, first_walk, (zero,) * n_taps)
        for j, acc in enumerate(sums):
            dtaps_ref[0, 8 * j:8 * j + 8] += acc
        # the 8 rows after the tile (beyond a sequence's end: no gradient)
        after = g_after[0].astype(f32)[:8] * c_after[0].astype(f32)[:8]
        d_conv[rows:] = jnp.where(i == tiles - 1, 0.0, after)

        def second_walk(k, carry):
            r0 = pl.multiple_of(k * walk, walk)
            window = d_conv[pl.ds(r0, walk + 8), :]
            d_gated = taps_ref[n_taps - 1:n_taps] * window[:walk]
            for j in range(n_taps - 1):
                ahead = n_taps - 1 - j
                d_gated = d_gated + taps_ref[j:j + 1] * pltpu.roll(
                    window, walk + 8 - ahead, 0)[:walk]
            d_ref[0, pl.ds(r0, walk), :] = (
                d_gated * _rows(ext_z, r0, walk)).astype(d_ref.dtype)
            d_z[pl.ds(r0, walk), :] = (
                d_gated * _rows(ext_b, r0, walk)).astype(d_z.dtype)
            return carry

        lax.fori_loop(0, rows // walk, second_walk, None)

    @pl.when(part == 1)
    def _c():
        d_ref[0] = d_c[...]

    @pl.when(part == 2)
    def _z():
        d_ref[0] = d_z[...]


# ---------------------------------------------------------------------------
# The kernels' calls
# ---------------------------------------------------------------------------
def _taps(taps):
    """``[K + 1, width]`` float32: the taps over a bias of zeros (the shared
    helper's pre-activation starts from its last row)."""
    return jnp.pad(taps.astype(jnp.float32), ((0, 1), (0, 0)))


@_once_a_program
def _fwd_pallas(src, taps, *, width, tile, interpret):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    bsz, t, _ = src.shape
    n_taps, tr, tc = taps.shape[0], tile, _lane_tile(width)
    halos, per = tr // _HALO, width // tc
    rows = lambda first: pl.BlockSpec(  # noqa: E731
        (1, tr, tc), lambda b, c, i: (b, i, first + c))
    before = lambda first: pl.BlockSpec(  # noqa: E731
        (1, _HALO, tc), lambda b, c, i: (
            b, jnp.maximum(i * halos - 1, 0), first + c))
    return pl.pallas_call(
        functools.partial(_fwd_kernel, n_taps=n_taps, walk=min(WALK, tr)),
        grid=(bsz, per, t // tr),
        in_specs=[rows(0), before(0), rows(2 * per), before(2 * per),
                  rows(per),
                  pl.BlockSpec((n_taps + 1, tc), lambda b, c, i: (0, c))],
        out_specs=rows(0),
        out_shape=jax.ShapeDtypeStruct((bsz, t, width), src.dtype,
                                       vma=jax.typeof(src).vma),
        scratch_shapes=[pltpu.VMEM((_HALO + tr, tc), jnp.float32)] * 2,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret, name=KERNEL_NAMES[0])(
            src, src, src, src, src, _taps(taps))


@_once_a_program
def _bwd_pallas(src, taps, g, *, width, tile, interpret):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    bsz, t, _ = src.shape
    n_taps, tr, tc = taps.shape[0], tile, _lane_tile(width)
    halos, per, last = tr // _HALO, width // tc, t // _HALO - 1
    f32, vma = jnp.float32, jax.typeof(src).vma
    rows = lambda first: pl.BlockSpec(  # noqa: E731
        (1, tr, tc), lambda b, c, i, p: (b, i, first + c))
    before = lambda first: pl.BlockSpec(  # noqa: E731
        (1, _HALO, tc), lambda b, c, i, p: (
            b, jnp.maximum(i * halos - 1, 0), first + c))
    after = lambda first: pl.BlockSpec(  # noqa: E731
        (1, _HALO, tc), lambda b, c, i, p: (
            b, jnp.minimum((i + 1) * halos, last), first + c))
    d_src, dtaps = pl.pallas_call(
        functools.partial(_bwd_kernel, n_taps=n_taps, walk=min(WALK, tr)),
        grid=(bsz, per, t // tr, 3),
        in_specs=[rows(0), before(0), rows(2 * per), before(2 * per),
                  rows(per), after(per), rows(0), after(0),
                  pl.BlockSpec((n_taps + 1, tc), lambda b, c, i, p: (0, c))],
        out_specs=[
            # the three widths of a tile, one a step of the last axis
            pl.BlockSpec((1, tr, tc), lambda b, c, i, p: (b, i, p * per + c)),
            # a sequence's sum over its row tiles: the block stays put
            pl.BlockSpec((1, 8 * n_taps, tc), lambda b, c, i, p: (b, 0, c))],
        out_shape=[
            jax.ShapeDtypeStruct(src.shape, src.dtype, vma=vma),
            jax.ShapeDtypeStruct((bsz, 8 * n_taps, width), f32, vma=vma)],
        scratch_shapes=[pltpu.VMEM((_HALO + tr, tc), f32),
                        pltpu.VMEM((_HALO + tr, tc), f32),
                        pltpu.VMEM((tr + 8, tc), f32),
                        pltpu.VMEM((tr, tc), src.dtype),
                        pltpu.VMEM((tr, tc), src.dtype)],
        compiler_params=pltpu.CompilerParams(dimension_semantics=(
            "parallel", "parallel", "arbitrary", "arbitrary")),
        interpret=interpret, name=KERNEL_NAMES[1])(
            src, src, src, src, src, src, g, g, _taps(taps))
    return d_src, dtaps.reshape(bsz, n_taps, 8, width).sum(
        axis=(0, 2)).astype(taps.dtype)


# ---------------------------------------------------------------------------
# The op
# ---------------------------------------------------------------------------
@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3, 4))
def _gated(src, taps, width, tile, interpret):
    return _gated_fwd(src, taps, width, tile, interpret)[0]


def _gated_fwd(src, taps, width, tile, interpret):
    out = _choose(tile, interpret,
                  functools.partial(_fwd_pallas, width=width),
                  functools.partial(gated_conv_jnp, width=width), src, taps)
    return out, (src, taps)


def _gated_bwd(width, tile, interpret, residuals, g):
    def jnp_fn(src, taps, g):
        return jax.vjp(functools.partial(gated_conv_jnp, width=width),
                       src, taps)[1](g)

    return _choose(tile, interpret,
                   functools.partial(_bwd_pallas, width=width), jnp_fn,
                   *residuals, g)


_gated.defvjp(_gated_fwd, _gated_bwd)


def gated_conv(src, taps, width: int, rows: int = ROW_TILE,
               interpret: bool = False):
    """``C * conv(B * z)`` in ``src``'s dtype, ``[B, T, width]``: ``src [B,
    T, 3 width]`` is the operator's input projection (``B`` from column 0,
    ``C`` from ``width``, ``z`` from ``2 width``: the kernels read them by
    block index), ``taps [K, width]`` the depthwise causal convolution's.
    Differentiable in both."""
    width = int(width)
    if (src.ndim != 3 or taps.ndim != 2 or src.shape[2] != 3 * width
            or taps.shape[1] != width):
        raise ValueError(f"src {src.shape}, taps {taps.shape}: three widths "
                         f"of {width} channels, a tap a channel")
    from raydp_tpu import metrics as rdt_metrics

    t = src.shape[1]
    why = kernel_ineligible(t, width, rows, taps.shape[0])
    rdt_metrics.inc("short_conv_total", label="jnp" if why else "kernel")
    return _gated(src, taps, width, None if why else _row_tile(t, rows),
                  bool(interpret))


def gated_conv_sharded(src, taps, width: int, mesh, **kwargs):
    """:func:`gated_conv` mapped over the mesh's data axes."""
    fn = lambda s, k: gated_conv(s, k, width, **kwargs)  # noqa: E731
    return _over_batch(fn, mesh, (3, 0), 3)(src, taps)
