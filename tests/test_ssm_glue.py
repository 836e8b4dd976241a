"""What a state-space mixer does round its scan (``raydp_tpu/ops/ssm_glue.py``):
the causal convolution with its SiLU and the gated grouped RMSNorm, each as
two Pallas kernels (interpreted here) against its ``jax.numpy`` form
(``causal_conv``, ``gated_norm_jnp``: the CPU's path), value and every
gradient; the halo of three rows at tile borders, at a sequence's start and
(the backward's, the other way) at its end; shapes the kernels do not take;
the wrappers over a mesh.
"""

import numpy as np
import pytest

F32_TOL = 1e-5


def _conv_case(b, t, widths, offset, extra, dtype, seed=0):
    """A source ``[b, t, offset + sum(widths) + extra]``, taps, a bias, and
    one cotangent a width."""
    import jax.numpy as jnp
    r = np.random.default_rng(seed)
    channels = sum(widths)
    src = jnp.asarray(r.normal(size=(b, t, offset + channels + extra)), dtype)
    kernel = jnp.asarray(0.5 * r.normal(size=(4, channels)), jnp.float32)
    bias = jnp.asarray(0.3 * r.normal(size=channels), jnp.float32)
    grads = tuple(jnp.asarray(r.normal(size=(b, t, w)), dtype) for w in widths)
    return (src, kernel, bias), grads


def _value_and_grads(fn, operands, grads):
    """``fn(*operands)`` (one array or a tuple) and the gradient of its sum
    against ``grads`` in every operand."""
    import jax
    import jax.numpy as jnp

    def weighed(*a):
        out = fn(*a)
        both = zip(jax.tree.leaves(out), jax.tree.leaves(grads))
        return sum(jnp.sum(o.astype(jnp.float32) * g.astype(jnp.float32))
                   for o, g in both), out
    (_, out), d = jax.value_and_grad(
        weighed, argnums=tuple(range(len(operands))), has_aux=True)(*operands)
    return jax.tree.leaves(out), list(d)


def _in_float32(tree):
    import jax
    import jax.numpy as jnp
    return jax.tree.map(lambda a: a.astype(jnp.float32), tree)


def _same(got, want, names):
    """float32 against float32: 1e-5 of the largest value. A bfloat16 result
    against what the ``jax.numpy`` form gives for the same numbers in
    float32: an ulp (2^-7 of the value: the float32 result rounded once;
    autodiff of the bfloat16 form rounds a gradient once a tap and adds in
    bfloat16, so it is no reference to an ulp)."""
    import jax.numpy as jnp
    assert len(got) == len(want) == len(names)
    for name, g, w in zip(names, got, want):
        assert g.shape == w.shape and w.dtype == jnp.float32, name
        ulp = 2.0 ** -7 if g.dtype == jnp.bfloat16 else 0.0
        g, w = np.asarray(g, np.float32), np.asarray(w, np.float32)
        assert np.isfinite(g).all(), name
        top = max(float(np.abs(w).max()), 1e-3)
        assert (np.abs(g - w) <= ulp * np.abs(w) + F32_TOL * top).all(), name


# ------------------------------------------------------------ the convolution
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("b,t,widths,offset,extra,rows", [
    (1, 16, (128,), 0, 0, 16), (1, 64, (128,), 0, 0, 16),
    (2, 48, (128,), 0, 0, 16), (1, 32, (384,), 0, 0, 16),
    (1, 32, (256, 128, 128), 0, 0, 16), (2, 64, (256, 128, 128), 256, 64, 16),
    (1, 128, (512, 128), 128, 0, 64), (1, 256, (128,), 128, 32, 1024)],
    ids=["one_tile", "four_row_tiles", "two_sequences", "three_lane_tiles",
         "three_outputs", "read_inside_a_wider_source", "tiles_of_two_walks",
         "the_default_tile_fitted_down"])
def test_the_convolution_kernels_are_the_jnp_form(b, t, widths, offset, extra,
                                                 rows, dtype):
    """``conv_silu`` through its two kernels against ``silu(causal_conv)``
    cast and split: the outputs and the gradients of the source (zeros off
    the channels read), the taps and the bias; one tile and several (the
    three rows before a tile lie in the tile before it, the three after it
    in the next), a batch, one lane tile and several, one output and three,
    channels that start inside a wider source, a tile walked in two pieces,
    and the default tile fitted down to a short sequence."""
    import jax.numpy as jnp
    from raydp_tpu.ops import ssm_glue as sg

    dtype = jnp.dtype(dtype)
    operands, grads = _conv_case(b, t, widths, offset, extra, dtype)
    assert sg.kernel_ineligible(t, widths, offset, rows) is None
    got = _value_and_grads(lambda *a: sg.conv_silu(
        *a, widths, offset, rows=rows, interpret=True), operands, grads)
    want = _value_and_grads(lambda *a: sg._conv_jnp(*a, offset, widths),
                            _in_float32(operands), _in_float32(grads))
    assert all(o.dtype == dtype for o in got[0] + got[1][:1])
    _same(got[0], want[0], [f"out{i}" for i in range(len(widths))])
    _same(got[1], want[1], ["src", "kernel", "bias"])
    if offset:      # nothing flows to the columns that were not read
        assert not np.asarray(got[1][0], np.float32)[..., :offset].any()


def test_the_first_positions_see_zeros_and_no_sequence_sees_another():
    """Position ``t < 3`` of EVERY sequence takes zeros for what lies before
    it: written out for the first three rows, and the second sequence's
    outputs and input gradients do not move when the first one's last rows
    (its tile's, and the halo block a clamped index would read) do."""
    import jax.numpy as jnp
    from raydp_tpu.ops import ssm_glue as sg

    (src, kernel, bias), grads = _conv_case(2, 32, (128,), 0, 0, jnp.float32)
    run = lambda s: _value_and_grads(lambda *a: sg.conv_silu(  # noqa: E731
        *a, (128,), rows=16, interpret=True), (s, kernel, bias), grads)
    (out,), (d_src, _, _) = run(src)
    x, w = np.asarray(src), np.asarray(kernel)
    for t in range(3):
        pre = np.asarray(bias) + sum(
            w[j] * x[:, t - 3 + j] for j in range(4) if t - 3 + j >= 0)
        np.testing.assert_allclose(
            np.asarray(out)[:, t], pre / (1 + np.exp(-pre)), rtol=1e-5,
            atol=1e-6)
    moved = src.at[0, 16:].add(3.0)
    (out2,), (d_src2, _, _) = run(moved)
    np.testing.assert_array_equal(np.asarray(out2)[1], np.asarray(out)[1])
    np.testing.assert_array_equal(np.asarray(d_src2)[1], np.asarray(d_src)[1])
    assert np.abs(np.asarray(out2)[0, 16:] - np.asarray(out)[0, 16:]).max() > 0.1


def test_the_backwards_halo_ends_with_the_sequence():
    """The taps run the other way in the backward pass: ``dx_t`` takes the
    pre-activation's gradient of rows ``t .. t + 3``. Inside the sequence
    they lie in the next tile (a gradient there moves ``dx`` of the last
    three rows of the tile before); after the last tile there are none (the
    clamped look-ahead block holds the tile's own rows: they must not count
    twice), and the second sequence's first rows are not the first one's
    next."""
    import jax.numpy as jnp
    from raydp_tpu.ops import ssm_glue as sg

    (src, kernel, bias), (g,) = _conv_case(2, 32, (128,), 0, 0, jnp.float32)
    g = g.at[:, 12:].multiply(50.0)     # loud where a mistaken halo would read
    run = lambda fn, g: _value_and_grads(  # noqa: E731
        fn, (src, kernel, bias), (g,))[1][0]
    ours = lambda *a: sg.conv_silu(*a, (128,), rows=16, interpret=True)  # noqa: E731
    got, want = run(ours, g), run(lambda *a: sg._conv_jnp(*a, 0, (128,)), g)
    scale = float(np.abs(np.asarray(want)).max())
    assert np.abs(np.asarray(got) - np.asarray(want)).max() <= F32_TOL * scale
    # row 16's gradient reaches dx of rows 13..16 and no further back
    more = run(ours, g.at[0, 16].add(1.0))
    changed = np.abs(np.asarray(more) - np.asarray(got)).max(axis=-1)
    assert (changed[0, 13:17] > 0).all() and not changed[0, :13].any()
    assert not changed[0, 17:].any() and not changed[1].any()


# ------------------------------------------------------------- the gated norm
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("b,t,inner,groups,offset,extra,rows", [
    (1, 16, 128, 1, 0, 0, 16), (1, 64, 256, 2, 0, 0, 16),
    (2, 32, 1024, 2, 0, 64, 16), (2, 48, 256, 2, 512, 128, 16),
    (1, 256, 128, 1, 0, 0, 128)],
    ids=["one_tile_one_group", "four_row_tiles_two_groups_of_128",
         "groups_of_512_in_a_batch", "the_gate_inside_a_wider_source",
         "tiles_of_two_walks"])
def test_the_norm_kernels_are_the_jnp_form(b, t, inner, groups, offset, extra,
                                          rows, dtype):
    """``gated_norm`` through its two kernels against ``gated_norm_jnp``: the
    output and the gradients of ``y``, of the source the gate is read from
    (zeros off its columns) and of the weight; groups of 128 lanes and of 512
    (the published group), several row tiles, a batch, a gate that starts
    inside a wider source."""
    import jax.numpy as jnp
    from raydp_tpu.ops import ssm_glue as sg

    dtype = jnp.dtype(dtype)
    r = np.random.default_rng(3)
    y = jnp.asarray(r.normal(size=(b, t, inner)), dtype)
    src = jnp.asarray(r.normal(size=(b, t, offset + inner + extra)), dtype)
    weight = jnp.asarray(1 + 0.3 * r.normal(size=inner), jnp.float32)
    g = jnp.asarray(r.normal(size=(b, t, inner)), dtype)
    got = _value_and_grads(lambda *a: sg.gated_norm(
        *a, groups, 1e-5, offset, rows=rows, interpret=True),
        (y, src, weight), g)
    want = _value_and_grads(lambda *a: sg._norm_jnp(
        *a, groups, 1e-5, offset), _in_float32((y, src, weight)),
        _in_float32(g))
    assert all(o.dtype == dtype for o in got[0] + got[1][:2])
    _same(got[0], want[0], ["out"])
    _same(got[1], want[1], ["y", "src", "weight"])
    assert float(jnp.abs(got[1][1].astype(jnp.float32)).max()) > 0
    if offset:
        assert not np.asarray(got[1][1], np.float32)[..., :offset].any()


# ------------------------------------------------ shapes the kernels refuse
def _kernels_in(fn, *operands):
    import jax
    return str(jax.make_jaxpr(fn)(*operands)).count("pallas_call")


@pytest.mark.parametrize("t,widths,offset,why", [
    (40, (128,), 0, "whole number of row tiles"),
    (32, (96,), 0, "multiples of 128 lanes"),
    (32, (128,), 64, "multiples of 128 lanes")],
    ids=["no_whole_row_tiles", "a_width_of_96", "an_offset_of_64"])
def test_a_shape_the_kernels_do_not_take_goes_the_jnp_way(t, widths, offset,
                                                        why):
    """``kernel_ineligible`` says why, the call holds no kernel (even asked
    to interpret them), counts itself ``jnp`` and gives what the
    ``jax.numpy`` form gives, gradients too."""
    import jax.numpy as jnp
    from raydp_tpu import metrics as registry
    from raydp_tpu.ops import ssm_glue as sg

    assert why in sg.kernel_ineligible(t, widths, offset, 16)
    assert sg.kernel_ineligible(16384, (4096, 1024, 1024), 4096) is None
    assert sg.kernel_ineligible(16384, (512,)) is None
    assert "whole number of row tiles" in sg.kernel_ineligible(16400, (512,))
    assert "taps" in sg.kernel_ineligible(1024, (512,), taps=10)
    operands, grads = _conv_case(1, t, widths, offset, 0, jnp.float32)
    ours = lambda *a: sg.conv_silu(  # noqa: E731
        *a, widths, offset, rows=16, interpret=True)
    counted = lambda: dict(  # noqa: E731
        registry.snapshot()["counters"].get("ssm_glue_total", {}))
    before = counted()
    assert _kernels_in(ours, *operands) == 0
    assert counted().get("jnp", 0) == before.get("jnp", 0) + 1
    assert counted().get("kernel", 0) == before.get("kernel", 0)
    got = _value_and_grads(ours, operands, grads)
    want = _value_and_grads(lambda *a: sg._conv_jnp(*a, offset, widths),
                            operands, grads)
    _same(got[0] + got[1], want[0] + want[1],
          ["out", "src", "kernel", "bias"])
    # the norm: a group of 96 lanes, and a gate that starts inside a group
    y, weight = operands[0][..., :widths[0]], jnp.ones(widths[0])
    norm = lambda *a: sg.gated_norm(*a, 1, 1e-5, rows=16, interpret=True)  # noqa: E731
    assert (_kernels_in(norm, y, y, weight) == 0) == (
        t % 16 != 0 or widths[0] % 128 != 0)
    wide = jnp.concatenate([y, y], axis=-1)
    assert _kernels_in(lambda *a: sg.gated_norm(
        *a, 1, 1e-5, 64, rows=16, interpret=True), y, wide, weight) == 0


def test_an_eligible_call_holds_its_kernels_by_name():
    """Forward: one ``rdt_ssm_conv_fwd`` a width and one ``rdt_ssm_norm_fwd``;
    differentiated: as many ``rdt_ssm_conv_bwd`` and one
    ``rdt_ssm_norm_bwd``; each call counts itself ``kernel`` once. No name
    begins as the scan kernels' do (their roofline reads by prefix)."""
    import jax
    import jax.numpy as jnp
    from raydp_tpu import metrics as registry
    from raydp_tpu.ops import ssd_scan, ssm_glue as sg

    assert not any(name.startswith("rdt_ssd") for name in sg.KERNEL_NAMES)
    assert ssd_scan.KERNEL_NAMES == ("rdt_ssd_fwd", "rdt_ssd_bwd")
    widths = (256, 128, 128)
    operands, grads = _conv_case(1, 32, widths, 0, 0, jnp.float32)
    counted = lambda: registry.snapshot()["counters"].get(  # noqa: E731
        "ssm_glue_total", {}).get("kernel", 0)
    before = counted()
    conv = lambda *a: sg.conv_silu(*a, widths, rows=16, interpret=True)  # noqa: E731
    text = str(jax.make_jaxpr(lambda *a: _value_and_grads(
        conv, a, grads))(*operands))
    assert text.count("name=rdt_ssm_conv_fwd") == 3
    assert text.count("name=rdt_ssm_conv_bwd") == 3
    y, weight = operands[0][..., :256], jnp.ones(256)
    norm = lambda *a: sg.gated_norm(*a, 2, 1e-5, rows=16, interpret=True)  # noqa: E731
    text = str(jax.make_jaxpr(lambda *a: _value_and_grads(
        norm, a, y))(y, y, weight))
    assert text.count("name=rdt_ssm_norm_fwd") == 1
    assert text.count("name=rdt_ssm_norm_bwd") == 1
    assert counted() == before + 2


def test_the_ops_refuse_shapes_that_do_not_belong_together():
    import jax.numpy as jnp
    from raydp_tpu.ops import ssm_glue as sg

    (src, kernel, bias), _ = _conv_case(1, 16, (128,), 0, 0, jnp.float32)
    with pytest.raises(ValueError, match="widths sum to the kernel's"):
        sg.conv_silu(src, kernel, bias, (64,))
    with pytest.raises(ValueError, match="lie inside src"):
        sg.conv_silu(src, kernel, bias, (128,), offset=128)
    with pytest.raises(ValueError, match="groups divide the width"):
        sg.gated_norm(src, src, jnp.ones(128), 3, 1e-5)
    with pytest.raises(ValueError, match="the gate lies inside src"):
        sg.gated_norm(src, src, jnp.ones(128), 1, 1e-5, offset=128)


# ------------------------------------------------------------------ on a mesh
def test_the_stages_are_mapped_over_a_meshs_batch():
    """Over ``data`` each device's rows go through a stage as the whole batch
    does (the halo runs along ``T``, never along the batch), values and
    gradients, the parameters' summed over the devices (on the CPU's
    ``jax.numpy`` path: the Pallas interpreter takes no mapped axes)."""
    import jax
    import jax.numpy as jnp
    from raydp_tpu.ops import ssm_glue as sg
    from raydp_tpu.parallel import make_mesh

    mesh = make_mesh({"data": 2}, devices=jax.devices()[:2])
    widths, rules = (256, 128), dict(rows=16)
    operands, grads = _conv_case(2, 32, widths, 128, 0, jnp.float32)
    got = jax.jit(lambda *a: _value_and_grads(
        lambda *b: sg.conv_silu_sharded(*b, widths, mesh, offset=128,
                                        **rules), a, grads))(*operands)
    want = _value_and_grads(lambda *a: sg.conv_silu(
        *a, widths, 128, **rules), operands, grads)
    _same(got[0] + got[1], want[0] + want[1],
          ["x", "B", "src", "kernel", "bias"])
    y, weight = operands[0][..., :256], 1 + operands[2][:256]
    got = jax.jit(lambda *a: _value_and_grads(
        lambda *b: sg.gated_norm_sharded(*b, 2, 1e-5, mesh, offset=256,
                                         **rules), a, y))(
                                             y, operands[0], weight)
    want = _value_and_grads(lambda *a: sg.gated_norm(
        *a, 2, 1e-5, 256, **rules), (y, operands[0], weight), y)
    _same(got[0] + got[1], want[0] + want[1],
          ["out", "y", "src", "weight"])
    # no mesh, or one device: the plain call
    assert sg._over_batch(sg.conv_silu, None, (3, 0, 0), (3,)) is sg.conv_silu
