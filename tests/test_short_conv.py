"""The gated short convolution of a convolution operator
(``raydp_tpu/ops/short_conv.py``): ``C * conv(B * z)`` as two Pallas kernels
(interpreted here) against its ``jax.numpy`` form (the CPU's path), value and
all three gradients (``B``, ``C``, ``z`` as one array, and the taps); the halo
of two rows at tile borders, at a sequence's start and (the backward's, the
other way) at its end; a shape the kernels do not take; the counter's two
labels; the wrapper over a mesh; and the flash kernels' interpreter path at
the family's heads of 64 on a grouped K/V of four.
"""

import numpy as np
import pytest

from tests.test_ssm_glue import (_in_float32, _kernels_in, _same,
                                 _value_and_grads)


def _case(b, t, width, dtype, taps=3, seed=0):
    """``W_in u [b, t, 3 width]``, the taps, and the output's cotangent."""
    import jax.numpy as jnp
    r = np.random.default_rng(seed)
    src = jnp.asarray(r.normal(size=(b, t, 3 * width)), dtype)
    kernel = jnp.asarray(0.5 * r.normal(size=(taps, width)), jnp.float32)
    return (src, kernel), jnp.asarray(r.normal(size=(b, t, width)), dtype)


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("b,t,width,rows,taps", [
    (1, 16, 128, 16, 3), (1, 64, 128, 16, 3), (2, 48, 128, 16, 3),
    (1, 32, 640, 16, 3), (1, 128, 256, 64, 3), (1, 256, 128, 512, 3),
    (2, 32, 128, 16, 4)],
    ids=["one_tile", "four_row_tiles", "two_sequences", "five_lane_tiles",
         "tiles_of_two_walks", "the_default_tile_fitted_down", "four_taps"])
def test_the_kernels_are_the_jnp_form(b, t, width, rows, taps, dtype):
    """``gated_conv`` through its two kernels against the ``jax.numpy`` form
    in float32: the output and the gradients of ``W_in u`` (all three widths,
    one array) and of the taps; one tile and several (the two rows before a
    tile lie in the tile before it, the two after it in the next), a batch,
    one lane tile and several (640 lanes: tiles of 128), a tile walked in two
    pieces, the default tile fitted down to a short sequence, four taps."""
    import jax.numpy as jnp
    from raydp_tpu.ops import short_conv as sc

    dtype = jnp.dtype(dtype)
    operands, grad = _case(b, t, width, dtype, taps)
    assert sc.kernel_ineligible(t, width, rows, taps) is None
    got = _value_and_grads(lambda *a: sc.gated_conv(
        *a, width, rows=rows, interpret=True), operands, grad)
    want = _value_and_grads(lambda *a: sc.gated_conv_jnp(*a, width),
                            _in_float32(operands), _in_float32(grad))
    assert got[0][0].dtype == got[1][0].dtype == dtype
    assert got[1][1].dtype == jnp.float32
    _same(got[0] + got[1], want[0] + want[1], ["out", "src", "taps"])
    # all three widths carry a gradient
    d = np.asarray(got[1][0], np.float32)
    assert all(np.abs(d[..., i * width:(i + 1) * width]).max() > 0.01
               for i in range(3))


def test_the_first_positions_see_zeros_and_no_sequence_sees_another():
    """Position ``t < 2`` of EVERY sequence takes zeros for what lies before
    it (written out for the first rows), and the second sequence's outputs
    and gradients do not move when the first one's last rows do."""
    import jax.numpy as jnp
    from raydp_tpu.ops import short_conv as sc

    (src, taps), grad = _case(2, 32, 128, jnp.float32)
    run = lambda s: _value_and_grads(lambda *a: sc.gated_conv(  # noqa: E731
        *a, 128, rows=16, interpret=True), (s, taps), grad)
    (out,), (d_src, _) = run(src)
    x, w = np.asarray(src), np.asarray(taps)
    gated = x[..., :128] * x[..., 256:]
    for t in range(3):
        conv = sum(w[j] * gated[:, t - 2 + j] for j in range(3)
                   if t - 2 + j >= 0)
        np.testing.assert_allclose(np.asarray(out)[:, t],
                                   x[:, t, 128:256] * conv, rtol=1e-5,
                                   atol=1e-6)
    moved = src.at[0, 16:].add(3.0)
    (out2,), (d_src2, _) = run(moved)
    np.testing.assert_array_equal(np.asarray(out2)[1], np.asarray(out)[1])
    np.testing.assert_array_equal(np.asarray(d_src2)[1], np.asarray(d_src)[1])
    assert np.abs(np.asarray(out2)[0, 16:] - np.asarray(out)[0, 16:]).max() \
        > 0.1


def test_the_backwards_halo_ends_with_the_sequence():
    """The taps run the other way in the backward pass: ``dg_t`` takes
    ``d c`` of rows ``t .. t + 2``. Inside the sequence they lie in the next
    tile (a gradient there moves ``dB`` and ``dz`` of the last two rows of
    the tile before, and no earlier row); after the last tile there are none
    (the clamped look-ahead block holds the tile's own rows: the kernels
    agree with the ``jax.numpy`` form at the last rows)."""
    import jax.numpy as jnp
    from raydp_tpu.ops import short_conv as sc

    (src, taps), grad = _case(1, 32, 128, jnp.float32)
    run = lambda g: _value_and_grads(lambda *a: sc.gated_conv(  # noqa: E731
        *a, 128, rows=16, interpret=True), (src, taps), g)[1][0]
    d_src = np.asarray(run(grad))
    pushed = np.asarray(run(grad.at[0, 16].add(5.0)))       # next tile's row
    changed = np.abs(pushed - d_src)[0].max(axis=-1)
    assert changed[:14].max() == 0 and changed[14:17].min() > 0
    assert changed[17:].max() == 0
    want = _value_and_grads(lambda *a: sc.gated_conv_jnp(*a, 128),
                            (src, taps), grad)[1][0]
    np.testing.assert_allclose(d_src[0, -3:], np.asarray(want)[0, -3:],
                               rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("t,width,why", [
    (40, 128, "whole number of row tiles"),
    (32, 96, "multiples of 128 lanes")],
    ids=["no_whole_row_tiles", "a_width_of_96"])
def test_a_shape_the_kernels_do_not_take_goes_the_jnp_way(t, width, why):
    """``kernel_ineligible`` says why, the call holds no kernel (even asked
    to interpret them), counts itself ``jnp`` and gives what the
    ``jax.numpy`` form gives, gradients too; the published shape is taken."""
    import jax.numpy as jnp
    from raydp_tpu import metrics as registry
    from raydp_tpu.ops import short_conv as sc

    assert why in sc.kernel_ineligible(t, width, 16)
    assert sc.kernel_ineligible(8192, 2048) is None
    assert "taps" in sc.kernel_ineligible(1024, 512, taps=10)
    operands, grad = _case(1, t, width, jnp.float32)
    ours = lambda *a: sc.gated_conv(  # noqa: E731
        *a, width, rows=16, interpret=True)
    counted = lambda: dict(  # noqa: E731
        registry.snapshot()["counters"].get("short_conv_total", {}))
    before = counted()
    assert _kernels_in(ours, *operands) == 0
    assert counted().get("jnp", 0) == before.get("jnp", 0) + 1
    assert counted().get("kernel", 0) == before.get("kernel", 0)
    got = _value_and_grads(ours, operands, grad)
    want = _value_and_grads(lambda *a: sc.gated_conv_jnp(*a, width),
                            operands, grad)
    _same(got[0] + got[1], want[0] + want[1], ["out", "src", "taps"])


def test_an_eligible_call_holds_its_two_kernels_by_name_and_counts_once():
    """Forward one ``rdt_gated_conv_fwd``, differentiated one
    ``rdt_gated_conv_bwd`` more; the call counts itself ``kernel`` once. No
    name begins as a state-space mixer's kernels' do (``ssm_glue_share`` and
    the scan's rooflines read by prefix and by scope)."""
    import jax
    import jax.numpy as jnp
    from raydp_tpu import metrics as registry
    from raydp_tpu.ops import short_conv as sc

    assert sc.KERNEL_NAMES == ("rdt_gated_conv_fwd", "rdt_gated_conv_bwd")
    operands, grad = _case(1, 32, 256, jnp.float32)
    counted = lambda: registry.snapshot()["counters"].get(  # noqa: E731
        "short_conv_total", {}).get("kernel", 0)
    before = counted()
    op = lambda *a: sc.gated_conv(*a, 256, rows=16, interpret=True)  # noqa: E731
    text = str(jax.make_jaxpr(lambda *a: _value_and_grads(
        op, a, grad))(*operands))
    assert text.count("name=rdt_gated_conv_fwd") == 1
    assert text.count("name=rdt_gated_conv_bwd") == 1
    assert "rdt_ssm" not in text and counted() == before + 1
    with pytest.raises(ValueError, match="three widths"):
        sc.gated_conv(operands[0], operands[1], 128)
    with pytest.raises(ValueError, match="a tap a channel"):
        sc.gated_conv(operands[0], operands[1][:, :128], 256)


def test_the_stage_is_mapped_over_a_meshs_batch():
    """Over ``data`` each device's rows go through the stage as the whole
    batch does, value and gradients, the taps' summed over the devices (on
    the CPU's ``jax.numpy`` path: the Pallas interpreter takes no mapped
    axes)."""
    import jax
    import jax.numpy as jnp
    from raydp_tpu.ops import short_conv as sc
    from raydp_tpu.parallel import make_mesh

    mesh = make_mesh({"data": 2}, devices=jax.devices()[:2])
    operands, grad = _case(2, 32, 128, jnp.float32)
    got = jax.jit(lambda *a: _value_and_grads(
        lambda *b: sc.gated_conv_sharded(*b, 128, mesh, rows=16), a,
        grad))(*operands)
    want = _value_and_grads(lambda *a: sc.gated_conv(*a, 128, rows=16),
                            operands, grad)
    _same(got[0] + got[1], want[0] + want[1], ["out", "src", "taps"])
    plain = sc.gated_conv_sharded(*operands, 128, None, rows=16)
    np.testing.assert_array_equal(plain, want[0][0])


@pytest.mark.parametrize("t", [64, 48], ids=["four_blocks", "three_blocks"])
def test_flash_at_heads_of_64_on_a_group_of_four_matches_dense_attention(t):
    """The family's attention shape, cut in count: 8 query heads on 2 K/V
    heads of 64 (four a group, as 32 on 8). The flash kernels (interpreted,
    blocks of 16) against dense masked attention, output and all three
    gradients; ``kernel_ineligible`` takes heads of 64 at the published
    length."""
    import jax
    import jax.numpy as jnp
    from raydp_tpu.ops import flash_attention as fa
    from raydp_tpu.ops.ring_attention import dense_attention

    assert fa.kernel_ineligible(8192, 64) is None
    r = np.random.default_rng(3)
    q = jnp.asarray(r.normal(size=(2, t, 8, 64)), jnp.float32)
    k, v = (jnp.asarray(r.normal(size=(2, t, 2, 64)), jnp.float32)
            for _ in range(2))
    w = jnp.asarray(r.normal(size=q.shape), jnp.float32)
    flash = lambda *a: fa.flash_attention(  # noqa: E731
        *a, causal=True, interpret=True, block_q=16, block_k=16)
    dense = lambda *a: dense_attention(*a, causal=True)  # noqa: E731
    text = str(jax.make_jaxpr(flash)(q, k, v))
    assert "name=rdt_flash_fwd" in text
    np.testing.assert_allclose(flash(q, k, v), dense(q, k, v), rtol=2e-4,
                               atol=2e-5)
    got = jax.grad(lambda *a: jnp.sum(flash(*a) * w), (0, 1, 2))(q, k, v)
    want = jax.grad(lambda *a: jnp.sum(dense(*a) * w), (0, 1, 2))(q, k, v)
    for g, x in zip(got, want):
        assert g.shape == x.shape
        np.testing.assert_allclose(g, x, rtol=2e-3, atol=2e-4)
