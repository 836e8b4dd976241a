"""The scan of a Kimi Delta Attention layer (``raydp_tpu/ops/kda_scan.py``):
the chunked form against the recurrence a position at a time, output and all
five gradients, at chunks of one and of several sub-blocks, with a row that is
no whole number of chunks and with decays that drive a chunk's running sum
under -500; the state carried across a chunk's edge and empty at a row's
first rows; bfloat16 operands; the shapes refused; the counters' labels.
Small shapes, whole programs (``jax.jit``), the CPU.
"""

import functools

import numpy as np
import pytest


def _inputs(seed, batch, t, heads, keys, values, decay=0.3, dtype=None):
    import jax
    import jax.numpy as jnp

    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    unit = lambda a: a / jnp.linalg.norm(a, axis=-1, keepdims=True)  # noqa: E731
    q = unit(jax.random.normal(ks[0], (batch, t, heads, keys))) * keys ** -0.5
    k = unit(jax.random.normal(ks[1], (batch, t, heads, keys)))
    v = jax.random.normal(ks[2], (batch, t, heads, values))
    g = -decay * jax.nn.softplus(jax.random.normal(
        ks[3], (batch, t, heads, keys)))
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (batch, t, heads)))
    if dtype is not None:
        q, k, v = (a.astype(dtype) for a in (q, k, v))
    return q, k, v, g, beta


@functools.lru_cache(maxsize=None)
def _programs(chunk):
    """(forward, gradients by all five under seeded weights) of the chunked
    op and of the recurrence, each one program."""
    import jax
    import jax.numpy as jnp

    from raydp_tpu.ops.kda_scan import kda_recurrent_jnp, kda_scan

    def both(fn):
        def weighed(w, *args):
            return jnp.sum(fn(*args).astype(jnp.float32) * w)
        return jax.jit(fn), jax.jit(jax.grad(weighed, argnums=(1, 2, 3, 4, 5)))
    return (both(functools.partial(kda_scan, chunk=chunk)),
            both(kda_recurrent_jnp))


@pytest.mark.parametrize("chunk,t,decay", [
    (16, 48, 0.3), (64, 80, 0.3), (8, 200, 0.3), (16, 48, 40.0),
    (64, 80, 40.0)],
    ids=["two_sub_blocks", "eight_sub_blocks_a_ragged_row",
         "two_segments_of_one_sub_block_chunks_a_ragged_row", "strong_decay",
         "strong_decay_eight_sub_blocks"])
def test_the_chunked_form_is_the_recurrence(chunk, t, decay):
    """Output and the gradients by q, k, v, g and beta, float32 against
    float32; ``two_segments``: 25 chunks of 8 are two segments of 16, the
    second filled, so the state and its gradient cross a segment's edge.
    ``strong_decay``: a chunk's running sum of ``g`` falls under -500
    (``exp`` of its negation is no float32): everything stays finite and
    equal, because only differences are exponentiated."""
    import jax

    args = _inputs(3, 2, t, 2, 32, 16, decay)
    (fwd, grad), (fwd_r, grad_r) = _programs(chunk)
    if decay > 1:
        total = np.cumsum(np.asarray(args[3])[:, :chunk], axis=1)
        assert total.min() < -500
    got, want = np.asarray(fwd(*args)), np.asarray(fwd_r(*args))
    assert got.shape == want.shape == (2, t, 2, 16)
    assert np.all(np.isfinite(got))
    np.testing.assert_allclose(got, want, rtol=0, atol=2e-6)
    w = jax.random.normal(jax.random.PRNGKey(9), want.shape)
    for name, g, r in zip("q k v g beta".split(), grad(w, *args),
                          grad_r(w, *args)):
        g, r = np.asarray(g), np.asarray(r)
        assert np.all(np.isfinite(g)), name
        assert np.abs(g - r).max() <= 1e-4 * max(np.abs(r).max(), 1e-3), name
        assert np.abs(r).max() > 1e-4, name


def test_the_state_crosses_a_chunks_edge_and_starts_empty():
    """A row's first position reads an empty state (``o_0 = b_0 (k_0 . q_0)
    v_0``); a change at position 0 reaches the last position of the third
    chunk; rows of a batch and heads do not meet."""
    q, k, v, g, beta = (np.asarray(a) for a in _inputs(5, 2, 48, 2, 32, 16,
                                                       0.05))
    (fwd, _), _ = _programs(16)
    out = np.asarray(fwd(q, k, v, g, beta))
    first = beta[:, 0, :, None] * np.sum(k[:, 0] * q[:, 0], -1,
                                         keepdims=True) * v[:, 0]
    np.testing.assert_allclose(out[:, 0], first, rtol=0, atol=1e-6)
    moved = v.copy()
    moved[0, 0, 0] += 1.0
    other = np.asarray(fwd(q, k, moved, g, beta))
    assert np.abs(other[0, -1, 0] - out[0, -1, 0]).max() > 1e-4
    np.testing.assert_array_equal(other[1], out[1])
    np.testing.assert_array_equal(other[0, :, 1], out[0, :, 1])


def test_bfloat16_operands_give_bfloat16_and_stay_close():
    """q, k, v at the activations' dtype: the output is theirs, the decays,
    the solve and the state stay float32, and the result is within what
    bfloat16 operands cost of the float32 recurrence."""
    import jax.numpy as jnp

    args = _inputs(7, 1, 64, 2, 32, 32, 0.3, jnp.bfloat16)
    (fwd, _), (fwd_r, _) = _programs(16)
    got = fwd(*args)
    assert got.dtype == jnp.bfloat16
    want = np.asarray(fwd_r(*args))
    err = np.sqrt(np.mean((np.asarray(got, np.float32) - want) ** 2)
                  / np.mean(want ** 2))
    assert err < 0.02


def test_shapes_that_are_not_a_scans_are_refused():
    from raydp_tpu.ops.kda_scan import kda_scan

    q, k, v, g, beta = _inputs(0, 1, 16, 2, 8, 8)
    with pytest.raises(ValueError, match="beta"):
        kda_scan(q, k, v, g, beta[..., None])
    with pytest.raises(ValueError, match="alike"):
        kda_scan(q, k[..., :4], v, g, beta)
    with pytest.raises(ValueError, match="alike"):
        kda_scan(q, k, v, g[:, :8], beta)


def test_the_counters_say_path_and_passes():
    """``kda_scan_total{jnp}`` once a built call; ``kda_chunks_total``:
    sequences x heads x chunks (a ragged row's last chunk counts) under
    ``forward`` where the call is built and under ``backward`` where its
    transpose is."""
    import jax
    import jax.numpy as jnp

    from raydp_tpu import metrics
    from raydp_tpu.ops.kda_scan import kda_scan
    from tests import lm_testing

    args = _inputs(1, 2, 40, 3, 8, 8)
    before = lm_testing.counters()
    jax.eval_shape(lambda *a: kda_scan(*a, chunk=16), *args)
    assert lm_testing.moved(before, "kda_scan_total") == {"jnp": 1}
    assert lm_testing.moved(before, "kda_chunks_total") == {
        "forward": 2 * 3 * 3}
    before = lm_testing.counters()
    jax.eval_shape(jax.grad(lambda *a: jnp.sum(kda_scan(*a, chunk=8))), *args)
    assert lm_testing.moved(before, "kda_chunks_total") == {
        "forward": 2 * 3 * 5, "backward": 2 * 3 * 5}
    for name, labels in (("kda_scan_total", ("jnp", "kernel")),
                         ("kda_chunks_total", ("forward", "backward"))):
        assert metrics.METRICS[name].kind == metrics.COUNTER
        for label in labels:
            assert f"`{label}`" in metrics.METRICS[name].doc
