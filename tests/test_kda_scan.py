"""The scan of a Kimi Delta Attention layer (``raydp_tpu/ops/kda_scan.py``):
the chunked form, and the kernel pair through the Pallas interpreter
(``kernels``), against the recurrence a position at a time, output and all
five gradients, at chunks of one and of several sub-blocks, with a row that is
no whole number of chunks (which the kernels leave to the form) and with
decays that drive a chunk's running sum under -500; the state carried across
a chunk's and a grid step's edge and empty at a row's first rows; bfloat16
operands; the kernels against the form at the benchmark's CPU cut's shape;
the shapes refused and the shapes the kernels leave; the counters' labels;
the kernels compiled for a described chip at the published shape. Small
shapes, whole programs (``jax.jit``), the CPU.
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


PATHS = pytest.mark.parametrize("kernels", [False, True],
                                ids=["form", "kernels"])


@functools.lru_cache(maxsize=None)
def _programs(chunk, kernels=False):
    """(forward, gradients by all five under seeded weights) of the chunked
    op (``kernels``: on the kernel pair, interpreted) and of the recurrence,
    each one program."""
    import jax
    import jax.numpy as jnp

    from raydp_tpu.ops.kda_scan import kda_recurrent_jnp, kda_scan

    def both(fn):
        def weighed(w, *args):
            return jnp.sum(fn(*args).astype(jnp.float32) * w)
        return jax.jit(fn), jax.jit(jax.grad(weighed, argnums=(1, 2, 3, 4, 5)))
    return (both(functools.partial(kda_scan, chunk=chunk, interpret=kernels)),
            both(kda_recurrent_jnp))


@PATHS
@pytest.mark.parametrize("chunk,t,decay", [
    (16, 48, 0.3), (64, 80, 0.3), (8, 200, 0.3), (16, 48, 40.0),
    (64, 80, 40.0), (64, 128, 40.0)],
    ids=["two_sub_blocks", "eight_sub_blocks_a_ragged_row",
         "two_segments_of_one_sub_block_chunks_a_ragged_row", "strong_decay",
         "strong_decay_eight_sub_blocks", "strong_decay_two_whole_chunks"])
def test_the_chunked_form_is_the_recurrence(chunk, t, decay, kernels):
    """Output and the gradients by q, k, v, g and beta, float32 against
    float32; ``two_segments``: 25 chunks of 8 are two segments of 16, the
    second filled, so the state and its gradient cross a segment's edge (the
    kernels walk them as five grid steps of five). ``strong_decay``: a
    chunk's running sum of ``g`` falls under -500 (``exp`` of its negation is
    no float32): everything stays finite and equal, because only differences
    are exponentiated. ``kernels``: the pair interpreted wherever the row is
    whole chunks; a ragged row is left to the form, and the counter says
    so."""
    import jax

    from tests import lm_testing

    args = _inputs(3, 2, t, 2, 32, 16, decay)
    from raydp_tpu.ops.kda_scan import kda_scan

    before = lm_testing.counters()
    jax.eval_shape(functools.partial(kda_scan, chunk=chunk,
                                     interpret=kernels), *args)
    (fwd, grad), (fwd_r, grad_r) = _programs(chunk, kernels)
    assert set(lm_testing.moved(before, "kda_scan_total")) == {
        "kernel" if kernels and t % chunk == 0 else "jnp"}
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


@pytest.mark.parametrize("chunk,kernels", [(16, False), (16, True), (2, True)],
                         ids=["form", "kernels", "kernels_two_grid_steps"])
def test_the_state_crosses_a_chunks_edge_and_starts_empty(chunk, kernels):
    """A row's first position reads an empty state (``o_0 = b_0 (k_0 . q_0)
    v_0``); a change at position 0 reaches the last position of the third
    chunk (``two_grid_steps``: 24 chunks of 2 rows are two grid steps of 12,
    so it crosses the state a kernel carries in VMEM from a step to the
    next); rows of a batch and heads do not meet."""
    q, k, v, g, beta = (np.asarray(a) for a in _inputs(5, 2, 48, 2, 32, 16,
                                                       0.05))
    (fwd, _), _ = _programs(chunk, kernels)
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


@PATHS
def test_bfloat16_operands_give_bfloat16_and_stay_close(kernels):
    """q, k, v at the activations' dtype: the output is theirs, the decays,
    the solve and the state stay float32, and the result is within what
    bfloat16 operands cost of the float32 recurrence (one bound for the form
    and for the kernels)."""
    import jax.numpy as jnp

    args = _inputs(7, 1, 64, 2, 32, 32, 0.3, jnp.bfloat16)
    (fwd, _), (fwd_r, _) = _programs(16, kernels)
    got = fwd(*args)
    assert got.dtype == jnp.bfloat16
    want = np.asarray(fwd_r(*args))
    err = np.sqrt(np.mean((np.asarray(got, np.float32) - want) ** 2)
                  / np.mean(want ** 2))
    assert err < 0.02


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_the_kernels_give_the_forms_gradients_at_the_cpu_cuts_shape(dtype):
    """``[1, 256, 2, 128]`` in chunks of 64 (what the benchmark's CPU cut
    runs, and a shape the compiled kernels take): the kernel pair
    interpreted against the ``jax.numpy`` form, output and the five
    gradients; float32 to rounding, bfloat16 operands within what their
    rounding costs (the two lay their products out differently)."""
    import jax
    import jax.numpy as jnp

    from raydp_tpu.ops.kda_scan import kernel_ineligible

    assert kernel_ineligible(256, 64, 128, 128) is None
    args = _inputs(11, 1, 256, 2, 128, 128, 0.3,
                   None if dtype == "float32" else jnp.bfloat16)
    (fwd, grad), _ = _programs(64, False)
    (fwd_k, grad_k), _ = _programs(64, True)
    want = np.asarray(fwd(*args), np.float32)
    got = np.asarray(fwd_k(*args), np.float32)
    bound = 2e-5 if dtype == "float32" else 2e-2
    rms = lambda a: np.sqrt(np.mean(np.square(a)))  # noqa: E731
    assert rms(got - want) <= bound * rms(want)
    w = jax.random.normal(jax.random.PRNGKey(2), want.shape)
    for name, g, r in zip("q k v g beta".split(), grad_k(w, *args),
                          grad(w, *args)):
        g, r = np.asarray(g, np.float32), np.asarray(r, np.float32)
        assert g.shape == r.shape and np.all(np.isfinite(g)), name
        assert rms(g - r) <= bound * rms(r), (name, rms(g - r), rms(r))


@pytest.mark.parametrize("t,chunk,keys,values,why", [
    (16384, 64, 128, 128, None), (256, 64, 128, 128, None),
    (2048, 128, 128, 256, None),
    (16400, 64, 128, 128, "no whole number of chunks"),
    (16384, 64, 64, 128, "multiples of the 128 lanes"),
    (16384, 64, 128, 192, "multiples of the 128 lanes"),
    (16384, 8, 128, 128, "no multiple of 16 sublanes"),
    (16416, 48, 128, 128, "times a power of two"),
    (64 * 24, 64, 128, 128, "no whole number of segments")],
    ids=["published", "cpu_cut", "wider_values", "ragged_row", "narrow_keys",
         "values_off_the_lanes", "short_chunk", "chunk_of_six_sub_blocks",
         "a_segment_and_a_half"])
def test_the_kernels_say_which_shapes_they_leave(t, chunk, keys, values, why):
    """``kernel_ineligible`` case by case, and the counter of a call built
    for such a shape: ``jnp`` alone where the kernels leave it; ``kernel``
    and ``jnp`` where they take it (the form is the branch of every platform
    but a TPU, so it is traced too)."""
    import jax
    import jax.numpy as jnp

    from raydp_tpu.ops.kda_scan import kda_scan, kernel_ineligible
    from tests import lm_testing

    said = kernel_ineligible(t, chunk, keys, values)
    assert said is None if why is None else why in said, said
    shapes = [jax.ShapeDtypeStruct((1, t, 2, n), jnp.float32)
              for n in (keys, keys, values, keys)]
    before = lm_testing.counters()
    jax.eval_shape(lambda *a: kda_scan(*a, chunk=chunk), *shapes,
                   jax.ShapeDtypeStruct((1, t, 2), jnp.float32))
    assert lm_testing.moved(before, "kda_scan_total") == (
        {"jnp": 1, "kernel": 1} if why is None else {"jnp": 1})


KERNELS_AT_THE_PUBLISHED_SHAPE = """
import os
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ.setdefault("TPU_LOG_DIR", "disabled")
import jax, jax.numpy as jnp
from jax.experimental import topologies
from jax.sharding import SingleDeviceSharding
from raydp_tpu.ops.kda_scan import (KERNEL_NAMES, kda_scan,
                                    kda_scan_sharded)
jax.config.update("jax_enable_compilation_cache", False)
topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
chip = SingleDeviceSharding(topo.devices[0])
a = lambda *shape, dtype=jnp.bfloat16: jax.ShapeDtypeStruct(
    shape, dtype, sharding=chip)
wide, f32 = (1, 16384, 32, 128), jnp.float32
both = jax.jit(lambda *x: jax.vjp(kda_scan, *x[:5])[1](x[5]))
text = both.lower(a(*wide), a(*wide), a(*wide), a(*wide, dtype=f32),
                  a(*wide[:3], dtype=f32), a(*wide)).compile().as_text()
for name in KERNEL_NAMES:
    assert name in text, name
assert "triangular" not in text.lower()
# over a mesh's data axis: a row a chip, the kernels inside the shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
import numpy as np
mesh = Mesh(np.array(topo.devices), ("data",))
rows = lambda *shape, dtype=jnp.bfloat16: jax.ShapeDtypeStruct(
    shape, dtype, sharding=NamedSharding(mesh, P("data")))
wide = (4, 1024, 2, 128)
mapped = lambda *x: kda_scan_sharded(*x, mesh)
both = jax.jit(lambda *x: jax.vjp(mapped, *x[:5])[1](x[5]))
text = both.lower(rows(*wide), rows(*wide), rows(*wide),
                  rows(*wide, dtype=f32), rows(*wide[:3], dtype=f32),
                  rows(*wide)).compile().as_text()
for name in KERNEL_NAMES:
    assert name in text, name
print("KERNELS COMPILED")
"""


def test_the_kernels_compile_chip_free_at_the_published_shape():
    """The pair at ``[1, 16384, 32, 128]`` bfloat16 in chunks of 64 for a
    described v5e chip: the tiling (a segment of four heads a grid step), the
    rolls, the float32 products and the VMEM the backward's three scratch
    buffers take are the compiler's to refuse; the compiled text holds both
    kernels and no triangular solve. And four rows over the ``data`` axis of
    the described 2x2 mesh: the kernels inside ``kda_scan_sharded``'s
    ``shard_map``."""
    import os
    import re
    import subprocess
    import sys

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    proc = subprocess.run(
        [sys.executable, "-c", KERNELS_AT_THE_PUBLISHED_SHAPE], cwd=repo,
        capture_output=True, text=True, timeout=900,
        env={**{k: v for k, v in os.environ.items() if k != "XLA_FLAGS"},
             "PYTHONPATH": repo})
    if "KERNELS COMPILED" not in proc.stdout and re.search(
            r"topolog|libtpu|lockfile", proc.stderr, re.IGNORECASE):
        pytest.skip(f"no v5e topology can be described here: "
                    f"{proc.stderr[-300:]}")
    assert proc.returncode == 0, proc.stderr[-2000:]


def test_the_scan_is_mapped_over_a_meshs_batch():
    """Over ``data`` the scan of each device's rows is the whole scan's; no
    mesh or one device is the plain call. (The kernels under a mesh are the
    chip-free compile's: the interpreter's own loop does not pass under a
    ``shard_map``.)"""
    import jax

    from raydp_tpu.ops.kda_scan import kda_scan, kda_scan_sharded
    from raydp_tpu.parallel import make_mesh

    mesh = make_mesh({"data": 2}, devices=jax.devices()[:2])
    args = _inputs(13, 2, 32, 2, 32, 16)
    want = np.asarray(kda_scan(*args, chunk=16))
    got = jax.jit(lambda *a: kda_scan_sharded(*a, mesh, chunk=16))(*args)
    np.testing.assert_allclose(np.asarray(got), want, rtol=1e-5, atol=1e-6)
    alone = kda_scan_sharded(*args, None, chunk=16)
    np.testing.assert_array_equal(np.asarray(alone), want)


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
    """``kda_scan_total{jnp}`` once a built call that traces the form,
    ``{kernel}`` once a built call that holds the kernel pair (interpreted:
    the pair alone); ``kda_chunks_total``: sequences x heads x chunks (a
    ragged row's last chunk counts) under ``forward`` where the call is
    built and under ``backward`` where its transpose is, once a pass
    whichever path takes it."""
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
    before = lm_testing.counters()
    jax.eval_shape(jax.grad(lambda *a: jnp.sum(kda_scan(
        *a, chunk=8, interpret=True))), *args)
    assert lm_testing.moved(before, "kda_scan_total") == {"kernel": 1}
    assert lm_testing.moved(before, "kda_chunks_total") == {
        "forward": 2 * 3 * 5, "backward": 2 * 3 * 5}
    for name, labels in (("kda_scan_total", ("jnp", "kernel")),
                         ("kda_chunks_total", ("forward", "backward"))):
        assert metrics.METRICS[name].kind == metrics.COUNTER
        for label in labels:
            assert f"`{label}`" in metrics.METRICS[name].doc
