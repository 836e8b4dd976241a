"""State-space / attention / sparse-expert hybrid LM (``nemotron_h``:
nemotron-3-nano-30b-a3b): the chunked scan (its ``jax.numpy`` path and its two
Pallas kernels, interpreted) against the recurrence itself, the causal
convolution, ``Mamba2Mixer`` alone, the parameter tree, the whole model's
logits, loss, gradients and balancing bias through the estimator's train
step, a recomputed state-space layer, and the older families' programs left
as they were; against the plain reference
(``chipbench/reference/nemotron-3-nano-30b-a3b.py``: float32 ``jax.numpy``,
the recurrence one position a step), at small sizes on the CPU, seeded random
weights. Widths are small here, and only here
(``tests/chipbench_contract/test_chipbench_nemotron_3_nano.py`` keeps them).
"""

import functools
import hashlib
import os

import numpy as np
import pytest

from tests import lm_testing
from tests.lm_testing import (F32_TOL, ROOT, close as _close,
                              leaves as _leaves, step_text as _step_text,
                              tokens as _tokens, train_step as _train_step,
                              variables as _variables)

CONFIG = "nemotron-3-nano-30b-a3b"

# 4 state-space heads of 8 in 2 groups, a state of 16, chunks of 8, 4 taps;
# 4 query heads on 2 K/V heads of 8; 16 experts of width 16 of which expert 2
# is held, 6 a token (as published), a shared expert of 24; the nine-letter
# pattern; 64 of 512 vocabulary rows, 32 positions (four chunks)
TINY = {"hidden_size": 32, "mamba_num_heads": 4, "mamba_head_dim": 8,
        "n_groups": 2, "ssm_state_size": 16, "chunk_size": 8,
        "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 8,
        "moe_intermediate_size": 16, "intermediate_size": 16,
        "moe_shared_expert_intermediate_size": 24,
        "n_routed_experts": 16, "first_expert": 2, "experts_held": 1,
        "vocab_size": 512, "vocab_rows_held": 64, "seq_len": 32,
        "compared_positions": 8, "compute_dtype": "float32",
        "attention": "dense", "init_std": 0.3, "remat_blocks": False}
_files = functools.partial(lm_testing.files, CONFIG, TINY)


def _kernels_of(jaxpr, found=None, outer=""):
    """(name, scopes) of every Pallas kernel of a traced program, through
    every loop, checkpoint and call it holds (a jitted call's own program
    names its scopes from the call on: ``outer`` carries the caller's)."""
    found = [] if found is None else found
    jaxpr = getattr(jaxpr, "jaxpr", jaxpr)
    for eqn in jaxpr.eqns:
        scopes = f"{outer}/{eqn.source_info.name_stack}"
        if eqn.primitive.name == "pallas_call":
            found.append((str(eqn.params.get("name", "")), scopes))
        for value in eqn.params.values():
            for inner in value if isinstance(value, (tuple, list)) \
                    else (value,):
                if hasattr(getattr(inner, "jaxpr", inner), "eqns"):
                    _kernels_of(inner, found, scopes)
    return found


@pytest.fixture
def scan_kernels(monkeypatch):
    """A state-space layer's scan runs its two Pallas kernels here,
    interpreted (off the chip the op would take its jnp path); the fixture
    counts a kernel's calls in a traced program."""
    from raydp_tpu.ops import ssd_scan as ssd

    monkeypatch.setattr(ssd, "ssd_scan", functools.partial(
        ssd.ssd_scan, interpret=True))
    return lambda jaxpr, name: sum(
        name in kernel for kernel, _ in _kernels_of(jaxpr))


@pytest.fixture
def glue_kernels(monkeypatch):
    """A state-space layer's convolution and gated norm run their four
    Pallas kernels here, interpreted, in row tiles of 16 (off the chip the
    ops would take their jnp path); the fixture lists the kernels of a
    traced program with the scopes they lie under."""
    from raydp_tpu.ops import ssm_glue

    for op in ("conv_silu", "gated_norm"):
        monkeypatch.setattr(ssm_glue, op, functools.partial(
            getattr(ssm_glue, op), rows=16, interpret=True))
    return _kernels_of


# ------------------------------------------------------- (a) the scan alone
def _scan_inputs(b, t, h, p, g, n, seed=0, steep=False):
    """``steep``: ``dt A`` sums to about -200 to -400 inside a chunk of 128
    (``dt`` 0.1 to 0.2, ``A`` -16: a layer late in training), where
    ``exp(l_t)`` alone would be no float32."""
    r = np.random.default_rng(seed)
    f = lambda *shape: r.normal(size=shape).astype(np.float32)  # noqa: E731
    x = f(b, t, h, p)
    if steep:
        dt, a = (r.uniform(0.1, 0.2, (b, t, h)).astype(np.float32),
                 np.full(h, -16.0, np.float32))
    else:
        dt, a = (0.5 * np.log1p(np.exp(f(b, t, h))),
                 -np.exp(r.uniform(0, 1.5, h)).astype(np.float32))
    return x, dt, a, f(b, t, g, n), f(b, t, g, n), f(h)


@functools.lru_cache(maxsize=None)
def _recurrence():
    """The reference's recurrence and its six gradients under a cotangent
    (the first argument), jitted: once a worker, for both paths of a shape."""
    import jax
    import jax.numpy as jnp
    from chipbench import manifest
    recurrence = manifest.load_module(ROOT, "reference",
                                      f"{CONFIG}.py").recurrence
    return jax.jit(recurrence), jax.jit(jax.grad(
        lambda g_y, *a: jnp.sum(recurrence(*a) * g_y), argnums=range(1, 7)))


@pytest.mark.parametrize("path", ["jnp", "kernels"])
@pytest.mark.parametrize("b,t,h,p,g,n,chunk,steep", [
    (1, 8, 2, 4, 2, 8, 8, False), (1, 32, 4, 4, 1, 8, 8, False),
    (2, 24, 4, 4, 2, 8, 8, False), (1, 20, 2, 4, 1, 8, 8, False),
    (1, 16, 8, 4, 1, 8, 8, False), (1, 16, 3, 4, 1, 8, 8, False),
    (1, 16, 2, 16, 1, 8, 8, False), (1, 32, 4, 4, 2, 8, 8, False),
    (1, 16, 4, 64, 1, 8, 8, False), (1, 16, 2, 128, 2, 8, 8, False),
    (1, 256, 2, 4, 1, 8, 128, True)],
    ids=["one_chunk", "four_chunks_one_group", "batch_of_two",
         "no_whole_chunks", "eight_heads_a_group", "three_heads_a_group",
         "heads_wider_than_the_state", "two_groups_four_chunks",
         "two_lane_tiles_of_two_heads", "a_head_a_lane_tile",
         "steep_decays"])
def test_the_chunked_scan_is_the_recurrence(b, t, h, p, g, n, chunk, steep,
                                            path):
    """Values and every gradient (x, dt, A, B, C, D) of the chunked form, in
    ``jax.numpy`` and through the two kernels interpreted, against the
    reference's recurrence, one position a step: at one chunk, several
    chunks, heads that share a group (two, three: no power of two, eight: the
    published group), a batch of two, heads wider than the state, a group
    whose lanes are two 128-lane tiles of two heads and one whose heads are a
    tile each (what a group does once it does a tile at a time), and decays
    so steep that only ``exp`` of a DIFFERENCE ``l_t - l_s`` is a float32; a
    sequence that is no whole number of chunks takes the ``jax.numpy`` path
    either way."""
    import jax
    import jax.numpy as jnp
    from raydp_tpu.ops.ssd_scan import ssd_scan

    recurrence, recurrence_grads = _recurrence()
    args = tuple(map(jnp.asarray, _scan_inputs(b, t, h, p, g, n,
                                               steep=steep)))
    g_y = jnp.asarray(np.random.default_rng(1).normal(
        size=(b, t, h, p)).astype(np.float32))
    ours = lambda *a: ssd_scan(  # noqa: E731
        *a, chunk=chunk, interpret=path == "kernels")
    got = jax.jit(ours)(*args)
    want = recurrence(*args)
    assert got.shape == want.shape == (b, t, h, p)
    scale = float(jnp.abs(want).max())
    assert bool(jnp.isfinite(got).all())
    assert float(jnp.abs(got - want).max()) <= F32_TOL * scale
    grads = jax.jit(jax.grad(lambda *a: jnp.sum(ours(*a) * g_y),
                             argnums=range(6)))(*args)
    wants = recurrence_grads(g_y, *args)
    for name, got, want in zip("x dt A B C D".split(), grads, wants):
        assert got.shape == want.shape and got.dtype == want.dtype, name
        assert bool(jnp.isfinite(got).all()), name
        assert float(jnp.abs(got - want).max()) <= 10 * F32_TOL * max(
            float(jnp.abs(want).max()), 1.0), name


def test_the_scan_refuses_shapes_that_do_not_belong_together():
    import jax.numpy as jnp
    from raydp_tpu.ops.ssd_scan import kernel_ineligible, ssd_scan

    x, dt, a, b, c, d = map(jnp.asarray, _scan_inputs(1, 8, 4, 4, 3, 8))
    with pytest.raises(ValueError, match="groups that divide the heads"):
        ssd_scan(x, dt, a, b, c, d)
    # the compiled kernels take the published shape and say why not another
    assert kernel_ineligible(16384, 128, 8, 64, 128) is None
    assert "whole number of chunks" in kernel_ineligible(100, 128, 8, 64, 128)
    assert "multiples of 128" in kernel_ineligible(256, 64, 8, 64, 128)


def test_the_kernel_sweep_runs_interpreted(capsys):
    """``benchmarks/ssd_scan_sweep.py`` (the two kernels alone; on the chip it
    times them, and no cell runs it) end to end at a toy shape through the
    interpreter: every kept form of a group's work gives the forward the
    float32 ``jax.numpy`` form gives, with finite gradients, and the file
    another checkout would be timed from is loaded beside it."""
    import importlib.util

    path = os.path.join(ROOT, "benchmarks", "ssd_scan_sweep.py")
    spec = importlib.util.spec_from_file_location("ssd_scan_sweep", path)
    sweep = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(sweep)
    toy = ["--interpret", "--seq-len", "16", "--heads", "6", "--head-dim",
           "4", "--groups", "2", "--state", "8", "--chunk", "8", "--iters",
           "1", "--dtype", "float32"]
    beside = os.path.join(ROOT, "raydp_tpu", "ops", "ssd_scan.py")
    out = sweep.main(toy + ["--forms", "--beside", beside])
    assert len(out["forms"]) == len(sweep.FORMS) == 6
    for name, read in [("beside", out["beside"]), *out["forms"].items()]:
        assert read["forward_rel_rms"] < F32_TOL, name
        assert read["gradients_finite"], name
        assert read["backward"][1] is None      # no device, no kernel time
        np.testing.assert_allclose(read["digests"], out["beside"]["digests"],
                                   rtol=1e-5)
    assert "not measured" in capsys.readouterr().out
    with pytest.raises(SystemExit, match="needs a TPU"):
        sweep.main(toy[1:])


def test_the_glue_sweep_runs_interpreted(capsys):
    """``benchmarks/ssd_scan_sweep.py --glue`` (the convolution's and the
    gated norm's kernels alone beside their ``jax.numpy`` forms; on the chip
    it times each op and prints the gate) end to end at a toy shape through
    the interpreter: both stages' values and gradients are the ``jax.numpy``
    forms', with the module's tile rules set aside and put back."""
    import importlib.util

    from raydp_tpu.ops import ssm_glue

    path = os.path.join(ROOT, "benchmarks", "ssd_scan_sweep.py")
    spec = importlib.util.spec_from_file_location("ssd_scan_sweep", path)
    sweep = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(sweep)
    rules = (ssm_glue.LANE_TILE, ssm_glue.CONV_WALK, ssm_glue.NORM_WALK)
    out = sweep.main(["--glue", "--interpret", "--seq-len", "64", "--heads",
                      "4", "--head-dim", "64", "--groups", "2", "--state",
                      "64", "--rows", "32", "--lanes", "128", "--walk", "16",
                      "--iters", "1", "--dtype", "float32"])["glue"]
    assert rules == (ssm_glue.LANE_TILE, ssm_glue.CONV_WALK,
                     ssm_glue.NORM_WALK)
    assert set(out) == {"conv", "norm"}
    for stage, read in out.items():
        assert read["worst"] < F32_TOL, stage
        assert read["backward"][1] is None and "layer_ms" not in read
    said = capsys.readouterr().out
    assert "not measured" in said and "bytes at 819 GB/s" in said


# ------------------------------------------------- (b) the causal convolution
def test_the_convolution_is_a_sum_over_shifted_copies_and_looks_at_no_later():
    import jax.numpy as jnp
    from chipbench import manifest
    from raydp_tpu.models.transformer import causal_conv

    reference = manifest.load_module(ROOT, "reference", f"{CONFIG}.py")
    rng = np.random.default_rng(0)
    x = rng.normal(size=(2, 12, 6)).astype(np.float32)
    taps = rng.normal(size=(4, 6)).astype(np.float32)
    bias = rng.normal(size=(6,)).astype(np.float32)
    got = np.asarray(causal_conv(jnp.asarray(x), taps, bias))
    np.testing.assert_allclose(got, reference.convolution(
        jnp.asarray(x), taps, bias), rtol=1e-6, atol=1e-6)
    # written out: y_t = b + sum_j w_j x_{t-3+j}
    want = np.zeros_like(x) + bias
    for t in range(12):
        for j in range(4):
            if t - 3 + j >= 0:
                want[:, t] += taps[j] * x[:, t - 3 + j]
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
    # position t reads nothing after t
    later = x.copy()
    later[:, 7:] += 1.0
    moved = np.asarray(causal_conv(jnp.asarray(later), taps, bias))
    np.testing.assert_array_equal(moved[:, :7], got[:, :7])
    assert np.abs(moved[:, 7] - got[:, 7]).max() > 0.01


# ------------------------------------------------- (c) the sub-layer alone
@pytest.mark.parametrize("dtype,kernels,tol", [
    ("float32", False, 10 * F32_TOL), ("float32", True, 10 * F32_TOL),
    ("bfloat16", True, 0.03)], ids=["f32_jnp", "f32_kernels", "bf16_kernels"])
def test_the_mixer_matches_the_references(dtype, kernels, tol, request):
    import jax
    import jax.numpy as jnp
    from chipbench.harness import relative_rms_error

    cfg, pipeline, reference = _files()
    if kernels:
        request.getfixturevalue("scan_kernels")
    from raydp_tpu.models.transformer import Mamba2Mixer
    spec = pipeline.build_model(cfg).ssm
    layer = Mamba2Mixer(spec, jnp.dtype(dtype), cfg["layer_norm_epsilon"],
                        0.3)
    u = np.random.default_rng(1).normal(size=(2, 32, 32)).astype(np.float32)
    params = jax.tree.map(np.asarray, jax.jit(layer.init)(
        jax.random.PRNGKey(1), u)["params"])
    assert set(params) == {"in_proj", "conv", "conv_bias", "dt_bias", "A_log",
                           "D", "norm", "out_proj"}
    rng = np.random.default_rng(2)
    for name in ("conv_bias", "D", "norm"):
        params[name] = params[name] + rng.normal(
            0, 0.3, params[name].shape).astype(np.float32)
    got = jax.jit(layer.apply)({"params": params},
                               jnp.asarray(u, jnp.dtype(dtype)))
    assert got.dtype == jnp.dtype(dtype) and got.shape == u.shape
    mixer = jax.jit(lambda p: reference.mixer(p, u, cfg))
    want = mixer(params)
    assert relative_rms_error(np.asarray(got, np.float32), want) <= tol
    # the skip D, the gated norm's weight and the convolution's bias are in
    # the result
    for name in ("D", "norm", "conv_bias"):
        other = dict(params, **{name: np.ones_like(params[name])})
        assert relative_rms_error(mixer(other), want) > 0.01, name
    # dt_bias and A_log start where the configuration says, in float32
    dt = np.log1p(np.exp(params["dt_bias"]))
    assert params["dt_bias"].dtype == np.float32
    assert 1e-4 <= dt.min() and dt.max() <= 0.1 + 1e-6
    a = np.exp(params["A_log"])
    assert 1.0 <= a.min() and a.max() <= 16.0


def test_a_state_space_layer_takes_no_seq_axis():
    import jax
    import jax.numpy as jnp
    from raydp_tpu.models.transformer import Mamba2Mixer, SSMSpec
    from raydp_tpu.parallel import make_mesh

    mesh = make_mesh({"seq": 2}, devices=jax.devices()[:2])
    layer = Mamba2Mixer(SSMSpec(2, 4, 1, 8, chunk_size=8), mesh=mesh)
    with pytest.raises(NotImplementedError, match="seq axis"):
        layer.init(jax.random.PRNGKey(0), jnp.zeros((1, 8, 16)))


def test_the_scan_is_mapped_over_a_meshs_batch():
    """Over ``data`` the scan of each device's rows is the whole scan's."""
    import jax
    import jax.numpy as jnp
    from raydp_tpu.ops.ssd_scan import ssd_scan, ssd_scan_sharded
    from raydp_tpu.parallel import make_mesh

    mesh = make_mesh({"data": 2}, devices=jax.devices()[:2])
    args = tuple(map(jnp.asarray, _scan_inputs(2, 16, 4, 4, 2, 8)))
    got = jax.jit(lambda *a: ssd_scan_sharded(*a, mesh, chunk=8))(*args)
    np.testing.assert_allclose(got, ssd_scan(*args, chunk=8), rtol=1e-5,
                               atol=1e-5)


# ----------------------------------------------------- (d) the whole model
def test_the_parameter_tree_is_the_published_layers():
    """Names and shapes at the tiny widths; and at the PUBLISHED widths, by
    ``jax.eval_shape`` (nothing is allocated), a state-space layer is
    38,742,208 parameters, the attention layer 23,396,352, an expert layer as
    held 100,122,624, each with a norm of 2,688."""
    import jax
    cfg, pipeline, _ = _files()
    model = pipeline.build_model(cfg)
    params, state = _variables(model, _tokens(cfg, 1))
    shapes = {k: v.shape for k, v in _leaves(params).items()}
    ssm = {"ssm/in_proj/kernel": (32, 2 * 32 + 2 * 32 + 4),
           "ssm/conv": (4, 32 + 2 * 32), "ssm/conv_bias": (96,),
           "ssm/dt_bias": (4,), "ssm/A_log": (4,), "ssm/D": (4,),
           "ssm/norm": (32,), "ssm/out_proj/kernel": (32, 32),
           "norm/scale": (32,)}
    assert {k: v for k, v in shapes.items() if k.startswith("block_0/")} \
        == {f"block_0/{k}": v for k, v in ssm.items()}
    assert {k: v for k, v in shapes.items() if k.startswith("block_1/")} == {
        "block_1/moe/router": (32, 16),
        "block_1/moe/experts_up": (1, 32, 16),      # two matrices: no gate
        "block_1/moe/experts_down": (1, 16, 32),
        "block_1/moe/shared_up/kernel": (32, 24),
        "block_1/moe/shared_down/kernel": (24, 32),
        "block_1/norm/scale": (32,)}
    assert {k: v for k, v in shapes.items() if k.startswith("block_5/")} == {
        "block_5/attn/q/kernel": (32, 4, 8), "block_5/attn/k/kernel":
        (32, 2, 8), "block_5/attn/v/kernel": (32, 2, 8),
        "block_5/attn/o/kernel": (4, 8, 32), "block_5/norm/scale": (32,)}
    assert {k: v.shape for k, v in _leaves(state).items()} == {
        f"block_{i}/moe/{name}": (16,) for i in (1, 3, 6, 8)
        for name in ("bias", "counts")}
    assert model.layer_kinds == "MEMEM*EME"
    assert model.attention_layers == {"window": 0, "full": 1}
    assert model.attention_forward == {"twice": 1} or not model.remat_blocks
    assert model.ssm_layers == {"plain": 4}
    assert [model._sparse(i) for i in range(9)] == [0, 1, 0, 1, 0, 0, 1, 0, 1]

    from chipbench import manifest
    published = manifest.load_json(ROOT, "configs", f"{CONFIG}.json")
    big = pipeline.build_model(dict(published, vocab_rows_held=8))
    tree = jax.eval_shape(lambda: big.init(
        jax.random.PRNGKey(0), np.zeros((1, 8), np.int32)))["params"]
    count = lambda t: sum(int(np.prod(x.shape))  # noqa: E731
                          for x in jax.tree.leaves(t))
    for i, letter in enumerate("MEMEM*EME"):
        block = tree[f"block_{i}"]
        assert count(block["norm"]) == 2688
        assert count(block) - 2688 == {"M": 38742208, "*": 23396352,
                                       "E": 100122624}[letter]
    block = tree["block_0"]["ssm"]
    assert block["in_proj"]["kernel"].shape == (2688, 10304)
    assert block["conv"].shape == (4, 6144)
    assert block["norm"].shape == (4096,)
    assert block["out_proj"]["kernel"].shape == (4096, 2688)
    assert tree["block_5"]["attn"]["k"]["kernel"].shape == (2688, 2, 128)
    assert tree["block_1"]["moe"]["experts_up"].shape == (8, 2688, 1856)
    assert tree["block_1"]["moe"]["shared_up"]["kernel"].shape == (2688, 3712)


def test_layer_kinds_say_what_a_layer_is_or_raise():
    import jax
    from raydp_tpu.models import TransformerLM
    from raydp_tpu.models.transformer import SSMSpec

    tokens = np.zeros((1, 8), np.int32)
    make = lambda **kw: TransformerLM(  # noqa: E731
        vocab_size=16, dim=16, num_heads=2, attention="dense", **kw)
    with pytest.raises(ValueError, match="3 letters"):
        make(num_layers=3, layer_kinds="M*").init(jax.random.PRNGKey(0),
                                                  tokens)
    with pytest.raises(ValueError, match="ssm=SSMSpec"):
        make(num_layers=1, layer_kinds="M").init(jax.random.PRNGKey(0),
                                                 tokens)
    with pytest.raises(ValueError, match="num_experts"):
        make(num_layers=1, layer_kinds="E").init(jax.random.PRNGKey(0),
                                                 tokens)
    # a pair beside one-sub-layer layers; the default is all pairs
    mixed = make(num_layers=3, layer_kinds="BM*",
                 ssm=SSMSpec(2, 4, 1, 8, chunk_size=8))
    params = mixed.init(jax.random.PRNGKey(0), tokens)["params"]
    assert set(params["block_0"]) == {"ln1", "attn", "ln2", "gate", "up",
                                      "down"}
    assert set(params["block_1"]) == {"norm", "ssm"}
    assert set(params["block_2"]) == {"norm", "attn"}
    assert mixed.attention_layers == {"window": 0, "full": 2}
    assert make(num_layers=2).attention_layers == {"window": 0, "full": 2}
    assert make(num_layers=2).ssm_layers == {"plain": 0}


@pytest.mark.parametrize("dtype,kernels,tol", [
    ("float32", False, 10 * F32_TOL), ("float32", True, 10 * F32_TOL),
    ("bfloat16", True, 0.1)], ids=["f32_jnp", "f32_kernels", "bf16_kernels"])
def test_forward_logits_match_the_reference(dtype, kernels, tol, request):
    """What check (a) compares, with biases that move picks: float32 to
    rounding on the ``jax.numpy`` path and through the scan and flash kernels
    (interpreted); bfloat16 inside what near-tied picks cost."""
    from chipbench.harness import relative_rms_error
    if kernels:
        request.getfixturevalue("scan_kernels")
        request.getfixturevalue("forward_flash_kernels")
    cfg, pipeline, _ = _files(
        compute_dtype=dtype, attention="flash" if kernels else "dense")
    model = pipeline.build_model(cfg)
    tokens = _tokens(cfg, 2, seed=5)
    params, state = _variables(model, tokens, bias_std=0.1)
    variables = {"params": params, "batch_stats": state}
    got = pipeline.compared(lm_testing.logits(model, variables, tokens), cfg)
    forward = lm_testing.reference_program(CONFIG, cfg, "forward")
    want = forward(variables, tokens)
    assert got.shape == want.shape == (2, 8, 64)
    assert relative_rms_error(np.asarray(got, np.float32), want) <= tol
    # the biases matter to the outputs compared
    zero = forward({"params": params}, tokens)
    assert relative_rms_error(zero, want) > 100 * F32_TOL


@pytest.mark.parametrize("remat,kernels", [
    (False, False), (True, False), (True, True)],
    ids=["kept", "recomputed", "recomputed_kernels"])
def test_loss_gradients_and_the_bias_after_three_steps_match_the_reference(
        remat, kernels, request):
    """The model's own loss (fused head over the rows held, no auxiliary
    loss) and the gradient of every leaf of the nine-letter pattern, with
    seeded biases; then three optimizer steps of the estimator's train step:
    after each, every expert layer's bias is the reference's ``next_bias`` of
    the slots ALL experts were picked for in the step's tokens, and the
    counts are empty again."""
    import jax
    import optax
    if kernels:
        request.getfixturevalue("scan_kernels")
        request.getfixturevalue("forward_flash_kernels")
    cfg, pipeline, reference = _files(
        remat_blocks=remat, attention="flash" if kernels else "dense")
    model = pipeline.build_model(cfg)
    tokens = _tokens(cfg, 4, seed=1)
    params, state = _variables(model, tokens, bias_std=0.1)
    w = np.full(4, 0.25, np.float32)
    (loss, counts), grads = lm_testing.loss_and_grads(model, params, state,
                                                      tokens, w)
    want_loss, want_grads = lm_testing.reference_program(
        CONFIG, cfg, "loss", grad=True)(params, state, tokens)
    assert abs(float(loss) - float(want_loss)) <= F32_TOL * float(want_loss)
    _close(grads, want_grads)
    counts_of = lm_testing.reference_program(CONFIG, cfg, "slot_counts")
    picked = np.stack(counts_of(params, state, tokens))
    assert float(counts[1]) == tokens.size * 6 * 4      # top-6, four layers
    assert float(counts[0]) == picked.max(axis=1).sum()
    assert float(counts[2]) == picked[:, 2:3].sum() < float(counts[1])

    step, create, arguments = _train_step(model, optax.sgd(0.05))
    run = jax.jit(step)
    now = create(params, state)
    bias = {name: b["moe"]["bias"] for name, b in state.items()}
    for i in range(3):
        batch = _tokens(cfg, 4, seed=10 + i)
        before = jax.tree.map(np.asarray, (now.params, now.batch_stats))
        now, _, _ = run(*arguments(now, batch))
        for (name, b), c in zip(sorted(bias.items()),
                                counts_of(*before, batch)):
            assert float(np.sum(c)) == batch.size * 6
            bias[name] = np.asarray(reference.next_bias(b, c, cfg))
            got = now.batch_stats[name]["moe"]
            np.testing.assert_allclose(got["bias"], bias[name], rtol=0,
                                       atol=1e-7)
            assert not np.any(np.asarray(got["counts"]))
    assert any(np.abs(bias[n] - state[n]["moe"]["bias"]).max() > 1e-3
               for n in bias)


# ------------------------------------- (h) a recomputed state-space layer
def test_a_recomputed_state_space_layer_scans_again(scan_kernels,
                                                    forward_flash_kernels):
    """Loss and gradients are the unrecomputed model's; the built step holds
    the forward scan kernel TWICE a state-space layer (the recomputation runs
    it again and hands the backward kernel the chunks' states: nothing of the
    scan is kept) and the backward kernel once, two grouped products an
    expert trip forward, and counts its layers ``rescanned``."""
    import jax
    import optax

    def built(remat):
        cfg, pipeline, _ = _files(remat_blocks=remat, attention="flash")
        return cfg, pipeline.build_model(cfg)

    cfg, plain = built(False)
    _, recomputed = built(True)
    tokens = _tokens(cfg, 2, seed=2)
    params, state = _variables(plain, tokens, bias_std=0.1)
    w = np.full(2, 0.5, np.float32)
    (loss, _), grads = lm_testing.loss_and_grads(recomputed, params, state,
                                                 tokens, w)
    (want_loss, _), want_grads = lm_testing.loss_and_grads(
        plain, params, state, tokens, w)
    assert abs(float(loss) - float(want_loss)) <= F32_TOL * float(want_loss)
    _close(grads, want_grads)

    before = lm_testing.counters()
    step, create, arguments = _train_step(recomputed, optax.sgd(0.05))
    program = jax.make_jaxpr(step)(*arguments(create(params, state), tokens))
    moved = [lm_testing.moved(before, name) for name in (
        "train_ssm_layers_total", "train_attention_layers_total",
        "ssd_chunks_total")]
    assert moved[:2] == [{"rescanned": 4}, {"full": 1}]
    # sequences x groups x chunks a built kernel: 2 x 2 x 4
    assert moved[2]["backward"] == 4 * 16 and moved[2]["forward"] >= 8 * 16
    assert scan_kernels(program, "rdt_ssd_fwd") == 8
    assert scan_kernels(program, "rdt_ssd_bwd") == 4
    assert forward_flash_kernels(str(program)) == 1
    step, create, arguments = _train_step(plain, optax.sgd(0.05))
    program = jax.make_jaxpr(step)(*arguments(create(params, state), tokens))
    assert scan_kernels(program, "rdt_ssd_fwd") == 4
    assert scan_kernels(program, "rdt_ssd_bwd") == 4


# widths the four kernels round the scan take: 4 heads of 64 in 2 groups (a
# norm group of 128 lanes), a state of 64 (B and C of 128), chunks of 16
LANE_WIDE = {"mamba_num_heads": 4, "mamba_head_dim": 64, "n_groups": 2,
             "ssm_state_size": 64, "chunk_size": 16}


def test_the_mixer_on_its_kernels_is_the_mixer_on_jax_numpy(scan_kernels,
                                                            glue_kernels):
    """With the convolution's, the scan's and the norm's kernels interpreted
    the mixer gives what it gives on the ``jax.numpy`` path, outputs and
    every parameter's gradient; differentiated, its program names the four
    kernels round the scan under the scopes they replace (``conv``: one a
    width, forward and backward; ``norm``) and the scan's two under ``scan``,
    and each stage counts itself ``kernel`` once a layer call."""
    import jax
    import jax.numpy as jnp
    from raydp_tpu.models.transformer import Mamba2Mixer
    from raydp_tpu.ops import ssd_scan, ssm_glue

    cfg, pipeline, _ = _files(**LANE_WIDE)
    layer = Mamba2Mixer(pipeline.build_model(cfg).ssm, jnp.float32,
                        cfg["layer_norm_epsilon"], 0.3)
    u = jnp.asarray(np.random.default_rng(1).normal(size=(2, 32, 32)),
                    jnp.float32)
    params = jax.tree.map(np.asarray, layer.init(jax.random.PRNGKey(1),
                                                 u)["params"])
    rng = np.random.default_rng(2)
    for name in ("conv_bias", "D", "norm"):
        params[name] = params[name] + rng.normal(
            0, 0.3, params[name].shape).astype(np.float32)
    g = jnp.asarray(rng.normal(size=u.shape), jnp.float32)
    both = jax.jit(jax.value_and_grad(lambda p, u: jnp.sum(
        layer.apply({"params": p}, u) * g), argnums=(0, 1), has_aux=False))
    before = lm_testing.counters()
    program = jax.make_jaxpr(both)(params, u)
    assert lm_testing.moved(before, "ssm_glue_total") == {"kernel": 2}
    found = glue_kernels(program)
    under = lambda name: sorted(  # noqa: E731
        scope for kernel, scopes in found if kernel == name
        for scope in {"conv", "scan", "norm"} & set(scopes.split("/")))
    assert under("rdt_ssm_conv_fwd") == under("rdt_ssm_conv_bwd") == [
        "conv"] * 3
    assert under("rdt_ssm_norm_fwd") == under("rdt_ssm_norm_bwd") == ["norm"]
    assert under("rdt_ssd_fwd") == under("rdt_ssd_bwd") == ["scan"]
    assert {k for k, _ in found} == {*ssm_glue.KERNEL_NAMES,
                                     *ssd_scan.KERNEL_NAMES}
    value, grads = both(params, u)
    with pytest.MonkeyPatch.context() as plain:
        # what a lowering for the CPU picks: every op's jax.numpy form
        for module in (ssm_glue, ssd_scan):
            plain.setattr(module, "_by_platform", lambda kernel_fn, jnp_fn,
                          interpret, *args: jnp_fn(*args))
        jax.clear_caches()      # an op's traced rules are kept by its arguments
        assert not glue_kernels(jax.make_jaxpr(both)(params, u))
        want_value, want_grads = both(params, u)
    jax.clear_caches()
    assert abs(float(value) - float(want_value)) <= 10 * F32_TOL * abs(
        float(want_value))
    _close(grads, want_grads)


@pytest.mark.parametrize("widths,path", [({}, "jnp"), (LANE_WIDE, "kernel")],
                         ids=["narrow_widths", "lane_wide"])
def test_a_built_step_counts_its_glue_stages(widths, path, scan_kernels,
                                             glue_kernels):
    """``ssm_glue_total``: two stages a state-space layer of a built step
    (four layers: eight), ``jnp`` at the tests' narrow widths (no kernel of
    theirs in the program) and ``kernel`` at widths of whole lane tiles (a
    recomputed layer: its convolution's and its norm's forward kernels run
    twice, as its scan's does, the backward ones once)."""
    import jax
    import optax

    cfg, pipeline, _ = _files(remat_blocks=True, **widths)
    model = pipeline.build_model(cfg)
    tokens = _tokens(cfg, 2, seed=2)
    params, state = _variables(model, tokens, bias_std=0.1)
    step, create, arguments = _train_step(model, optax.sgd(0.05))
    built = create(params, state)
    before = lm_testing.counters()
    program = jax.make_jaxpr(step)(*arguments(built, tokens))
    assert lm_testing.moved(before, "ssm_glue_total") == {path: 8}
    names = [kernel for kernel, _ in glue_kernels(program)]
    per_layer = {"rdt_ssm_conv_fwd": 6, "rdt_ssm_conv_bwd": 3,
                 "rdt_ssm_norm_fwd": 2, "rdt_ssm_norm_bwd": 1}
    for kernel, calls in per_layer.items():
        assert names.count(kernel) == (4 * calls if path == "kernel" else 0)
    assert names.count("rdt_ssd_fwd") == 8 and names.count("rdt_ssd_bwd") == 4


def test_an_expert_trip_forward_is_two_grouped_products(grouped_products):
    """The held share's walk of experts of two matrices: two grouped products
    a trip forward (up, down) where gated experts take three, and fewer in
    the backward's trip too."""
    import jax
    import jax.numpy as jnp
    from raydp_tpu.models.moe import MoE

    def products(gated):
        layer = MoE(16, 6, 16, jnp.float32, first_expert=2, experts_held=1,
                    activation="relu2", normalize_top_k=True,
                    routing="sigmoid", gated=gated)
        x = jnp.ones((8, 32))
        variables = layer.init(jax.random.PRNGKey(0), x)
        forward = jax.make_jaxpr(lambda v: layer.apply(v, x)[0])(variables)
        both = jax.make_jaxpr(jax.grad(lambda v: jnp.sum(layer.apply(
            v, x)[0])))(variables)
        return grouped_products(forward), grouped_products(both)

    assert products(False)[0] == 2 and products(True)[0] == 3
    assert products(False)[1] < products(True)[1]


# ------------------------------------------------------- (i) older models
# sha256 of the estimator's train step as jax lowers it (the StableHLO text,
# no source locations; every op on its ``jax.numpy`` path) for the four older
# families' CPU cuts, computed on the commit before this family (6e8d15d)
# with ``_step_text``. A PR that means to change one of these programs
# replaces its line (PR 58 the three of the models that hold a share of
# their experts: its held rows come back to token order in runs; PR 62 the
# two of the recomputed models that keep their attention's inputs: ``olmoe``,
# not recomputed, and ``kanana-2``, whose latent attention keeps none and
# binds none of their names, are the parent's).
PARENT_STEP = {
    "olmoe-1b-7b":
        "7116221b2cffe66cee500a7f382bd80cd42ca76514520e3b205d118bb54d4d84",
    "smallthinker-21b-a3b":
        "488f046c56e147acc45b7d9f0520c168c1c7dbcff72c4cdd547dcf966b71b1eb",
    "trinity-mini":
        "6149ea892919880ca66abfe82453aa7cf17090c637cd687392f314e990c03a9a",
    "kanana-2-30b-a3b":
        "b7799bd0596e87de6c326f210ba3bfdb752df8beca49759474efecb6d5e8535b",
}


@pytest.mark.parametrize("config,cell", [
    ("olmoe-1b-7b", "olmoe_1b7b_train"),
    ("smallthinker-21b-a3b", "smallthinker_21ba3b_16k_train"),
    ("trinity-mini", "trinity_mini_8k_train"),
    ("kanana-2-30b-a3b", "kanana2_30ba3b_16k_train")])
def test_an_older_familys_step_is_the_parents_text(config, cell):
    """The default layer kind is the pair and experts are gated by default:
    the blocks build what they built, the expert layer's walk takes its three
    grouped products in the order it took them, and the lowered step is the
    text it was."""
    model, text, params = _step_text(config, cell)
    assert (model.layer_kinds, model.ssm, model.expert_gated) == (
        "", None, True)
    assert not any(model.ssm_layers.values())
    assert model.kda is None and not any(model.kda_layers.values())
    block = params[f"block_{model.num_layers - 1}"]
    assert {"ln1", "attn", "ln2", "moe"} <= set(block)
    assert "experts_gate" in block["moe"] and "norm" not in block
    assert set(model.attention_inputs) == {
        "olmoe-1b-7b": set(), "kanana-2-30b-a3b": {"rebuilt"}}.get(
            config, {"kept"})
    assert hashlib.sha256(text.encode()).hexdigest() == PARENT_STEP[config]
