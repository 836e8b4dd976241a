"""The ``looped_lm`` family (Ouro-2.6B: one stack of layers run several times
on shared weights, an exit gate a pass, the expected loss over the passes) on
the CPU at tiny sizes: the program against
``chipbench/reference/ouro-2.6b.py`` (forward, the loss and every gradient on
seeded weights), a shared weight's gradient as the sum over untied copies,
recomputed blocks, the head loss under weights a position that carry a
gradient, the exit distribution and its counters through the train step, the
passes as ONE loop in the lowered step, and the plain model's step as it was.
"""

from __future__ import annotations

import functools
import hashlib
import re

import numpy as np
import pytest

from tests import lm_testing
from tests.lm_testing import (F32_TOL, close as _close, leaves as _leaves,
                              step_text as _step_text, tokens as _tokens,
                              train_step as _train_step)

CONFIG = "ouro-2.6b"

# 2 layers run 3 times, width 64 on 4 heads of 16, a feed-forward of 96, a
# vocabulary of 128, 32 positions
TINY = {"hidden_size": 64, "head_dim": 16, "num_attention_heads": 4,
        "num_key_value_heads": 4, "intermediate_size": 96, "vocab_size": 128,
        "layers": 2, "total_ut_steps": 3, "seq_len": 32,
        "compared_positions": 8, "compared_vocab": 48,
        "compute_dtype": "float32", "attention": "dense", "init_std": 0.3,
        "remat_blocks": False}
_files = functools.partial(lm_testing.files, CONFIG, TINY)


def _moved(model, tokens, seed=0):
    """Seeded weights; the norms' weights moved off 1 and the gate's bias off
    0 so that they count."""
    import jax

    params, _ = lm_testing.variables(model, tokens[:, :8], seed)
    rng = np.random.default_rng(seed)
    return jax.tree_util.tree_map_with_path(
        lambda path, leaf: leaf if path[-1].key not in ("scale", "bias")
        else (leaf + rng.normal(0, 0.2, leaf.shape)).astype(np.float32),
        params)


# ----------------------------------------------------------- (a) the model
def test_the_parameter_tree_is_a_plain_models_plus_the_gate():
    """``embed``, ``block_0..block_{N-1}``, ``ln_f``, ``lm_head`` as a model
    that runs its layers once has them, leaf for leaf, plus ``exit_gate``
    (hidden + 1 parameters): what checkpoints, the decay mask and the
    sharding rules see."""
    import dataclasses

    from chipbench import manifest
    cfg, pipeline, _ = _files()
    model = pipeline.build_model(cfg)
    tokens = _tokens(cfg, 1)
    looped = _leaves(lm_testing.variables(model, tokens[:, :8])[0])
    plain = _leaves(lm_testing.variables(dataclasses.replace(
        model, total_ut_steps=1, exit_entropy_weight=None,
        exit_probs_out=False), tokens[:, :8])[0])
    gate = {"exit_gate/kernel": (64, 1), "exit_gate/bias": (1,)}
    assert {k: v.shape for k, v in looped.items()} == {
        **{k: v.shape for k, v in plain.items()}, **gate}
    flops = manifest.load_module(lm_testing.ROOT, "flops", "looped_lm.py")
    assert sum(v.size for v in looped.values()) == sum(
        flops.parameters(cfg).values())
    assert not model.rng_streams


@pytest.mark.parametrize("dtype,attention,std,tol", [
    ("float32", "dense", 0.3, 10 * F32_TOL),
    ("bfloat16", "flash", 0.1, 0.05)])
def test_forward_logits_and_exit_probabilities_match_the_reference(
        dtype, attention, std, tol):
    """What check (a) compares, through the pipeline's ``compared``: the last
    pass's logits over a slice of the vocabulary and the exit probabilities
    (scaled), which sum to 1 a position; in float32 to summation order, in
    bfloat16 through the flash op inside the cell's tolerance (weights of
    std 0.1: at 0.3 three passes at width 64 amplify bfloat16's rounding to
    0.25, in the reference rounded to bfloat16 as in the program)."""
    import jax

    from chipbench.harness import relative_rms_error
    cfg, pipeline, reference = _files(compute_dtype=dtype,
                                      attention=attention, init_std=std)
    assert tol <= reference.TOLERANCE
    model = pipeline.build_model(cfg)
    tokens = _tokens(cfg, 2, pipeline=pipeline)
    params = _moved(model, tokens)
    out = lm_testing.logits(model, {"params": params}, tokens)
    assert out.shape == (2, 32, 128 + 3)
    np.testing.assert_allclose(np.asarray(out[..., -3:]).sum(-1), 1.0,
                               rtol=1e-5)
    got = jax.jit(lambda o: pipeline.compared(o, cfg))(out)
    want = lm_testing.reference_program(CONFIG, cfg, "forward")(
        {"params": params}, tokens)
    assert got.shape == want.shape == (2, 8, 48 + 3)
    assert relative_rms_error(got, want) <= tol
    # the probabilities alone, unscaled: a wrong gate is not hidden by the
    # logits beside it
    scale = pipeline.exit_scale(cfg)
    assert np.abs(np.asarray(got[..., -3:]) - np.asarray(want[..., -3:])
                  ).max() / scale <= (1e-5 if dtype == "float32" else 0.02)


@pytest.mark.parametrize("remat", [False, True], ids=["kept", "recomputed"])
def test_the_loss_and_every_gradient_match_the_reference(remat):
    """``loss_rows`` (three passes as one scan, the gate, the exit
    distribution in logarithms, the passes' hidden states through ONE fused
    head scan under the exit probabilities, the entropy) against ``jax.grad``
    of the reference's written-out loss, every leaf, the gate's two among
    them; blocks recomputed or not give the same."""
    import jax.numpy as jnp

    cfg, pipeline, _ = _files(remat_blocks=remat)
    model = pipeline.build_model(cfg)
    tokens = _tokens(cfg, 2, pipeline=pipeline)
    params = _moved(model, tokens)
    weights = jnp.full((2,), 0.5, jnp.float32)
    (got, counts), got_g = lm_testing.loss_and_grads(model, params, None,
                                                     tokens, weights)
    want, want_g = lm_testing.reference_program(CONFIG, cfg, "loss",
                                                grad=True)(params, tokens)
    np.testing.assert_allclose(got, want, rtol=1e-5)
    _close(got_g, want_g, tol=20 * F32_TOL)
    assert np.abs(_leaves(got_g)["exit_gate/kernel"]).max() > 1e-4
    # the exit masses add up to the positions that carry a loss
    assert counts.shape == (4,) and float(counts[-1]) == 2 * 31
    np.testing.assert_allclose(float(counts[:3].sum()), 2 * 31, rtol=1e-5)


def test_a_shared_weights_gradient_is_the_sum_over_three_untied_copies():
    """The reference with a copy of the blocks and the final norm for each
    pass, differentiated by each copy at the point where all three are the
    shared weights: the copies' gradients add up to the program's gradient of
    the shared ones (which the scan's transpose sums into one tree)."""
    import jax
    import jax.numpy as jnp

    cfg, pipeline, reference = _files()
    model = pipeline.build_model(cfg)
    tokens = _tokens(cfg, 2, pipeline=pipeline)
    params = _moved(model, tokens)
    weights = jnp.full((2,), 0.5, jnp.float32)
    _, got_g = lm_testing.loss_and_grads(model, params, None, tokens,
                                         weights)
    shared = {k: v for k, v in params.items()
              if k.startswith("block_") or k == "ln_f"}
    by_copy = jax.jit(jax.grad(lambda copies: reference.loss(
        params, tokens, cfg, untied=copies)))([shared] * 3)
    assert len(by_copy) == 3
    first, total = _leaves(by_copy[0]), _leaves(jax.tree.map(
        lambda *g: sum(g), *by_copy))
    _close({k: v for k, v in got_g.items() if k in shared}, total,
           tol=20 * F32_TOL)
    # and no one copy's is the whole of it
    assert any(np.abs(first[k] - total[k]).max() > 1e-3 * np.abs(
        total[k]).max() for k in total)


def test_a_saturated_gate_gives_finite_probabilities_and_gradients():
    import jax
    import jax.numpy as jnp
    from raydp_tpu.models.transformer import exit_distribution

    z = jnp.asarray([[200.0, -200.0, 0.0], [-200.0, 200.0, 0.0],
                     [0.0, 0.0, 0.0]], jnp.float32)          # [P, positions]

    def entropy(z):
        log_p = exit_distribution(z)
        return -jnp.sum(jnp.exp(log_p) * log_p)

    value, grad = jax.value_and_grad(entropy)(z)
    assert np.isfinite(value) and np.isfinite(np.asarray(grad)).all()
    p = np.exp(np.asarray(exit_distribution(z)))
    np.testing.assert_allclose(p.sum(0), 1.0, rtol=1e-6)
    np.testing.assert_allclose(p[:, 2], [0.5, 0.25, 0.25], rtol=1e-6)
    np.testing.assert_allclose(p[:, 0], [1, 0, 0], atol=1e-6)
    np.testing.assert_allclose(p[:, 1], [0, 1, 0], atol=1e-6)


# ------------------------------------------------------- (b) the head loss
@pytest.mark.parametrize("chunk", [7, 64])
def test_the_weighted_next_token_head_loss_is_the_plain_form(chunk):
    """``next_token_weights``: value, rows, ``d hidden``, ``d kernel`` AND
    ``d weights`` against autodiff of ``log_softmax`` written out, chunks
    that divide the positions and chunks that do not; the last position's
    weight is not read; with weights of one it is the loss as it was."""
    import jax
    import jax.numpy as jnp
    from raydp_tpu.models.transformer import lm_head_loss

    rng = np.random.default_rng(0)
    b, t, d, vocab = 3, 24, 16, 40
    hidden = jnp.asarray(rng.normal(size=(b, t, d)), jnp.float32)
    kernel = jnp.asarray(rng.normal(size=(d, vocab)), jnp.float32)
    tokens = jnp.asarray(rng.integers(0, vocab, (b, t)), jnp.int32)
    rows_w = jnp.asarray([0.5, 0.3, 0.2], jnp.float32)
    pos_w = jnp.asarray(rng.random((b, t)), jnp.float32)

    def plain(hidden, kernel, pos_w):
        logp = jax.nn.log_softmax(hidden[:, :-1] @ kernel, axis=-1)
        ce = -jnp.take_along_axis(logp, tokens[:, 1:, None], -1)[..., 0]
        rows = jnp.sum(pos_w[:, :-1] * ce, axis=1) / (t - 1)
        return jnp.sum(rows_w * rows), rows

    def fused(hidden, kernel, pos_w):
        return lm_head_loss(hidden, kernel, tokens, rows_w, chunk,
                            next_token_weights=pos_w)

    (want, want_rows), want_g = jax.value_and_grad(
        plain, (0, 1, 2), has_aux=True)(hidden, kernel, pos_w)
    (got, got_rows), got_g = jax.jit(jax.value_and_grad(
        fused, (0, 1, 2), has_aux=True))(hidden, kernel, pos_w)
    np.testing.assert_allclose(got, want, rtol=1e-5)
    np.testing.assert_allclose(got_rows, want_rows, rtol=1e-5)
    _close(got_g, want_g)
    assert not np.asarray(got_g[2])[:, -1].any()
    np.testing.assert_allclose(jax.jit(fused)(hidden, kernel, pos_w)[0],
                               want, rtol=1e-5)
    old = jax.value_and_grad(lambda h, k: lm_head_loss(
        h, k, tokens, rows_w, chunk)[0], (0, 1))(hidden, kernel)
    new = jax.value_and_grad(lambda h, k: lm_head_loss(
        h, k, tokens, rows_w, chunk, next_token_weights=jnp.ones((b, t)))[0],
        (0, 1))(hidden, kernel)
    np.testing.assert_allclose(new[0], old[0], rtol=1e-5)
    _close(new[1], old[1])
    with pytest.raises(ValueError):
        lm_head_loss(hidden, kernel, tokens, rows_w, chunk,
                     position_weights=pos_w, next_token_weights=pos_w)


def _scans(jaxpr):
    """Every ``scan`` equation of a jaxpr, nested ones too."""
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "scan":
            yield eqn
        for sub in eqn.params.values():
            for inner in (sub if isinstance(sub, (list, tuple)) else [sub]):
                inner = getattr(inner, "jaxpr", inner)
                if hasattr(inner, "eqns"):
                    yield from _scans(inner)


def test_the_passes_share_one_head_scan_and_one_kernel_gradient_carry():
    """In the differentiated loss ONE scan carries a float32 ``[D, V]`` (the
    head kernel's gradient, all passes' together), and the loop over the
    passes is one scan of ``total_ut_steps`` trips forward and one back."""
    import jax
    import jax.numpy as jnp

    cfg, pipeline, _ = _files(remat_blocks=True)
    model = pipeline.build_model(cfg)
    tokens = _tokens(cfg, 2, pipeline=pipeline)
    params = _moved(model, tokens)
    weights = jnp.full((2,), 0.5, jnp.float32)
    jaxpr = jax.make_jaxpr(jax.grad(lambda p: model.apply(
        {"params": p}, tokens, tokens, weights,
        method=model.loss_rows)[0]))(params).jaxpr
    scans = list(_scans(jaxpr))
    carries = [eqn for eqn in scans if any(
        v.aval.shape == (64, 128) and v.aval.dtype == jnp.float32
        for v in eqn.outvars[:eqn.params["num_carry"]])]
    assert len(carries) == 1
    # 3 passes x 2 rows as 6 rows, 31 positions in chunks of 31
    assert carries[0].params["length"] == 1
    over_passes = [eqn for eqn in scans if eqn.params["length"] == 3]
    assert len(over_passes) == 2


# ----------------------------------------------- (c) the step and the counts
def test_the_counts_are_executions_a_step_and_the_gate_is_replicated():
    """Layers times passes; a model that runs its layers once counts what it
    counted; the exit gate matches its own sharding rule before the
    SwiGLU's."""
    import dataclasses

    import jax
    from jax.sharding import PartitionSpec

    from raydp_tpu.models.transformer import transformer_param_rules
    from raydp_tpu.parallel import make_mesh, param_sharding_rules
    cfg, pipeline, _ = _files(remat_blocks=True)
    model = pipeline.build_model(cfg)
    assert model.attention_layers == {"window": 0, "full": 6}
    assert model.attention_forward == {"twice": 6}      # dense: nothing kept
    assert model.sublayer_out == {"kept": 6, "rebuilt": 6}
    assert model.loop_passes == {"recomputed": 6}
    assert model.loss_counters == (
        ("train_exit_mass_total", "1"), ("train_exit_mass_total", "2"),
        ("train_exit_mass_total", "3"), ("train_exit_positions_total", ""))
    once = dataclasses.replace(model, total_ut_steps=1,
                               exit_entropy_weight=None)
    assert once.attention_layers == {"window": 0, "full": 2}
    assert once.sublayer_out == {"kept": 2, "rebuilt": 2}
    assert once.loop_passes == {} and once.loss_counters == ()
    ungated = dataclasses.replace(model, exit_entropy_weight=None)
    assert ungated.loop_passes == {"recomputed": 6}
    assert ungated.loss_counters == ()
    tokens = _tokens(cfg, 1)
    shapes = jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0),
                                               tokens[:, :8]))["params"]
    mesh = make_mesh({"data": 2, "tensor": 2}, devices=jax.devices()[:4])
    flat, _ = jax.tree_util.tree_flatten_with_path(param_sharding_rules(
        mesh, transformer_param_rules())(shapes))
    specs = {"/".join(k.key for k in path): sharding.spec
             for path, sharding in flat}
    assert specs["exit_gate/kernel"] == specs["exit_gate/bias"] \
        == PartitionSpec()
    assert specs["block_0/gate/kernel"] == PartitionSpec(None, "tensor")


def test_a_train_step_counts_the_exit_masses_and_leaves_a_padded_row_out():
    """Through the estimator's own step: the loss falls over three steps, the
    registry's ``train_exit_mass_total`` labels add up to
    ``train_exit_positions_total``, a row of weight zero is left out, the
    built step counts its layer executions once, and the expected exit moves
    off a fresh gate's value (the gate's gradient arrives)."""
    import jax
    import optax

    cfg, pipeline, _ = _files(remat_blocks=True, init_std=0.02)
    model = pipeline.build_model(cfg)
    tokens = _tokens(cfg, 2, pipeline=pipeline)
    params, _ = lm_testing.variables(model, tokens[:, :8])
    before = lm_testing.counters()
    step, create, arguments = _train_step(model, optax.adam(0.05))
    run = jax.jit(step)
    state, losses, exits = create(params), [], []
    for _ in range(4):
        state, loss, (counts,) = run(*arguments(state, tokens))
        mass, positions = np.asarray(counts[:3]), float(counts[3])
        assert positions == 2 * 31
        np.testing.assert_allclose(mass.sum(), positions, rtol=1e-5)
        losses.append(float(loss))
        exits.append(float((mass * [1, 2, 3]).sum() / positions))
    # a fresh gate: lambda ~ 1/2, the expected exit ~ 1.75 of 3
    assert abs(exits[0] - 1.75) < 0.05
    assert losses[-1] < losses[0] and abs(exits[-1] - exits[0]) > 1e-3
    assert lm_testing.moved(before, "train_loop_passes_total") == {
        "recomputed": 6}
    assert lm_testing.moved(before, "train_attention_layers_total") == {
        "full": 6}
    # the model's own counts with a padded row
    import jax.numpy as jnp
    _, counts = jax.jit(lambda p: model.apply(
        {"params": p}, tokens, tokens, jnp.asarray([1.0, 0.0]),
        method=model.loss_rows))(params)
    assert float(counts[3]) == 31
    np.testing.assert_allclose(float(counts[:3].sum()), 31, rtol=1e-5)


def test_the_lowered_step_holds_each_layer_once_whatever_the_passes():
    """The passes are a loop in the program: the step of a model that runs
    its two layers five times has the operations of one that runs them
    three times (a ``stablehlo.while`` forward and one back), not five
    thirds of them; a fit's step is traced with each layer once."""
    import dataclasses

    import jax
    import optax

    cfg, pipeline, _ = _files(remat_blocks=True)
    tokens = _tokens(cfg, 1, pipeline=pipeline)

    def text(passes):
        model = dataclasses.replace(pipeline.build_model(cfg),
                                    total_ut_steps=passes)
        shapes = jax.eval_shape(lambda: model.init(
            jax.random.PRNGKey(0), tokens[:, :8]))["params"]
        step, create, arguments = _train_step(model, optax.sgd(0.05))
        state = jax.eval_shape(lambda: create(jax.tree.map(
            lambda s: np.zeros(s.shape, s.dtype), shapes)))
        return jax.jit(step).lower(*arguments(state, tokens)).as_text()

    three, five = text(3), text(5)
    count = lambda t, op: len(re.findall(op, t))  # noqa: E731
    assert count(three, r"stablehlo\.while") == count(
        five, r"stablehlo\.while") >= 2
    dots = count(three, r"stablehlo\.dot_general")
    assert dots == count(five, r"stablehlo\.dot_general")
    once = text(1)          # a gate on one pass: still the loop's form
    assert count(once, r"stablehlo\.dot_general") == dots


# ------------------------------------------------------- (d) older models
# sha256 of the estimator's train step as jax lowers it (the StableHLO text,
# no source locations; every op on its ``jax.numpy`` path) for the newest of
# the older families' CPU cuts and for the one whose blocks this family's
# are (four norms, a dense SwiGLU), computed on the commit before this family
# (322b140) with ``_step_text``. The others are held, with the hashes they
# had, by ``tests/test_blockdiff_moe_lm.py``, ``tests/test_ssm_moe_lm.py``
# and ``tests/test_mla_moe_lm.py``, which this PR leaves as they are. A PR
# that means to change one of these programs replaces its line (PR 58 both:
# a share's held rows come back to token order in runs). Since PR 61 (a pair
# with a convolution operator, tied embeddings, the routing's epsilon a
# field: all off by default) the looped family's own cut is held too, with
# the hash it had on the commit before that PR (3eb6cc9). PR 62 (a recomputed
# block keeps its attention's inputs) replaced the lines of ``sdar`` and
# ``trinity``, which keep them, and left the looped cut's: a looped stack
# keeps none and binds none of their names, so its text is the parent's.
PARENT_STEP = {
    "ouro-2.6b":
        "31a058df325c6a3362ae0e92c7fe6efcf05781d1f51c21a7f03d65bcb7bd8cce",
    "sdar-30b-a3b-chat":
        "6f62a2c1355f5dbe6b17d318cb0b10482b563ff9e59990148a82147a9995b68a",
    "trinity-mini":
        "6149ea892919880ca66abfe82453aa7cf17090c637cd687392f314e990c03a9a",
}


@pytest.mark.parametrize("config,cell", [
    ("sdar-30b-a3b-chat", "sdar_30ba3b_8k_blockdiff_train"),
    ("trinity-mini", "trinity_mini_8k_train"),
    ("ouro-2.6b", "ouro_2p6b_8k_train")])
def test_a_model_of_one_pass_and_no_gate_is_the_step_it_was(config, cell):
    """``total_ut_steps`` 1 and no gate are the defaults: the layers are
    built by the one helper the loop's body uses too, and the lowered step of
    an older family is the text it was, to the letter. So are no ``C`` layer,
    a head of its own and the routing's 1e-20: the looped family's step (its
    head and its loop handed the embedding's module since) is its text too."""
    model, text, params = _step_text(config, cell)
    assert (model.total_ut_steps == 1) == (config != "ouro-2.6b")
    assert (model.exit_entropy_weight is None) == (config != "ouro-2.6b")
    assert bool(model.loop_passes) == model.exit_probs_out == (
        config == "ouro-2.6b")
    assert not model.tie_embeddings and "lm_head" in params
    assert model.conv_layers == {"recomputed": 0}
    assert model.route_norm_eps == 1e-20 and "C" not in model.layer_kinds
    assert set(model.attention_inputs) == {
        "rebuilt" if config == "ouro-2.6b" else "kept"}
    assert hashlib.sha256(text.encode()).hexdigest() == PARENT_STEP[config]
