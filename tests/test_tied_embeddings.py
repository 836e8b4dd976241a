"""Tied embeddings (``TransformerLM(tie_embeddings=True)``: the head IS the
embedding, one ``[rows, dim]`` array; the ``lfm2`` family's): the one leaf's
gradient against an untied twin with equal weights (the gather's plus the
head's), every loss form of a tied model against its twin's, and the fused
head loss on a kernel that lies vocabulary-major. Small sizes, CPU, seeded.
"""

import functools

import numpy as np
import pytest

from tests import lm_testing
from tests.lm_testing import (F32_TOL, close as _close, leaves as _leaves,
                              tokens as _tokens, variables as _variables)
from tests.test_conv_moe_lm import CONFIG, TINY

_files = functools.partial(lm_testing.files, CONFIG, TINY)


def test_the_tied_leafs_gradient_is_the_embeddings_plus_the_heads():
    """An untied twin with equal weights (``lm_head`` the embedding's
    transpose): the same logits and loss, and the tied model's ONE gradient
    leaf is the twin's embedding gradient plus its head gradient transposed;
    every other leaf's gradient is the twin's. AdamW then holds one pair of
    moments for the array."""
    import jax
    cfg, pipeline, _ = _files()
    tied = pipeline.build_model(cfg)
    twin = tied.clone(tie_embeddings=False)
    tokens = _tokens(cfg, 4, seed=2)
    params, state = _variables(tied, tokens, bias_std=0.1)
    untied = dict(params, lm_head={
        "kernel": np.ascontiguousarray(params["embed"]["embedding"].T)})
    assert set(jax.eval_shape(twin.init, jax.random.PRNGKey(0), tokens[:1])[
        "params"]) == set(params) | {"lm_head"}
    same = lambda m, p: lm_testing.logits(  # noqa: E731
        m, {"params": p, "batch_stats": state}, tokens)
    np.testing.assert_allclose(same(tied, params), same(twin, untied),
                               rtol=1e-5, atol=1e-5)
    w = np.full(4, 0.25, np.float32)
    (loss, _), got = lm_testing.loss_and_grads(tied, params, state, tokens, w)
    (twin_loss, _), want = lm_testing.loss_and_grads(twin, untied, state,
                                                     tokens, w)
    assert abs(float(loss) - float(twin_loss)) <= F32_TOL * float(twin_loss)
    head = np.asarray(want["lm_head"]["kernel"]).T
    gather = np.asarray(want["embed"]["embedding"])
    assert np.abs(head).max() > 1e-3 and np.abs(gather).max() > 1e-3
    np.testing.assert_allclose(got["embed"]["embedding"], gather + head,
                               rtol=1e-4, atol=1e-6)
    rest = lambda g: {k: v for k, v in g.items()  # noqa: E731
                      if k not in ("embed", "lm_head")}
    _close(rest(got), rest(want))
    opt = pipeline.build_optimizer(cfg).init(params)
    moments = [v for v in jax.tree.leaves(opt) if np.ndim(v) > 0]
    assert sum(v.size for v in moments) == 2 * sum(
        v.size for v in _leaves(params).values())


@pytest.mark.parametrize("form", ["next_token", "diffusion", "looped"])
def test_every_loss_of_a_tied_model_is_its_untied_twins(form):
    """The plain next-token loss takes the embedding as it lies (the three
    products contract over its layout); the block-diffusion and the looped
    losses take its transpose: each is the untied twin's loss, and so is
    the one leaf's gradient."""
    import jax
    from raydp_tpu.models import TransformerLM
    from raydp_tpu.models.transformer import BlockDiffusionSpec
    extra = {"next_token": {}, "looped": dict(
        total_ut_steps=2, exit_entropy_weight=0.1),
        "diffusion": dict(diffusion=BlockDiffusionSpec(4, 63))}[form]
    tied = TransformerLM(vocab_size=64, dim=32, num_heads=4, num_layers=2,
                         ffn_dim=48, attention="dense", init_std=0.3,
                         tie_embeddings=True, **extra)
    twin = tied.clone(tie_embeddings=False)
    tokens = _tokens({"seq_len": 16, "max_position_embeddings": 16,
                      "vocab_size": 60}, 2, seed=4)
    params, _ = _variables(tied, tokens)
    untied = dict(params, lm_head={
        "kernel": np.ascontiguousarray(params["embed"]["embedding"].T)})
    w = np.full(2, 0.5, np.float32)
    (loss, _), got = lm_testing.loss_and_grads(tied, params, None, tokens, w)
    (want_loss, _), want = lm_testing.loss_and_grads(twin, untied, None,
                                                     tokens, w)
    assert abs(float(loss) - float(want_loss)) <= F32_TOL * float(want_loss)
    np.testing.assert_allclose(
        got["embed"]["embedding"], np.asarray(want["embed"]["embedding"])
        + np.asarray(want["lm_head"]["kernel"]).T, rtol=1e-4, atol=1e-6)


def test_the_fused_head_loss_takes_a_vocab_major_kernel_as_it_lies():
    """``lm_head_loss(vocab_major=True)`` on ``[V, D]`` is the loss on its
    transpose, the hidden states' gradient too, and the kernel's gradient
    comes back ``[V, D]``; the program holds no transpose of the kernel's
    size."""
    import jax
    import jax.numpy as jnp
    from raydp_tpu.models.transformer import lm_head_loss
    r = np.random.default_rng(0)
    hidden = jnp.asarray(r.normal(size=(2, 24, 16)), jnp.float32)
    kernel = jnp.asarray(r.normal(size=(40, 16)), jnp.float32)     # [V, D]
    tokens = jnp.asarray(r.integers(0, 40, (2, 24)), jnp.int32)
    w = jnp.full((2,), 0.5)
    major = lambda h, k: lm_head_loss(  # noqa: E731
        h, k, tokens, w, chunk=8, vocab_major=True)[0]
    plain = lambda h, k: lm_head_loss(h, k, tokens, w, chunk=8)[0]  # noqa: E731
    got = jax.value_and_grad(major, (0, 1))(hidden, kernel)
    want = jax.value_and_grad(plain, (0, 1))(hidden, kernel.T)
    np.testing.assert_allclose(got[0], want[0], rtol=1e-6)
    np.testing.assert_allclose(got[1][0], want[1][0], rtol=1e-5, atol=1e-7)
    np.testing.assert_allclose(got[1][1], want[1][1].T, rtol=1e-5, atol=1e-7)
    text = str(jax.make_jaxpr(jax.grad(major, (0, 1)))(hidden, kernel))
    assert "f32[16,40] = transpose" not in text
    assert "f32[40,16] = transpose" not in text
    assert "f32[16,40] = transpose" in str(jax.make_jaxpr(jax.grad(
        lambda h, k: plain(h, k.T), (0, 1)))(hidden, kernel))
