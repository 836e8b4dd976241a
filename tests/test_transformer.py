"""Transformer LM + flash attention tests.

Covers the long-context tier: flash kernel vs dense reference (fwd + grad,
both the jnp path and the Pallas kernel in interpret mode), ring-vs-dense
equivalence through the full model on a sequence-sharded mesh, and a short
training-loss check.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from raydp_tpu.models.transformer import BlockDiffusionSpec
from raydp_tpu.ops.flash_attention import flash_attention
from raydp_tpu.ops.ring_attention import dense_attention


def _qkv(b=2, t=128, h=2, d=32, seed=0):
    rng = np.random.RandomState(seed)
    return tuple(jnp.asarray(rng.randn(b, t, h, d).astype(np.float32)) * 0.3
                 for _ in range(3))


@pytest.mark.parametrize("causal", [True, False])
def test_flash_matches_dense(causal):
    q, k, v = _qkv()
    ref = dense_attention(q, k, v, causal=causal)
    got = flash_attention(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref), atol=2e-5)


def test_flash_pallas_interpret_matches_dense():
    q, k, v = _qkv(t=256, d=128)
    ref = dense_attention(q, k, v, causal=True)
    got = flash_attention(q, k, v, causal=True, interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref), atol=2e-5)


def test_flash_grads_match_dense():
    q, k, v = _qkv(t=64)

    def loss(f):
        return lambda q, k, v: jnp.sum(f(q, k, v, causal=True) ** 2)

    g_ref = jax.grad(loss(dense_attention), argnums=(0, 1, 2))(q, k, v)
    g_got = jax.grad(loss(flash_attention), argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g_ref, g_got):
        np.testing.assert_allclose(np.asarray(b), np.asarray(a), atol=1e-4)


@pytest.mark.parametrize("blocks", [(256, 256), (64, 64), (64, 128)])
@pytest.mark.parametrize("causal", [True, False])
def test_flash_pallas_bwd_interpret_matches_dense(causal, blocks):
    """The Pallas dq / dkdv kernels (interpret mode) against dense grads —
    the hardware backward path, exercised on CPU. The sub-256 block cases run
    multi-block grids (up to 4x4), covering cross-block accumulation, scratch
    init/finalize, the causal block skip, and rectangular blk_q != blk_k."""
    bq, bk = blocks
    q, k, v = _qkv(t=256, d=64)

    def loss(f, **kw):
        return lambda q, k, v: jnp.sum(f(q, k, v, causal=causal, **kw) ** 2)

    g_ref = jax.grad(loss(dense_attention), argnums=(0, 1, 2))(q, k, v)
    g_got = jax.grad(loss(flash_attention, interpret=True,
                          block_q=bq, block_k=bk),
                     argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g_ref, g_got):
        np.testing.assert_allclose(np.asarray(b), np.asarray(a), atol=1e-4)


@pytest.mark.parametrize("interpret", [False, True], ids=["jnp", "kernel"])
@pytest.mark.parametrize("policy,forwards", [
    ("the_names", 1), ("nothing_saveable", 2), ("no_policy", 2)])
def test_a_checkpoint_that_keeps_the_named_pair_runs_the_forward_once(
        interpret, policy, forwards, capsys, forward_flash_kernels):
    """``_flash_fwd`` names what the backward reads beside q, k, v. Under
    ``save_only_these_names`` of the two a ``jax.checkpoint`` keeps them and
    its recomputation holds no forward (one a call in the gradient's
    program); under ``nothing_saveable`` (the estimator's ``remat="full"``)
    and with no policy the forward still runs twice. The gradients are the
    un-checkpointed ones either way."""
    from jax.ad_checkpoint import print_saved_residuals

    from raydp_tpu.ops.flash_attention import RESIDUAL_NAMES

    policies = jax.checkpoint_policies
    kept = {"the_names": policies.save_only_these_names(*RESIDUAL_NAMES),
            "nothing_saveable": policies.nothing_saveable,
            "no_policy": None}[policy]
    q, k, v = _qkv(t=128, h=4, d=32, seed=3)
    k, v = k[:, :, :2], v[:, :, :2]       # two query heads a K/V head

    def loss(q, k, v):
        out = flash_attention(q, k, v, causal=True, interpret=interpret,
                              block_q=64, block_k=64, window=96)
        return jnp.sum(jnp.tanh(out) * q)

    def forward_calls(fn):
        # the forward kernel by name or, on the jnp path, the row maxima of
        # its log-sum-exp, which nothing else in these programs takes
        program = jax.make_jaxpr(grad(fn))(q, k, v)
        return forward_flash_kernels(program) if interpret \
            else str(program).count("= reduce_max[")

    grad = lambda f: jax.grad(f, argnums=(0, 1, 2))  # noqa: E731
    recomputed = jax.checkpoint(loss, policy=kept)
    assert (forward_calls(loss), forward_calls(recomputed)) == (1, forwards)
    for got, want in zip(grad(recomputed)(q, k, v), grad(loss)(q, k, v)):
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-6, atol=1e-6)
    # what the checkpoint keeps beside its arguments: the kernel's output
    # ([B*H, T, D]; it is read on after the op, so jax lists it by the
    # reduce_precision it puts on such a residual) and the row sums by name
    capsys.readouterr()
    print_saved_residuals(recomputed, q, k, v)
    inside = [line for line in capsys.readouterr().out.splitlines()
              if "from the argument" not in line]
    if forwards == 1:
        assert sorted(line.split()[0] for line in inside) == [
            "f32[8,128,32]", "f32[8,128]"]
        assert any(f"named '{RESIDUAL_NAMES[1]}'" in line for line in inside)
    else:
        assert inside == []


def test_explicit_flash_on_a_tpu_backend_raises_where_the_kernel_cannot_run(
        monkeypatch):
    """On the chip an explicit ``flash`` never quietly runs the jnp path
    (which materializes the T×T scores): a shape the kernel cannot take
    raises and says why. Off the chip the same call is the jnp path."""
    from raydp_tpu.models.transformer import Attention
    from raydp_tpu.ops import flash_attention as fa

    q, k, v = _qkv(t=20, d=32)      # 20 = the whole sequence, not 8-aligned
    ref = dense_attention(q, k, v, causal=True)
    got = flash_attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref), atol=2e-5)

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    with pytest.raises(ValueError, match="not multiples of 8"):
        flash_attention(q, k, v, causal=True)
    with pytest.raises(ValueError, match="head_dim 12"):
        flash_attention(*_qkv(t=128, d=12), causal=True)
    assert "neither" in fa.kernel_ineligible(2112, 128)  # 64·33: blocks of 64
    assert fa.kernel_ineligible(8192, 128) is None
    assert fa.kernel_ineligible(1024, 64) is None
    # `auto` picks the kernel only for shapes it takes; explicit flash stays
    assert Attention(num_heads=2)._dispatch(20, 32) == "dense"
    assert Attention(num_heads=2)._dispatch(8192, 128) == "flash"
    assert Attention(num_heads=2, attention="flash")._dispatch(20, 32) == "flash"


def test_flash_sharded_maps_kernel_over_batch_and_heads():
    """``flash_attention_sharded`` runs the kernel per (data, tensor) tile —
    no gather of q/k/v ahead of it — and matches dense attention, forward
    and backward (kernel in interpret mode on the virtual mesh)."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from raydp_tpu.ops.flash_attention import flash_attention_sharded
    from raydp_tpu.parallel import MeshSpec, make_mesh

    mesh = make_mesh(MeshSpec(data=4, tensor=2))
    q, k, v = _qkv(b=4, t=256, d=64)
    sharding = NamedSharding(mesh, P("data", None, "tensor", None))
    qs, ks, vs = (jax.device_put(x, sharding) for x in (q, k, v))

    def sharded(q, k, v, causal=True):
        return flash_attention_sharded(q, k, v, mesh, causal=causal,
                                       interpret=True, block_q=64,
                                       block_k=128)

    fwd = jax.jit(sharded)
    np.testing.assert_allclose(
        np.asarray(fwd(qs, ks, vs)),
        np.asarray(dense_attention(q, k, v, causal=True)), atol=2e-5)
    assert "all-gather" not in fwd.lower(qs, ks, vs).compile().as_text()

    def loss(f):
        return lambda q, k, v: jnp.sum(f(q, k, v, causal=True) ** 2)

    g_ref = jax.grad(loss(dense_attention), argnums=(0, 1, 2))(q, k, v)
    g_got = jax.jit(jax.grad(loss(sharded), argnums=(0, 1, 2)))(qs, ks, vs)
    for a, b in zip(g_ref, g_got):
        np.testing.assert_allclose(np.asarray(b), np.asarray(a), atol=1e-4)


def _tokens(b, t, vocab, seed=0):
    rng = np.random.RandomState(seed)
    return jnp.asarray(rng.randint(0, vocab, size=(b, t)).astype(np.int32))


def test_lm_forward_shapes():
    from raydp_tpu.models import TransformerLM

    model = TransformerLM(vocab_size=64, dim=32, num_heads=2, num_layers=2,
                          attention="dense")
    tokens = _tokens(2, 16, 64)
    variables = model.init(jax.random.PRNGKey(0), tokens)
    logits = model.apply(variables, tokens)
    assert logits.shape == (2, 16, 64)
    assert logits.dtype == jnp.float32


def test_lm_ring_matches_dense_on_mesh():
    """Full model, sequence sharded over seq=4: ring attention output equals
    the dense single-device reference."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from raydp_tpu.models import TransformerLM
    from raydp_tpu.parallel import MeshSpec, make_mesh

    mesh = make_mesh(MeshSpec(data=2, seq=4))
    vocab, b, t = 64, 4, 32

    dense_model = TransformerLM(vocab_size=vocab, dim=32, num_heads=2,
                                num_layers=2, attention="dense")
    ring_model = TransformerLM(vocab_size=vocab, dim=32, num_heads=2,
                               num_layers=2, attention="ring", mesh=mesh)
    tokens = _tokens(b, t, vocab)
    variables = dense_model.init(jax.random.PRNGKey(0), tokens)

    ref = dense_model.apply(variables, tokens)

    sharded_tokens = jax.device_put(
        tokens, NamedSharding(mesh, P("data", "seq")))
    with mesh:
        got = jax.jit(ring_model.apply)(variables, sharded_tokens)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               atol=2e-4, rtol=2e-4)


def test_lm_tensor_parallel_matches_replicated():
    """Megatron-split params over tensor=2: one train step produces the same
    loss and updated params as the fully-replicated run — GSPMD inserts the
    per-block all-reduces, the math is unchanged."""
    import optax

    from raydp_tpu.models import TransformerLM, lm_loss, \
        transformer_param_rules
    from raydp_tpu.parallel import (
        MeshSpec, batch_sharding, make_mesh, param_sharding_rules,
    )

    vocab, b, t = 64, 8, 32
    model = TransformerLM(vocab_size=vocab, dim=32, num_heads=2, num_layers=2,
                          attention="dense")
    tokens = _tokens(b, t, vocab)
    params = model.init(jax.random.PRNGKey(0), tokens)["params"]
    tx = optax.sgd(1e-1)

    def one_step(mesh, rules):
        shardings_of = param_sharding_rules(mesh, rules)
        p = jax.tree.map(jax.device_put, params, shardings_of(params))
        opt = jax.tree.map(jax.device_put, tx.init(params),
                           shardings_of(tx.init(params)))
        toks = jax.device_put(tokens, batch_sharding(mesh))

        @jax.jit
        def step(p, opt, toks):
            loss, grads = jax.value_and_grad(
                lambda p_: lm_loss(model.apply({"params": p_}, toks), toks))(p)
            upd, opt = tx.update(grads, opt)
            return optax.apply_updates(p, upd), loss

        with mesh:
            new_p, loss = step(p, opt, toks)
        return new_p, float(loss)

    p_rep, l_rep = one_step(make_mesh(MeshSpec()), None)
    tp_mesh = make_mesh(MeshSpec(data=4, tensor=2))
    rules = transformer_param_rules("tensor")
    p_tp, l_tp = one_step(tp_mesh, rules)

    np.testing.assert_allclose(l_tp, l_rep, rtol=1e-5)

    flat_tp = {jax.tree_util.keystr(k): v
               for k, v in jax.tree_util.tree_flatten_with_path(p_tp)[0]}
    for k, v in jax.tree_util.tree_flatten_with_path(p_rep)[0]:
        key = jax.tree_util.keystr(k)
        np.testing.assert_allclose(np.asarray(flat_tp[key]), np.asarray(v),
                                   atol=2e-5, err_msg=key)

    # the split actually took: a q kernel holds half its heads per shard
    qkey = next(k for k in flat_tp if "attn']['q']['kernel" in k
                or "attn/q/kernel" in k)
    qarr = flat_tp[qkey]
    assert qarr.sharding.shard_shape(qarr.shape)[1] == qarr.shape[1] // 2


def test_lm_training_reduces_loss():
    import optax

    from raydp_tpu.models import TransformerLM, lm_loss

    vocab = 32
    model = TransformerLM(vocab_size=vocab, dim=64, num_heads=2, num_layers=2,
                          attention="dense")
    # learnable structure: next token = (token + 1) % vocab
    rng = np.random.RandomState(0)
    start = rng.randint(0, vocab, size=(64, 1))
    tokens = jnp.asarray((start + np.arange(24)[None, :]) % vocab,
                         dtype=jnp.int32)

    variables = model.init(jax.random.PRNGKey(0), tokens[:, :1])
    tx = optax.adam(1e-2)
    opt_state = tx.init(variables["params"])

    @jax.jit
    def step(params, opt_state, batch):
        def loss_fn(p):
            return lm_loss(model.apply({"params": p}, batch), batch)

        loss, grads = jax.value_and_grad(loss_fn)(params)
        updates, opt_state = tx.update(grads, opt_state)
        return optax.apply_updates(params, updates), opt_state, loss

    params = variables["params"]
    losses = []
    for i in range(30):
        params, opt_state, loss = step(params, opt_state, tokens)
        losses.append(float(loss))
    assert losses[-1] < 0.3 * losses[0], losses[::10]


def test_lm_loss_fused_matches_materialized():
    """The chunked fused lm_head+CE must equal the materialized-logits loss
    in value AND gradients (incl. the lm_head kernel, which only receives
    gradient through the fused path's explicit matmul)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from raydp_tpu.models import TransformerLM, lm_loss
    from raydp_tpu.models.transformer import lm_loss_fused

    vocab, T, B = 97, 37, 3  # odd sizes: exercises the chunk padding path
    model = TransformerLM(vocab_size=vocab, dim=32, num_heads=2,
                          num_layers=2, attention="dense")
    rng = np.random.RandomState(0)
    tokens = jnp.asarray(rng.randint(0, vocab, size=(B, T)), jnp.int32)
    params = jax.jit(model.init)(jax.random.PRNGKey(0), tokens)["params"]
    assert "lm_head" in params  # registered on the plain init path

    def loss_mat(p):
        return lm_loss(model.apply({"params": p}, tokens), tokens)

    def loss_fused(p):
        hidden = model.apply({"params": p}, tokens, return_hidden=True)
        return lm_loss_fused(hidden, p["lm_head"]["kernel"], tokens, chunk=16)

    v1, g1 = jax.jit(jax.value_and_grad(loss_mat))(params)
    v2, g2 = jax.jit(jax.value_and_grad(loss_fused))(params)
    np.testing.assert_allclose(float(v1), float(v2), rtol=1e-5)
    flat1 = jax.tree_util.tree_leaves_with_path(g1)
    g2_by_path = dict(jax.tree_util.tree_leaves_with_path(g2))
    for path, leaf in flat1:
        np.testing.assert_allclose(np.asarray(leaf),
                                   np.asarray(g2_by_path[path]),
                                   rtol=2e-4, atol=1e-6,
                                   err_msg=str(path))


# the fused head loss against the plain loss on materialised logits: float32
# both sides, what is left is summation order (the tolerances of the test
# above); with bfloat16 hidden states the logits are the same float32
# accumulations and the gradients differ by one rounding to bfloat16
_F32 = dict(rtol=2e-4, atol=1e-6)
_BF16_REL = 2.0 ** -8


def _head_case(dtype, B=3, T=37, D=16, V=97, seed=0):
    import jax.numpy as jnp
    import numpy as np

    rng = np.random.RandomState(seed)
    hidden = jnp.asarray(rng.randn(B, T, D), dtype)
    kernel = jnp.asarray(rng.randn(D, V) * 0.3, jnp.float32)
    tokens = jnp.asarray(rng.randint(0, V, size=(B, T)), jnp.int32)
    return hidden, kernel, tokens


def _plain_head_loss(hidden, kernel, tokens, weights):
    """Materialised float32 logits (the head's product as the model's plain
    path makes it), each row's mean cross entropy, the weighted sum."""
    import jax.numpy as jnp
    import optax

    logits = jnp.dot(hidden, kernel.astype(hidden.dtype),
                     preferred_element_type=jnp.float32)
    rows = optax.softmax_cross_entropy_with_integer_labels(
        logits[:, :-1], tokens[:, 1:]).mean(axis=1)
    return jnp.sum(weights * rows), rows


@pytest.mark.parametrize("name,weights,chunk,dtype", [
    ("uniform", [1 / 3, 1 / 3, 1 / 3], 12, "float32"),
    ("unequal", [0.5, 0.125, 0.375], 12, "float32"),
    ("pad_row", [0.5, 0.5, 0.0], 12, "float32"),      # mask / sum(mask)
    ("ragged_chunks", [0.25, 0.25, 0.5], 16, "float32"),  # 36 = 2 x 16 + 4
    ("one_chunk", [0.25, 0.25, 0.5], 1024, "float32"),    # chunk > T - 1
    ("bfloat16", [0.5, 0.125, 0.375], 12, "bfloat16"),
])
def test_fused_head_loss_value_and_gradients(name, weights, chunk, dtype):
    """``lm_head_loss`` takes its two gradients inside its forward scan
    (a custom rule, not autodiff): value, rows, the hidden states' gradient,
    the kernel's and the weights' equal ``jax.value_and_grad`` of the plain
    loss on materialised float32 logits."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from raydp_tpu.models.transformer import lm_head_loss

    hidden, kernel, tokens = _head_case(jnp.dtype(dtype))
    weights = jnp.asarray(weights, jnp.float32)
    (want, want_rows), want_g = jax.value_and_grad(
        _plain_head_loss, argnums=(0, 1, 3), has_aux=True)(
            hidden, kernel, tokens, weights)
    (got, rows), got_g = jax.value_and_grad(
        lambda h, k, w: lm_head_loss(h, k, tokens, w, chunk=chunk),
        argnums=(0, 1, 2), has_aux=True)(hidden, kernel, weights)
    np.testing.assert_allclose(float(got), float(want), rtol=1e-5)
    np.testing.assert_allclose(rows, want_rows, rtol=1e-5)
    # not differentiated: the same value from the forward product alone
    plain, plain_rows = jax.jit(
        lambda h, k, w: lm_head_loss(h, k, tokens, w, chunk=chunk))(
            hidden, kernel, weights)
    np.testing.assert_allclose(float(plain), float(want), rtol=1e-5)
    np.testing.assert_allclose(plain_rows, want_rows, rtol=1e-5)
    for g, w in zip(got_g, want_g):
        assert g.dtype == w.dtype and g.shape == w.shape
        g, w = np.asarray(g, np.float32), np.asarray(w, np.float32)
        if dtype == "bfloat16":
            assert np.abs(g - w).max() <= _BF16_REL * np.abs(w).max()
        else:
            np.testing.assert_allclose(g, w, **_F32)
    if name == "pad_row":       # a masked row carries no gradient
        assert not np.asarray(got_g[0][2]).any()
        assert np.asarray(got_g[0][0]).any()
    # the last position predicts nothing
    assert not np.asarray(got_g[0][:, -1], np.float32).any()


def _vocab_products(jaxpr, vocab, scans=()):
    """``[(enclosing scan eqns, ...)]`` of every ``dot_general`` in the
    jaxpr (and the jaxprs inside its equations) with a vocabulary-sized
    dimension among its operands' or its result's."""
    found = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "dot_general":
            shapes = [v.aval.shape for v in (*eqn.invars, *eqn.outvars)]
            if any(vocab in shape for shape in shapes):
                found.append(scans)
        inner = scans + (id(eqn),) if eqn.primitive.name == "scan" else scans
        for value in eqn.params.values():
            for sub in (value if isinstance(value, (list, tuple))
                        else (value,)):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    found += _vocab_products(sub, vocab, inner)
    return found


@pytest.mark.parametrize("differentiated,products", [(True, 3), (False, 1)])
def test_fused_head_loss_counts_its_head_products(differentiated, products):
    """Differentiated, a chunk runs the head's product three times (logits,
    the hidden states' gradient, the kernel's), all inside ONE scan: nothing
    is recomputed in a backward scan (the checkpointed scan this replaces
    had four products in two scans). Not differentiated: the one."""
    import jax
    import jax.numpy as jnp

    from raydp_tpu.models.transformer import lm_loss_fused

    hidden, kernel, tokens = _head_case(jnp.float32)
    loss = lambda h, k: lm_loss_fused(h, k, tokens, chunk=12)  # noqa: E731
    fn = jax.grad(loss, argnums=(0, 1)) if differentiated else loss
    found = _vocab_products(jax.make_jaxpr(fn)(hidden, kernel).jaxpr, 97)
    assert len(found) == products
    assert len(set(found)) == 1 and len(found[0]) == 1    # one scan holds all


def test_return_hidden_registers_head_params():
    """Init THROUGH the hidden path still creates the lm_head kernel, so a
    fused-loss training setup has the full param tree from the start."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from raydp_tpu.models import TransformerLM

    model = TransformerLM(vocab_size=64, dim=16, num_heads=2, num_layers=1,
                          attention="dense")
    tokens = jnp.asarray(np.zeros((1, 8)), jnp.int32)
    params = model.init(jax.random.PRNGKey(0), tokens,
                        return_hidden=True)["params"]
    assert params["lm_head"]["kernel"].shape == (16, 64)


# ---------------------------------------------------------------------------
# What a recomputed attention keeps of its inputs
# ---------------------------------------------------------------------------
_TINY_LM = dict(vocab_size=64, dim=32, num_heads=4, head_dim=8, num_layers=2,
                ffn_dim=48, attention="flash", init_std=0.3)
_LATENT = dict(kv_lora_rank=16, qk_nope_head_dim=8, qk_rope_head_dim=8,
               v_head_dim=8, head_dim=None)


_GROUPED = {"num_kv_heads": 2}
_ATTENTION_CASES = {
    "no_norm": {},
    "grouped": _GROUPED,
    "head_norm": dict(_GROUPED, qk_norm="head"),
    "whole_norm": {"qk_norm": True},
    "gated": dict(_GROUPED, attention_gate=True),
    "gated_head_norm_sandwich": dict(
        _GROUPED, qk_norm="head", attention_gate=True, sandwich_norms=True),
    "windowed": dict(_GROUPED, sliding_window=8, window_layers=(1, 0),
                     rope_layers=(1, 0)),
    "block_diffusion": dict(_GROUPED, qk_norm="head",
                            diffusion=BlockDiffusionSpec(4, 63)),
    "one_sublayer": dict(_GROUPED, layer_kinds="*B"),
}


def _lm(remat, **fields):
    from raydp_tpu.models import TransformerLM

    return TransformerLM(**{**_TINY_LM, **fields}, remat_blocks=remat)


def _lm_case(model, seed=0):
    import lm_testing

    tokens = np.random.default_rng(seed).integers(0, 62, (2, 16),
                                                  dtype=np.int32)
    params, state = lm_testing.variables(model, tokens, seed)
    return params, state, tokens, np.full((2,), 0.5, np.float32)


@pytest.mark.parametrize("case", list(_ATTENTION_CASES))
def test_a_block_that_keeps_its_attentions_inputs_is_the_plain_block(case):
    """A recomputed block reads q, k, v and the raw projections a head norm
    or a gate reads from what it kept, and they are the arrays the plain
    block's backward reads: the loss and every gradient leaf are the
    un-recomputed model's, whatever stands between projection and kernel
    (no norm, a head norm, a norm over the whole projection, a gate, a
    window, the block-diffusion mask, grouped K/V heads, a layer of one
    sub-layer)."""
    import lm_testing

    fields = _ATTENTION_CASES[case]
    plain, kept = _lm(False, **fields), _lm(True, **fields)
    assert plain.attention_inputs == {} and kept.attention_inputs == {
        "kept": 2}
    args = _lm_case(plain)
    (want, _), want_g = lm_testing.loss_and_grads(plain, *args)
    (got, _), got_g = lm_testing.loss_and_grads(kept, *args)
    np.testing.assert_allclose(got, want, rtol=1e-6)
    lm_testing.close(got_g, want_g, tol=1e-5)
    assert all(np.abs(leaf).max() > 0 for leaf in jax.tree.leaves(want_g))


def _head_projections(jaxpr):
    """How many ``dot_general``s of a traced program (through every
    checkpoint, loop and call it holds) multiply an activation by a kernel
    ``[D, heads, d]`` into ``[B, T, heads, d]``: the projections to heads,
    forward or run again. A backward product's result has three axes, or
    (``W_o``'s) contracts the kernel's last."""
    jaxpr, found = getattr(jaxpr, "jaxpr", jaxpr), 0
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "dot_general":
            shapes = [len(v.aval.shape) for v in (*eqn.invars, *eqn.outvars)]
            (_, kernel_axes), _ = eqn.params["dimension_numbers"]
            found += shapes == [3, 3, 4] and tuple(kernel_axes) == (0,)
        for value in eqn.params.values():
            for sub in value if isinstance(value, (list, tuple)) else (value,):
                if hasattr(getattr(sub, "jaxpr", sub), "eqns"):
                    found += _head_projections(sub)
    return found


def _backward_program(model):
    import lm_testing

    params, state, tokens, weights = _lm_case(model)
    return jax.make_jaxpr(lm_testing.loss_program(model))(
        params, state, tokens, weights)


@pytest.mark.parametrize("fields,projections,kept", [
    ({"num_kv_heads": 2}, 3, True),
    ({"num_kv_heads": 2, "qk_norm": "head", "attention_gate": True}, 4, True),
    ({"layer_kinds": "*B"}, 3, True),
    (_LATENT, 2, False),                    # q and the latent's kv_b
    ({"total_ut_steps": 2}, 3, False),
    ({"attention": "dense"}, 3, False),
], ids=["grouped", "gated_head_norm", "one_sublayer", "latent", "looped",
        "dense"])
def test_the_recomputed_backward_runs_no_projection_it_kept(
        fields, projections, kept):
    """Where the rule keeps an attention's inputs the differentiated program
    holds each projection to heads once, as the plain model's does; where it
    keeps none (latent attention, a looped stack, ``dense``) the
    recomputation runs them again, as it did, and no name of theirs is bound
    (the traced program is the one it was)."""
    from raydp_tpu.models import transformer

    plain, model = _lm(False, **fields), _lm(True, **fields)
    layers = 2 * model.total_ut_steps
    assert model.attention_inputs == {"kept" if kept else "rebuilt": layers}
    assert bool(transformer._kept_inputs(model)) == kept
    once = _head_projections(_backward_program(plain))
    assert once == 2 * projections          # a looped body is traced once
    program = _backward_program(model)
    assert _head_projections(program) == (once if kept else 2 * once)
    from raydp_tpu.ops.flash_attention import INPUT_NAMES

    bound = set(INPUT_NAMES) | set(transformer.RAW_NAMES.values())
    named = {name for name in bound if f"name={name}]" in str(program)}
    assert named == (set(transformer._kept_inputs(model)) if kept else set())
    assert not any(f"name={name}]" in str(_backward_program(plain))
                   for name in bound)


_QKV = ("rdt_flash_q", "rdt_flash_k", "rdt_flash_v")
_RAW_QK = ("rdt_attn_q_raw", "rdt_attn_k_raw")


@pytest.mark.parametrize("config,cell,inputs,counted", [
    ("olmoe-1b-7b", "olmoe_1b7b_train", None, {}),
    ("smallthinker-21b-a3b", "smallthinker_21ba3b_16k_train", _QKV,
     {"kept": 4}),
    ("trinity-mini", "trinity_mini_8k_train",
     _QKV + _RAW_QK + ("rdt_attn_gate_raw",), {"kept": 2}),
    ("kanana-2-30b-a3b", "kanana2_30ba3b_16k_train", (), {"rebuilt": 2}),
    ("nemotron-3-nano-30b-a3b", "nemotron3_nano_30ba3b_16k_train", _QKV,
     {"kept": 1}),
    ("sdar-30b-a3b-chat", "sdar_30ba3b_8k_blockdiff_train", _QKV + _RAW_QK,
     {"kept": 1}),
    ("ouro-2.6b", "ouro_2p6b_8k_train", (), {"rebuilt": 8}),
    ("lfm2-8b-a1b", "lfm2_8ba1b_8k_train", _QKV + _RAW_QK, {"kept": 1}),
])
def test_what_each_benchmark_model_keeps_of_its_attentions_inputs(
        config, cell, inputs, counted):
    """The rule, model by model, at the CPU cut of each LM cell: a model that
    is not recomputed keeps nothing by name (no policy at all); latent
    attention and a looped stack keep the parent's three names; the others
    q, k, v as the kernel takes them, the raw ``W_q u`` and ``W_k u`` under a
    head norm and the raw gate where the attention is gated."""
    import lm_testing
    from raydp_tpu.models import transformer
    from raydp_tpu.ops.flash_attention import RESIDUAL_NAMES

    model, _ = lm_testing.cut_model(config, cell)
    kept = transformer._kept(model)
    assert kept == (None if inputs is None else (
        *RESIDUAL_NAMES, transformer.SUBLAYER_OUT, *inputs))
    assert model.attention_inputs == counted


@pytest.mark.parametrize("remat,fields,counted", [
    (True, {"num_kv_heads": 2, "qk_norm": "head"}, {"kept": 2}),
    (True, {"total_ut_steps": 3}, {"rebuilt": 6}),
    (True, _LATENT, {"rebuilt": 2}),
    (False, {"num_kv_heads": 2}, {}),
], ids=["kept", "looped", "latent", "not_recomputed"])
def test_a_built_step_counts_what_becomes_of_its_attentions_inputs(
        remat, fields, counted):
    """``train_attention_inputs_total`` is bumped once a built train step,
    beside ``train_attention_forward_total``, by the attention layers the
    step executes: ``kept`` where the recomputation reads q, k and v from
    what the block kept, ``rebuilt`` where the rule keeps none, nothing where
    no layer is recomputed."""
    import lm_testing
    import optax

    model = _lm(remat, **fields)
    before = lm_testing.counters()
    lm_testing.train_step(model, optax.sgd(0.05))
    assert lm_testing.moved(before, "train_attention_inputs_total") == counted
    assert lm_testing.moved(before, "train_attention_forward_total") == {
        "once": 2 * model.total_ut_steps}
