"""The row-wise embedding-table update (raydp_tpu/train/rowwise.py,
doc/training.md): the step differentiates and updates the rows a batch looked
up, gives the dense step's results, engages only where the code can see that
it may, and leaves everything else on the step it had."""

import logging
import re

import numpy as np
import pytest

NUM_DENSE = 4
SIZES = [1000, 16, 600, 8, 300, 48]     # three tables wider than the batch
WIDE = [0, 2, 4]
B = 64
STEPS = 20


def _model(**kw):
    from raydp_tpu.models import DLRM

    return DLRM(categorical_sizes=SIZES, num_dense=NUM_DENSE, embedding_dim=8,
                bottom_mlp=(16, 8), top_mlp=(16, 1), **kw)


class _Undeclared:
    """The same model with its lookup declaration hidden: the dense step."""

    def __init__(self, model):
        self.init, self.apply = model.init, model.apply


def _batches(n=STEPS, tail_rows=None, seed=0):
    """Zipf ids, so every batch looks a row up more than once. With
    ``tail_rows`` the last batch is a pad-and-mask tail: zero rows past it."""
    import jax.numpy as jnp

    from raydp_tpu.data.feed import MASK_KEY

    rng = np.random.default_rng(seed)
    out = []
    for k in range(n):
        sparse = np.stack([np.minimum(rng.zipf(1.2, B), v) - 1
                           for v in SIZES], 1)
        assert all(len(np.unique(sparse[:, j])) < B for j in WIDE)
        feats = np.concatenate([rng.random((B, NUM_DENSE)), sparse], 1)
        batch = {"features": feats.astype(np.float32),
                 "label": rng.integers(0, 2, B).astype(np.float32)}
        if tail_rows is not None:
            real = B if k < n - 1 else tail_rows
            batch = {key: np.where(
                (np.arange(B) < real).reshape((B,) + (1,) * (a.ndim - 1)),
                a, 0).astype(a.dtype) for key, a in batch.items()}
            batch[MASK_KEY] = (np.arange(B) < real).astype(np.float32)
        out.append({key: jnp.asarray(a) for key, a in batch.items()})
    return out


def _state(model, tx, mesh=None, rules=None):
    import jax
    import jax.numpy as jnp
    from flax.training import train_state

    from raydp_tpu.models import dlrm_param_rules
    from raydp_tpu.parallel import param_sharding_rules

    class State(train_state.TrainState):
        batch_stats: object = None

    v = model.init(jax.random.PRNGKey(0), {
        "dense": jnp.zeros((1, NUM_DENSE)),
        "sparse": jnp.zeros((1, len(SIZES)), jnp.int32)})
    state = State.create(apply_fn=model.apply, params=v["params"], tx=tx,
                         batch_stats=None)
    if mesh is None:
        return state
    return jax.device_put(state, param_sharding_rules(
        mesh, rules or dlrm_param_rules("expert"))(state))


def _step(model, mesh=None, accum=1, placed=None):
    """The estimator's step. ``placed``: the state's shardings, as ``fit``
    hands them over; without them every table is walked as one."""
    from raydp_tpu.models import criteo_batch_preprocessor
    from raydp_tpu.parallel import batch_sharding
    from raydp_tpu.train.flax_estimator import (_make_apply, _make_train_step,
                                                _resolve_loss)

    apply_fn = _make_apply(model, False,
                           criteo_batch_preprocessor(NUM_DENSE), None)
    mb = (batch_sharding(mesh), None) if mesh is not None else None
    return _make_train_step(apply_fn, _resolve_loss("bce"), [], accum, "none",
                            mb_shardings=mb, state_shardings=placed)


def _run_each(step, state, batches, mesh=None):
    """The state after the batches and the loss sum after each."""
    import jax
    import jax.numpy as jnp

    from raydp_tpu.parallel import batch_sharding

    jitted = jax.jit(step)
    loss, sums = jnp.zeros(()), []
    for batch in batches:
        if mesh is not None:
            batch = jax.device_put(batch, batch_sharding(mesh))
        state, loss, _ = jitted(state, batch, (), loss)
        sums.append(float(loss))
    return state, sums


def _run(step, state, batches, mesh=None):
    state, sums = _run_each(step, state, batches, mesh)
    return state, sums[-1]


def _counts(name):
    from raydp_tpu import metrics

    return dict(metrics.snapshot()["counters"].get(name, {}))


def _since(name, before, labels):
    after = _counts(name)
    return {k: after.get(k, 0) - before.get(k, 0) for k in labels}


def _table_counts():
    return _counts("train_table_updates_total")


def _counted(before):
    return _since("train_table_updates_total", before, ("rowwise", "dense"))


@pytest.fixture
def step_log():
    """What the estimator's logger said (it does not propagate)."""
    lines = []

    class Keep(logging.Handler):
        def emit(self, record):
            lines.append(record.getMessage())

    logger = logging.getLogger("raydp_tpu.train.rowwise")
    handler = Keep(level=logging.INFO)
    logger.addHandler(handler)
    yield lines
    logger.removeHandler(handler)


def _mesh(placement):
    import jax

    from raydp_tpu.parallel import make_mesh

    if placement == "one_device":
        return None
    return make_mesh(dict(data=2, expert=2), devices=jax.devices()[:4])


# ------------------------------------------------- (a) row-wise against dense
@pytest.mark.parametrize("walk", ["one_pass", "in_passes"])
@pytest.mark.parametrize("tail", [None, 37], ids=["full", "masked_tail"])
@pytest.mark.parametrize("placement", ["one_device", "data2_expert2"])
def test_rowwise_matches_dense(placement, tail, walk, monkeypatch):
    import jax
    import optax

    from raydp_tpu.models import dlrm_param_rules
    from raydp_tpu.parallel import param_sharding_rules
    from raydp_tpu.train import rowwise

    if walk == "in_passes":
        # what a table of millions of rows gets: uniq walked in chunks, as
        # many as hold real ids (here 16 rows a pass, 2 to 4 passes a table)
        monkeypatch.setattr(rowwise, "STAGED_BYTES", 0)
        monkeypatch.setattr(rowwise, "CHUNK", 16)
    mesh = _mesh(placement)
    model = _model()
    batches = _batches(tail_rows=tail)
    tx = optax.adagrad(0.05)
    before = _table_counts()
    # on the mesh the step is told the state's shardings, as fit tells it:
    # every shard walks its own rows
    row_step = _step(model, mesh, placed=None if mesh is None else
                     param_sharding_rules(mesh, dlrm_param_rules("expert"))(
                         _state(model, tx)))
    row, row_loss = _run(row_step, _state(model, tx, mesh), batches, mesh)
    assert _counted(before) == {"rowwise": 3, "dense": 3}
    text = str(jax.make_jaxpr(row_step)(_state(model, tx), batches[0], (),
                                        0.0))
    assert ("while" in text) == (walk == "in_passes")
    dense, dense_loss = _run(_step(_Undeclared(model), mesh),
                             _state(model, tx, mesh), batches, mesh)
    assert row_loss == pytest.approx(dense_loss, rel=1e-6)
    a = jax.tree_util.tree_leaves_with_path((row.params, row.opt_state))
    b = jax.tree.leaves((dense.params, dense.opt_state))
    assert len(a) == len(b)
    for (path, x), y in zip(a, b):
        np.testing.assert_allclose(np.asarray(x), np.asarray(y), rtol=1e-5,
                                   atol=1e-7, err_msg=str(path))

    # rows no batch looked up hold the bits they were initialised with
    start = _state(model, tx)
    sparse = np.concatenate([np.asarray(b_["features"])[:, NUM_DENSE:]
                             for b_ in batches]).astype(np.int64)
    for j in WIDE:
        name = f"embedding_{j}"
        untouched = np.setdiff1d(np.arange(SIZES[j]), sparse[:, j])
        assert len(untouched) > SIZES[j] // 4
        for tree0, tree1 in ((start.params, row.params),
                             (start.opt_state[0].sum_of_squares,
                              row.opt_state[0].sum_of_squares)):
            np.testing.assert_array_equal(
                np.asarray(tree1[name]["embedding"])[untouched],
                np.asarray(tree0[name]["embedding"])[untouched])
        if mesh is not None:     # and the table is still sharded by rows
            table = row.params[name]["embedding"]
            assert table.sharding.shard_shape(table.shape)[0] == SIZES[j] // 2


# ------------------------------------------------------------ (b) the probe
def _optimizers():
    import optax

    return {
        "adagrad": (optax.adagrad(0.05), True),
        "sgd": (optax.sgd(0.1), True),
        "adam": (optax.adam(1e-2), False),
        "sgd_momentum": (optax.sgd(0.1, momentum=0.9), False),
        "adagrad_weight_decay": (optax.chain(
            optax.add_decayed_weights(1e-4), optax.adagrad(0.05)), False),
    }


@pytest.mark.parametrize("name", ["adagrad", "sgd", "adam", "sgd_momentum",
                                  "adagrad_weight_decay"])
def test_probe_decides_and_result_is_the_optimizers_own(name, step_log):
    """Whichever way the probe decides, the step gives what the parent's step
    gave: ``value_and_grad`` of the plain model and one ``tx.update`` on the
    whole tree, written out here with nothing of the estimator's."""
    import jax
    import jax.numpy as jnp
    import optax

    from raydp_tpu.models import criteo_batch_preprocessor
    from raydp_tpu.train.flax_estimator import _resolve_loss

    tx, rowwise = _optimizers()[name]
    model = _model()
    batches = _batches(6)
    before = _table_counts()
    got, _ = _run(_step(model), _state(model, tx), batches)
    assert _counted(before) == ({"rowwise": 3, "dense": 3} if rowwise
                                else {"rowwise": 0, "dense": 6})
    said = [line for line in step_log if "embedding tables" in line]
    assert len(said) == 1 and ("probe" in said[0]) == (not rowwise)

    prep, loss_fn = criteo_batch_preprocessor(NUM_DENSE), _resolve_loss("bce")

    @jax.jit
    def plain(params, opt_state, batch):
        def loss(p):
            inputs, labels = prep(batch)
            return loss_fn(model.apply({"params": p}, inputs).squeeze(-1)
                           .astype(jnp.float32), labels)

        grads = jax.grad(loss)(params)
        updates, opt_state = tx.update(grads, opt_state, params)
        return optax.apply_updates(params, updates), opt_state

    start = _state(model, tx)
    params, opt_state = start.params, start.opt_state
    for batch in batches:
        params, opt_state = plain(params, opt_state, batch)
    for x, y in zip(jax.tree.leaves((got.params, got.opt_state)),
                    jax.tree.leaves((params, opt_state))):
        np.testing.assert_allclose(np.asarray(x), np.asarray(y), rtol=1e-5,
                                   atol=1e-7)


@pytest.mark.parametrize("name,expected", [
    ("sgd_schedule", True), ("clip_then_adagrad", True), ("lars", False),
    ("rmsprop", False), ("adamw", False)])
def test_probe_on_other_transformations(name, expected):
    """The probe tries the row view itself, so it also passes what only counts
    steps (a schedule) or sums over gradients (a global-norm clip), and fails
    what reads a whole leaf's parameters (LARS) or decays a moment."""
    import optax

    from raydp_tpu.train import rowwise

    tx = {"sgd_schedule": optax.sgd(optax.linear_schedule(0.1, 0.01, 10)),
          "clip_then_adagrad": optax.chain(optax.clip_by_global_norm(1.0),
                                           optax.adagrad(0.05)),
          "lars": optax.lars(0.1), "rmsprop": optax.rmsprop(0.01),
          "adamw": optax.adamw(1e-3)}[name]
    params = _state(_model(), optax.sgd(0.1)).params
    tables = {(f"embedding_{j}", "embedding") for j in WIDE}
    assert rowwise.same_as_dense(tx, params, tables) is expected


def test_probe_leaves_no_program_behind():
    """The probe is one compiled program of the host's, gone when it has
    answered (a live program of the CPU client hides the chip's programs from
    a profiler trace: PERF.md, PR 63)."""
    import jax
    import optax

    from raydp_tpu.train import rowwise

    params = _state(_model(), optax.sgd(0.1)).params
    tables = {(f"embedding_{j}", "embedding") for j in WIDE}
    client = jax.local_devices(backend="cpu")[0].client
    live = len(client.live_executables())
    assert rowwise.same_as_dense(optax.adagrad(0.05), params, tables) is True
    assert rowwise.same_as_dense(optax.adam(1e-2), params, tables) is False
    assert len(client.live_executables()) <= live


def test_two_tables_of_one_shape_are_told_apart_by_path():
    """State leaves are matched to their parameter by path: with two tables of
    one shape and only one of them row-wise, the other's accumulator is swept
    whole (its untouched rows still accumulate nothing, but its view is the
    table) and both come out as the dense step's."""
    import jax
    import jax.numpy as jnp
    import optax

    from raydp_tpu.train import rowwise

    tx = optax.adagrad(0.1)
    params = {"a": {"embedding": jnp.ones((8, 2))},
              "b": {"embedding": jnp.ones((8, 2)) * 2}}
    state = tx.init(params)
    uniq = {("b", "embedding"): rowwise.Rows(
        jnp.asarray([1, 5, 8], jnp.int32), 2)}
    p_idx, s_idx = rowwise.index_trees(tx, params, state, uniq)
    view = rowwise.take_rows((params, state), (p_idx, s_idx))
    shapes = [x.shape for x in jax.tree.leaves(view)]
    assert shapes == [(8, 2), (3, 2), (8, 2), (3, 2)]
    new = jax.tree.map(lambda x: x + 1, view)
    back = rowwise.put_rows((params, state), new, (p_idx, s_idx))
    b = np.asarray(back[0]["b"]["embedding"])
    assert (b[[1, 5]] == 3).all() and (np.delete(b, [1, 5], 0) == 2).all()
    assert (np.asarray(back[0]["a"]["embedding"]) == 2).all()


def test_unique_rows_is_sorted_unique_and_inverts():
    import jax.numpy as jnp

    from raydp_tpu.train import rowwise

    ids = jnp.asarray([7, 3, 7, 7, 0, 3, 9, 0], jnp.int32)
    rows, inv = rowwise.unique_rows(ids, 10)
    uniq, inv = np.asarray(rows.uniq), np.asarray(inv)
    assert int(rows.count) == 4
    assert list(uniq[:4]) == [0, 3, 7, 9]
    assert (uniq[4:] >= 10).all() and len(set(uniq)) == len(uniq)
    assert (np.diff(uniq) > 0).all()
    assert (uniq[inv] == np.asarray(ids)).all()


# ------------------------------------- (c) accumulated and pipelined: dense
def test_accumulated_step_stays_dense_and_says_why(step_log):
    import optax

    model = _model()
    before = _table_counts()
    _run(_step(model, accum=2), _state(model, optax.adagrad(0.05)),
         _batches(2))
    assert _counted(before) == {"rowwise": 0, "dense": 6}
    said = [line for line in step_log if "embedding tables" in line]
    assert len(said) == 1 and "accum" in said[0]


def test_pipeline_model_stays_dense_and_says_why(step_log):
    import flax.linen as nn
    import jax
    import jax.numpy as jnp
    import optax
    from flax.training import train_state

    from raydp_tpu.parallel import make_mesh
    from raydp_tpu.train import PipelineModel
    from raydp_tpu.train.flax_estimator import (_make_pipeline_apply,
                                                _make_train_step,
                                                _resolve_loss)

    class Embed(nn.Module):
        @nn.nowrap
        def lookups(self, inputs):
            return {("table", "embedding"): inputs[:, 0]}

        @nn.compact
        def __call__(self, inputs, rows=None):
            return nn.Embed(1000, 8, name="table")(inputs[:, 0])

    class Block(nn.Module):
        @nn.compact
        def __call__(self, x):
            return x + nn.Dense(8)(x)

    class Head(nn.Module):
        @nn.compact
        def __call__(self, x):
            return nn.Dense(1)(x)

    model = PipelineModel([Block(), Block()], embed=Embed(), head=Head())
    split = lambda b: (b["features"].astype(jnp.int32), b["label"])  # noqa: E731
    batch = {"features": jnp.arange(16).reshape(16, 1) % 7,
             "label": jnp.zeros((16,))}
    params = model.init(jax.random.PRNGKey(0), split(batch)[0])["params"]

    class State(train_state.TrainState):
        batch_stats: object = None

    state = State.create(apply_fn=model.apply, params=params,
                         tx=optax.adagrad(0.05), batch_stats=None)
    mesh = make_mesh(dict(data=1), devices=jax.devices()[:1])
    apply_fn = _make_pipeline_apply(model, split, None, mesh, 2, {})
    step = _make_train_step(apply_fn, _resolve_loss("mse"), [], 1, "none")
    before = _table_counts()
    new, _, _ = jax.jit(step)(state, batch, (), jnp.zeros(()))
    assert _counted(before) == {"rowwise": 0, "dense": 1}
    said = [line for line in step_log if "embedding tables" in line]
    assert len(said) == 1 and "pipeline: embed/table/embedding" in said[0]
    assert new.params["embed"]["table"]["embedding"].shape == (1000, 8)


# ---------------------------------------- (d) a model that declares nothing
def test_undeclared_model_lowers_no_sort_unique_or_scatter():
    """An MLP's step (and any model's without a declaration) takes the branch
    it took: nothing of the row-wise path is in its jaxpr, and no table is
    counted."""
    import jax
    import jax.numpy as jnp
    import optax
    from flax.training import train_state

    from raydp_tpu.models import MLP
    from raydp_tpu.train.flax_estimator import (_make_apply, _make_train_step,
                                                _resolve_loss)

    model = MLP(features=(16, 8), use_batch_norm=False)
    batch = {"features": jnp.ones((B, 3)), "label": jnp.ones((B,))}
    params = model.init(jax.random.PRNGKey(0), batch["features"])["params"]

    class State(train_state.TrainState):
        batch_stats: object = None

    state = State.create(apply_fn=model.apply, params=params,
                         tx=optax.adagrad(0.05), batch_stats=None)
    step = _make_train_step(
        _make_apply(model, False, lambda b: (b["features"], b["label"]),
                    None), _resolve_loss("mse"), [], 1, "none")
    before = _table_counts()
    text = str(jax.make_jaxpr(step)(state, batch, (), jnp.zeros(())))
    assert _counted(before) == {"rowwise": 0, "dense": 0}
    for word in ("sort", "unique", "scatter", "gather", "cumsum"):
        assert word not in text, word

    # the row-wise step of the DLRM does hold them (the check can fail)
    dlrm = _model()
    text = str(jax.make_jaxpr(_step(dlrm))(
        _state(dlrm, optax.adagrad(0.05)), _batches(1)[0], (),
        jnp.zeros(())))
    assert "sort" in text and "scatter" in text


# ------------------------- (e) what a CPU can count in the compiled program
#: what may touch an array with more rows than the batch: the program's
#: arguments and results, the lookups and the row write-back, and what only
#: carries or re-labels a buffer. Everything else (element-wise arithmetic,
#: converts, broadcasts, copies, every collective) may not.
_MAY_HOLD_A_TABLE = {"parameter", "tuple", "get-tuple-element", "bitcast",
                     "gather", "scatter", "fusion", "while", "call",
                     "conditional", "dynamic-slice", "dynamic-update-slice"}


def _ops_on_tables(hlo: str, batch: int):
    """``{opcode: [instruction, ...]}`` of the instructions whose result or an
    operand has more than ``batch`` rows, fused computations included."""
    rows = {}
    lines = []
    for line in hlo.splitlines():
        m = re.match(r"\s*(?:ROOT )?%?([\w.\-]+) = (\(?)(\w+)\[([\d,]*)\]"
                     r".*? ([\w\-]+)\((.*)", line)
        if not m:
            continue
        name, is_tuple, _, dims, opcode, rest = m.groups()
        dims = [int(d) for d in dims.split(",") if d]
        rows[name] = 0 if is_tuple or not dims else dims[0]
        lines.append((name, opcode, rest))
    found = {}
    for name, opcode, rest in lines:
        operands = re.findall(r"%([\w.\-]+)", rest.split(")")[0])
        if max([rows[name]] + [rows.get(o, 0) for o in operands]) > batch:
            found.setdefault(opcode, []).append(name)
    return found


@pytest.mark.parametrize("declared", [True, False],
                         ids=["rowwise", "dense_for_contrast"])
def test_compiled_step_on_the_mesh_touches_no_table(declared):
    """The DLRM step compiled for the 8-device mesh (data 2 x expert 4): on a
    row-wise table's shard nothing but the lookup and the write-back runs: no
    collective, no element-wise op, no copy. The dense step fails the same
    check, so the check can see."""
    import jax
    import jax.numpy as jnp
    import optax

    from raydp_tpu.parallel import batch_sharding, make_mesh

    mesh = make_mesh(dict(data=2, expert=4))
    model = _model()
    state = _state(model, optax.adagrad(0.05), mesh)
    batch = jax.device_put(_batches(1)[0], batch_sharding(mesh))
    step = _step(model if declared else _Undeclared(model), mesh)
    before = _table_counts()
    compiled = jax.jit(step, donate_argnums=(0, 3)).lower(
        state, batch, (), jnp.zeros(())).compile()
    # every wide table's shard (75 rows and more) is wider than the batch
    assert min(SIZES[j] for j in WIDE) // 4 > B
    found = _ops_on_tables(compiled.as_text(), B)
    assert "parameter" in found
    extra = set(found) - _MAY_HOLD_A_TABLE
    if declared:
        assert _counted(before) == {"rowwise": 3, "dense": 3}
        assert not extra, {k: found[k][:3] for k in extra}
        assert len(found["scatter"]) == 6       # 3 tables + 3 accumulators
    else:
        assert _counted(before) == {"rowwise": 0, "dense": 0}
        assert extra


# ------------------------ (f) on a mesh: each shard walks its own rows
#: placement -> (mesh axes, devices, the tables' rule): rows split two ways,
#: four ways, and over two axes at once (what the role policy gives an
#: embedding no rule names on an fsdp x tensor mesh)
_PLACEMENTS = {
    "data2_expert2": (dict(data=2, expert=2), 4, ("expert", None)),
    "data2_expert4": (dict(data=2, expert=4), 8, ("expert", None)),
    "fsdp2_tensor2": (dict(fsdp=2, tensor=2), 4, (("fsdp", "tensor"), None)),
}


def _placement(name):
    import jax

    from raydp_tpu.parallel import make_mesh

    axes, n, spec = _PLACEMENTS[name]
    mesh = make_mesh(axes, devices=jax.devices()[:n])
    shards = int(np.prod([mesh.shape[a] for a in np.ravel(spec[0])]))
    return mesh, [("embedding", spec)], shards


def _ids(pattern, v, shards, rng):
    """``B`` ids into a table of ``v`` rows held in ``shards`` equal ranges
    (walked 16 ids a pass: ``uniq``'s last chunk is positions 48 to 63)."""
    per = v // shards
    if pattern == "spread":             # some for every shard, 8 repeated
        ids = rng.integers(0, v, B - 8)
        ids = np.concatenate([ids, ids[:8]])
        assert len(np.unique(ids // per)) == shards
    elif pattern == "one_shard":        # the other shards run no pass
        ids = (shards - 1) * per + rng.integers(0, per, B)
    elif pattern == "all_distinct":     # count == B: uniq holds no fill id
        ids = rng.permutation(v)[:B]
    else:                               # "pulled_back": shard 1's slice is
        first = rng.permutation(per)[:53]       # positions 53 to 59 of uniq
        ids = np.concatenate([first, per + rng.permutation(per)[:7],
                              first[:4]])
    return rng.permutation(ids)


def _batches_of(pattern, shards, n=3, seed=0):
    import jax.numpy as jnp

    rng = np.random.default_rng(seed)
    out = []
    for _ in range(n):
        sparse = np.stack([
            _ids(pattern, v, shards, rng) if j in WIDE
            else rng.integers(0, v, B) for j, v in enumerate(SIZES)], 1)
        feats = np.concatenate([rng.random((B, NUM_DENSE)), sparse], 1)
        out.append({"features": jnp.asarray(feats, jnp.float32),
                    "label": jnp.asarray(rng.integers(0, 2, B), jnp.float32)})
    return out


def _walk_counts():
    return _counts("train_table_walk_total")


def _walked(before):
    return _since("train_table_walk_total", before,
                  ("shard_local", "global"))


def _same_state(a, b):
    import jax

    a = jax.tree_util.tree_leaves_with_path((a.params, a.opt_state))
    b = jax.tree.leaves((b.params, b.opt_state))
    assert len(a) == len(b)
    for (path, x), y in zip(a, b):
        assert x.sharding == y.sharding, str(path)
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y),
                                      err_msg=str(path))


@pytest.mark.parametrize("placement", list(_PLACEMENTS))
def test_sharded_walk_moves_the_rows_numpy_moves(placement, monkeypatch):
    """The lookup and the write-back of a row-sharded table, shard by shard,
    against numpy's gather and scatter of the same rows: nothing is computed,
    so every bit is the table's or the view's, for every id pattern."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec

    from raydp_tpu.train import rowwise

    monkeypatch.setattr(rowwise, "STAGED_BYTES", 0)
    monkeypatch.setattr(rowwise, "CHUNK", 16)
    mesh, rules, shards = _placement(placement)
    sharding = NamedSharding(mesh, PartitionSpec(*rules[0][1]))
    v = SIZES[0]
    rng = np.random.default_rng(3)
    table = rng.standard_normal((v, 8)).astype(np.float32)

    @jax.jit
    def walk(table, ids, new):
        rows, _ = rowwise.unique_rows(ids, v)
        tree, idx, placed = {"t": table}, {"t": rows}, {"t": sharding}
        view = rowwise.take_rows(tree, idx, placed)
        return (rows.count, view["t"],
                rowwise.put_rows(tree, {"t": new}, idx, placed)["t"])

    for pattern in ("spread", "one_shard", "all_distinct", "pulled_back"):
        ids = _ids(pattern, v, shards, rng)
        new = rng.standard_normal((B, 8)).astype(np.float32)
        count, view, back = walk(jax.device_put(table, sharding),
                                 jnp.asarray(ids, jnp.int32), new)
        uniq = np.unique(ids)
        assert int(count) == len(uniq)
        np.testing.assert_array_equal(np.asarray(view)[:len(uniq)],
                                      table[uniq], err_msg=pattern)
        assert not np.asarray(view)[len(uniq):].any()
        want = table.copy()
        want[uniq] = new[:len(uniq)]
        np.testing.assert_array_equal(np.asarray(back), want, err_msg=pattern)
        assert back.sharding == sharding


@pytest.mark.parametrize("placement,pattern", [
    (p, i) for p in ("data2_expert2", "data2_expert4")
    for i in ("spread", "one_shard", "all_distinct", "pulled_back")
] + [("fsdp2_tensor2", "spread"), ("fsdp2_tensor2", "pulled_back")])
def test_local_walk_is_the_global_walk_bit_for_bit(placement, pattern,
                                                   monkeypatch):
    """Told the state's shardings, the step reads and writes a row-sharded
    table shard by shard, each its own slice of ``uniq``; not told, every
    shard walks all of it. Same parameters and losses, to the bit, three
    steps. (Under ``sgd(0.5)``, whose update rounds once however a compiler
    contracts the multiply and the add: with Adagrad the CPU compiler fuses
    them in one of the two programs and not in the other on some meshes, and
    one element in 8000 differs in its last bit. Adagrad's accumulators go
    through ``test_sharded_walk_moves_the_rows_numpy_moves`` to the bit and
    through ``test_rowwise_matches_dense`` on the mesh.)"""
    import optax

    from raydp_tpu.parallel import param_sharding_rules
    from raydp_tpu.train import rowwise

    monkeypatch.setattr(rowwise, "STAGED_BYTES", 0)
    monkeypatch.setattr(rowwise, "CHUNK", 16)
    mesh, rules, shards = _placement(placement)
    model, tx = _model(), optax.sgd(0.5)
    batches = _batches_of(pattern, shards)
    ids = np.asarray(batches[0]["features"])[:, NUM_DENSE + WIDE[0]]
    assert len(np.unique(ids)) == {"spread": len(np.unique(ids)),
                                   "one_shard": len(np.unique(ids)),
                                   "all_distinct": B, "pulled_back": 60
                                   }[pattern]
    if pattern == "one_shard":
        assert ids.min() >= SIZES[WIDE[0]] // shards * (shards - 1)
    placed = param_sharding_rules(mesh, rules)(_state(model, tx))

    before = _walk_counts()
    local, local_sums = _run_each(_step(model, mesh, placed=placed),
                                  _state(model, tx, mesh, rules), batches,
                                  mesh)
    assert _walked(before) == {"shard_local": 3, "global": 0}   # once a step
    before = _walk_counts()
    whole, whole_sums = _run_each(_step(model, mesh),
                                  _state(model, tx, mesh, rules), batches,
                                  mesh)
    assert _walked(before) == {"shard_local": 0, "global": 3}
    assert local_sums == whole_sums and local_sums[2] > local_sums[0] > 0
    _same_state(local, whole)
    start = _state(model, tx)
    for j in WIDE:
        table = local.params[f"embedding_{j}"]["embedding"]
        assert table.sharding.shard_shape(table.shape)[0] == SIZES[j] // shards
        assert not np.array_equal(np.asarray(table), np.asarray(
            start.params[f"embedding_{j}"]["embedding"]))


def test_only_a_table_split_by_rows_is_walked_locally(monkeypatch):
    """One mesh, three specs: rows over ``expert`` (walked shard by shard),
    columns over ``expert`` and not sharded at all (both as before). The
    step's results do not depend on which."""
    import jax
    import optax

    from raydp_tpu.parallel import param_sharding_rules
    from raydp_tpu.train import rowwise

    monkeypatch.setattr(rowwise, "STAGED_BYTES", 0)
    monkeypatch.setattr(rowwise, "CHUNK", 16)
    mesh, _, _ = _placement("data2_expert2")
    rules = [("embedding_0", ("expert", None)),
             ("embedding_2", (None, "expert")), ("embedding", ())]
    model, tx = _model(), optax.sgd(0.5)     # rounds once: see above
    batches = _batches_of("spread", 2)
    placed = param_sharding_rules(mesh, rules)(_state(model, tx))
    before = _walk_counts()
    told_step = _step(model, mesh, placed=placed)
    told, told_sums = _run_each(told_step, _state(model, tx, mesh, rules),
                                batches, mesh)
    assert _walked(before) == {"shard_local": 1, "global": 2}
    untold, untold_sums = _run_each(_step(model, mesh),
                                    _state(model, tx, mesh, rules), batches,
                                    mesh)
    assert told_sums == untold_sums
    _same_state(told, untold)
    # the lookup and the write-back of one table (sgd keeps no state)
    text = str(jax.make_jaxpr(told_step)(_state(model, tx), batches[0], (),
                                         0.0))
    assert text.count("shard_map") == 2
    assert "shard_map" not in str(jax.make_jaxpr(_step(model, mesh))(
        _state(model, tx), batches[0], (), 0.0))


@pytest.mark.parametrize("spec,shape,expected", [
    (("expert", None), (1000, 8), ("expert",)),
    ((("data", "expert"), None), (1000, 8), ("data", "expert")),
    ((("expert", "tensor"), None), (1000, 8), ("expert",)),  # tensor is 1
    (("tensor", None), (1000, 8), None),
    ((None, "expert"), (1000, 8), None),
    (("data", "expert"), (1000, 8), None),
    ((), (1000, 8), None),
    (("expert", None), (1001, 8), None),
], ids=["rows", "rows_over_two_axes", "an_axis_of_one_is_none", "axis_of_one",
        "columns", "rows_and_columns", "replicated", "uneven_rows"])
def test_row_axes_reads_the_spec(spec, shape, expected):
    from jax.sharding import NamedSharding, PartitionSpec

    from raydp_tpu.train import rowwise

    mesh, _, _ = _placement("data2_expert2")
    assert rowwise.row_axes(NamedSharding(mesh, PartitionSpec(*spec)),
                            shape) == expected
    assert rowwise.row_axes(None, shape) is None


def _computations(hlo: str):
    """``{name: [instruction line, ...]}`` of a compiled program's text."""
    comps, name = {}, None
    for line in hlo.splitlines():
        m = re.match(r"(?:ENTRY )?%?([\w.\-]+) \(.*\{\s*$", line)
        if m and not line.startswith(" "):
            name = m.group(1)
            comps[name] = []
        elif line.startswith("}"):
            name = None
        elif name:
            comps[name].append(line)
    return comps


def _run_by(comps, roots):
    """The computations ``roots`` and whatever they call."""
    todo, inside = list(roots), set()
    while todo:
        c = todo.pop()
        if c in comps and c not in inside:
            inside.add(c)
            todo += [x for line in comps[c] for x in re.findall(
                r"(?:body|condition|calls|to_apply)=%?([\w.\-]+)", line)]
    return inside


def _loops(comps, what="body|condition"):
    return [c for lines in comps.values() for line in lines
            if " while(" in line
            for c in re.findall(rf"(?:{what})=%?([\w.\-]+)", line)]


def _collectives_in_loops(hlo: str):
    """The collective instructions of the computations a ``while`` runs
    (its body and condition, and whatever they call)."""
    comps = _computations(hlo)
    inside = _run_by(comps, _loops(comps))
    assert inside
    return [line.strip() for c in inside for line in comps[c] if re.search(
        r" (all-reduce|all-gather|reduce-scatter|all-to-all"
        r"|collective-permute)(-start)?\(", line)]


def _loop_bodies_with(hlo: str, pattern: str):
    """The ``while`` bodies that hold (themselves or in what they call) an
    instruction matching ``pattern``."""
    comps = _computations(hlo)
    return {b for b in set(_loops(comps, "body")) if any(
        re.search(pattern, line) for c in _run_by(comps, [b])
        for line in comps[c])}


@pytest.mark.parametrize("told", [True, False],
                         ids=["told", "not_told_for_contrast"])
def test_compiled_local_walk_holds_no_collective_in_a_loop(told, monkeypatch):
    """The step compiled for data 2 x expert 4 with tables walked in passes:
    told the shardings, no collective sits inside a ``while`` (the shards'
    trip counts differ) and none has a table-sized operand; what crosses
    chips is batch-sized. Not told, the partitioner sums every pass's rows
    over ``expert`` inside the loop: the check can see."""
    import jax
    import jax.numpy as jnp
    import optax

    from raydp_tpu.parallel import batch_sharding, param_sharding_rules
    from raydp_tpu.train import rowwise

    monkeypatch.setattr(rowwise, "STAGED_BYTES", 0)
    monkeypatch.setattr(rowwise, "CHUNK", 16)
    mesh, rules, _ = _placement("data2_expert4")
    model, tx = _model(), optax.adagrad(0.05)
    state = _state(model, tx, mesh, rules)
    placed = param_sharding_rules(mesh, rules)(_state(model, tx))
    batch = jax.device_put(_batches(1)[0], batch_sharding(mesh))
    hlo = jax.jit(_step(model, mesh, placed=placed if told else None),
                  donate_argnums=(0, 3)).lower(
                      state, batch, (), jnp.zeros(())).compile().as_text()
    found = _ops_on_tables(hlo, B)
    assert len(found["scatter"]) == 6 and " while(" in hlo
    if told:
        assert not _collectives_in_loops(hlo)
        assert not set(found) - _MAY_HOLD_A_TABLE
    else:
        assert _collectives_in_loops(hlo)


# ------------- (f2) on a mesh: the sum over the row axes carries the real rows
def _sum_counts():
    return _counts("train_table_sum_total")


def _summed(before):
    return _since("train_table_sum_total", before, ("real_rows", "all_rows"))


def _whole_sum_view(table, uniq, sharding):
    """The parent's formulation, kept here to compare with: each shard walks
    its slice of ``uniq`` and ONE ``psum`` carries all ``B`` rows."""
    import jax
    import jax.numpy as jnp
    from jax import lax
    from jax.sharding import PartitionSpec as P

    from raydp_tpu.train import rowwise

    axes = rowwise.row_axes(sharding, table.shape)

    def shard(table, uniq):
        def visit(start, ids, out):
            own = (ids >= 0) & (ids < table.shape[0])
            got = jnp.take(table, ids, axis=0, mode="clip")
            return lax.dynamic_update_slice_in_dim(
                out, jnp.where(own[:, None], got, 0), start, axis=0)

        return lax.psum(rowwise.shard_rows(uniq, table.shape[0], axes).passes(
            table, visit, lax.pcast(
                jnp.zeros(uniq.shape + table.shape[1:], table.dtype), axes,
                to="varying")), axes)

    return jax.shard_map(shard, mesh=sharding.mesh, in_specs=(P(axes), P()),
                         out_specs=P())(table, uniq)


def _ids_with(count, v, rng, lo=0):
    """``B`` ids of which exactly ``count`` are distinct, from ``lo`` on."""
    distinct = lo + rng.permutation(v - lo)[:count]
    return rng.permutation(np.concatenate(
        [distinct, rng.choice(distinct, B - count)]))


#: case -> (placement, distinct ids of B = 64 at 16 rows a pass of the sum,
#: whether every id lies in the last shard's range, whether the table is
#: walked in passes or, under STAGED_BYTES, visited once)
_SUM_CASES = {
    "one_real_row": ("data2_expert2", 1, False, True),
    "one_below_a_pass": ("data2_expert2", 31, False, True),
    "a_whole_pass": ("data2_expert2", 32, False, True),
    "one_above_a_pass": ("data2_expert2", 33, False, True),
    "all_distinct": ("data2_expert2", B, False, True),
    "one_shard_holds_all": ("data2_expert2", 23, True, True),
    "one_shard_of_four_holds_all": ("data2_expert4", 40, True, True),
    "table_under_staged_bytes": ("data2_expert2", 23, False, False),
    "rows_over_two_axes": ("fsdp2_tensor2", 37, False, True),
}


@pytest.mark.parametrize("case", list(_SUM_CASES))
def test_bounded_sum_is_the_whole_sum_bit_for_bit(case, monkeypatch):
    """``take_rows`` told the shardings sums ``ceil(count / 16)`` passes of
    rows over the row axes. Its view is the view of one ``psum`` over all
    ``B`` rows (the parent's), of the global walk, and numpy's: the real rows
    first, zeros after them, for a parameter and its accumulator summed in
    one call."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec

    from raydp_tpu.train import rowwise

    placement, count, one_shard, in_passes = _SUM_CASES[case]
    monkeypatch.setattr(rowwise, "SUM_PASS", 16)
    monkeypatch.setattr(rowwise, "CHUNK", 16)
    if in_passes:
        monkeypatch.setattr(rowwise, "STAGED_BYTES", 0)
    mesh, rules, shards = _placement(placement)
    sharding = NamedSharding(mesh, PartitionSpec(*rules[0][1]))
    v = SIZES[0]
    rng = np.random.default_rng(count)
    table = rng.standard_normal((v, 8)).astype(np.float32)
    accum = rng.standard_normal((v, 8)).astype(np.float32)
    ids = _ids_with(count, v, rng, v // shards * (shards - 1) if one_shard
                    else 0)
    assert len(np.unique(ids)) == count

    @jax.jit
    def views(table, accum, ids):
        rows, _ = rowwise.unique_rows(ids, v)
        tree, idx = {"t": table, "a": accum}, {"t": rows, "a": rows}
        told = rowwise.take_rows(tree, idx, {"t": sharding, "a": sharding})
        return (told, rowwise.take_rows(tree, idx),
                {k: _whole_sum_view(x, rows.uniq, sharding)
                 for k, x in tree.items()})

    placed = [jax.device_put(x, sharding) for x in (table, accum)]
    text = str(jax.make_jaxpr(views)(*placed, jnp.asarray(ids, jnp.int32)))
    assert "psum" in text
    told, whole_walk, whole_sum = views(*placed, jnp.asarray(ids, jnp.int32))
    uniq = np.unique(ids)
    for k, x in (("t", table), ("a", accum)):
        got = np.asarray(told[k])
        np.testing.assert_array_equal(got[:count], x[uniq])
        assert not got[count:].any()
        np.testing.assert_array_equal(got, np.asarray(whole_sum[k]))
        # the global walk clips a fill id inside its last pass to the
        # table's last row instead of masking it: equal in the real rows
        np.testing.assert_array_equal(got[:count],
                                      np.asarray(whole_walk[k])[:count])


@pytest.mark.parametrize("placement", ["data2_expert2", "fsdp2_tensor2"])
def test_step_with_a_bounded_sum_matches_dense(placement, monkeypatch):
    """The whole step (``take_rows`` -> ``tx.update`` -> ``put_rows``) with
    the sum in passes of 16 rows against the dense step, as
    ``test_rowwise_matches_dense`` asserts it, and against the same step with
    one whole sum (a pass wider than the batch) to the bit."""
    import jax
    import optax

    from raydp_tpu.parallel import param_sharding_rules
    from raydp_tpu.train import rowwise

    monkeypatch.setattr(rowwise, "STAGED_BYTES", 0)
    monkeypatch.setattr(rowwise, "CHUNK", 16)
    mesh, rules, _ = _placement(placement)
    model, tx = _model(), optax.adagrad(0.05)
    batches = _batches(6)
    placed = param_sharding_rules(mesh, rules)(_state(model, tx))

    def run(pass_rows):
        monkeypatch.setattr(rowwise, "SUM_PASS", pass_rows)
        before = _sum_counts()
        out = _run_each(_step(model, mesh, placed=placed),
                        _state(model, tx, mesh, rules), batches, mesh)
        return out, _summed(before)

    (bounded, bounded_sums), counted = run(16)
    assert counted == {"real_rows": 3, "all_rows": 0}
    (whole, whole_sums), counted = run(256)
    assert counted == {"real_rows": 0, "all_rows": 3}
    assert bounded_sums == whole_sums
    _same_state(bounded, whole)
    dense, dense_sums = _run_each(_step(_Undeclared(model), mesh),
                                  _state(model, tx, mesh, rules), batches,
                                  mesh)
    assert bounded_sums[-1] == pytest.approx(dense_sums[-1], rel=1e-6)
    for x, y in zip(jax.tree.leaves((bounded.params, bounded.opt_state)),
                    jax.tree.leaves((dense.params, dense.opt_state))):
        np.testing.assert_allclose(np.asarray(x), np.asarray(y), rtol=1e-5,
                                   atol=1e-7)


@pytest.mark.parametrize("pass_rows", [16, 256],
                         ids=["in_passes", "one_whole_sum_for_contrast"])
def test_compiled_sum_carries_passes_not_the_batch(pass_rows, monkeypatch):
    """The step compiled for data 2 x expert 4 (a count, not a timing). With
    the sum in passes: ONE all-reduce a shard-local table, inside a loop of
    its own, whose operands are the 16 rows a pass of the parameter and of
    its Adagrad accumulator; no all-reduce anywhere carries a leaf's ``B``
    rows; and the loops of the walk (they hold the gathers and scatters)
    hold no collective still. A pass wider than the batch sums all ``B``
    rows at once and fails the same checks: they can see."""
    import jax
    import jax.numpy as jnp
    import optax

    from raydp_tpu.parallel import batch_sharding, param_sharding_rules
    from raydp_tpu.train import rowwise

    monkeypatch.setattr(rowwise, "STAGED_BYTES", 0)
    monkeypatch.setattr(rowwise, "CHUNK", 16)
    monkeypatch.setattr(rowwise, "SUM_PASS", pass_rows)
    mesh, rules, _ = _placement("data2_expert4")
    model, tx = _model(), optax.adagrad(0.05)
    state = _state(model, tx, mesh, rules)
    placed = param_sharding_rules(mesh, rules)(_state(model, tx))
    batch = jax.device_put(_batches(1)[0], batch_sharding(mesh))
    hlo = jax.jit(_step(model, mesh, placed=placed),
                  donate_argnums=(0, 3)).lower(
                      state, batch, (), jnp.zeros(())).compile().as_text()
    in_loops = _collectives_in_loops(hlo) if pass_rows < B else []
    # over ``expert``, the row axis: two groups of four of the eight devices
    over_rows = ("replica_groups={{0,1,2,3},{4,5,6,7}}",
                 "replica_groups=[2,4]<=[8]")
    whole = [line for line in hlo.splitlines()
             if re.search(r" all-reduce(-start)?\(", line)
             and any(g in line for g in over_rows)
             and f"f32[{B},8]" in line.split(" all-reduce")[0]]
    if pass_rows < B:
        assert len(in_loops) == len(WIDE)
        for line in in_loops:
            assert " all-reduce(" in line and over_rows[0] in line
            assert re.findall(r"f32\[[\d,]*\]", line.split(" all-reduce(")[0]
                              ) == [f"f32[{pass_rows},8]"] * 2
        assert not whole
        summing = _loop_bodies_with(hlo, r" all-reduce(-start)?\(")
        walking = _loop_bodies_with(hlo, r" (gather|scatter)\(")
        assert len(summing) == len(WIDE) and len(walking) == 4 * len(WIDE)
        assert not summing & walking
    else:
        assert len(whole) == 1 and whole[0].split(" all-reduce")[0].count(
            f"f32[{B},8]") == 2 * len(WIDE)


@pytest.mark.parametrize("where", ["one_device", "not_told", "told"])
def test_sum_counter_counts_the_tables_a_sum_puts_together(where,
                                                           monkeypatch):
    """``train_table_sum_total``: once a built step for every table whose
    looked-up rows a sum over mesh axes puts together; nothing on one device
    or where the step was not told the state's shardings (the global walk)."""
    import optax

    from raydp_tpu.parallel import param_sharding_rules
    from raydp_tpu.train import rowwise

    monkeypatch.setattr(rowwise, "SUM_PASS", 16)
    model, tx = _model(), optax.adagrad(0.05)
    mesh, rules, _ = (None, None, None) if where == "one_device" \
        else _placement("data2_expert2")
    placed = param_sharding_rules(mesh, rules)(_state(model, tx)) \
        if where == "told" else None
    before = _sum_counts()
    _run_each(_step(model, mesh, placed=placed),
              _state(model, tx, mesh, rules), _batches(2), mesh)
    assert _summed(before) == {"real_rows": 3 if where == "told" else 0,
                               "all_rows": 0}


# ----------------------- (f3) the de-duplication: one stacked pass of sorts
def _unique_rows_alone(ids, num_rows):
    """One table's de-duplication as the step ran it before the stacked
    pass, a ``jnp.unique`` a table: what the pass is held to, bit for bit."""
    import jax.numpy as jnp

    from raydp_tpu.train import rowwise

    b = ids.shape[0]
    uniq, inv = jnp.unique(ids, size=b, fill_value=num_rows,
                           return_inverse=True)
    real = uniq < num_rows
    uniq = jnp.where(real, uniq, num_rows + jnp.arange(b, dtype=uniq.dtype))
    return rowwise.Rows(uniq, jnp.sum(real, dtype=jnp.int32)), inv.reshape(b)


def _unique_rows_in_a_loop(tables, num_rows):
    """``rowwise.unique_rows_of`` as a loop over the tables."""
    out = {path: _unique_rows_alone(ids, num_rows[path])
           for path, ids in tables.items()}
    return ({path: rows for path, (rows, _) in out.items()},
            {path: inv for path, (_, inv) in out.items()})


def _zipf(v, rng, b=B):
    return np.minimum(rng.zipf(1.2, b), v) - 1


#: name -> (rows of each table, its ids [B] from a generator)
_DEDUP_CASES = {
    "one_table": ([1000], _zipf),
    "three_tables": ([1000, 600, 300], _zipf),
    "ten_tables": ([1000, 600, 300, 70, 65, 4096, 143091, 2_000_000, 128,
                    99], _zipf),
    "differing_rows": ([3, 100_000, 64, 65], _zipf),
    "all_equal": ([500, 80, 7], lambda v, rng: np.full(B, v - 1)),
    "all_distinct": ([500, 64, 4096], lambda v, rng: rng.permutation(v)[:B]),
    # a table's ids lie past the end of the one before it (and below the
    # next one's fill ids): tables are told apart by position, not by value
    "another_tables_range": ([40, 1000, 100_000], lambda v, rng: (
        v - 1 - rng.integers(0, max(v // 2, 1), B))),
}


def _dedup_case(name, seed=0):
    import jax.numpy as jnp

    sizes, draw = _DEDUP_CASES[name]
    rng = np.random.default_rng(seed)
    tables = {(f"t{j}", "embedding"): jnp.asarray(draw(v, rng), jnp.int32)
              for j, v in enumerate(sizes)}
    return tables, {path: v for path, v in zip(tables, sizes)}


def _same_rows(got, expected):
    rows, inv = got
    rows_, inv_ = expected
    assert list(rows) == list(rows_) and list(inv) == list(inv_)
    for path in rows:
        for a, b in ((rows[path].uniq, rows_[path].uniq),
                     (rows[path].count, rows_[path].count),
                     (inv[path], inv_[path])):
            assert a.dtype == b.dtype and a.shape == b.shape, path
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                          err_msg=str(path))
        assert rows[path].first is None


def _jitted_rows(fn, tables, num_rows):
    """``fn``'s rows computed inside one jitted program, as a step does."""
    import jax

    from raydp_tpu.train import rowwise

    paths = list(tables)

    def run(ids):
        rows, inv = fn(dict(zip(paths, ids)), num_rows)
        return [(rows[p].uniq, rows[p].count, inv[p]) for p in paths]

    out = jax.jit(run)([tables[p] for p in paths])
    return ({p: rowwise.Rows(u, c) for p, (u, c, _) in zip(paths, out)},
            {p: i for p, (_, _, i) in zip(paths, out)})


@pytest.mark.parametrize("case", list(_DEDUP_CASES))
def test_stacked_pass_is_the_per_table_pass_bit_for_bit(case):
    """``unique_rows_of``: every table's ``(uniq, count, inv)`` from one pass
    of sorts over the stacked ids is what a ``jnp.unique`` a table gave;
    ``uniq`` sorted and without repeats, its tail the table's own fill
    ids."""
    from raydp_tpu.train import rowwise

    tables, num_rows = _dedup_case(case)
    got = _jitted_rows(rowwise.unique_rows_of, tables, num_rows)
    _same_rows(got, _jitted_rows(_unique_rows_in_a_loop, tables, num_rows))
    for path, rows in got[0].items():
        uniq, count = np.asarray(rows.uniq), int(rows.count)
        assert (np.diff(uniq) > 0).all()
        assert count == len(np.unique(np.asarray(tables[path])))
        assert list(uniq[count:]) == list(num_rows[path]
                                          + np.arange(count, B))
        assert (uniq[np.asarray(got[1][path])]
                == np.asarray(tables[path])).all()


@pytest.mark.parametrize("placement,case", [
    ("data2_expert2", "three_tables"), ("data2_expert2", "ten_tables"),
    ("fsdp2_tensor2", "ten_tables"), ("fsdp2_tensor2", "one_table"),
    ("data2_expert4", "ten_tables"), ("data2_expert4", "all_distinct"),
    ("data2_expert4", "another_tables_range")])
def test_pass_on_a_mesh_is_the_one_chip_pass_bit_for_bit(placement, case):
    """The ids as a step on a mesh meets them, a batch's, split over the
    batch axes: every chip runs the pass whole and holds the arrays one chip
    computes, bit for bit."""
    import jax

    from raydp_tpu.parallel import batch_sharding
    from raydp_tpu.train import rowwise

    mesh, _, _ = _placement(placement)
    tables, num_rows = _dedup_case(case)
    alone = _jitted_rows(rowwise.unique_rows_of, tables, num_rows)
    on_mesh = _jitted_rows(rowwise.unique_rows_of,
                           jax.device_put(tables, batch_sharding(mesh)),
                           num_rows)
    _same_rows(on_mesh, alone)


def test_one_table_is_the_one_table_case_of_the_pass():
    """``unique_rows`` is ``unique_rows_of`` of one table: one trace of the
    same body, no collective and no ``shard_map`` in it."""
    import jax

    from raydp_tpu.train import rowwise

    tables, num_rows = _dedup_case("one_table")
    (path, ids), = tables.items()
    _same_rows(_jitted_rows(
        lambda t, n: tuple({path: x} for x in rowwise.unique_rows(
            t[path], n[path])), tables, num_rows),
        _jitted_rows(rowwise.unique_rows_of, tables, num_rows))
    text = str(jax.make_jaxpr(
        lambda i: rowwise.unique_rows(i, num_rows[path])[1])(ids))
    assert "shard_map" not in text and "all_gather" not in text
    assert text.count("sort[") == 3


@pytest.mark.parametrize("placement", ["data2_expert2", "fsdp2_tensor2",
                                       "data2_expert4"])
def test_step_with_the_stacked_pass_matches_dense_and_the_loop(placement,
                                                               monkeypatch):
    """Whole Adagrad steps on a mesh with the de-duplication as one stacked
    pass against the dense step, and against the same step built with a
    ``jnp.unique`` a table: the same state and losses, to the bit."""
    import jax
    import optax

    from raydp_tpu.parallel import param_sharding_rules
    from raydp_tpu.train import rowwise

    monkeypatch.setattr(rowwise, "STAGED_BYTES", 0)
    monkeypatch.setattr(rowwise, "CHUNK", 16)
    mesh, rules, _ = _placement(placement)
    model, tx = _model(), optax.adagrad(0.05)
    batches = _batches(6)
    placed = param_sharding_rules(mesh, rules)(_state(model, tx))

    stacked, stacked_sums = _run_each(_step(model, mesh, placed=placed),
                                  _state(model, tx, mesh, rules), batches,
                                  mesh)
    monkeypatch.setattr(rowwise, "unique_rows_of", _unique_rows_in_a_loop)
    loop, loop_sums = _run_each(_step(model, mesh, placed=placed),
                                _state(model, tx, mesh, rules), batches, mesh)
    assert stacked_sums == loop_sums
    _same_state(stacked, loop)
    dense, dense_sums = _run_each(_step(_Undeclared(model), mesh),
                                  _state(model, tx, mesh, rules), batches,
                                  mesh)
    assert stacked_sums[-1] == pytest.approx(dense_sums[-1], rel=1e-6)
    for x, y in zip(jax.tree.leaves((stacked.params, stacked.opt_state)),
                    jax.tree.leaves((dense.params, dense.opt_state))):
        np.testing.assert_allclose(np.asarray(x), np.asarray(y), rtol=1e-5,
                                   atol=1e-7)


def test_one_device_step_is_the_loops_step_bit_for_bit(monkeypatch):
    """On one device the stacked pass against a ``jnp.unique`` a table:
    twenty Adagrad steps, the same state and losses to the bit."""
    import optax

    from raydp_tpu.train import rowwise

    model, tx = _model(), optax.adagrad(0.05)
    batches = _batches()
    stacked, stacked_sums = _run_each(_step(model), _state(model, tx),
                                      batches)
    monkeypatch.setattr(rowwise, "unique_rows_of", _unique_rows_in_a_loop)
    loop, loop_sums = _run_each(_step(model), _state(model, tx), batches)
    assert stacked_sums == loop_sums
    _same_state(stacked, loop)


def _sorts(hlo: str):
    """The shapes a compiled program's ``sort`` instructions sort."""
    return [re.match(r"\(?(\w+\[[\d,]*\])", line.split(" = ")[1]).group(1)
            for line in hlo.splitlines() if re.search(r" sort\(", line)]


@pytest.mark.parametrize("where,built", [
    ("one_device", "stacked"), ("data2_expert2", "stacked"),
    ("data2_expert4", "stacked"), ("data2_expert2", "loop_for_contrast")])
def test_compiled_step_sorts_the_tables_ids_together(where, built,
                                                     monkeypatch):
    """The compiled step (a count, not a timing) de-duplicates the ids of its
    three row-wise tables in ONE stacked pass: three ``sort`` instructions
    (the ids with their positions, the ranks back by position, the firsts
    alone), each of ``[3, B]``, on one device and on every chip of a mesh; no
    sort of a ``[B]`` operand is left, and the pass holds no gather and no
    scatter. Built with a ``jnp.unique`` a table it sorts ``[B]`` three times
    and gathers and scatters under no scope: the check can see."""
    import jax
    import jax.numpy as jnp
    import optax

    from raydp_tpu.parallel import batch_sharding, param_sharding_rules
    from raydp_tpu.train import rowwise

    if built != "stacked":
        monkeypatch.setattr(rowwise, "unique_rows_of",
                            _unique_rows_in_a_loop)
    model, tx = _model(), optax.adagrad(0.05)
    if where == "one_device":
        mesh, placed = None, None
        state, batch = _state(model, tx), _batches(1)[0]
    else:
        mesh, rules, _ = _placement(where)
        state = _state(model, tx, mesh, rules)
        placed = param_sharding_rules(mesh, rules)(_state(model, tx))
        batch = jax.device_put(_batches(1)[0], batch_sharding(mesh))
    hlo = jax.jit(_step(model, mesh, placed=placed),
                  donate_argnums=(0, 3)).lower(
                      state, batch, (), jnp.zeros(())).compile().as_text()
    moved = [line for line in hlo.splitlines() if "/table_dedup/" in line
             and re.search(r" (gather|scatter)\(", line)]
    assert not moved
    if built != "stacked":
        assert _sorts(hlo) == [f"s32[{B}]"] * len(WIDE)
        assert "/table_dedup/" not in hlo
        return
    assert _sorts(hlo) == [f"s32[{len(WIDE)},{B}]"] * 3
    assert "/table_dedup/" in hlo


# ------------------------------- (g) a dense fit's checkpoint, row-wise step
def test_dense_checkpoint_resumes_under_the_rowwise_step(tmp_path):
    """One tree: what a dense step (here: accumulated) saved restores into the
    state a row-wise step runs on, and the next step from it is the dense
    step's."""
    import jax
    import optax

    from raydp_tpu.parallel import make_mesh, param_sharding_rules
    from raydp_tpu.train import checkpoint as ckpt

    model = _model()
    tx = optax.adagrad(0.05)
    batches = _batches(5)
    dense, _ = _run(_step(model, accum=2), _state(model, tx), batches[:4])
    ckpt.save(str(tmp_path), dense, step=0)

    template = _state(model, tx)
    shardings = param_sharding_rules(
        make_mesh(dict(data=1), devices=jax.devices()[:1]), None)(template)
    restored, step = ckpt.restore_placed(str(tmp_path), template, shardings)
    assert step == 0
    assert jax.tree.structure((restored.params, restored.opt_state)) \
        == jax.tree.structure((dense.params, dense.opt_state))
    before = _table_counts()
    row, _ = _run(_step(model), restored, batches[4:])
    assert _counted(before) == {"rowwise": 3, "dense": 3}
    again, _ = _run(_step(_Undeclared(model)), dense, batches[4:])
    for x, y in zip(jax.tree.leaves((row.params, row.opt_state)),
                    jax.tree.leaves((again.params, again.opt_state))):
        np.testing.assert_allclose(np.asarray(x), np.asarray(y), rtol=1e-6,
                                   atol=1e-7)


# ------------------------------------------------- through the estimator
def test_fit_engages_from_the_model_and_optimizer_alone(session, step_log):
    """An ordinary ``fit_on_frame``: no knob, no argument. The DLRM with
    Adagrad trains its wide tables row-wise; the forward gathers float32 rows
    and casts the rows, so a bf16 model's parameters stay float32."""
    import jax.numpy as jnp
    import optax
    import pandas as pd

    from raydp_tpu.models import criteo_batch_preprocessor
    from raydp_tpu.train import FlaxEstimator

    rng = np.random.RandomState(0)
    n = 4 * B
    data = {"_c0": rng.randint(0, 2, n).astype(np.float64)}
    for i in range(1, NUM_DENSE + 1):
        data[f"_c{i}"] = rng.random_sample(n)
    for j, vocab in enumerate(SIZES):
        data[f"_c{NUM_DENSE + 1 + j}"] = np.minimum(
            rng.zipf(1.2, n), vocab) - 1
    df = session.createDataFrame(pd.DataFrame(data), num_partitions=2)
    est = FlaxEstimator(
        model=_model(dtype=jnp.bfloat16), optimizer=optax.adagrad(0.05),
        loss="bce_with_logits",
        feature_columns=[f"_c{i}" for i in range(1, NUM_DENSE + 1 + len(SIZES))],
        label_column="_c0", feature_dtype=np.float64, batch_size=B,
        num_epochs=2, batch_preprocessor=criteo_batch_preprocessor(NUM_DENSE))
    before = _table_counts()
    result = est.fit_on_frame(df)
    assert _counted(before) == {"rowwise": 3, "dense": 3}
    assert result.history[-1]["train_loss"] < result.history[0]["train_loss"]
    table = result.state.params["embedding_0"]["embedding"]
    assert table.dtype == jnp.float32 and table.shape == (SIZES[0], 8)
    assert any("3 of 6 declared update row-wise" in line and "shape" in line
               for line in step_log)


@pytest.mark.parametrize("name,label", [
    ("train_table_updates_total", "path"), ("train_table_walk_total", "path"),
    ("train_table_sum_total", "carries")])
def test_counter_is_registered_and_documented(name, label):
    import os

    from raydp_tpu import metrics

    m = metrics.METRICS[name]
    assert (m.kind, m.label) == (metrics.COUNTER, label)
    doc = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "doc", "observability.md")
    with open(doc) as fh:
        assert f"| `{name}` | counter |" in fh.read()
