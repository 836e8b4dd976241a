"""What the LM family test files (``test_moe_lm``, ``test_mla_moe_lm``,
``test_swa_moe_lm``, ``test_afmoe_lm``, ``test_ssm_moe_lm``,
``test_blockdiff_moe_lm``) share: the tiny cut of a configuration's files,
seeded tokens and parameters, the comparison of two trees, the estimator's
train step round a model, and the jitted forms of a whole model's forward and
loss. A new family's test file imports this module and runs whole models
through ``variables``, ``logits`` and ``loss_and_grads``: a model is traced
once and run as one program, never op by op (an eager ``model.init`` or
``model.apply`` of a tiny model is 10-20 s of dispatch on the CPU).
"""

import copy
import functools
import json
import os

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
# float32 against float32-highest: what is left is summation order (a sorted
# grouped product against a dense masked one). A bfloat16 router moves
# near-tied top-k choices and a bfloat16 loss rounds at 2**-8: either fails
# this by orders of magnitude.
F32_TOL = 2e-5
# options of the program alone: no reference reads them
PROGRAM_ONLY = ("remat_blocks", "attention", "compute_dtype")


def files(config, tiny, **changed):
    """(configuration cut to ``tiny`` and ``changed``, pipeline, reference) of
    ``chipbench/{configs,pipelines,reference}/<config>``; a dict among the
    new values is merged into the configuration's own."""
    from chipbench import manifest
    cfg = manifest.load_json(ROOT, "configs", f"{config}.json")
    cfg["input"] = dict(cfg["input"], eos_id=63)
    for key, value in [*copy.deepcopy(tiny).items(), *changed.items()]:
        cfg[key] = dict(cfg[key], **value) if isinstance(value, dict) \
            else value
    return (cfg, manifest.load_module(ROOT, "pipelines", f"{config}.py"),
            manifest.load_module(ROOT, "reference", f"{config}.py"))


def tokens(cfg, rows, seed=0, pipeline=None):
    """``rows`` seeded rows of the vocabulary rows held: uniform, or what
    ``pipeline`` generates for the configuration."""
    length = cfg["seq_len"] if "seq_len" in cfg \
        else cfg["max_position_embeddings"]
    if pipeline is not None:
        col = pipeline.generate(rows, seed, cfg)["tokens"].combine_chunks()
        return col.flatten().to_numpy().reshape(rows, length)
    return np.random.default_rng(seed).integers(
        0, cfg.get("vocab_rows_held", cfg["vocab_size"]), (rows, length),
        dtype=np.int32)


def leaves(tree):
    import jax
    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    return {"/".join(str(getattr(k, "key", getattr(k, "name", k)))
                     for k in path): np.asarray(v) for path, v in flat}


def close(got, want, tol=10 * F32_TOL):
    got, want = leaves(got), leaves(want)
    assert set(got) == set(want)
    for name, g in got.items():
        scale = max(np.abs(want[name]).max(), 1e-3)
        assert np.abs(g - want[name]).max() <= tol * scale, name


@functools.lru_cache(maxsize=None)
def _initialised(model, shape, seed):
    """``model.init`` as one program (as ``FlaxEstimator`` runs it: the
    jitted sampler differs from the eager one in the last bit), once a
    worker for a model, an input's shape and a seed."""
    import jax
    return jax.jit(model.init)(jax.random.PRNGKey(seed),
                               np.zeros(shape, np.int32))


def variables(model, tokens, seed=0, bias_std=0.0):
    """A copy of the caller's own of the seeded parameters and the routing
    state (None where the model has none): with ``bias_std`` seeded non-zero
    biases, and the state-space layers' 1-D parameters moved off their round
    initial values."""
    import jax
    from raydp_tpu.models.moe import STATE
    v = jax.tree.map(np.array, _initialised(model, (1,) + tokens.shape[1:],
                                            seed))
    rng = np.random.default_rng(seed)
    for block in v.get(STATE, {}).values():
        block["moe"]["bias"] = rng.normal(
            0, bias_std, block["moe"]["bias"].shape).astype(np.float32)
    for block in v["params"].values():
        for name in ("D", "norm", "conv_bias") if "ssm" in block else ():
            leaf = block["ssm"][name]
            block["ssm"][name] = (leaf + rng.normal(
                0, 0.2, leaf.shape)).astype(np.float32)
    return v["params"], v.get(STATE)


def _collections(params, state):
    return {"params": params} if state is None else {
        "params": params, "batch_stats": state}


def logits(model, variables, tokens):
    """The model's plain call, as one program."""
    import jax
    return jax.jit(model.apply)(variables, tokens)


def loss_program(model):
    """``(params, state, tokens, weights) -> ((loss, counts), gradients)`` of
    the model's own ``loss_rows``, jitted."""
    import jax
    return jax.jit(jax.value_and_grad(
        lambda p, state, t, w: model.apply(
            _collections(p, state), t, t, w, method=model.loss_rows),
        has_aux=True))


def loss_and_grads(model, params, state, tokens, weights):
    return loss_program(model)(params, state, tokens, weights)


@functools.lru_cache(maxsize=None)
def _reference_program(config, cfg_json, name, grad):
    import jax
    from chipbench import manifest
    fn = getattr(manifest.load_module(ROOT, "reference", f"{config}.py"), name)
    cfg = json.loads(cfg_json)
    call = lambda *arrays: fn(*arrays, cfg)  # noqa: E731
    return jax.jit(jax.value_and_grad(call) if grad else call)


def reference_program(config, cfg, name, grad=False):
    """The reference's ``name(*arrays, cfg)`` as one program (``grad``: its
    value and its gradient by the first array): built once a worker for a
    configuration, whatever the program's own options, so the cases of a
    parametrised test share it."""
    return _reference_program(config, json.dumps(
        {k: v for k, v in cfg.items() if k not in PROGRAM_ONLY},
        sort_keys=True), name, grad)


def train_step(model, tx, accum=1, seed=0):
    """The estimator's own train step round the model (not yet jitted), a
    state for it, and its arguments."""
    from flax.training import train_state
    from raydp_tpu.train.flax_estimator import _make_apply, _make_train_step
    from raydp_tpu.train.metrics import model_counters

    class State(train_state.TrainState):
        batch_stats: object = None

    apply_fn = _make_apply(model, False, lambda b: (b["tokens"], b["tokens"]),
                           None)
    metrics = model_counters(model)
    step = _make_train_step(apply_fn, None, metrics, accum, "none", seed=seed)

    def create(params, state=None):
        return State.create(apply_fn=model.apply, params=params, tx=tx,
                            batch_stats=state)

    def arguments(state, tokens):
        return (state, {"tokens": tokens}, tuple(m.init() for m in metrics),
                np.float32(0))
    return step, create, arguments


def counters():
    from raydp_tpu import metrics
    return copy.deepcopy(metrics.snapshot()["counters"])


def moved(before, name):
    """What counter ``name`` gained since ``before = counters()``, by label;
    a label that gained nothing (another test's, earlier in this process)
    is left out."""
    was = before.get(name, {})
    return {k: v - was.get(k, 0) for k, v in counters().get(name, {}).items()
            if v != was.get(k, 0)}


def token_frame(session, tmp_path, cfg, pipeline, rows, seed):
    """(persisted frame, the ETL's info, the table) of ``rows`` generated
    rows written as two parquet files and read through the pipeline's ETL."""
    import pyarrow.parquet as pq
    path = str(tmp_path / "tokens")
    os.makedirs(path)
    table = pipeline.generate(rows, seed, cfg)
    for i in range(2):
        pq.write_table(table.slice(i * rows // 2, rows // 2),
                       os.path.join(path, f"part-{i}.parquet"))
    wl = {"seq_len": cfg["max_position_embeddings"]}
    df, info = pipeline.etl(session.read.parquet(path), cfg, wl)
    return df.persist(), info, table


def estimator(cfg, pipeline, info, mesh, **fit):
    from raydp_tpu.train import FlaxEstimator
    return FlaxEstimator(
        model=pipeline.build_model(cfg, mesh), loss=None,
        optimizer=pipeline.build_optimizer(cfg), mesh=mesh,
        columns_spec={"tokens": (info["tokens"], np.int32)},
        batch_preprocessor=lambda b: (b["tokens"], b["tokens"]),
        shuffle=False, seed=0, **fit)


def cut_model(config, cell):
    """(the model, the workload) of a configuration's CPU cut of a cell."""
    from chipbench import manifest
    cfg = manifest.load_json(ROOT, "configs", f"{config}.json")
    pipeline = manifest.load_module(ROOT, "pipelines", f"{config}.py")
    wl = manifest.load_json(ROOT, "workloads", f"{cell}.json")
    pipeline.cpu_cut(cfg, wl, 1)
    return pipeline.build_model(cfg), wl


def step_text(config, cell):
    """(model, the StableHLO text of the estimator's train step, the shapes
    of the parameters) of a configuration's CPU cut of a cell."""
    import jax
    import optax
    model, wl = cut_model(config, cell)
    tokens = np.zeros((1, wl["seq_len"]), np.int32)
    shapes = jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0),
                                               tokens[:, :8]))
    step, create, arguments = train_step(model, optax.sgd(0.05))
    state = jax.eval_shape(lambda: create(
        jax.tree.map(lambda s: np.zeros(s.shape, s.dtype), shapes["params"]),
        jax.tree.map(lambda s: np.zeros(s.shape, s.dtype),
                     shapes.get("batch_stats"))))
    return (model, jax.jit(step).lower(*arguments(state, tokens)).as_text(),
            shapes["params"])
