"""FlaxEstimator: the TorchEstimator-parity trainer, pjit-compiled for TPU.

Parity map (reference torch/estimator.py):

- model/optimizer/loss as instances **or** creator callables (177-220) — here a
  Flax module (or creator), an optax transformation (or creator), and a loss
  callable or name.
- ``fit``: per-epoch train/evaluate loops with metric reporting (272-310) — here
  one jitted SPMD step; the DDP wrap + allreduce (243) is replaced by sharding
  annotations: batch sharded over the mesh's data axes, params replicated (or
  fsdp-sharded), XLA inserting the gradient ``psum`` over ICI.
- rank-0 checkpoint per epoch via Ray Train Checkpoint (259-270) — here orbax,
  saved by process 0.
- ``fit(..., max_retries)`` / ``FailureConfig`` (312-356) — here the epoch loop
  resumes from the last orbax checkpoint on failure, which is *stronger* than the
  reference's replay-from-scratch (SURVEY.md §5 checkpoint/resume gap).
- ``fit_on_spark`` with object-store or parquet-spill conversion and optional
  ``stop_spark_after_conversion`` + ownership transfer (358-390) —
  ``fit_on_frame`` below mirrors all three.
"""

from __future__ import annotations

import functools
import tempfile
import time
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple, Union

import numpy as np

from raydp_tpu import knobs, profiler
from raydp_tpu import metrics as rdt_metrics
from raydp_tpu.log import get_logger
from raydp_tpu.train import loop
from raydp_tpu.train.estimator import (
    EstimatorInterface,
    FrameEstimatorInterface,
)
from raydp_tpu.train.metrics import (Metric, build_metrics,
                                     model_counters)

logger = get_logger("train.flax_estimator")


@dataclass
class TrainingResult:
    state: Any
    history: List[Dict[str, float]] = field(default_factory=list)
    checkpoint_dir: Optional[str] = None

    @property
    def final_metrics(self) -> Dict[str, float]:
        return self.history[-1] if self.history else {}


def _takes_train(model) -> bool:
    """Does the module's __call__ accept a ``train`` kwarg (dropout/BN mode)?
    Shared by the train loop and predict so both pass the same kwargs."""
    import inspect

    try:
        return "train" in inspect.signature(type(model).__call__).parameters
    except (TypeError, ValueError):
        return False


def _init_variables(model, rng, inputs0):
    """``model.init`` as ONE jitted program, for every path that builds a
    state from nothing (a fresh fit, the rebuild after a failure with no
    checkpoint, the online fit): an eager init dispatches the samplers op by
    op, and the two forms differ in the parameters' last bit, so all three
    take this one."""
    import jax

    kwargs = {"train": False} if _takes_train(model) else {}
    return jax.jit(lambda key, x: model.init(key, x, **kwargs))(rng, inputs0)


@functools.lru_cache(maxsize=None)
def _state_class():
    """``TrainState`` with the collection a model with BatchNorm carries
    beside its params."""
    from flax.training import train_state

    class _State(train_state.TrainState):
        batch_stats: Any = None

    return _State


def _init_state(model, tx, rng, inputs0):
    """A state from nothing: :func:`_init_variables` under the optimizer."""
    variables = _init_variables(model, rng, inputs0)
    return _state_class().create(
        apply_fn=model.apply, params=variables["params"], tx=tx,
        batch_stats=variables.get("batch_stats"))


def _cast_floating(inputs, dtype):
    """Cast the floating leaves of a batch pytree to the compute dtype —
    THE cast policy, shared by the train loop and predict."""
    if dtype is None:
        return inputs
    import jax
    import jax.numpy as jnp

    return jax.tree.map(
        lambda a: a.astype(dtype)
        if jnp.issubdtype(a.dtype, jnp.floating) else a, inputs)


def _masked_mean(x, mask):
    """Mean of ``x`` over REAL rows only: per-row reduce the non-batch dims,
    then weight by the 0/1 mask. ``mask=None`` is a plain mean — bit-for-bit
    the pre-mask loss, so unpadded feeds are untouched."""
    import jax.numpy as jnp

    if mask is None:
        return jnp.mean(x)
    if x.ndim > 1:
        x = jnp.mean(x, axis=tuple(range(1, x.ndim)))
    return jnp.sum(x * mask) / jnp.maximum(jnp.sum(mask), 1.0)


def _mean_weights(mask, rows: int):
    """The rows' weights under which a weighted SUM over rows is
    :func:`_masked_mean`: ``mask / max(sum(mask), 1)``, or ``1 / rows`` each
    without a mask. What a model that brings its own loss is handed."""
    import jax.numpy as jnp

    if mask is None:
        return jnp.full((rows,), 1.0 / rows, jnp.float32)
    mask = mask.astype(jnp.float32)
    return mask / jnp.maximum(jnp.sum(mask), 1.0)


def _resolve_loss(loss) -> Callable:
    import jax.numpy as jnp

    if callable(loss):
        return loss
    name = (loss or "mse").lower()

    # every named loss is elementwise-then-_masked_mean so a pad-and-mask
    # feed's zero rows contribute nothing (mask=None reduces identically
    # to the plain mean)
    def mse(preds, labels, mask=None):
        return _masked_mean((preds - labels) ** 2, mask)

    def mae(preds, labels, mask=None):
        return _masked_mean(jnp.abs(preds - labels), mask)

    def smooth_l1(preds, labels, beta=1.0, mask=None):
        # parity: the reference's NYCTaxi example trains with SmoothL1Loss
        # (examples/pytorch_nyctaxi.py:69-105)
        d = jnp.abs(preds - labels)
        return _masked_mean(jnp.where(d < beta, 0.5 * d * d / beta,
                                      d - 0.5 * beta), mask)

    def bce_with_logits(logits, labels, mask=None):
        return _masked_mean(jnp.clip(logits, 0) - logits * labels
                            + jnp.log1p(jnp.exp(-jnp.abs(logits))), mask)

    def softmax_cross_entropy(logits, labels, mask=None):
        import optax
        return _masked_mean(optax.softmax_cross_entropy_with_integer_labels(
            logits, labels.astype(jnp.int32)), mask)

    table = {"mse": mse, "l2": mse, "mae": mae, "l1": mae,
             "smooth_l1": smooth_l1, "huber": smooth_l1,
             "bce": bce_with_logits, "bce_with_logits": bce_with_logits,
             "cross_entropy": softmax_cross_entropy}
    if name not in table:
        raise ValueError(f"unknown loss {name!r}; have {sorted(table)}")
    return table[name]


def _loss_takes_mask(loss) -> bool:
    """Can this loss spec weight out padded rows? Named losses all can; a
    user callable must accept a ``mask`` kwarg — otherwise the feed falls
    back to dropping the tail (never silently mis-averaging pad zeros)."""
    if not callable(loss):
        return True
    import inspect

    try:
        return "mask" in inspect.signature(loss).parameters
    except (TypeError, ValueError):
        return False


def _strip_mask(batch):
    """Split the feed's validity mask off a batch dict (None when the feed
    is not padding) — model/preprocessor code never sees the mask key."""
    from raydp_tpu.data.feed import MASK_KEY

    mask = batch.get(MASK_KEY)
    if mask is None:
        return batch, None
    return {k: v for k, v in batch.items() if k != MASK_KEY}, mask


def _update_metric(m, stats, preds, labels, mask):
    """Metric update with the mask passed ONLY when one exists: builtin
    metrics take it; a custom Metric without mask support keeps working on
    unpadded feeds and fails loudly (not silently wrong) on padded ones."""
    if mask is None:
        return m.update(stats, preds, labels)
    return m.update(stats, preds, labels, mask=mask)


def _make_apply(model, takes_train, split_batch, compute_dtype):
    """Build THE forward used by fit's train/eval steps and partial_fit —
    one source for the split/cast/mutable-batch-stats/squeeze policy, so the
    online twin cannot drift from the epoch loop.

    Returns ``apply_fn(params, bstats, batch, train, rows=None, mask=None)
    -> (preds_f32, labels, new_bstats)``. Where the model declares its lookups
    (``raydp_tpu/train/rowwise.py``), ``apply_fn.lookups(batch)`` gives them
    and ``rows`` hands the forward the rows the step already gathered.

    A model that declares ``loss_rows(inputs, labels, weights)`` hands the
    step its loss itself (``apply_fn.model_loss``): the forward calls that
    method in place of ``__call__``, with the weights under which a sum over
    rows is the step's mean over the real rows (:func:`_mean_weights` of the
    batch's ``mask``; no other model's forward reads the mask), and
    ``preds`` is what it returns, the pair (loss, what
    ``model.loss_counters`` counts) with ``loss`` the scalar the step
    differentiates (:func:`_step_loss`). With the weights in hand a model
    can take gradients inside its forward (a language model's fused head
    loss does), and its ``[B, T, vocab]`` logits need never exist; called
    plainly the model still returns them.

    A model with ``after_step(state)`` moves state of its own outside the
    gradient (``apply_fn.after_step``): the train step hands it the mutable
    collection it carries once an optimizer step, after the gradients are
    applied and after every micro-batch's forward has written to it, and
    carries on what it returns (a language model's routing bias:
    ``models/transformer.py``).

    A model that names random streams (``rng_streams``: a block-diffusion
    language model's ``diffusion``) is handed them as flax's ``rngs=``, each
    folded from the ``rng`` the train step passes (a new key each optimizer
    step: :func:`_make_train_step`); without ``rng`` (evaluation, ``predict``)
    and for every other model the forward is called as it was."""
    import jax
    import jax.numpy as jnp

    model_loss = callable(getattr(model, "loss_rows", None))
    streams = tuple(getattr(model, "rng_streams", None) or ())

    def apply_fn(params, bstats, batch, train: bool, rows=None, mask=None,
                 rng=None):
        inputs, labels = split_batch(batch)
        inputs = _cast_floating(inputs, compute_dtype)
        variables = {"params": params}
        args = (inputs,)
        kwargs = {"train": train} if takes_train else {}
        if rows is not None:
            kwargs["rows"] = rows
        if streams and rng is not None:
            kwargs["rngs"] = {name: jax.random.fold_in(rng, i)
                              for i, name in enumerate(streams)}
        if model_loss:
            args = (inputs, labels, _mean_weights(mask, labels.shape[0]))
            kwargs["method"] = model.loss_rows
        if bstats is not None:
            variables["batch_stats"] = bstats
            if train:
                preds, updates = model.apply(
                    variables, *args, mutable=["batch_stats"], **kwargs)
                new_bstats = updates["batch_stats"]
            else:
                preds = model.apply(variables, *args, **kwargs)
                new_bstats = bstats
        else:
            preds = model.apply(variables, *args, **kwargs)
            new_bstats = None
        if model_loss:
            return preds, labels, new_bstats
        if preds.ndim == labels.ndim + 1 and preds.shape[-1] == 1:
            preds = preds.squeeze(-1)
        return preds.astype(jnp.float32), labels, new_bstats

    apply_fn.model_loss = model_loss
    apply_fn.rng_streams = streams
    # state the model moves itself, once an optimizer step (the collection
    # ``bstats`` carries -> the same, after the step): none for most models
    apply_fn.after_step = getattr(model, "after_step", None)
    # {"window": n, "full": m} where the model says so (a language model)
    apply_fn.attention_layers = getattr(model, "attention_layers", None) or {}
    # {"once": n} or {"twice": n}: how often a step runs their forward
    apply_fn.attention_forward = getattr(
        model, "attention_forward", None) or {}
    # {"kept": n} or {"rebuilt": n}: a recomputed attention's q, k and v
    apply_fn.attention_inputs = getattr(
        model, "attention_inputs", None) or {}
    # {"kept": n, "rebuilt": m}: second norms' inputs in a recomputed block
    apply_fn.sublayer_out = getattr(model, "sublayer_out", None) or {}
    # {"plain": n} or {"rescanned": n}: state-space layers, by their scan
    apply_fn.ssm_layers = getattr(model, "ssm_layers", None) or {}
    # {"recomputed": n} or {"plain": n}: a looped model's layers x passes
    apply_fn.loop_passes = getattr(model, "loop_passes", None) or {}
    # {"recomputed": n} or {"plain": n}: pairs with a convolution operator
    apply_fn.conv_layers = getattr(model, "conv_layers", None) or {}
    # {"rescanned": n} or {"plain": n}: pairs with a delta-rule operator
    apply_fn.kda_layers = getattr(model, "kda_layers", None) or {}
    if callable(getattr(model, "lookups", None)):
        apply_fn.lookups = lambda batch: model.lookups(split_batch(batch)[0])
    return apply_fn


def _step_loss(apply_fn, loss_fn) -> Callable:
    """The loss a step built round ``apply_fn`` takes: the estimator's, or,
    where the model brings its own (:func:`_make_apply`), the scalar the
    model returned: it was handed the rows' weights, so the mean over the
    real rows is already taken."""
    if not getattr(apply_fn, "model_loss", False):
        return loss_fn
    return lambda preds, labels, mask=None: preds[0]


class PipelineModel:
    """A layer-list model description for pipeline-parallel placement.

    ``layers`` is a sequence of stage-homogeneous Flax modules (identical
    parameter structure and shapes — the transformer-block case); ``embed``
    and ``head`` are optional entry/exit modules that run OUTSIDE the
    pipeline (embed must map the batch inputs to the hidden array the blocks
    consume). On a mesh with ``stage > 1`` the estimator stacks the per-layer
    parameter pytrees via
    :func:`raydp_tpu.parallel.pipeline.stack_stage_params` onto a leading
    ``stage_stack`` axis (role-driven specs shard it over ``stage``) and runs
    the blocks through the ``shard_map`` GPipe schedule; on ``stage == 1``
    meshes the same description trains through a sequential ``vmap`` fallback
    — one model description, any mesh.

    ``init``/``apply`` mirror the Flax module surface the estimator and the
    serving bundle consume (``apply`` is the host-side sequential form used
    by ``predict``/``export_serving`` — row-identical to the pipelined
    forward). BatchNorm-style mutable collections are not supported in the
    blocks (``init`` raises: running stats cannot hop stages).
    """

    def __init__(self, layers, embed=None, head=None):
        if not layers:
            raise ValueError("PipelineModel needs at least one layer")
        self.layers = list(layers)
        self.embed = embed
        self.head = head

    def init(self, rng, inputs):
        import jax

        from raydp_tpu.parallel.pipeline import stack_stage_params

        params: Dict[str, Any] = {}
        h = inputs
        if self.embed is not None:
            rng, k = jax.random.split(rng)
            v = self.embed.init(k, h)
            self._reject_mutable(v, "embed")
            params["embed"] = v["params"]
            h = self.embed.apply({"params": params["embed"]}, h)
        layer_params = []
        for i, layer in enumerate(self.layers):
            rng, k = jax.random.split(rng)
            v = layer.init(k, h)
            self._reject_mutable(v, f"layers[{i}]")
            layer_params.append(v["params"])
            h = layer.apply({"params": v["params"]}, h)
        # jnp.stack raises on shape mismatch — the stage-homogeneity check
        params["stage_stack"] = stack_stage_params(layer_params)
        if self.head is not None:
            rng, k = jax.random.split(rng)
            v = self.head.init(k, h)
            self._reject_mutable(v, "head")
            params["head"] = v["params"]
        return {"params": params}

    @staticmethod
    def _reject_mutable(variables, where: str):
        extra = sorted(set(variables) - {"params"})
        if extra:
            raise ValueError(
                f"PipelineModel {where} carries mutable collections {extra} "
                f"(e.g. BatchNorm batch_stats): running stats cannot hop "
                f"pipeline stages — use stat-free blocks (LayerNorm)")

    def apply(self, variables, inputs):
        """Host/serving forward: the layers applied sequentially from the
        stacked tree — the exact math of the pipelined forward, one device."""
        import jax

        p = variables["params"]
        h = inputs
        if self.embed is not None:
            h = self.embed.apply({"params": p["embed"]}, h)
        stack = p["stage_stack"]
        n_layers = int(jax.tree.leaves(stack)[0].shape[0])
        block = self.layers[0]
        for i in range(n_layers):
            h = block.apply(
                {"params": jax.tree.map(lambda a: a[i], stack)}, h)
        if self.head is not None:
            h = self.head.apply({"params": p["head"]}, h)
        return h


def _make_pipeline_apply(model: "PipelineModel", split_batch, compute_dtype,
                         mesh, n_micro: int, seg_modes: Dict[str, str]):
    """The pipeline twin of :func:`_make_apply`: same
    ``apply_fn(params, bstats, batch, train, mask=None) -> (preds_f32,
    labels, None)`` signature, but the forward splits the batch into ``n_micro`` microbatches
    and marches them through the ``shard_map`` GPipe schedule
    (:func:`raydp_tpu.parallel.pipeline.pipeline_apply`).

    This is where accumulation and pipeline microbatching UNIFY: the
    estimator's ``accum_steps`` microbatches ARE the pipeline's microbatches
    — one ``lax.scan`` of ``n_micro + n_stages - 1`` ticks runs the whole
    forward, and AD of it is the reverse pipeline, so the train step wraps
    this forward with ``accum=1`` (a second scan would re-microbatch the
    microbatches). ``seg_modes`` maps each segment (``embed`` /
    ``stage_stack`` / ``head``) to its remat mode — the per-role policy
    resolved against each segment's dominant parameter role.
    """
    import jax
    import jax.numpy as jnp

    from raydp_tpu.parallel.pipeline import pipeline_apply
    from raydp_tpu.parallel.roles import apply_remat

    embed_mod, head_mod, block = model.embed, model.head, model.layers[0]

    def _block_fwd(p, x):
        return block.apply({"params": p}, x)

    block_fwd = apply_remat(_block_fwd, seg_modes.get("stage_stack", "none"))
    embed_fwd = head_fwd = None
    if embed_mod is not None:
        embed_fwd = apply_remat(
            lambda p, x: embed_mod.apply({"params": p}, x),
            seg_modes.get("embed", "none"))
    if head_mod is not None:
        head_fwd = apply_remat(
            lambda p, x: head_mod.apply({"params": p}, x),
            seg_modes.get("head", "none"))

    def apply_fn(params, bstats, batch, train: bool, mask=None):
        # pipeline blocks are stat-free and mode-free; the mask is for a
        # model that brings its own loss, which a layer list does not
        del bstats, train, mask
        inputs, labels = split_batch(batch)
        inputs = _cast_floating(inputs, compute_dtype)
        h = embed_fwd(params["embed"], inputs) if embed_fwd is not None \
            else inputs
        rows = int(h.shape[0])
        if rows % n_micro:
            raise ValueError(
                f"pipeline microbatching: accum_steps={n_micro} does not "
                f"divide the batch dimension {rows} — pad-and-mask the tail "
                f"(RDT_TRAIN_PAD_TAIL) or drop it (drop_last=True)")
        h_micro = h.reshape((n_micro, rows // n_micro) + h.shape[1:])
        out = pipeline_apply(block_fwd, params["stage_stack"], h_micro, mesh)
        h2 = out.reshape((rows,) + out.shape[2:])
        preds = head_fwd(params["head"], h2) if head_fwd is not None else h2
        if preds.ndim == labels.ndim + 1 and preds.shape[-1] == 1:
            preds = preds.squeeze(-1)
        return preds.astype(jnp.float32), labels, None

    if callable(getattr(embed_mod, "lookups", None)):
        # declared, and kept dense: the step counts it and says why
        apply_fn.lookups = lambda batch: {
            ("embed",) + tuple(path): ids for path, ids in
            embed_mod.lookups(split_batch(batch)[0]).items()}
        apply_fn.rowwise_dense_because = "pipeline"
    return apply_fn


def _make_train_step(apply_fn, loss_fn, metrics, accum: int, remat_mode: str,
                     mb_shardings=None, state_shardings=None, seed: int = 0):
    """Build the jitted train-step body shared by ``fit`` and
    ``partial_fit``: one optimizer update from one global batch.

    With ``accum > 1`` the batch reshapes to ``[accum, B/accum, ...]``
    microbatches folded through a ``lax.scan``: per-microbatch grads, loss
    and metric stats accumulate ROW-WEIGHTED (a masked microbatch — even an
    all-pad one from a pad-and-mask tail — weighs in by its real rows), so
    the single ``apply_gradients`` at the end reproduces the unaccumulated
    update to float-summation-order tolerance while only ONE microbatch's
    activations are ever live. ``remat_mode`` wraps the forward in
    ``jax.checkpoint`` per :func:`raydp_tpu.parallel.roles.apply_remat`;
    both knobs together are the activation-residency lever the
    ``mesh_bench --activation`` record measures.

    ``mb_shardings`` — optional ``(batch_sharding, seq_sharding)`` pair
    (seq may be None) re-asserted on every microbatch inside the scan: the
    ``[B, ...] → [accum, B/accum, ...]`` reshape breaks GSPMD sharding
    propagation, and without the constraint XLA gathers each microbatch
    onto every data shard — erasing most of the residency win the
    accumulation exists for (measured 4× worse peak temp bytes on an 8-way
    mesh). Leaf rule matches the feed's: ndim >= 2 leaves take the
    seq-extended spec, 1-D leaves (labels, masks) the plain batch spec.

    ``state_shardings`` — optional: the shardings the caller placed the state
    with (``param_sharding_rules(mesh, rules)(state)``; a traced leaf carries
    no spec of its own). With them a row-wise table whose rows are split over
    mesh axes is read and written shard by shard (``train/rowwise.py``); not
    told, every table is walked as one.

    ``seed`` — the fit's: where the model names random streams
    (``apply_fn.rng_streams``) the step folds its key from the seed and the
    optimizer step the state counts (and the micro-batch), on the device, so
    a row met in two epochs draws anew and nothing more rides the feed. A
    model that names none gets no key and its step is the step it was.
    """
    import jax
    import jax.numpy as jnp
    from jax import lax

    from raydp_tpu.parallel.roles import apply_remat

    from raydp_tpu.train import rowwise

    loss_fn = _step_loss(apply_fn, loss_fn)
    if getattr(apply_fn, "model_loss", False):
        # once a built step, like the table counter below
        rdt_metrics.inc("train_head_loss_total", label="forward_grad")
    for metric, of_model in (
            ("train_attention_layers_total", "attention_layers"),
            ("train_attention_forward_total", "attention_forward"),
            ("train_attention_inputs_total", "attention_inputs"),
            ("train_sublayer_out_total", "sublayer_out"),
            ("train_ssm_layers_total", "ssm_layers"),
            ("train_loop_passes_total", "loop_passes"),
            ("train_conv_layers_total", "conv_layers"),
            ("train_kda_layers_total", "kda_layers")):
        for kind, layers in getattr(apply_fn, of_model, {}).items():
            if layers:      # once a built step, by kind of layer
                rdt_metrics.inc(metric, layers, kind)
    counted: list = []      # the table counters are bumped once a built step
    placed = None if state_shardings is None else (
        state_shardings.params, state_shardings.opt_state)
    after_step = getattr(apply_fn, "after_step", None)
    streams = getattr(apply_fn, "rng_streams", ())

    def _step_key(state):
        if not streams:
            return None
        return jax.random.fold_in(jax.random.PRNGKey(seed), state.step)

    def _carry_on(new_state, new_bstats):
        """The stepped state with the forward's collection, which the model
        has moved on where it keeps state of its own there."""
        if new_bstats is None:
            return new_state
        if after_step is not None:
            new_bstats = after_step(new_bstats)
        return new_state.replace(batch_stats=new_bstats)

    def _microbatch_grads(params, bstats, batch, mask, inv=None, rng=None):
        def _loss(p):
            # with ``inv``, p is a row view: a row-wise table's leaf holds
            # its uniq rows, and uniq[inv] are the rows the batch looked up
            given = {"rows": {path: rowwise.leaf_at(p, path)[i]
                              for path, i in inv.items()}} if inv else {}
            if rng is not None:
                given["rng"] = rng
            preds, labels, new_bstats = apply_fn(p, bstats, batch, train=True,
                                                 mask=mask, **given)
            lv = loss_fn(preds, labels, mask=mask) if mask is not None \
                else loss_fn(preds, labels)
            return lv, (preds, labels, new_bstats)

        fwd = apply_remat(_loss, remat_mode)
        return jax.value_and_grad(fwd, has_aux=True)(params)

    def _update(state, batch, mask, tables, rng=None):
        """One optimizer update from one batch. The declared ``tables`` (if
        any) are differentiated and updated in the rows the batch looked up
        (``train/rowwise.py``): the user's ``tx.update`` runs once, on the
        row view of params and opt_state."""
        if not tables:
            out, grads = _microbatch_grads(state.params, state.batch_stats,
                                           batch, mask, rng=rng)
            return state.apply_gradients(grads=grads), out
        uniq, inv = rowwise.unique_rows_of(
            tables, {path: rowwise.leaf_at(state.params, path).shape[0]
                     for path in tables})
        whole = (state.params, state.opt_state)
        idx = rowwise.index_trees(state.tx, *whole, uniq)
        view_params, view_opt = rowwise.take_rows(whole, idx, placed)
        out, grads = _microbatch_grads(view_params, state.batch_stats, batch,
                                       mask, inv, rng)
        new_view = state.replace(
            params=view_params, opt_state=view_opt).apply_gradients(
                grads=grads)
        new_params, new_opt = rowwise.put_rows(
            whole, (new_view.params, new_view.opt_state), idx, placed)
        return new_view.replace(params=new_params, opt_state=new_opt), out

    def train_step(state, batch, mstats, loss_sum):
        batch, mask = _strip_mask(batch)
        tables = rowwise.tables_to_update(
            apply_fn, state, batch, accum, counted,
            getattr(state_shardings, "params", None))
        key = _step_key(state)
        if accum <= 1:
            new_state, (loss_val, (preds, labels, new_bstats)) = _update(
                state, batch, mask, tables, key)
            new_state = _carry_on(new_state, new_bstats)
            new_mstats = tuple(
                _update_metric(m, s, preds, labels, mask)
                for m, s in zip(metrics, mstats))
            return (new_state, loss_sum + loss_val.astype(jnp.float32),
                    new_mstats)

        def _split(a):
            if a.shape[0] % accum:
                raise ValueError(
                    f"accum_steps={accum} does not divide the batch "
                    f"dimension {a.shape[0]}")
            return a.reshape((accum, a.shape[0] // accum) + a.shape[1:])

        micro = jax.tree.map(_split, batch)
        micro_mask = None if mask is None else _split(mask)
        # grads/loss accumulate in f32 regardless of the param dtype: k-1
        # additions in bf16 would lose exactly the low bits the parity
        # contract (accum=k == accum=1 to tolerance) protects
        g0 = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32),
                          state.params)
        ms0 = jax.tree.map(lambda x: jnp.asarray(x, jnp.float32), mstats)

        def body(carry, xs):
            g_acc, l_acc, r_acc, bstats, ms = carry
            mb = xs[0]
            mb_mask = xs[1] if micro_mask is not None else None
            if mb_shardings is not None:
                b_sh, s_sh = mb_shardings
                mb = jax.tree.map(
                    lambda a: lax.with_sharding_constraint(
                        a, s_sh if s_sh is not None and a.ndim >= 2
                        else b_sh), mb)
                if mb_mask is not None:
                    mb_mask = lax.with_sharding_constraint(mb_mask, b_sh)
            (lv, (preds, labels, new_bstats)), g = _microbatch_grads(
                state.params, bstats, mb, mb_mask, rng=None if key is None
                else jax.random.fold_in(key, xs[-1]))
            rows = jnp.sum(mb_mask) if mb_mask is not None \
                else jnp.float32(labels.shape[0])
            g_acc = jax.tree.map(
                lambda a, gg: a + gg.astype(jnp.float32) * rows, g_acc, g)
            l_acc = l_acc + lv.astype(jnp.float32) * rows
            r_acc = r_acc + rows
            ms = tuple(_update_metric(m, s, preds, labels, mb_mask)
                       for m, s in zip(metrics, ms))
            return (g_acc, l_acc, r_acc, new_bstats, ms), ()

        xs = (micro,) if micro_mask is None else (micro, micro_mask)
        if key is not None:     # a micro-batch's place in the step
            xs += (jnp.arange(accum),)
        carry0 = (g0, jnp.float32(0), jnp.float32(0), state.batch_stats, ms0)
        (g_acc, l_acc, r_acc, new_bstats, new_mstats), _ = lax.scan(
            body, carry0, xs)
        denom = jnp.maximum(r_acc, 1.0)
        grads = jax.tree.map(lambda a, p: (a / denom).astype(p.dtype),
                             g_acc, state.params)
        new_state = _carry_on(state.apply_gradients(grads=grads), new_bstats)
        return new_state, loss_sum + l_acc / denom, new_mstats

    return train_step


def _epoch_zeros(mesh, metrics, sums: int = 1):
    """The program that makes an epoch's starting accumulators, ``(*sums,
    mstats)``: ``sums`` f32 scalar zeros and every metric's ``init()``, each
    leaf strong-typed (``Metric.init`` gives Python floats) and replicated on
    ``mesh``: the types the step returns them with, so the step's first call
    of a fit and every later one present ``jax.jit`` one signature and the
    step is built once. Call it anew each epoch (the step donates the loss
    sum, the resident scan its whole carry). A jitted program and not a
    ``device_put``: every process of a gang dispatches it at the same place
    in the loop and none waits for another."""
    import jax
    import jax.numpy as jnp

    from raydp_tpu.parallel.mesh import replicated

    def zeros():
        return (*(jnp.zeros((), jnp.float32) for _ in range(sums)),
                tuple(jax.tree.map(lambda x: jnp.array(np.asarray(x)),
                                   m.init()) for m in metrics))

    return jax.jit(zeros, out_shardings=replicated(mesh))


def _metric_pairs(metrics, mstats):
    """A report's (name, value) pairs of the metrics' states, fetched and
    computed as they are iterated; a metric that publishes elsewhere (the
    model's counters) gives None and no pair."""
    import jax

    for m, s in zip(metrics, mstats):
        value = m.compute(jax.tree.map(np.asarray, s))
        if value is not None:
            yield m.name, value


def _carry_hooks(jit_train, epoch_zeros, metrics):
    """The loop's ``(step, zeros, read)`` (``loop.Trainee``) round the jitted
    train step: the carry is what ``jit_train`` returns, ``(state, loss_sum,
    mstats)``, handed back to it in its own argument order."""
    def step(carry, batch):
        return jit_train(carry[0], batch, carry[2], carry[1])

    def zeros(carry):
        return (carry[0], *epoch_zeros())

    def read(carry):
        return carry[1], _metric_pairs(metrics, carry[2])

    return step, zeros, read


class FlaxEstimator(EstimatorInterface, FrameEstimatorInterface):
    def __init__(
        self,
        model=None,
        model_creator: Optional[Callable] = None,
        optimizer=None,
        optimizer_creator: Optional[Callable] = None,
        loss: Union[str, Callable, None] = "mse",
        feature_columns: Optional[Sequence[str]] = None,
        label_column: Optional[str] = None,
        batch_size: int = 64,
        num_epochs: int = 10,
        mesh=None,
        mesh_spec=None,
        metrics: Optional[Sequence[Union[str, Metric]]] = None,
        checkpoint_dir: Optional[str] = None,
        seed: int = 0,
        feature_dtype=np.float32,
        label_dtype=np.float32,
        shuffle: bool = True,
        param_rules=None,
        batch_preprocessor: Optional[Callable] = None,
        columns_spec: Optional[Dict] = None,
        compute_dtype=None,
        drop_last: bool = True,
        callbacks: Optional[Sequence[Callable[[Dict], None]]] = None,
        checkpoint_interval: int = 1,
        prefetch_to_device: Optional[int] = None,
        accum_steps: Optional[int] = None,
        remat: Optional[str] = None,
        seq_sharded: Optional[bool] = None,
    ):
        if model is None and model_creator is None:
            raise ValueError("pass model or model_creator")
        self._model = model
        self._model_creator = model_creator
        self._optimizer = optimizer
        self._optimizer_creator = optimizer_creator
        self._loss = loss
        self.feature_columns = list(feature_columns or [])
        self.label_column = label_column
        self.batch_size = batch_size
        self.num_epochs = num_epochs
        self._mesh = mesh
        self._mesh_spec = mesh_spec
        self._metrics = build_metrics(metrics or [])
        self.checkpoint_dir = checkpoint_dir
        self.seed = seed
        self.feature_dtype = feature_dtype
        self.label_dtype = label_dtype
        self.shuffle = shuffle
        self.param_rules = param_rules
        self.batch_preprocessor = batch_preprocessor
        self.columns_spec = columns_spec
        self.compute_dtype = compute_dtype
        self.drop_last = drop_last
        self.callbacks = list(callbacks or [])
        #: checkpoint every N-th epoch (the final epoch always saves). The
        #: reference checkpoints per epoch (default 1 keeps that); with the
        #: device-resident path an epoch can be cheaper than its checkpoint,
        #: so long runs may want a sparser cadence — a retry/resume then
        #: replays at most N-1 epochs from the last save.
        self.checkpoint_interval = max(1, int(checkpoint_interval))
        #: device-placed batches the streaming feed keeps ahead of the train
        #: step (None = the feed default / RDT_PREFETCH_TO_DEVICE, 2): H2D
        #: for batch k+1 overlaps the compute of batch k — bit-identical to
        #: synchronous placement (tests/test_feed_pipeline.py). The
        #: device-resident path ignores it (nothing streams).
        self.prefetch_to_device = prefetch_to_device
        #: gradient-accumulation microbatches per optimizer step (None = the
        #: RDT_TRAIN_ACCUM_STEPS knob, default 1). k splits every global
        #: batch into k scanned microbatches whose row-weighted grad/loss/
        #: metric accumulation reproduces the unaccumulated update while
        #: only one microbatch's activations are live — peak activation
        #: bytes drop ~k×. Must divide batch_size.
        self.accum_steps = accum_steps
        #: rematerialization policy for the train-step forward: a global
        #: mode ('none' | 'dots' | 'full' — the default policy) or a
        #: per-role 'role=mode,...' map over the param roles
        #: ('embedding=none,kernel=dots,default=full'); None = the
        #: RDT_TRAIN_REMAT knob. jax.checkpoint placement per
        #: parallel/roles.py parse_remat_policy / remat_policy
        self.remat = remat
        #: shard declared sequence dims (dim 1 of ndim >= 2 batch leaves)
        #: over the mesh's ``seq`` axis (None = auto: on whenever the mesh
        #: has a >1 seq extent). Layout-only — results stay row-identical.
        self.seq_sharded = seq_sharded
        self._result: Optional[TrainingResult] = None

    def _resolve_accum(self) -> int:
        """The effective accumulation factor for THIS fit (the constructor
        argument wins over the knob; knob read at call time — per-action
        scope). Validated against batch_size: k must slice the global batch
        into equal microbatches or the scanned program cannot reshape it."""
        k = self.accum_steps if self.accum_steps is not None \
            else int(knobs.get("RDT_TRAIN_ACCUM_STEPS"))
        k = max(1, int(k))
        if k > 1 and self.batch_size % k:
            raise ValueError(
                f"accum_steps={k} must divide batch_size={self.batch_size}")
        return k

    def _resolve_remat(self) -> Dict[str, str]:
        """The effective remat POLICY for THIS fit: a role→mode map parsed
        (and validated, eagerly — long before any compile) by
        :func:`raydp_tpu.parallel.roles.parse_remat_policy`. A bare mode
        string (the pre-r20 global form) parses to ``{"default": mode}`` —
        the global mode IS the default policy, so old specs behave
        identically; ``"embedding=none,kernel=dots"`` picks per parameter
        role the way the param specs are picked."""
        from raydp_tpu.parallel.roles import parse_remat_policy

        spec = (self.remat if self.remat is not None
                else str(knobs.get("RDT_TRAIN_REMAT"))).lower()
        return parse_remat_policy(spec)

    def _make_forward(self, model, mesh, takes_train, params):
        """Build THIS fit's forward + the train-step knobs around it — ONE
        source shared by ``fit`` and ``partial_fit`` so the two cannot drift.

        Returns ``(apply_fn, step_accum, step_remat, n_micro, n_stages)``:
        the forward with :func:`_make_apply`'s signature, the accumulation
        factor and remat mode ``_make_train_step`` should apply AROUND it,
        and the pipeline geometry. For a :class:`PipelineModel` the forward
        is the GPipe schedule with the resolved ``accum_steps`` as its
        microbatch count — so ``step_accum`` is 1 and ``step_remat`` is
        ``none`` (microbatching and remat both live INSIDE the pipelined
        forward, per segment); a monolithic model keeps the scan-around-
        the-forward shape, its mode resolved from the params' dominant
        role under the per-role policy."""
        from raydp_tpu.parallel.mesh import stage_extent
        from raydp_tpu.parallel.roles import (remat_mode_for_role,
                                              segment_role)

        accum = self._resolve_accum()
        policy = self._resolve_remat()
        n_stages = stage_extent(mesh)
        if isinstance(model, PipelineModel):
            n_layers = len(model.layers)
            if n_stages > 1 and n_layers % n_stages:
                raise ValueError(
                    f"PipelineModel has {n_layers} layers; the mesh's "
                    f"stage={n_stages} must divide them (each stage applies "
                    f"a contiguous run of layers)")
            seg_modes = {
                name: remat_mode_for_role(policy, segment_role(sub))
                for name, sub in params.items()}
            papply = _make_pipeline_apply(model, self._split_batch,
                                          self.compute_dtype, mesh, accum,
                                          seg_modes)
            return papply, 1, "none", accum, n_stages
        if n_stages > 1:
            raise ValueError(
                f"mesh has stage={n_stages} but the model is not a "
                f"PipelineModel: stage-stacked placement needs the "
                f"layer-list description (raydp_tpu.train.PipelineModel)")
        mode = remat_mode_for_role(policy, segment_role(params))
        apply_fn = _make_apply(model, takes_train, self._split_batch,
                               self.compute_dtype)
        return apply_fn, accum, mode, accum, 1

    def _use_seq(self, mesh) -> bool:
        """Does THIS fit extend batch shardings over the mesh's seq axis?
        Auto-on when the mesh has a >1 seq extent; ``seq_sharded=False``
        opts out (and True without a seq extent stays off — there is
        nothing to shard over)."""
        from raydp_tpu.parallel.mesh import seq_extent

        if seq_extent(mesh) <= 1:
            return False
        return True if self.seq_sharded is None else bool(self.seq_sharded)

    # ------------------------------------------------------------------ build
    def _build_model(self):
        return self._model if self._model is not None else self._model_creator()

    def _build_optimizer(self):
        import optax
        if self._optimizer is not None:
            return self._optimizer
        if self._optimizer_creator is not None:
            return self._optimizer_creator()
        return optax.adam(1e-3)

    def _build_mesh(self):
        if self._mesh is not None:
            return self._mesh
        from raydp_tpu.parallel import make_mesh
        return make_mesh(self._mesh_spec)

    def _columns(self) -> Dict:
        if self.columns_spec is not None:
            return self.columns_spec
        if not self.feature_columns or self.label_column is None:
            raise ValueError("pass feature_columns + label_column or columns_spec")
        return {
            "features": (self.feature_columns, self.feature_dtype),
            "label": (self.label_column, self.label_dtype),
        }

    def _may_pad_tail(self) -> bool:
        """Can a ragged tail pad to a full batch and mask its pad rows out?
        ``RDT_TRAIN_PAD_TAIL=0`` or a loss that takes no mask says no, and
        the tail is dropped where it cannot travel as it is."""
        return bool(knobs.get("RDT_TRAIN_PAD_TAIL")) \
            and _loss_takes_mask(self._loss)

    def _split_batch(self, batch: Dict):
        if self.batch_preprocessor is not None:
            return self.batch_preprocessor(batch)
        return batch["features"], batch["label"]

    # -------------------------------------------------------------------- fit
    def fit(self, train_ds, evaluate_ds=None, max_retries: int = 0
            ) -> TrainingResult:
        profiler.watch_jit_builds()
        mesh = self._build_mesh()
        ckpt_dir = self.checkpoint_dir or tempfile.mkdtemp(prefix="rdt-ckpt-")
        feeds = loop.plan_feeds(
            train_ds, evaluate_ds, self._columns(), mesh, self.batch_size,
            shuffle=self.shuffle, seed=self.seed, drop_last=self.drop_last,
            prefetch_to_device=self.prefetch_to_device,
            may_pad=self._may_pad_tail(), seq=self._use_seq(mesh))
        state, history = self._train_loop(mesh, feeds, ckpt_dir,
                                          max_retries=max_retries)
        self._result = TrainingResult(state=state, history=history,
                                      checkpoint_dir=ckpt_dir)
        return self._result

    def _train_loop(self, mesh, feeds, ckpt_dir: str, max_retries: int = 0,
                    resume: bool = False):
        """Build the fit's state and its jitted programs, and hand them to
        the loop (``train/loop.py``) as its :class:`~loop.Trainee`."""
        import jax
        import jax.numpy as jnp

        from raydp_tpu.parallel import batch_sharding, param_sharding_rules
        from raydp_tpu.train import checkpoint as ckpt

        if not resume and self.checkpoint_dir:
            ckpt.warn_if_reused_dir(ckpt_dir)
        model = self._build_model()
        tx = self._build_optimizer()
        loss_fn = _resolve_loss(self._loss)
        metrics = self._metrics

        cache, eval_cache = feeds.cache, feeds.eval_cache
        # ---- init params from one host batch's shapes ----
        first = feeds.first_batch(self.batch_size, self.drop_last)
        with profiler.trace("fit:init", "training"):
            inputs0, _ = self._split_batch(
                {k: jnp.asarray(v[:1]) for k, v in first.items()})
            rng = jax.random.PRNGKey(self.seed)
            takes_train = _takes_train(model)
            state = _init_state(model, tx, rng, inputs0)
            state_sharding = param_sharding_rules(mesh,
                                                  self.param_rules)(state)
        from raydp_tpu.parallel.roles import addressable_nbytes
        with profiler.trace("train:place", "training"):
            state = ckpt.place_tree(state, state_sharding)
        # the fsdp memory claim, observed where it is true: bytes of params
        # + optimizer state resident on THIS process's devices after
        # placement (replicated leaves count one copy per device)
        rdt_metrics.set_gauge("train_param_bytes_per_process",
                            addressable_nbytes(state))
        b_sharding = batch_sharding(mesh)
        # seq-extended sharding for ndim >= 2 batch leaves on the resident
        # path (the streaming DeviceFeed carries its own: the feed plan's);
        # None when the mesh has no >1 seq extent
        seq_sharding = batch_sharding(mesh, seq=True) \
            if self._use_seq(mesh) else None

        # the activation-side plane: accumulation factor, remat policy and
        # (on a stage>1 mesh) the GPipe schedule, resolved per fit
        # (constructor args win over the PER_ACTION knobs). In pipeline mode
        # the accum microbatches ARE the pipeline microbatches — one scan —
        # so the step wraps the forward with accum=1/remat "none" (both live
        # inside the pipelined forward, per segment).
        _apply, step_accum, step_remat, accum, n_stages = self._make_forward(
            model, mesh, takes_train, state.params)
        pipelined = n_stages > 1 or isinstance(model, PipelineModel)
        rdt_metrics.set_gauge("train_accum_steps", accum)
        if pipelined:
            rdt_metrics.set_gauge("train_pipeline_stages", n_stages)

        loss_fn = _step_loss(_apply, loss_fn)      # eval_step's, below
        # what the model's own loss counts rides the train metrics' slot:
        # summed inside the step, fetched with the epoch's loss
        train_metrics = metrics + model_counters(model)
        train_step = _make_train_step(_apply, loss_fn, train_metrics,
                                      step_accum, step_remat,
                                      mb_shardings=(b_sharding, seq_sharding),
                                      state_shardings=state_sharding,
                                      seed=self.seed)

        # publish the compiled step's peak temp (activation) bytes when the
        # activation plane is engaged — the residency number accumulation/
        # remat/pipelining drive down, read off XLA's memory_analysis at
        # first dispatch. The lower().compile() below IS the step's one
        # compile, not a second: the jit call that follows finds the same
        # executable in jax's in-process cache (counted on jax 0.9.0 via the
        # backend_compile monitoring event: 1 for the pair, either order).
        # Best-effort: some backends lack the analysis, and telemetry must
        # never fail (or slow an un-engaged) fit.
        engaged = accum > 1 or step_remat != "none" or pipelined
        _compile_span = "train:pipeline" if pipelined else "train:accum"

        def _note_activation(fn, *args):
            try:
                with profiler.trace(_compile_span, "training"):
                    mem = fn.lower(*args).compile().memory_analysis()
                temp = getattr(mem, "temp_size_in_bytes", None)
                if temp is not None:
                    local = sum(1 for d in mesh.devices.flat
                                if d.process_index == jax.process_index())
                    rdt_metrics.set_gauge(
                        "train_activation_bytes_per_process",
                        int(temp) * max(1, local))
            except Exception:  # noqa: BLE001 - telemetry only
                pass

        # eval threads BOTH accumulators (row-weighted loss sum AND the row
        # count) through the jitted step: under pad-and-mask the real row
        # count is mask.sum(), known on device — a host-side shape[0] count
        # would bill padded rows into the eval mean
        def eval_step(state, batch, mstats, loss_sum, cnt_sum):
            batch, mask = _strip_mask(batch)
            preds, labels, _ = _apply(state.params, state.batch_stats, batch,
                                      train=False, mask=mask)
            if mask is None:
                rows = jnp.float32(labels.shape[0])
                loss_val = loss_fn(preds, labels).astype(jnp.float32)
            else:
                rows = jnp.sum(mask)
                loss_val = loss_fn(preds, labels,
                                   mask=mask).astype(jnp.float32)
            new_mstats = tuple(
                _update_metric(m, s, preds, labels, mask)
                for m, s in zip(metrics, mstats))
            return loss_sum + loss_val * rows, cnt_sum + rows, new_mstats

        jit_train = jax.jit(train_step, donate_argnums=(0, 3))
        jit_eval = jax.jit(eval_step, donate_argnums=(3, 4))
        eval_zeros = _epoch_zeros(mesh, metrics, sums=2)

        def restore(carry, max_step):
            restored = ckpt.restore_placed(ckpt_dir, carry[0], state_sharding,
                                           max_step=max_step)
            if restored is None:
                return None
            extra = ckpt.restore_extra(ckpt_dir, max_step=max_step) or {}
            return (restored[0], None, None), restored[1], \
                list(extra.get("history", ()))

        trainee = loop.Trainee(
            (state, None, None), *_carry_hooks(
                jit_train, _epoch_zeros(mesh, train_metrics), train_metrics),
            save=lambda carry, epoch, history: ckpt.save(
                ckpt_dir, carry[0], step=epoch, extra={"history": history}),
            restore=restore, fresh=lambda: (ckpt.place_tree(
                _init_state(model, tx, rng, inputs0), state_sharding),
                None, None))

        if cache is not None:
            # the resident epoch: the shared scan program built by
            # DeviceEpochCache round the step in scan form
            def _step(carry, batch):
                state, loss_sum, mstats = carry
                return train_step(state, batch, mstats, loss_sum)

            epoch_fn, _ = cache.make_epoch_fn(
                _step, self.batch_size, self.shuffle,
                batch_sharding=b_sharding, seq_sharding=seq_sharding)
            jit_epoch = jax.jit(epoch_fn, donate_argnums=(0,))
            trainee.epoch = lambda carry, key: jit_epoch(carry, cache.arrays,
                                                         key)
        if engaged:
            # the step's temp bytes, read off the program the first call is
            # about to run (the resident scan's, or the streaming step's)
            if cache is not None:
                trainee.before_first = lambda carry, key: _note_activation(
                    jit_epoch, carry, cache.arrays, key)
            else:
                trainee.before_first = lambda carry, batch: _note_activation(
                    jit_train, carry[0], batch, carry[2], carry[1])

        if feeds.eval_feed is not None or eval_cache is not None:
            # an eval pass's accumulators are what ``jit_eval`` returns and
            # ``eval_zeros`` makes: (loss sum, row count, mstats)
            def eval_read(acc):
                rows = float(acc[1])    # real rows only: pad rows mask to 0
                yield "loss", float(acc[0]) / rows if rows else float("nan")
                yield from _metric_pairs(metrics, acc[2])

            trainee.evaluation = ev = loop.Evaluation(
                zeros=eval_zeros, read=eval_read,
                step=lambda carry, acc, batch: jit_eval(
                    carry[0], batch, acc[2], acc[0], acc[1]))
        if eval_cache is not None:
            # every full batch of the resident eval set as ONE scan dispatch,
            # built by the same make_epoch_fn as the train scan (the ragged
            # tail is the loop's: one more call of the step). The state
            # rides the scan's carry through, before the accumulators
            eval_epoch_fn, _ = eval_cache.make_epoch_fn(
                lambda c, batch: (c[0], *eval_step(c[0], batch, c[3], c[1],
                                                   c[2])),
                self.batch_size, shuffle=False,
                batch_sharding=b_sharding, seq_sharding=seq_sharding)
            jit_eval_epoch = jax.jit(eval_epoch_fn)
            ev.epoch = lambda carry, acc: jit_eval_epoch(
                (carry[0], *acc), eval_cache.arrays,
                jax.random.PRNGKey(0))[1:]      # the key: unused, no shuffle

        carry, history = loop.run(
            trainee, feeds, num_epochs=self.num_epochs,
            batch_size=self.batch_size, seed=self.seed,
            checkpoint_interval=self.checkpoint_interval,
            callbacks=self.callbacks, max_retries=max_retries, resume=resume)
        return carry[0], history

    # ------------------------------------------------------------ partial_fit
    def _partial_fit_epoch(self, ds, epoch: int) -> Dict[str, float]:
        """One online update: a single gradient pass over the epoch's rows
        through the streaming ``DeviceFeed`` (decode and H2D prefetch
        overlap the jitted steps, as in ``fit``). State persists on the
        estimator across epochs; ``self._result`` tracks it so
        ``get_model``/``export_serving`` work mid-stream."""
        from raydp_tpu.data.feed import DeviceFeed

        o = getattr(self, "_online", None)
        if o is None:
            o = self._online_init(ds)
            if o is None:
                # an empty first epoch (a filter matching nothing is
                # routine in streaming) has no schema to init from: report
                # it and keep waiting for rows
                return loop.epoch_report(
                    epoch, "train_{}", 0.0, (), 0, self.batch_size,
                    time.perf_counter(), 0.0, (0.0,) * 4)[0]
            self._online = o
        feed = DeviceFeed(ds, self.batch_size, o["columns"], mesh=o["mesh"],
                          shuffle=False, drop_remainder=o["drop_last"],
                          pad_remainder=o["pad_tail"],
                          prefetch_to_device=self.prefetch_to_device,
                          seq=o.get("seq", False))
        t0 = time.perf_counter()
        carry, steps, walls = loop.stream_epoch(
            iter(feed), o["step"], o["zeros"]((o["state"], None, None)))
        o["state"] = carry[0]
        report, _ = loop.epoch_report(epoch, "train_{}", *o["read"](carry),
                                      steps, self.batch_size, t0, t0, walls,
                                      feed)
        o["history"].append(report)
        self._result = TrainingResult(state=o["state"],
                                      history=o["history"])
        return report

    def _online_init(self, ds) -> Optional[Dict[str, Any]]:
        """Build the persistent online-training state from the first
        epoch's schema: model/optimizer init, sharded placement, and the
        jitted train step (the same step shape as ``fit``'s, without the
        device-resident variant — a stream epoch is small).
        None when the epoch holds no rows to init from."""
        import jax
        import jax.numpy as jnp

        from raydp_tpu.data.feed import HostBatchIterator
        from raydp_tpu.parallel import param_sharding_rules
        from raydp_tpu.parallel.mesh import batch_sharding
        from raydp_tpu.train.checkpoint import place_tree

        mesh = self._build_mesh()
        columns = self._columns()
        model = self._build_model()
        tx = self._build_optimizer()
        loss_fn = _resolve_loss(self._loss)
        metrics = self._metrics
        first = next(iter(HostBatchIterator(ds, 1, columns, shuffle=False,
                                            drop_remainder=False)), None)
        if first is None:
            return None
        inputs0, _ = self._split_batch(
            {k: jnp.asarray(v[:1]) for k, v in first.items()})
        rng = jax.random.PRNGKey(self.seed)
        takes_train = _takes_train(model)
        state = _init_state(model, tx, rng, inputs0)
        state_sharding = param_sharding_rules(mesh, self.param_rules)(state)
        state = place_tree(state, state_sharding)

        # the SAME step body as fit()'s (one source): the online path gets
        # gradient accumulation, remat AND pipeline placement for free, and
        # the two cannot drift
        _apply, step_accum, step_remat, accum, n_stages = self._make_forward(
            model, mesh, takes_train, state.params)
        rdt_metrics.set_gauge("train_accum_steps", accum)
        if isinstance(model, PipelineModel):
            rdt_metrics.set_gauge("train_pipeline_stages", n_stages)
        train_step = _make_train_step(
            _apply, loss_fn, metrics, step_accum, step_remat,
            mb_shardings=(batch_sharding(mesh),
                          batch_sharding(mesh, seq=True)
                          if self._use_seq(mesh) else None),
            state_shardings=state_sharding, seed=self.seed)

        # the ragged micro-batch tail goes as fit()'s eval tail does (an
        # online epoch is often SMALLER than one batch — dropping its tail
        # silently skipped whole micro-batches)
        tail_ok, pad_tail = loop.tail_rule(mesh, self._may_pad_tail())
        step, zeros, read = _carry_hooks(
            jax.jit(train_step, donate_argnums=(0, 3)),
            _epoch_zeros(mesh, metrics), metrics)
        return {
            "mesh": mesh,
            "columns": columns,
            "state": state,
            "step": step, "zeros": zeros, "read": read,
            "drop_last": not tail_ok,
            "pad_tail": pad_tail,
            "seq": self._use_seq(mesh),
            "history": [],
        }

    # --------------------------------------------------------------- fit_gang
    def fit_gang(self, train_ds, evaluate_ds=None, *, num_workers: int = 2,
                 max_retries: int = 0, job_name: Optional[str] = None,
                 run_timeout: float = 3600.0,
                 start_timeout: float = 180.0,
                 worker_env: Optional[Dict[str, str]] = None
                 ) -> TrainingResult:
        """Train as a gang of ``num_workers`` processes under one global
        ``jax.distributed`` mesh.

        Parity: ``TorchTrainer`` + ``ScalingConfig(num_workers)`` +
        ``RunConfig(FailureConfig(max_failures))`` (reference
        torch/estimator.py:312-356). Each rank rebuilds the dataset from the
        object store, feeds its slice of every global batch
        (:class:`GangShardIterator` → ``make_array_from_process_local_data``),
        and runs the same jitted train loop; XLA inserts the gradient
        collectives over the global mesh. Parameters may be sharded ACROSS
        processes (fsdp/expert/tensor axes spanning hosts): checkpoints use
        the sharded multi-writer format (each process saves the shards it
        owns, see train/checkpoint.py) and the returned model is assembled
        with a ``process_allgather``.
        A dead or failing rank fails the whole gang (XLA collectives are not
        elastic mid-program, SURVEY.md §7 hard part (c)); the driver then
        restarts the gang, which resumes from the last checkpoint — up to
        ``max_retries`` restarts.

        ``worker_env`` adds/overrides rank-process environment (a ``None``
        value removes the variable) — e.g. pinning ranks to CPU devices on a
        machine whose one TPU chip the driver owns.

        **Shared storage requirement**: on a multi-machine gang,
        ``checkpoint_dir`` must be a filesystem mounted on every rank's host
        (the chief writes step dirs + COMPLETE markers all ranks must see,
        and each rank writes its own parameter shards there). The default —
        a driver-local temp dir — only works when all ranks share the
        driver's machine; ranks that cannot see the directory fail fast at
        startup with a clear error.
        """
        import copy
        import uuid as _uuid

        from raydp_tpu.spmd.job import create_spmd_job

        if self._mesh is not None:
            raise ValueError("fit_gang builds its mesh inside the ranks; "
                             "pass mesh_spec instead of a driver-built mesh")
        ckpt_dir = self.checkpoint_dir or tempfile.mkdtemp(prefix="rdt-gang-")
        if self.checkpoint_dir:
            # gang ranks run with resume=True by design (the restart loop
            # below depends on it), so THIS is the one path where a fresh fit
            # pointed at a reused dir silently ADOPTS the earlier run's
            # latest step — warn before the ranks start
            from raydp_tpu.train.checkpoint import warn_if_reused_dir
            warn_if_reused_dir(ckpt_dir)
        train_payload = train_ds.portable()
        eval_payload = evaluate_ds.portable() if evaluate_ds is not None else None

        est = copy.copy(self)
        est._result = None
        est.checkpoint_dir = ckpt_dir

        def _rank_fit(ctx):
            return est._gang_rank_fit(ctx, train_payload, eval_payload,
                                      ckpt_dir)

        job = create_spmd_job(job_name or f"flaxfit-{_uuid.uuid4().hex[:6]}",
                              num_workers, jax_distributed=True,
                              env=worker_env, timeout=start_timeout)
        attempts = 0
        while True:
            try:
                job.start()
                results = job.run(_rank_fit, timeout=run_timeout)
                job.stop()
                break
            except (KeyboardInterrupt, SystemExit):
                job.stop()
                raise
            except Exception as e:  # noqa: BLE001 - gang restart (FailureConfig)
                job.stop()
                attempts += 1
                if attempts > max_retries:
                    raise
                logger.warning("gang fit failed (%s); restarting gang from "
                               "last checkpoint (retry %d/%d)",
                               e, attempts, max_retries)

        chief = results[0]
        from types import SimpleNamespace
        state = SimpleNamespace(
            params=chief["model_vars"]["params"],
            batch_stats=chief["model_vars"].get("batch_stats"))
        self._result = TrainingResult(state=state, history=chief["history"],
                                      checkpoint_dir=ckpt_dir)
        return self._result

    def _gang_rank_fit(self, ctx, train_payload, eval_payload, ckpt_dir: str):
        """Runs inside each SPMD rank (the reference's ``train_func`` body,
        torch/estimator.py:177-310)."""
        import jax

        mesh = self._build_mesh()  # jax.devices() is global under the gang
        # sharded multi-writer checkpoints assume ONE filesystem: the chief
        # mkdirs each step dir and its COMPLETE marker must be visible to
        # every rank on resume — fail fast on per-host paths, don't deadlock
        from raydp_tpu.train.checkpoint import ensure_shared_dir
        ensure_shared_dir(ckpt_dir, "rdt_ckpt_dir_probe")
        feeds = loop.gang_feeds(
            ctx, train_payload, eval_payload, self._columns(), mesh,
            self.batch_size, shuffle=self.shuffle, seed=self.seed,
            prefetch_to_device=self.prefetch_to_device,
            may_pad=self._may_pad_tail(), seq=self._use_seq(mesh))
        state, history = self._train_loop(mesh, feeds, ckpt_dir,
                                          max_retries=0, resume=True)
        out = {"history": history}
        # collect the trained variables on every host (collective — all ranks
        # participate), then rank 0 returns them; with params sharded across
        # processes this is the only way any single process sees full values
        from jax.experimental import multihost_utils

        model_vars = {"params": state.params}
        bstats = getattr(state, "batch_stats", None)
        if bstats is not None:
            model_vars["batch_stats"] = bstats
        host_vars = jax.tree.map(
            np.asarray, multihost_utils.process_allgather(model_vars,
                                                          tiled=True))
        if ctx.rank == 0:
            out["model_vars"] = host_vars
        return out

    # ----------------------------------------------------------- fit_on_frame
    def fit_on_frame(self, train_df, evaluate_df=None, *,
                     fs_directory: Optional[str] = None,
                     stop_etl_after_conversion: bool = False,
                     max_retries: int = 0,
                     num_workers: Optional[int] = None) -> TrainingResult:
        with profiler.trace("fit:run", "training",
                            estimator=type(self).__name__,
                            epochs=self.num_epochs, batch=self.batch_size):
            train_ds, eval_ds = self._convert_frames(
                train_df, evaluate_df, fs_directory=fs_directory,
                stop_etl_after_conversion=stop_etl_after_conversion)

            gang = num_workers is not None and num_workers > 1
            if self.shuffle:
                # parity: random_shuffle before training
                # (torch/estimator.py:335-338) — except on the single-process
                # device-resident path, whose on-device per-epoch permutation
                # IS a uniform row shuffle: the extra O(dataset) pass through
                # the object store buys nothing
                from raydp_tpu.data.feed import DeviceEpochCache
                resident = not gang and DeviceEpochCache.eligible(
                    train_ds, self._columns(), self.batch_size,
                    self.drop_last)
                if not resident:
                    with profiler.trace("fit:shuffle", "training"):
                        train_ds = train_ds.random_shuffle(seed=self.seed)
            if gang:
                return self.fit_gang(train_ds, eval_ds,
                                     num_workers=num_workers,
                                     max_retries=max_retries)
            return self.fit(train_ds, eval_ds, max_retries=max_retries)

    # ---------------------------------------------------------------- predict
    def predict(self, ds, batch_size: Optional[int] = None) -> np.ndarray:
        """Run the trained model over a dataset and return predictions as
        one host array (row order = dataset block order).

        Convenience beyond the reference (whose users rebuild an inference
        loop around ``get_model``). Works for plain ``feature_columns``
        models AND for ``batch_preprocessor`` / ``columns_spec`` models
        (e.g. DLRM): those decode the same column spec the train feed used
        and run the preprocessor in-jit per batch, exactly like the train
        step. ANY spec entry whose column(s) the dataset lacks (the normal
        inference frame's label — whatever the entry is keyed, a
        preprocessor may name it anything) is synthesized as zeros — the
        preprocessor's label output is discarded anyway.
        """
        import jax
        import jax.numpy as jnp

        from raydp_tpu.data.feed import HostBatchIterator

        model = self._build_model()
        variables = self.get_model()   # raises if fit() has not run
        kwargs = {"train": False} if _takes_train(model) else {}

        compute_dtype = self.compute_dtype
        custom = (self.batch_preprocessor is not None
                  or self.columns_spec is not None)
        split_batch = self._split_batch

        @jax.jit
        def infer(jbatch):
            # preprocessor + cast run INSIDE jit, like the train step's
            # _apply — one dispatch per batch, no eager slicing/casting
            inputs = split_batch(jbatch)[0] if custom \
                else jbatch["features"]
            inputs = _cast_floating(inputs, compute_dtype)
            preds = model.apply(variables, inputs, **kwargs)
            if preds.ndim >= 2 and preds.shape[-1] == 1:
                preds = preds.squeeze(-1)
            return preds.astype(jnp.float32)

        cols = dict(self._columns()) if custom else {
            "features": (self.feature_columns, self.feature_dtype)}
        synth: Dict[str, Tuple[Tuple[str, ...], np.dtype]] = {}
        if custom:
            have = set(ds.schema.names)
            for name, (cspec, dt) in list(cols.items()):
                cnames = (cspec,) if isinstance(cspec, str) else tuple(cspec)
                missing = [c for c in cnames if c not in have]
                if missing and len(missing) < len(cnames):
                    # some of the entry's columns exist and some don't: that
                    # is a schema mismatch (renamed/dropped feature), not a
                    # label-less inference frame — zero-filling half a
                    # feature matrix would silently predict garbage
                    raise ValueError(
                        f"columns_spec entry {name!r} is partially missing "
                        f"from the dataset schema: missing {missing}")
                if missing:
                    # the entry is absent wholesale (the usual case: a label
                    # column inference data never carries, under whatever key
                    # the spec chose) — synthesize it as zeros
                    cols.pop(name)
                    synth[name] = (cnames, np.dtype(dt))
                    logger.info("predict: columns_spec entry %r absent from "
                                "the dataset schema; synthesizing zeros",
                                name)
            if not cols:
                raise ValueError(
                    "no columns_spec entry matches the dataset schema "
                    f"{sorted(have)}; cannot synthesize every input")
        it = HostBatchIterator(ds, batch_size or self.batch_size, cols,
                               shuffle=False, drop_remainder=False)
        out = []
        for batch in it:
            rows = len(next(iter(batch.values())))
            for name, (cnames, dt) in synth.items():
                # match the decoded shape contract of _as_numpy: one column
                # decodes to [rows], several to [rows, n]
                shape = (rows,) if len(cnames) == 1 else (rows, len(cnames))
                batch[name] = np.zeros(shape, dt)
            out.append(np.asarray(infer(
                {k: jnp.asarray(v) for k, v in batch.items()})))
        if not out:
            return np.empty((0,), np.float32)
        return np.concatenate(out, axis=0)

    # --------------------------------------------------------- export_serving
    def export_serving(self, export_dir: str) -> str:
        """Write a serving bundle for :class:`raydp_tpu.serve.ServingSession`:
        the trained variables through ``train/checkpoint.py`` plus the
        pickled inference recipe (model, column spec, preprocessor, cast
        policy) — exactly what :meth:`predict` uses, so a replica's output
        is row-identical to a driver-side ``predict()`` on the same rows.
        Multi-host executor pools need ``export_dir`` on shared storage (the
        gang-checkpoint contract)."""
        from raydp_tpu.serve.servable import export_bundle

        model = self._build_model()
        variables = self.get_model()   # raises if fit() has not run
        custom = (self.batch_preprocessor is not None
                  or self.columns_spec is not None)
        # non-custom models consume only "features"; the custom path ships
        # the full spec and the replica synthesizes absent entries (the
        # label) as zeros, like predict()
        columns = (dict(self._columns()) if custom
                   else {"features": (self.feature_columns,
                                      self.feature_dtype)})
        bundle = {
            "model": model,
            "columns": columns,
            "custom": custom,
            "preprocessor": self.batch_preprocessor,
            "compute_dtype": self.compute_dtype,
            "takes_train": _takes_train(model),
        }
        return export_bundle(export_dir, "flax", bundle, variables)

    # -------------------------------------------------------------- get_model
    def get_model(self):
        """Trained Flax variables (parity: get_model from checkpoint,
        torch/estimator.py:392-396)."""
        if self._result is None:
            raise RuntimeError("call fit()/fit_on_frame() first")
        out = {"params": self._result.state.params}
        bstats = getattr(self._result.state, "batch_stats", None)
        if bstats is not None:
            out["batch_stats"] = bstats
        return out

    def get_state(self):
        if self._result is None:
            raise RuntimeError("call fit()/fit_on_frame() first")
        return self._result.state
