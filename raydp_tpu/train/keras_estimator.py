"""KerasEstimator: the TFEstimator-parity trainer on Keras 3's JAX backend.

Parity map (reference tf/estimator.py):

- the estimator owns a serialized model *spec*, not a live object — the
  reference serializes the model to JSON and optimizer/loss/metrics through
  keras serialize (96-149) so they rebuild inside workers; here
  ``keras.saving.serialize_keras_object`` round-trips them the same way.
- ``train_func`` opens a ``tf.distribute.MultiWorkerMirroredStrategy`` scope →
  compile → ``to_tf`` dataset → ``model.fit`` (171-210); here the default
  training path is a **jitted stateless loop** over the device mesh — Keras 3's
  functional API (``model.stateless_call`` / ``optimizer.stateless_apply`` /
  stateless metrics) inside ONE ``jax.jit`` step with donated buffers, fed by
  the same :class:`~raydp_tpu.data.feed.DeviceFeed` streaming/prefetching
  pipeline the FlaxEstimator uses. That removes ``model.fit``'s per-batch
  Python dispatch (the 14× gap of round 2); collectives are XLA collectives
  over ICI, no TF runtime involved. Exotic ``fit_kwargs`` fall back to the
  stock ``model.fit`` path.
- ``fit_gang`` trains as a multi-process gang under ``jax.distributed`` —
  each rank feeds its shard of every global batch, parameters replicate, XLA
  inserts the gradient collectives (the MWMS-across-hosts analogue).
- ``merge_feature_columns`` via ray.data ``Concatenator`` (237-260) — the host
  feed stacks feature columns into one matrix the same way.
- chief-only checkpoint (202-210) — process-0 saves ``model.keras`` per epoch.
- same ``fit`` / ``fit_on_spark`` / ``get_model`` surface (212-310) —
  ``fit`` / ``fit_on_frame`` / ``get_model`` below.

Keras must run on the JAX backend; this module asserts it (the reference
equally hard-requires TF inside its workers).
"""

from __future__ import annotations

import os
import tempfile
from typing import Callable, Dict, List, Optional, Sequence, Union

import numpy as np

from raydp_tpu import profiler
from raydp_tpu.log import get_logger
from raydp_tpu.train import loop
from raydp_tpu.train.estimator import (
    EstimatorInterface,
    FrameEstimatorInterface,
    save_epoch_now,
)
from raydp_tpu.train.flax_estimator import TrainingResult

logger = get_logger("train.keras_estimator")

os.environ.setdefault("KERAS_BACKEND", "jax")


def _import_keras():
    import keras

    if keras.backend.backend() != "jax":
        raise RuntimeError(
            "raydp_tpu.KerasEstimator requires the JAX backend; set "
            "KERAS_BACKEND=jax before the first keras import "
            f"(found {keras.backend.backend()!r})")
    return keras


class KerasEstimator(EstimatorInterface, FrameEstimatorInterface):
    """sklearn-style estimator for Keras models, SPMD over the device mesh."""

    def __init__(
        self,
        model=None,
        model_builder: Optional[Callable] = None,
        optimizer="adam",
        loss: Union[str, Callable] = "mse",
        metrics: Optional[Sequence] = None,
        feature_columns: Optional[Sequence[str]] = None,
        label_column: Optional[str] = None,
        batch_size: int = 64,
        num_epochs: int = 10,
        shuffle: bool = True,
        data_parallel: bool = True,
        checkpoint_dir: Optional[str] = None,
        seed: int = 0,
        feature_dtype=np.float32,
        label_dtype=np.float32,
        drop_last: bool = True,
        fit_kwargs: Optional[Dict] = None,
        checkpoint_interval: int = 1,
        prefetch_to_device: Optional[int] = None,
    ):
        keras = _import_keras()
        if model is None and model_builder is None:
            raise ValueError("pass model or model_builder")
        # serialize the spec so fit() rebuilds fresh objects each run
        # (parity: tf/estimator.py:96-149 JSON/keras-serialize round-trip)
        self._model_spec = (keras.saving.serialize_keras_object(model)
                            if model is not None else None)
        self._model_builder = model_builder
        self._optimizer_spec = keras.saving.serialize_keras_object(
            keras.optimizers.get(optimizer))
        self._loss = loss
        self._metrics = list(metrics or [])
        self.feature_columns = list(feature_columns or [])
        self.label_column = label_column
        self.batch_size = batch_size
        self.num_epochs = num_epochs
        self.shuffle = shuffle
        self.data_parallel = data_parallel
        self.checkpoint_dir = checkpoint_dir
        self.seed = seed
        self.feature_dtype = feature_dtype
        self.label_dtype = label_dtype
        self.drop_last = drop_last
        self.fit_kwargs = dict(fit_kwargs or {})
        #: checkpoint every N-th epoch, final epoch always (see the flax
        #: twin; model.save of a keras archive can outweigh a resident epoch)
        self.checkpoint_interval = max(1, int(checkpoint_interval))
        #: device-placed batches the streaming feed keeps ahead of the train
        #: step (None = the feed default / RDT_PREFETCH_TO_DEVICE, 2) — see
        #: the flax twin; bit-identical to synchronous placement
        self.prefetch_to_device = prefetch_to_device
        self._trained_model = None
        self._result: Optional[TrainingResult] = None

    # ------------------------------------------------------------------ build
    def _build_model(self):
        keras = _import_keras()
        if self._model_spec is not None:
            return keras.saving.deserialize_keras_object(self._model_spec)
        return self._model_builder()

    def _maybe_distribute(self):
        """DataParallel over all local devices when >1 (the MWMS-scope
        analogue, tf/estimator.py:173-176). Returns the caller's previous
        distribution so ``fit`` can restore it."""
        keras = _import_keras()
        previous = keras.distribution.distribution()
        import jax
        if self.data_parallel and len(jax.devices()) > 1:
            keras.distribution.set_distribution(
                keras.distribution.DataParallel())
        return previous

    def _materialize(self, ds):
        """Dataset → (features [n, d], labels [n]) host arrays.

        Feature columns merge into one contiguous matrix (parity:
        ``merge_feature_columns`` Concatenator, tf/estimator.py:237-260)."""
        if ds is None:
            return None
        if not self.feature_columns or self.label_column is None:
            raise ValueError("pass feature_columns and label_column")
        table = ds.to_arrow()
        feats = np.stack(
            [table.column(c).to_numpy(zero_copy_only=False)
             .astype(self.feature_dtype, copy=False)
             for c in self.feature_columns], axis=1)
        labels = (table.column(self.label_column)
                  .to_numpy(zero_copy_only=False)
                  .astype(self.label_dtype, copy=False))
        return feats, labels

    def _trim(self, arrays, n_devices: int):
        """Static shapes under data parallelism: drop the ragged tail so every
        batch splits evenly over devices (same reason the DeviceFeed drops
        remainders — a changing batch dim retraces under jit)."""
        feats, labels = arrays
        if not self.drop_last:
            return feats, labels
        step = self.batch_size
        n = (len(feats) // step) * step
        if n == 0:
            n = (len(feats) // n_devices) * n_devices
        return (feats[:n], labels[:n]) if n else (feats, labels)

    # -------------------------------------------------------------------- fit
    def fit(self, train_ds, evaluate_ds=None, max_retries: int = 0
            ) -> TrainingResult:
        """Train. Default: the jitted stateless loop (fast path). Any custom
        ``fit_kwargs`` (validation_split, class_weight, ...) fall back to
        stock ``model.fit`` semantics."""
        if not self.fit_kwargs:
            return self._fit_stateless(train_ds, evaluate_ds,
                                       max_retries=max_retries)
        return self._fit_keras_loop(train_ds, evaluate_ds,
                                    max_retries=max_retries)

    # ---------------------------------------------------- stateless fast path
    def _columns(self) -> Dict:
        if not self.feature_columns or self.label_column is None:
            raise ValueError("pass feature_columns and label_column")
        return {
            "features": (list(self.feature_columns), self.feature_dtype),
            "label": (self.label_column, self.label_dtype),
        }

    def _mesh(self):
        import jax

        from raydp_tpu.parallel import make_mesh
        devices = jax.devices() if self.data_parallel else jax.devices()[:1]
        return make_mesh(devices=devices)

    def _fit_stateless(self, train_ds, evaluate_ds=None, max_retries: int = 0
                       ) -> TrainingResult:
        profiler.watch_jit_builds()
        mesh = self._mesh()
        ckpt_dir = self.checkpoint_dir or tempfile.mkdtemp(
            prefix="rdt-keras-ckpt-")
        os.makedirs(ckpt_dir, exist_ok=True)
        # the step takes no validity mask, so no tail is ever padded
        feeds = loop.plan_feeds(
            train_ds, evaluate_ds, self._columns(), mesh, self.batch_size,
            shuffle=self.shuffle, seed=self.seed, drop_last=self.drop_last,
            prefetch_to_device=self.prefetch_to_device, may_pad=False,
            seq=False)
        model, history = self._stateless_train_loop(
            mesh, feeds, ckpt_dir, max_retries=max_retries)
        self._trained_model = model
        self._result = TrainingResult(state=model, history=history,
                                      checkpoint_dir=ckpt_dir)
        return self._result

    def _metric_objects(self):
        """Fresh metric instances (spec round-trip so repeated fits and rank
        processes never share stateful metric objects). ``"accuracy"`` is
        resolved against the loss the way ``model.compile`` does — the bare
        ``Accuracy`` metric is exact-match and reads ~0 on probabilities."""
        keras = _import_keras()
        loss_name = (self._loss if isinstance(self._loss, str)
                     else getattr(self._loss, "name", ""))
        out = []
        for m in self._metrics:
            if isinstance(m, str) and m in ("accuracy", "acc"):
                if "binary" in loss_name:
                    out.append(keras.metrics.BinaryAccuracy(name="accuracy"))
                elif "sparse_categorical" in loss_name:
                    out.append(keras.metrics.SparseCategoricalAccuracy(
                        name="accuracy"))
                elif "categorical" in loss_name:
                    out.append(keras.metrics.CategoricalAccuracy(
                        name="accuracy"))
                else:
                    out.append(keras.metrics.get(m))
            elif isinstance(m, str):
                out.append(keras.metrics.get(m))
            else:
                out.append(keras.saving.deserialize_keras_object(
                    keras.saving.serialize_keras_object(m)))
        return out

    def _stateless_train_loop(self, mesh, feeds, ckpt_dir: str,
                              max_retries: int = 0, resume: bool = False):
        """One jitted train step over stateless Keras calls; in-jit loss and
        metric accumulation; donated state buffers; chief-only per-epoch
        ``model.keras`` checkpoint with a JSON epoch/history sidecar. Built
        here and handed to the loop (``train/loop.py``) as its
        :class:`~loop.Trainee`.

        Parity: the role ``model.fit`` under an MWMS scope plays for the
        reference (tf/estimator.py:171-210) — redesigned as an XLA-compiled
        step because per-batch Python dispatch is what made the round-2 Keras
        path 14× slower than the Flax path on the same chip."""
        import json as _json

        import jax
        import jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec

        keras = _import_keras()

        model = optimizer = None     # made by fresh() / restore(), below
        loss_obj = keras.losses.get(self._loss)
        train_metrics = self._metric_objects()
        eval_metrics = self._metric_objects()
        cache, eval_cache = feeds.cache, feeds.eval_cache

        saved_model = os.path.join(ckpt_dir, "model.keras")
        saved_meta = os.path.join(ckpt_dir, "state.json")
        saved_opt = os.path.join(ckpt_dir, "optimizer.npz")

        def _ckpt_available():
            return (os.path.exists(saved_model)
                    and os.path.exists(saved_meta))

        if not resume and self.checkpoint_dir and _ckpt_available():
            # checkpoint.warn_if_reused_dir, for the keras
            # model.keras/state.json format: this fit will overwrite, but
            # the user should learn the dir held an earlier run before a
            # later resume silently adopts whichever run wrote last
            logger.warning(
                "checkpoint_dir %r already holds a model.keras/state.json "
                "from an earlier run; this fit overwrites them — use a fresh "
                "checkpoint_dir per run to keep runs separate", ckpt_dir)

        # weights + optimizer slots are built from one sample batch's shapes
        first = feeds.first_batch(self.batch_size, self.drop_last)

        rep = NamedSharding(mesh, PartitionSpec())

        def _carry(opt_values=None, chief_sync=False):
            """The current model's and optimizer's values placed on the mesh,
            ``(tv, ntv, ov, None, None)``. ``chief_sync``: on a restored
            gang, every rank takes the CHIEF's host values — a rank that read
            a staler file version must not train different weights (the
            collective math would silently diverge)."""
            groups = [[v.value for v in model.trainable_variables],
                      [v.value for v in model.non_trainable_variables],
                      opt_values if opt_values is not None
                      else [v.value for v in optimizer.variables]]
            if chief_sync and jax.process_count() > 1:
                from jax.experimental import multihost_utils
                groups = multihost_utils.broadcast_one_to_all(
                    [[np.asarray(v) for v in g] for g in groups])
            return tuple([jax.device_put(jnp.asarray(v), rep) for v in g]
                         for g in groups) + (None, None)

        def _saved_opt_values():
            """Optimizer slots (Adam moments, iteration) from the sidecar —
            resuming with zeroed slots would silently diverge from an
            uninterrupted run (the FlaxEstimator checkpoints its full
            TrainState; this is the keras-format equivalent). None: start
            the slots fresh."""
            if not os.path.exists(saved_opt):
                return None
            with np.load(saved_opt) as z:
                vals = [z[f"v{i}"] for i in range(len(z.files))]
            if len(vals) != len(optimizer.variables):
                logger.warning("optimizer sidecar has %d slots, expected "
                               "%d; starting slots fresh", len(vals),
                               len(optimizer.variables))
                return None
            return vals

        def _match_rank(y, preds):
            if y.ndim == preds.ndim - 1 and preds.shape[-1] == 1:
                return y[..., None]
            return y

        def _loss_and_updates(tv, ntv, x, y):
            preds, ntv2 = model.stateless_call(tv, ntv, x, training=True)
            y2 = _match_rank(y, preds)
            # keras.losses.get("mse") yields the per-sample FUNCTION; Loss
            # instances already reduce — jnp.mean covers both
            loss = jnp.mean(loss_obj(y2, preds))
            return loss, (preds, y2, ntv2)

        grad_fn = jax.value_and_grad(_loss_and_updates, has_aux=True)

        def train_step(tv, ntv, ov, mvars, loss_sum, batch):
            x, y = batch["features"], batch["label"]
            (loss, (preds, y2, ntv2)), grads = grad_fn(tv, ntv, x, y)
            tv2, ov2 = optimizer.stateless_apply(ov, grads, tv)
            mvars2 = tuple(
                tuple(m.stateless_update_state(list(mv), y2, preds))
                for m, mv in zip(train_metrics, mvars))
            return tv2, ntv2, ov2, mvars2, loss_sum + loss

        def eval_step(tv, ntv, mvars, loss_sum, rows, batch):
            x, y = batch["features"], batch["label"]
            preds, _ = model.stateless_call(tv, ntv, x, training=False)
            y2 = _match_rank(y, preds)
            loss = jnp.mean(loss_obj(y2, preds))
            mvars2 = tuple(
                tuple(m.stateless_update_state(list(mv), y2, preds))
                for m, mv in zip(eval_metrics, mvars))
            return mvars2, loss_sum + loss * y.shape[0], rows + y.shape[0]

        jit_train = jax.jit(train_step, donate_argnums=(0, 1, 2, 3, 4))
        jit_eval = jax.jit(eval_step, donate_argnums=(2, 3, 4))

        def _zeros(metrics, sums):
            """The program that makes a pass's starting accumulators,
            ``(mvars, *sums)``, replicated on the mesh as the steps return
            them (``loop``'s module text says why a program). The metrics'
            initial states are read off the keras variables here, once: the
            device copies are donated into the jitted steps."""
            init = tuple(tuple(np.asarray(v.value) for v in m.variables)
                         for m in metrics)
            return jax.jit(
                lambda: (tuple(tuple(jnp.asarray(v) for v in t)
                               for t in init),
                         *(jnp.zeros((), jnp.float32) for _ in range(sums))),
                out_shardings=rep)

        train_zeros = _zeros(train_metrics, 1)
        eval_zeros = _zeros(eval_metrics, 2)    # the loss sum, the row count

        def _results(metrics, mvars):
            for m, mv in zip(metrics, mvars):
                yield m.name, float(m.stateless_result(list(mv)))

        def _host_val(a):
            """Host copy of a replicated array (the local replica shard IS
            the full value — collective-free even across processes)."""
            if hasattr(a, "addressable_data"):
                return np.asarray(a.addressable_data(0))
            return np.asarray(a)

        def _sync_model(carry):
            """Write the device state back into the keras variables."""
            for var, val in zip(model.trainable_variables, carry[0]):
                var.assign(_host_val(val))
            for var, val in zip(model.non_trainable_variables, carry[1]):
                var.assign(_host_val(val))

        chief = jax.process_index() == 0

        def save(carry, epoch, history):
            if not chief:
                return
            # chief-only checkpoint (parity: tf/estimator.py:202-210) +
            # optimizer sidecar so a resume keeps Adam slots. Every file
            # lands via tmp+rename and the meta sidecar is written LAST: a
            # crash mid-save leaves the previous complete trio, never a torn
            # archive resume trusts
            _sync_model(carry)
            tmp_model = saved_model + ".tmp.keras"
            model.save(tmp_model)
            os.replace(tmp_model, saved_model)
            tmp_opt = saved_opt + ".tmp.npz"
            np.savez(tmp_opt, **{
                f"v{i}": _host_val(v) for i, v in enumerate(carry[2])})
            os.replace(tmp_opt, saved_opt)
            tmp_meta = saved_meta + ".tmp"
            with open(tmp_meta, "w") as f:
                _json.dump({"epoch": epoch, "history": history}, f)
            os.replace(tmp_meta, saved_meta)

        def restore(carry, max_step):
            """The directory's one checkpoint (each save overwrites the
            last: ``max_step`` has nothing to choose from). A gang's ranks
            must resume the SAME epoch or their collective counts diverge
            and the first psum deadlocks: they take the CHIEF's view of the
            sidecar (lagging visibility on networked storage can make ranks
            disagree), exactly like checkpoint._latest_agreed."""
            nonlocal model, optimizer
            del carry, max_step
            meta = None
            if _ckpt_available():
                with open(saved_meta) as f:
                    meta = _json.load(f)
            chief_epoch = -1 if meta is None else int(meta["epoch"])
            if jax.process_count() > 1:
                from jax.experimental import multihost_utils
                chief_epoch = int(multihost_utils.broadcast_one_to_all(
                    np.int32(chief_epoch)))
            if chief_epoch < 0:
                return None
            if meta is None:
                raise FileNotFoundError(
                    f"chief resumes keras checkpoint epoch {chief_epoch} "
                    f"but this rank cannot see {ckpt_dir!r}; gangs need "
                    "shared checkpoint storage")
            model = keras.saving.load_model(saved_model)
            optimizer = keras.saving.deserialize_keras_object(
                self._optimizer_spec)
            optimizer.build(model.trainable_variables)
            return (_carry(_saved_opt_values(), chief_sync=True), chief_epoch,
                    list(meta["history"])[:chief_epoch + 1])

        def fresh():
            """The model and the optimizer from their specs and the seed."""
            nonlocal model, optimizer
            keras.utils.set_random_seed(self.seed)
            model = self._build_model()
            if not model.built:
                model.build(first["features"][:1].shape)
            optimizer = keras.saving.deserialize_keras_object(
                self._optimizer_spec)
            optimizer.build(model.trainable_variables)
            return _carry()

        trainee = loop.Trainee(
            carry=fresh(),
            step=lambda carry, batch: jit_train(*carry, batch),
            zeros=lambda carry: (*carry[:3], *train_zeros()),
            read=lambda carry: (carry[4], _results(train_metrics, carry[3])),
            save=save, restore=restore, fresh=fresh,
            train_key="{}", eval_key="val_{}")

        if cache is not None:
            # the resident epoch: the shared scan program built by
            # DeviceEpochCache round the step in scan form
            from raydp_tpu.parallel.mesh import batch_sharding

            epoch_fn, _ = cache.make_epoch_fn(
                lambda carry, batch: train_step(*carry, batch),
                self.batch_size, self.shuffle,
                batch_sharding=batch_sharding(mesh))
            jit_epoch = jax.jit(epoch_fn, donate_argnums=(0,))
            trainee.epoch = lambda carry, key: jit_epoch(carry, cache.arrays,
                                                         key)

        if feeds.eval_feed is not None or eval_cache is not None:
            # an eval pass's accumulators are what ``jit_eval`` returns and
            # ``eval_zeros`` makes: (mvars, loss sum, row count)
            def eval_read(acc):
                rows = float(acc[2])
                yield "loss", float(acc[1]) / rows if rows else float("nan")
                yield from _results(eval_metrics, acc[0])

            trainee.evaluation = ev = loop.Evaluation(
                zeros=eval_zeros, read=eval_read,
                step=lambda carry, acc, batch: jit_eval(
                    carry[0], carry[1], *acc, batch))
        if eval_cache is not None:
            # every full batch of the resident eval set as ONE scan
            # dispatch, built by the shared make_epoch_fn. The carry rides
            # tv/ntv through unchanged — not donated
            from raydp_tpu.parallel.mesh import batch_sharding

            eval_epoch_fn, _ = eval_cache.make_epoch_fn(
                lambda c, batch: (c[0], c[1], *eval_step(*c, batch)),
                self.batch_size, shuffle=False,
                batch_sharding=batch_sharding(mesh))
            jit_eval_epoch = jax.jit(eval_epoch_fn)
            ev.epoch = lambda carry, acc: jit_eval_epoch(
                (carry[0], carry[1], *acc), eval_cache.arrays,
                jax.random.PRNGKey(0))[2:]      # the key: unused, no shuffle

        # Keras has no ``callbacks`` argument: the loop's list is empty
        carry, history = loop.run(
            trainee, feeds, num_epochs=self.num_epochs,
            batch_size=self.batch_size, seed=self.seed,
            checkpoint_interval=self.checkpoint_interval,
            max_retries=max_retries, resume=resume)
        _sync_model(carry)
        return model, history

    def _fit_keras_loop(self, train_ds, evaluate_ds=None, max_retries: int = 0
                        ) -> TrainingResult:
        import jax
        keras = _import_keras()

        previous_distribution = self._maybe_distribute()
        try:
            keras.utils.set_random_seed(self.seed)
            model = self._build_model()
            optimizer = keras.saving.deserialize_keras_object(
                self._optimizer_spec)
            model.compile(optimizer=optimizer, loss=self._loss,
                          metrics=list(self._metrics))

            n_dev = len(jax.devices()) if self.data_parallel else 1
            x, y = self._trim(self._materialize(train_ds), n_dev)
            validation = self._materialize(evaluate_ds)
            if validation is not None and n_dev > 1:
                # validation batches must also split evenly over devices
                vx, vy = validation
                n = (len(vx) // n_dev) * n_dev
                validation = (vx[:n], vy[:n]) if n else None

            ckpt_dir = self.checkpoint_dir or tempfile.mkdtemp(
                prefix="rdt-keras-ckpt-")
            os.makedirs(ckpt_dir, exist_ok=True)
            saved_marker = {"saved": False}  # only THIS run's checkpoint may
            # be adopted by a retry — never a stale file from a reused dir
            callbacks = []
            if jax.process_index() == 0:
                # chief-only checkpoint (parity: tf/estimator.py:202-210);
                # the checkpoint_interval knob applies here too (keras's
                # ModelCheckpoint has no epoch-interval arg)
                interval = self.checkpoint_interval
                save_path = os.path.join(ckpt_dir, "model.keras")
                num_epochs = self.num_epochs

                class _IntervalCheckpoint(keras.callbacks.Callback):
                    def on_epoch_end(self, epoch, logs=None):
                        if save_epoch_now(epoch, interval, num_epochs):
                            self.model.save(save_path)
                            saved_marker["saved"] = True

                callbacks.append(_IntervalCheckpoint())

            # per-epoch wall times (keras's History has none), so throughput
            # can be reported steady-state like the FlaxEstimator's
            import time as _time

            epoch_times: list = []

            class _EpochTimer(keras.callbacks.Callback):
                """Times the TRAIN portion of each epoch (clock stops when
                validation starts), matching FlaxEstimator's train-only
                ``samples_per_s`` so bench comparisons are like-for-like."""

                def on_train_begin(self, logs=None):
                    epoch_times.clear()  # retries restart the clock

                def on_epoch_begin(self, epoch, logs=None):
                    self._t0 = _time.perf_counter()
                    self._train_end = None

                def on_test_begin(self, logs=None):
                    if getattr(self, "_t0", None) is not None \
                            and self._train_end is None:
                        self._train_end = _time.perf_counter()

                def on_epoch_end(self, epoch, logs=None):
                    end = self._train_end or _time.perf_counter()
                    epoch_times.append(end - self._t0)

            # first in the list: later callbacks' epoch-end work (e.g. the
            # ModelCheckpoint save) must not land inside the timed window
            callbacks.insert(0, _EpochTimer())

            attempt = 0
            while True:
                try:
                    hist = model.fit(
                        x, y, batch_size=self.batch_size,
                        epochs=self.num_epochs,
                        shuffle=self.shuffle,
                        validation_data=validation,
                        callbacks=callbacks,
                        verbose=0,
                        **self.fit_kwargs)
                    break
                except (KeyboardInterrupt, SystemExit):
                    raise
                except Exception as e:  # noqa: BLE001 - FailureConfig parity
                    attempt += 1
                    if attempt > max_retries:
                        raise
                    saved = os.path.join(ckpt_dir, "model.keras")
                    if (jax.process_count() == 1 and saved_marker["saved"]
                            and os.path.exists(saved)):
                        logger.warning("keras fit failed (%s); retry %d/%d "
                                       "from checkpoint", e, attempt,
                                       max_retries)
                        model = keras.saving.load_model(saved)
                    else:
                        # multi-host (or no checkpoint yet): a chief-only
                        # checkpoint cannot restore every replica consistently,
                        # so rebuild from the spec with the same seed — the
                        # reference's replay-from-scratch semantics
                        logger.warning("keras fit failed (%s); retry %d/%d "
                                       "from scratch", e, attempt, max_retries)
                        keras.utils.set_random_seed(self.seed)
                        model = self._build_model()
                        model.compile(
                            optimizer=keras.saving.deserialize_keras_object(
                                self._optimizer_spec),
                            loss=self._loss, metrics=list(self._metrics))

            n_rows = int(np.asarray(y).shape[0])
            vs = float(self.fit_kwargs.get("validation_split", 0.0) or 0.0)
            if 0.0 < vs < 1.0:
                # keras holds the tail split out of training; throughput must
                # count only trained rows
                n_rows = int(n_rows * (1.0 - vs))
            history = []
            for i in range(len(hist.epoch)):
                row = {"epoch": i,
                       **{k: float(v[i]) for k, v in hist.history.items()}}
                if i < len(epoch_times) and epoch_times[i] > 0:
                    row["epoch_time_s"] = epoch_times[i]
                    row["samples_per_s"] = n_rows / epoch_times[i]
                    from raydp_tpu import metrics as rdt_metrics
                    rdt_metrics.observe("train_epoch_seconds",
                                        epoch_times[i])
                history.append(row)
            self._trained_model = model
            self._result = TrainingResult(state=model, history=history,
                                          checkpoint_dir=ckpt_dir)
            logger.info("keras fit done: %s",
                        history[-1] if history else "{}")
            return self._result
        finally:
            keras.distribution.set_distribution(previous_distribution)

    # --------------------------------------------------------------- fit_gang
    def fit_gang(self, train_ds, evaluate_ds=None, *, num_workers: int = 2,
                 max_retries: int = 0, job_name: Optional[str] = None,
                 run_timeout: float = 3600.0, start_timeout: float = 180.0,
                 worker_env: Optional[Dict[str, str]] = None
                 ) -> TrainingResult:
        """Train as a gang of ``num_workers`` processes under one global
        ``jax.distributed`` mesh — the across-hosts MWMS analogue
        (tf/estimator.py:171-210 runs one ``train_func`` per Ray Train
        worker). Each rank feeds its slice of every global batch through
        :class:`GangShardIterator`; parameters replicate; XLA inserts the
        gradient collectives. The chief saves ``model.keras`` per epoch and a
        failed gang restarts from it (``checkpoint_dir`` must be shared
        storage on multi-machine gangs, as for FlaxEstimator.fit_gang)."""
        import copy
        import uuid as _uuid

        from raydp_tpu.spmd.job import create_spmd_job

        if self.fit_kwargs:
            # the gang runs only the stateless loop; silently dropping
            # model.fit-only options would mis-train without warning
            raise ValueError(
                "fit_gang does not support fit_kwargs "
                f"({sorted(self.fit_kwargs)}); use fit() for stock "
                "model.fit semantics")
        ckpt_dir = self.checkpoint_dir or tempfile.mkdtemp(
            prefix="rdt-keras-gang-")
        if self.checkpoint_dir and (
                os.path.exists(os.path.join(ckpt_dir, "model.keras"))
                or os.path.exists(os.path.join(ckpt_dir, "state.json"))):
            # gang ranks run with resume=True by design, so a fresh fit_gang
            # pointed at a reused dir silently ADOPTS the earlier run's
            # checkpoint — warn before the ranks start (the flax twin's
            # warn_if_reused_dir, for the keras model.keras/state.json format)
            logger.warning(
                "checkpoint_dir %r already holds a model.keras/state.json "
                "from an earlier run; this gang will RESUME from it — use a "
                "fresh checkpoint_dir per run to train from scratch",
                ckpt_dir)
        train_payload = train_ds.portable()
        eval_payload = (evaluate_ds.portable()
                        if evaluate_ds is not None else None)

        est = copy.copy(self)
        est._trained_model = None
        est._result = None
        est.checkpoint_dir = ckpt_dir

        def _rank_fit(ctx):
            return est._gang_rank_fit(ctx, train_payload, eval_payload,
                                      ckpt_dir)

        job = create_spmd_job(
            job_name or f"kerasfit-{_uuid.uuid4().hex[:6]}", num_workers,
            jax_distributed=True, env=worker_env, timeout=start_timeout)
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
            except Exception as e:  # noqa: BLE001 - gang restart
                job.stop()
                attempts += 1
                if attempts > max_retries:
                    raise
                logger.warning("keras gang fit failed (%s); restarting from "
                               "last checkpoint (retry %d/%d)", e, attempts,
                               max_retries)

        history = results[0]
        keras = _import_keras()
        saved = os.path.join(ckpt_dir, "model.keras")
        model = keras.saving.load_model(saved) if os.path.exists(saved) \
            else None
        self._trained_model = model
        self._result = TrainingResult(state=model, history=history,
                                      checkpoint_dir=ckpt_dir)
        return self._result

    def _gang_rank_fit(self, ctx, train_payload, eval_payload, ckpt_dir: str):
        """Runs inside each SPMD rank: global mesh, rank-sharded host feed,
        the same jitted stateless loop, resume from the chief checkpoint."""
        from raydp_tpu.parallel import make_mesh

        mesh = make_mesh()  # jax.devices() is global under the gang
        from raydp_tpu.train.checkpoint import ensure_shared_dir
        ensure_shared_dir(ckpt_dir, "rdt_keras_ckpt_probe")
        feeds = loop.gang_feeds(
            ctx, train_payload, eval_payload, self._columns(), mesh,
            self.batch_size, shuffle=self.shuffle, seed=self.seed,
            prefetch_to_device=self.prefetch_to_device, may_pad=False,
            seq=False)
        _, history = self._stateless_train_loop(
            mesh, feeds, ckpt_dir, max_retries=0, resume=True)
        return history

    # ----------------------------------------------------------- fit_on_frame
    # ------------------------------------------------------------ partial_fit
    def _partial_fit_epoch(self, ds, epoch: int) -> Dict[str, float]:
        """One online update, keras flavor: the compiled model persists on
        the estimator and ``model.fit(epochs=1)`` advances it over the
        epoch's materialized rows (keras fit is incremental by contract —
        weights are never reinitialized between calls)."""
        import time as _time

        keras = _import_keras()
        model = self._trained_model
        if model is None or not getattr(self, "_online_compiled", False):
            keras.utils.set_random_seed(self.seed)
            model = self._build_model()
            model.compile(optimizer=keras.saving.deserialize_keras_object(
                self._optimizer_spec), loss=self._loss,
                metrics=list(self._metrics))
            self._trained_model = model
            self._online_compiled = True
            self._online_history: List[Dict[str, float]] = []
        t0 = _time.perf_counter()
        x, y = self._materialize(ds)
        hist = model.fit(x, y, batch_size=self.batch_size, epochs=1,
                         shuffle=False, verbose=0)
        dt = _time.perf_counter() - t0
        report = {"epoch": epoch, "epoch_time_s": dt,
                  "steps": int(np.ceil(len(x) / self.batch_size)),
                  "samples_per_s": len(x) / dt if dt > 0 else 0.0}
        for k, v in hist.history.items():
            report[f"train_{k}" if not k.startswith("train_") else k] = \
                float(v[-1])
        if "train_loss" in report:
            report["train_loss"] = float(report["train_loss"])
        self._online_history.append(report)
        self._result = TrainingResult(state=None,
                                      history=self._online_history)
        return report

    def fit_on_frame(self, train_df, evaluate_df=None, *,
                     fs_directory: Optional[str] = None,
                     stop_etl_after_conversion: bool = False,
                     max_retries: int = 0) -> TrainingResult:
        train_ds, eval_ds = self._convert_frames(
            train_df, evaluate_df, fs_directory=fs_directory,
            stop_etl_after_conversion=stop_etl_after_conversion)
        return self.fit(train_ds, eval_ds, max_retries=max_retries)

    # -------------------------------------------------------------- get_model
    def get_model(self):
        """The trained keras model (parity: tf/estimator.py:306-310)."""
        if self._trained_model is None:
            raise RuntimeError("call fit()/fit_on_frame() first")
        return self._trained_model

    # --------------------------------------------------------- export_serving
    def export_serving(self, export_dir: str) -> str:
        """Serving-bundle export, keras flavor: the trained
        trainable/non-trainable variable lists go through
        ``train/checkpoint.py`` (they are what ``stateless_call`` consumes —
        the restored checkpoint is the weight truth; the pickled model
        object only contributes the architecture), plus the feature-column
        spec :meth:`predict` uses."""
        from raydp_tpu.serve.servable import export_bundle

        model = self.get_model()   # raises if fit() has not run
        state = {
            "tv": [np.asarray(v) for v in model.trainable_variables],
            "ntv": [np.asarray(v) for v in model.non_trainable_variables],
        }
        bundle = {
            "model": model,
            "columns": {"features": (self.feature_columns,
                                     self.feature_dtype)},
        }
        return export_bundle(export_dir, "keras", bundle, state)

    # ---------------------------------------------------------------- predict
    def predict(self, ds, batch_size: Optional[int] = None) -> np.ndarray:
        """Predictions over a dataset's feature columns as one host array
        (row order = dataset block order) — the flax twin's convenience for
        the keras path, via the same jitted ``stateless_call`` machinery the
        train loop uses (one dispatch per batch; ``model.predict``'s own
        per-batch Python loop is what made the r2 keras path slow)."""
        import jax
        import jax.numpy as jnp

        from raydp_tpu.data.feed import HostBatchIterator

        model = self.get_model()   # raises if fit has not run

        trainable = [jnp.asarray(v) for v in model.trainable_variables]
        non_trainable = [jnp.asarray(v)
                         for v in model.non_trainable_variables]

        @jax.jit
        def infer(tv, ntv, inputs):
            preds, _ = model.stateless_call(tv, ntv, inputs, training=False)
            if preds.ndim >= 2 and preds.shape[-1] == 1:
                preds = preds.squeeze(-1)
            return preds.astype(jnp.float32)

        cols = {"features": (self.feature_columns, self.feature_dtype)}
        it = HostBatchIterator(ds, batch_size or self.batch_size, cols,
                               shuffle=False, drop_remainder=False)
        out = [np.asarray(infer(trainable, non_trainable,
                                jnp.asarray(batch["features"])))
               for batch in it]
        if not out:
            return np.empty((0,), np.float32)
        return np.concatenate(out, axis=0)
