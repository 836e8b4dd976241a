"""The epochs of a fit, and the plan of its feeds — once, for every estimator.

An estimator builds its state and its jitted step and hands them over as a
:class:`Trainee`; this module decides where the batches come from
(:func:`plan_feeds`, :func:`gang_feeds`) and runs the epochs (:func:`run`).
The loop never asks which estimator it serves: the carry is opaque to it, a
report's loss and metric names are the estimator's (``train_key`` /
``eval_key``), the timing keys are the loop's.

An epoch's loss sum and metric states ride the carry, THROUGH the jitted
steps, and are not collected as a host-side list: under a multi-process gang
an eager op over global arrays (a ``jnp.stack`` of per-step losses) is a
cross-process computation every process must dispatch in the same order, and
a rank one step behind deadlocks the gang. The only host reads are of
replicated scalars at the epoch's end. An epoch's zeros come from a program
of the estimator's (``Trainee.zeros``), typed and placed as the step returns
them: the step is then built once a fit (``flax_estimator._epoch_zeros``).
"""

from __future__ import annotations

import contextlib
import time
from dataclasses import dataclass
from typing import Any, Callable, Dict, Iterable, List, Optional, Tuple

import numpy as np

from raydp_tpu import faults, profiler
from raydp_tpu import metrics as rdt_metrics
from raydp_tpu.log import get_logger
from raydp_tpu.train.estimator import save_epoch_now

logger = get_logger("train.loop")

#: a report's (name, value) pairs, each filed under the estimator's key for it
Pairs = Iterable[Tuple[str, float]]


# ------------------------------------------------------------------ the feeds
@dataclass
class Feeds:
    """Where a fit's batches come from: the train set resident on the device
    (``cache``) or streamed (``feed``), and the same for the eval set."""

    feed: Any = None            # DeviceFeed
    cache: Any = None           # DeviceEpochCache
    eval_feed: Any = None
    eval_cache: Any = None
    #: the eval set's ragged final batch is evaluated (False: dropped) ...
    eval_tail_ok: bool = False
    #: ... zero-padded to a full batch under a validity mask (False: as it is)
    eval_tail_pad: bool = False

    def first_batch(self, batch_size: int, drop_last: bool) -> Dict:
        """One host batch (a row is enough) for shape-driven init."""
        with profiler.trace("fit:feed", "training", what="first_batch"):
            first = self.cache.init_row if self.cache is not None \
                else next(iter(self.feed.host_iter), None)
        if first is None:
            rows = sum(self.feed.host_iter.dataset.block_sizes())
            raise ValueError(
                f"the training set has {rows} rows and yields no batch of "
                f"{batch_size} (drop_last={drop_last}): no step "
                f"would run. Lower batch_size, or pass drop_last=False to "
                f"train on a ragged batch.")
        return first

    def eval_tail(self, batch_size: int) -> Optional[Dict]:
        """The resident eval set's rows past its last full batch, as the one
        batch the eval pass calls its step with after the scan; None where
        there is none or the plan drops it."""
        cache = self.eval_cache
        if cache is None or not self.eval_tail_ok:
            return None
        off = cache.num_rows // batch_size * batch_size
        rows = cache.num_rows - off
        if rows <= 0:
            return None
        tail = {n: a[off:] for n, a in cache.arrays.items()}
        if self.eval_tail_pad:
            import jax.numpy as jnp

            from raydp_tpu.data.feed import MASK_KEY
            pad = batch_size - rows
            tail = {n: jnp.pad(a, [(0, pad)] + [(0, 0)] * (a.ndim - 1))
                    for n, a in tail.items()}
            tail[MASK_KEY] = (jnp.arange(batch_size) < rows
                              ).astype(jnp.float32)
        return tail


def tail_rule(mesh, may_pad: bool) -> Tuple[bool, bool]:
    """What happens to a ragged final batch, ``(travels, padded)``.

    ``may_pad``: the estimator's step can weight padded rows out of its loss
    (a validity mask). Under a >1 data extent a ragged batch then pads to a
    full (shardable) one and is not silently dropped; a >1 STAGE extent needs
    the same, because the pipelined forward reshapes every batch into
    microbatches. Under a size-1 data extent and no pipeline it travels as
    it is; where it would have to pad and may not, it is dropped."""
    from raydp_tpu.parallel.mesh import data_axes, stage_extent

    split = stage_extent(mesh) > 1 or int(
        np.prod([mesh.shape[a] for a in data_axes(mesh)])) > 1
    return not split or may_pad, split and may_pad


def plan_feeds(train_ds, evaluate_ds, columns: Dict, mesh, batch_size: int, *,
               shuffle: bool, seed: int, drop_last: bool,
               prefetch_to_device: Optional[int], may_pad: bool,
               seq: bool) -> Feeds:
    """Resident or streamed, decided once for train and eval so the two
    cannot disagree.

    The train set goes resident (pinned in HBM, a whole epoch one jitted
    dispatch with on-device shuffling) where ``DeviceEpochCache.eligible``
    says so, and streams through a :class:`DeviceFeed` otherwise. The eval
    set goes resident only beside a resident train set and under a COMBINED
    budget (train + eval together under the cap): the eval pass is then one
    scan dispatch (+ one for the ragged tail), not one a batch. Ragged tails
    go by :func:`tail_rule` (``may_pad``); ``seq``: declared sequence dims go
    onto the mesh's ``seq`` axis."""
    from raydp_tpu.data.feed import DeviceEpochCache, DeviceFeed

    tail_ok, pad_tail = tail_rule(mesh, may_pad)
    with profiler.trace("fit:feed", "training") as span:
        plan = Feeds()
        if DeviceEpochCache.eligible(train_ds, columns, batch_size,
                                     drop_last):
            plan.cache = DeviceEpochCache(train_ds, columns, mesh=mesh)
        else:
            plan.feed = DeviceFeed(
                train_ds, batch_size, columns, mesh=mesh, shuffle=shuffle,
                seed=seed, drop_remainder=drop_last,
                pad_remainder=pad_tail and not drop_last,
                prefetch_to_device=prefetch_to_device, seq=seq)
        if evaluate_ds is not None:
            plan.eval_tail_ok, plan.eval_tail_pad = tail_ok, pad_tail
            if (plan.cache is not None
                    and DeviceEpochCache.eligible(evaluate_ds, columns, 1,
                                                  True)
                    and plan.cache.nbytes + DeviceEpochCache.estimate_bytes(
                        evaluate_ds, columns)
                    <= DeviceEpochCache.cap_bytes()):
                plan.eval_cache = DeviceEpochCache(evaluate_ds, columns,
                                                   mesh=mesh)
            else:
                plan.eval_feed = DeviceFeed(
                    evaluate_ds, batch_size, columns, mesh=mesh,
                    shuffle=False, drop_remainder=not tail_ok,
                    pad_remainder=pad_tail,
                    prefetch_to_device=prefetch_to_device, seq=seq)
        profiler.add_args(
            span, route="resident" if plan.cache is not None else "stream")
    return plan


def gang_feeds(ctx, train_payload, eval_payload, columns: Dict, mesh,
               batch_size: int, *, shuffle: bool, seed: int,
               prefetch_to_device: Optional[int], may_pad: bool,
               seq: bool) -> Feeds:
    """A gang rank's feeds: this process's addressable slice of each global
    batch, derived from the actual batch sharding (replicated over a size-1
    data axis, EVERY process feeds the full batch; with a >1 data axis each
    feeds its contiguous rows). Where ``may_pad`` the ragged eval tail pads
    and masks (the gang's eval mean is over every row); else it is dropped."""
    from raydp_tpu.data.dataset import DistributedDataset
    from raydp_tpu.data.feed import (DeviceFeed, GangShardIterator,
                                     process_local_batch_rows)
    from raydp_tpu.parallel import batch_sharding

    row_range = process_local_batch_rows(batch_sharding(mesh), batch_size)

    def feed_of(payload, **how):
        ds = DistributedDataset.from_portable(payload)
        return DeviceFeed(
            ds, batch_size, columns, mesh=mesh,
            prefetch_to_device=prefetch_to_device, seq=seq,
            host_iter=GangShardIterator(
                ds, batch_size, ctx.world_size, ctx.rank, columns, seed=seed,
                row_range=row_range, **how))

    plan = Feeds(feed=feed_of(train_payload, shuffle=shuffle))
    if eval_payload is not None:
        plan.eval_feed = feed_of(eval_payload, shuffle=False,
                                 pad_remainder=may_pad)
    return plan


# --------------------------------------------------------------- the hand-over
@dataclass
class Evaluation:
    """An estimator's eval pass: accumulators ``acc`` threaded through its
    jitted eval step; the fit's carry is read, never donated (it lives on)."""

    #: () -> an eval pass's accumulators at zero
    zeros: Callable[[], Any]
    #: (carry, acc, batch) -> acc: one batch
    step: Callable[[Any, Any, Any], Any]
    #: acc -> the pass's pairs, ("loss", mean over the real rows) first
    read: Callable[[Any], Pairs]
    #: (carry, acc) -> acc: a resident eval set's full batches, ONE dispatch
    epoch: Optional[Callable[[Any, Any], Any]] = None


@dataclass
class Trainee:
    """What an estimator hands the loop (doc/training.md)."""

    #: the state and the epoch's accumulators, as the step returns them
    carry: Any
    #: (carry, batch) -> carry: one optimizer step, carry donated
    step: Callable[[Any, Any], Any]
    #: carry -> carry with the epoch's accumulators at zero
    zeros: Callable[[Any], Any]
    #: carry -> (the epoch's loss sum, still on the device; the train
    #: metrics' pairs, computed on the host as they are iterated)
    read: Callable[[Any], Tuple[Any, Pairs]]
    #: (carry, epoch, history) -> None: checkpoint this epoch
    save: Callable[[Any, int, List[Dict]], None]
    #: (carry, max_step) -> (carry, the epoch it holds, the history to it), or
    #: None where the directory holds nothing to adopt; ``max_step`` None: the
    #: latest, else none above it (:func:`_restore_for_retry`)
    restore: Callable[[Any, Optional[int]], Optional[Tuple[Any, int, List]]]
    #: () -> carry: start afresh, as a fit does
    fresh: Callable[[], Any]
    #: (carry, key) -> carry: a resident train set's epoch, ONE dispatch,
    #: shuffled on the device by the epoch's key
    epoch: Optional[Callable[[Any, Any], Any]] = None
    evaluation: Optional[Evaluation] = None
    #: (carry, batch or key) -> None: called before the fit's first call of
    #: its step program, with that call's arguments
    before_first: Optional[Callable[[Any, Any], None]] = None
    train_key: str = "train_{}"
    eval_key: str = "eval_{}"


# -------------------------------------------------------------------- an epoch
def stream_epoch(it, step, carry, first=None):
    """One epoch of streamed steps: pull, hand over, until the feed ends.

    Gives ``(carry, steps, (t_feed, t_disp, t_pull, t_handed))``: the walls
    of the pulls and of the step calls (a call blocks while the device's
    queue is full), the first pull's, which waits for the feed's new chain
    of stage threads, and the clock when the first program was handed over.
    The epoch's first step stands apart so that the loop below reads nothing
    more (``first`` calls it where it is the fit's first: :func:`_building`):
    between two dispatches the host does one ``next``, one call and four
    clock reads."""
    step_span = profiler.step
    clock = time.perf_counter
    steps = 0
    t_disp = 0.0
    tf = clock()
    with step_span("train:feed_wait"):
        batch = next(it, None)
    td = t_handed = clock()
    t_feed = t_pull = td - tf
    if batch is not None:
        with step_span("train:dispatch"):
            carry = (first or step)(carry, batch)
        t_handed = clock()
        t_disp = t_handed - td
        steps = 1
        while True:
            tf = clock()
            with step_span("train:feed_wait"):
                batch = next(it, None)
            t_feed += clock() - tf
            if batch is None:
                break
            td = clock()
            with step_span("train:dispatch"):
                carry = step(carry, batch)
            t_disp += clock() - td
            steps += 1
    return carry, steps, (t_feed, t_disp, t_pull, t_handed)


def epoch_report(epoch: int, key: str, loss_sum, pairs: Pairs, steps: int,
                 batch_size: int, t0: float, t_ready: float, walls,
                 feed=None) -> Tuple[Dict[str, float], float]:
    """Fetch the epoch's loss and build its report; also gives the clock at
    the fetch's return, from when the device has nothing queued.

    ``t0``: the epoch's start; ``t_ready``: when the device last ran dry (the
    last fetch's return, or the loop's start); ``walls``: ``stream_epoch``'s.
    ``lead_time_s``: from ``t_ready`` until the epoch's first program was
    handed over; the first pull (``first_pull_time_s``) is part of that."""
    step_span = profiler.step
    t_feed, t_disp, t_pull, t_handed = walls
    # the accumulated loss BEFORE the clock: dispatch is async, so only a
    # host scalar fetch makes the epoch wall include the device work
    with step_span("train:loss_fetch"):
        ts = time.perf_counter()
        loss = float(loss_sum) / steps if steps else float("nan")
        t_fetched = time.perf_counter()
    dt = time.perf_counter() - t0
    with step_span("train:report"):
        # the feed's thread-side phase split (decode, h2d): these walls
        # OVERLAP dispatch by design (that is the prefetch win), so they
        # attribute the epoch, they don't sum to it
        pipe = feed.timings.take() if feed is not None else {}
        report = {
            "epoch": epoch,
            key.format("loss"): loss,
            "steps": steps,
            "samples_per_s": steps * batch_size / dt if dt > 0 else 0.0,
            "epoch_time_s": dt,
            "feed_time_s": t_feed,
            "decode_time_s": pipe.get("decode", 0.0),
            "h2d_time_s": pipe.get("h2d", 0.0),
            "dispatch_time_s": t_disp,
            "sync_time_s": t_fetched - ts,
            "lead_time_s": t_handed - t_ready,
            "first_pull_time_s": t_pull,
        }
        for name, value in pairs:
            report[key.format(name)] = value
    return report, t_fetched


def _building(program, before):
    """``program`` as the fit calls it the first time: that call (its first
    build: trace, lower, compile or cache load, all synchronous) is a span,
    after ``before``. A later call whose argument types differ would build
    again, as ``jit:*`` spans under its epoch: the loop gives it none, every
    epoch starting from ``Trainee.zeros``'s accumulators."""
    def call(carry, x):
        if before is not None:
            before(carry, x)
        with profiler.trace("train:first_dispatch", "training"):
            return program(carry, x)
    return call


def _evaluate(ev: Evaluation, carry, feeds: Feeds, tail) -> Pairs:
    """The eval pass: the resident set as one scan dispatch and one call for
    its ragged tail, a streamed set a call a batch."""
    acc = ev.zeros()
    if ev.epoch is not None:
        acc = ev.epoch(carry, acc)
        if tail is not None:
            acc = ev.step(carry, acc, tail)
    else:
        for batch in feeds.eval_feed:
            acc = ev.step(carry, acc, batch)
    return ev.read(acc)


def _restore_for_retry(restore, carry, resume: bool,
                       last_written: Optional[int]):
    """THE rule on which checkpoint a retry may adopt: one only if an
    explicit resume claimed the directory, or THIS run wrote it, and then
    none above the step this run wrote. A reused directory's stale ones
    (possibly HIGHER-numbered, which latest-step selection would prefer) are
    foreign: one adopted would silently return an earlier run's model."""
    if resume:
        return restore(carry, None)
    if last_written is not None:
        return restore(carry, last_written)
    return None


# --------------------------------------------------------------------- the fit
def run(trainee: Trainee, feeds: Feeds, *, num_epochs: int, batch_size: int,
        seed: int, checkpoint_interval: int, callbacks=(),
        max_retries: int = 0, resume: bool = False
        ) -> Tuple[Any, List[Dict[str, float]]]:
    """The epochs of a fit; gives the final carry and the history.

    An epoch that raises is retried up to ``max_retries`` times, from the
    checkpoint :func:`_restore_for_retry` allows or from scratch."""
    import jax

    t = trainee
    step_span = profiler.step
    feed, cache, ev = feeds.feed, feeds.cache, t.evaluation
    program = t.epoch if cache is not None else t.step
    cache_steps = cache.num_rows // batch_size if cache is not None else 0
    tail = feeds.eval_tail(batch_size) if ev is not None else None
    built = False

    carry = t.carry
    history: List[Dict[str, float]] = []
    epoch = 0
    retries = 0
    #: highest checkpoint step THIS run wrote
    last_written: Optional[int] = None
    if resume:
        restored = t.restore(carry, None)
        if restored is not None:
            carry, done_epoch, history = restored
            epoch = done_epoch + 1
            logger.info("resuming from checkpoint step %d", done_epoch)

    # train:epoch_turn crosses the train:epoch phase span's close and open,
    # so no ``with`` block can hold it: the stack does, and is closed (a
    # no-op when empty) where the turn ends
    turn = contextlib.ExitStack()
    turn.enter_context(step_span("train:epoch_turn"))
    #: when the device last ran dry for the loop: its start, then the return
    #: of each epoch's loss fetch
    t_ready = time.perf_counter()
    while epoch < num_epochs:
        try:
            rule = faults.check("estimator.epoch", key=str(epoch))
            if rule is not None:  # chaos tests provoke the retry path here
                faults.apply(rule, "estimator.epoch")
            with profiler.trace("train:epoch", "training",
                                epoch=epoch) as epoch_span:
                t0 = time.perf_counter()
                carry = t.zeros(carry)
                first = None if built else _building(program, t.before_first)
                built = True
                if cache is not None:
                    # the WHOLE epoch is one jitted dispatch; steady-state
                    # host work an epoch: one dispatch + one scalar fetch
                    td = time.perf_counter()
                    ekey = jax.random.fold_in(jax.random.PRNGKey(seed), epoch)
                    turn.close()
                    with step_span("train:dispatch"):
                        carry = (first or program)(carry, ekey)
                        t_handed = time.perf_counter()
                        # dispatch is async: the loss is fetched INSIDE this
                        # window so that dispatch_time_s carries the epoch's
                        # device time (or the report's sync slot absorbs it)
                        loss_sum, pairs = t.read(carry)
                        loss_sum = np.float32(loss_sum)
                    walls = (0.0, time.perf_counter() - td, 0.0, t_handed)
                    steps = cache_steps
                else:
                    feed.set_epoch(epoch)
                    it = iter(feed)
                    turn.close()
                    carry, steps, walls = stream_epoch(it, program, carry,
                                                       first)
                    loss_sum, pairs = t.read(carry)
                with step_span("train:epoch_end"):
                    report, t_ready = epoch_report(
                        epoch, t.train_key, loss_sum, pairs, steps,
                        batch_size, t0, t_ready, walls, feed)
                    # registry twin of the epoch report (metrics_report()
                    # sees epoch walls without re-publishing the history)
                    rdt_metrics.observe("train_epoch_seconds",
                                        report["epoch_time_s"])
                    if ev is not None:
                        with step_span("train:eval"):
                            for name, value in _evaluate(ev, carry, feeds,
                                                         tail):
                                report[t.eval_key.format(name)] = value
                    history.append(report)
                    with step_span("train:callbacks"):
                        for cb in callbacks:
                            cb(report)
                    logger.info(
                        "epoch %d: %s", epoch,
                        {k: (round(v, 5) if isinstance(v, float) else v)
                         for k, v in report.items()})
                turn.enter_context(step_span("train:epoch_turn"))
                profiler.add_args(epoch_span, steps=steps)
            if save_epoch_now(epoch, checkpoint_interval, num_epochs):
                t.save(carry, epoch, history)
                last_written = epoch
            epoch += 1
        except (KeyboardInterrupt, SystemExit):
            raise
        except Exception as e:  # noqa: BLE001 - retry path (FailureConfig)
            turn.close()
            retries += 1
            if retries > max_retries:
                raise
            logger.warning("epoch %d failed (%s); restoring from checkpoint "
                           "(retry %d/%d)", epoch, e, retries, max_retries)
            restored = _restore_for_retry(t.restore, carry, resume,
                                          last_written)
            if restored is not None:
                carry, done_epoch, history = restored
                epoch = done_epoch + 1
            else:
                # no checkpoint of this run's (a failure before the first
                # interval save): the failed carry's buffers may already be
                # donated away, so start afresh like a new fit
                carry = t.fresh()
                epoch = 0
                history = []
            # the retried epoch gets a turn of its own
            turn.enter_context(step_span("train:epoch_turn"))
            t_ready = time.perf_counter()

    turn.close()
    return carry, history
