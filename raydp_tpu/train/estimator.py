"""Estimator ABCs (parity: reference estimator.py:23-43 + spark/interfaces.py:27-39)."""

from __future__ import annotations

import os
import tempfile
import time
from abc import ABC, abstractmethod
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple


@dataclass
class OnlineTrainingResult:
    """What one :meth:`EstimatorInterface.partial_fit` drive produced: the
    per-epoch train metric reports, the serving bundles it exported on the
    way (``(source epoch id, export dir)``), and how many stream epochs it
    consumed. The trained model itself lives on the estimator
    (``get_model`` / ``export_serving``), exactly as after ``fit``."""

    history: List[Dict[str, float]] = field(default_factory=list)
    exports: List[Tuple[int, str]] = field(default_factory=list)
    epochs: int = 0
    #: guarded-rollout outcome records, one per export shipped through
    #: ``rollout=`` (empty when exports hot-swap unguarded); a
    #: ``rolled_back`` entry means that epoch's model never took traffic —
    #: training continued past it by design
    rollouts: List[Dict] = field(default_factory=list)

    @property
    def final_metrics(self) -> Dict[str, float]:
        return self.history[-1] if self.history else {}


class EstimatorInterface(ABC):
    """``fit`` over datasets + ``get_model`` (reference estimator.py:23-43)."""

    @abstractmethod
    def fit(self, train_ds, evaluate_ds=None, max_retries: int = 0):
        ...

    @abstractmethod
    def get_model(self):
        ...

    def export_serving(self, export_dir: str) -> str:
        """Write a self-contained serving bundle (weights through
        ``train/checkpoint.py`` + the pickled inference recipe) that
        :class:`raydp_tpu.serve.ServingSession` loads onto executor
        replicas. Implemented by the flax and keras estimators; others
        (e.g. GBDT) have no jit-servable forward pass yet."""
        raise NotImplementedError(
            f"{type(self).__name__} does not support export_serving()")

    # ---------------------------------------------------------- partial_fit
    def partial_fit(self, stream, *, max_epochs: Optional[int] = None,
                    export_every: Optional[int] = None,
                    export_dir: Optional[str] = None,
                    serving=None,
                    rollout: bool = False,
                    timeout_s: Optional[float] = None
                    ) -> OnlineTrainingResult:
        """Online training over a continuous pipeline (doc/streaming.md).

        Consumes stream epochs — each one micro-batch's transformed rows,
        sealed in the object store — and updates the model incrementally:
        parameters persist across epochs (one gradient pass per epoch here,
        vs ``fit``'s many passes over one static dataset). Every epoch's
        rows flow through the same feed/``DevicePrefetcher`` plane ``fit``
        uses, and every epoch appends a train-metrics report to the
        returned history.

        ``stream`` may be a
        :class:`~raydp_tpu.stream.pipeline.ContinuousPipeline` (driven
        inline: each ``partial_fit`` step runs one source epoch), an
        :class:`~raydp_tpu.stream.pipeline.EpochStream` (a decoupled
        ledger consumer — e.g. of a pipeline running on its background
        thread), or any iterable of ``EpochResult``.

        Every ``export_every`` epochs (default ``RDT_STREAM_EXPORT_EVERY``;
        0 disables) the current model is ``export_serving``-ed under
        ``export_dir/v<n>`` and — when ``serving`` (a live
        :class:`~raydp_tpu.serve.ServingSession`) is attached — shipped
        into it under live traffic, tagged with the source epoch id:
        either an immediate atomic :meth:`hot_swap`, or, with
        ``rollout=True``, a GUARDED rollout — canary weight, ramp,
        per-version health judgment, auto-promote or auto-rollback
        (doc/serving.md "Guarded rollouts"). A rolled-back export does NOT stop training: the
        outcome lands in ``result.rollouts`` and the next epoch trains
        on — shipping a bad epoch to 100% of traffic is the failure mode
        the guard exists for, a bad epoch itself is routine.
        Stops after ``max_epochs``, or when the stream ends.
        """
        from raydp_tpu import knobs, metrics

        if export_every is None:
            export_every = int(knobs.get("RDT_STREAM_EXPORT_EVERY"))
        if export_every and export_dir is None:
            export_dir = tempfile.mkdtemp(prefix="rdt-online-")
        result = OnlineTrainingResult()
        for epoch_id, ds in self._stream_epochs(stream, max_epochs,
                                                timeout_s):
            t0 = time.perf_counter()
            report = self._partial_fit_epoch(ds, epoch_id)
            report.setdefault("epoch", epoch_id)
            report.setdefault("epoch_time_s", time.perf_counter() - t0)
            metrics.observe("train_epoch_seconds", report["epoch_time_s"])
            result.history.append(report)
            result.epochs += 1
            if export_every and result.epochs % export_every == 0:
                vdir = os.path.join(export_dir,
                                    f"v{len(result.exports) + 1}")
                self.export_serving(vdir)
                result.exports.append((epoch_id, vdir))
                if serving is not None:
                    tag = f"epoch-{epoch_id}"
                    if rollout:
                        result.rollouts.append(
                            serving.rollout(vdir, tag=tag))
                    else:
                        serving.hot_swap(vdir, tag=tag)
        return result

    @staticmethod
    def _stream_epochs(stream, max_epochs: Optional[int],
                       timeout_s: Optional[float]):
        """Normalize the accepted stream shapes to ``(epoch id, dataset)``
        pairs, each dataset a store-backed view of the epoch's rows."""
        from raydp_tpu.stream.pipeline import ContinuousPipeline, EpochStream

        if isinstance(stream, ContinuousPipeline):
            for er in stream.epochs(max_epochs=max_epochs,
                                    timeout_s=timeout_s):
                yield er.epoch, er.dataset()
            return
        if isinstance(stream, EpochStream):
            done = 0
            while max_epochs is None or done < max_epochs:
                item = stream.next(timeout_s if timeout_s is not None
                                   else 30.0)
                if item is None:
                    if stream.exhausted:
                        return
                    continue
                epoch, table = item
                ds, ref = _table_dataset(table)
                try:
                    # the consumer trains through the dataset before
                    # resuming this generator; the finally also covers a
                    # training failure closing the generator mid-yield
                    yield epoch, ds
                finally:
                    _free_refs([ref])
                done += 1
            return
        it = iter(stream)
        done = 0
        while max_epochs is None or done < max_epochs:
            # check the bound BEFORE pulling: a shared iterator must not
            # have an epoch consumed and silently dropped past the cap
            er = next(it, None)
            if er is None:
                return
            yield er.epoch, er.dataset()
            done += 1

    def _partial_fit_epoch(self, ds, epoch: int) -> Dict[str, float]:
        """One incremental update over one epoch's dataset; returns the
        epoch's train-metrics report. Implemented by estimators that
        support online training (flax, keras)."""
        raise NotImplementedError(
            f"{type(self).__name__} does not support partial_fit()")


def _table_dataset(table):
    """An already-fetched epoch table as a 1-block feed-plane dataset
    (the EpochStream consumer path — its tables left the store already).
    Returns ``(dataset, ref)``; the caller frees ``ref`` after training."""
    from raydp_tpu.data.dataset import BlockMeta, DistributedDataset
    from raydp_tpu.runtime.object_store import get_client

    ref = get_client().put_arrow(table)
    return DistributedDataset([BlockMeta(num_rows=table.num_rows, ref=ref)],
                              table.schema), ref


def _free_refs(refs) -> None:
    from raydp_tpu.runtime.object_store import get_client

    try:
        get_client().free(list(refs))
    except Exception:  # noqa: BLE001 - a stopping runtime reads as freed
        pass


class FrameEstimatorInterface(ABC):
    """``fit_on_frame`` — the ``fit_on_spark`` analogue
    (spark/interfaces.py:27-39): accepts ETL DataFrames, converts through the
    data plane (object store or a parquet spill directory), optionally stops the
    ETL engine after conversion with ownership transferred to the master."""

    @abstractmethod
    def fit_on_frame(self, train_df, evaluate_df=None, *,
                     fs_directory: Optional[str] = None,
                     stop_etl_after_conversion: bool = False,
                     max_retries: int = 0):
        ...

    @staticmethod
    def _convert_frames(train_df, evaluate_df=None, *,
                        fs_directory: Optional[str] = None,
                        stop_etl_after_conversion: bool = False):
        """Frames → datasets through the chosen conversion path; optionally
        stop the ETL engine with ownership transferred to the master so the
        data survives (parity: torch/estimator.py:358-390, dataset.py:137-158).
        Shared by every concrete estimator's ``fit_on_frame``."""
        import raydp_tpu
        from raydp_tpu import profiler
        from raydp_tpu.data import from_frame, from_frame_recoverable

        def convert(df, tag):
            if df is None:
                return None
            if fs_directory is not None:
                # parquet spill path (parity: torch/estimator.py:365-376)
                path = os.path.join(fs_directory, tag)
                df.write.parquet(path)
                session = df._session
                return from_frame(session.read.parquet(path))
            return from_frame_recoverable(df)

        with profiler.trace("fit:convert", "training"):
            train_ds = convert(train_df, "train")
            eval_ds = convert(evaluate_df, "eval")
            if stop_etl_after_conversion:
                train_ds.transfer_to_master()
                if eval_ds is not None:
                    eval_ds.transfer_to_master()
                raydp_tpu.stop(cleanup_data=False)
        return train_ds, eval_ds


def save_epoch_now(epoch: int, interval: int, num_epochs: int) -> bool:
    """The checkpoint cadence every estimator loop shares: every
    ``interval``-th epoch, and always the final one (so resume/get_model
    semantics hold at any interval)."""
    return (epoch + 1) % interval == 0 or epoch == num_epochs - 1
