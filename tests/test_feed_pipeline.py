"""The async double-buffered device feed (DevicePrefetcher) and its
per-phase instrumentation (ISSUE 1 tentpole).

Contract pinned here: the device-side prefetch stage only moves host
staging + ``device_put`` OFF the consumer's critical path — it must never
reorder, drop, or alter a batch (``prefetch_to_device=2`` bit-identical to
``=0`` through both estimators), it must propagate producer errors and shut
its threads down on early exit, and the ``decode/h2d`` timers it
feeds must surface in the estimators' epoch reports (the measured split
VERDICT r5 Weak #2 asked for)."""

import time

import numpy as np
import pandas as pd
import pytest

from raydp_tpu.data.feed import DevicePrefetcher


# --------------------------------------------------------------- unit level
def test_device_prefetcher_order_and_values():
    items = list(range(57))
    out = list(DevicePrefetcher(iter(items), fn=lambda x: x * 2, depth=2))
    assert out == [x * 2 for x in items]


def test_device_prefetcher_propagates_producer_error():
    def gen():
        yield 1
        raise RuntimeError("decode failed")

    it = iter(DevicePrefetcher(gen(), depth=2))
    assert next(it) == 1
    with pytest.raises(RuntimeError, match="decode failed"):
        next(it)


def test_device_prefetcher_early_exit_stops_producer():
    """Abandoning the consumer mid-stream must stop the background thread
    (an estimator error must not leak one producer thread per epoch)."""
    produced = []

    def gen():
        for i in range(10_000):
            produced.append(i)
            yield i

    stage = DevicePrefetcher(gen(), depth=2)
    it = iter(stage)
    assert next(it) == 0
    it.close()
    stage._thread.join(timeout=5.0)
    assert not stage._thread.is_alive()
    n = len(produced)
    time.sleep(0.2)
    assert len(produced) == n  # nothing produced after close


def test_device_prefetcher_backpressure_bounds_readahead():
    """The bounded queue is the backpressure: the producer can be at most
    depth (queued) + 1 (in flight) + 1 (consumed) items ahead."""
    pulled = []

    def gen():
        for i in range(100):
            pulled.append(i)
            yield i

    stage = DevicePrefetcher(gen(), depth=2)
    it = iter(stage)
    assert next(it) == 0
    time.sleep(0.3)  # let the producer run as far ahead as it can
    assert len(pulled) <= 5
    assert list(it) == list(range(1, 100))  # drains cleanly afterwards


class _TwoBlocks:
    """Two Arrow blocks of 65,536 rows: the shape of a DLRM cell's feed."""

    ROWS = 65_536

    def __init__(self):
        import pyarrow as pa
        self.tables = [pa.table({
            "id": np.arange(b * self.ROWS, (b + 1) * self.ROWS),
            "x": np.arange(self.ROWS, dtype=np.float64) + b}) for b in (0, 1)]

    def block_sizes(self):
        return [self.ROWS, self.ROWS]

    def get_block(self, i, zero_copy=False):
        return self.tables[i]


class _CountedRows:
    """A decoded column that counts the rows every ``[]`` on it copies or
    views, the way the host stage cuts its batches out of a block."""

    def __init__(self, array, counts):
        self.array, self.counts = array, counts

    def __getitem__(self, key):
        out = self.array[key]
        self.counts.append(len(out))
        return out


def test_first_batch_of_an_epoch_costs_a_batch_not_a_block():
    """Before the first ``yield`` of an epoch the host stage gathers the
    4,096 rows of that batch, not the block's 65,536: the chip waits for a
    batch at an epoch's boundary."""
    from raydp_tpu.data.feed import HostBatchIterator
    columns = {"feat": ("x", np.float32), "label": ("id", np.int64)}
    it = HostBatchIterator(_TwoBlocks(), 4096, columns, shuffle=True, seed=3)
    list(it)                # every block is in the cache, as after epoch 0
    counts = []
    decode_block = it._decode_block
    it._decode_block = lambda b: {
        n: _CountedRows(a, counts) for n, a in decode_block(b).items()}
    batches = iter(it)
    first = next(batches)
    assert counts == [4096, 4096]       # one gather a column
    assert first["label"].flags.owndata and first["label"].flags.writeable
    assert sum(1 for _ in batches) == 31
    assert counts == [4096] * 64        # an epoch gathers each row once


@pytest.mark.parametrize("shuffle, batch_size", [
    (True, 4096), (True, 24_576), (False, 4096), (False, 24_576)])
def test_batches_cut_are_counted_by_how(shuffle, batch_size):
    """``feed_batches_cut_total``: a batch inside one part is gathered (or,
    unshuffled, sliced), one that crosses the block's end is joined; the
    three labels add up to the batches yielded."""
    from raydp_tpu import metrics
    from raydp_tpu.data.feed import HostBatchIterator
    ds = _TwoBlocks()
    it = HostBatchIterator(ds, batch_size, {"label": ("id", np.int64)},
                           shuffle=shuffle, seed=5)
    metrics.reset()
    batches = list(it)
    cut = metrics.snapshot()["counters"]["feed_batches_cut_total"]
    assert sum(cut.values()) == len(batches) == 2 * ds.ROWS // batch_size
    joined = sum(1 for k in range(len(batches))
                 if k * batch_size // ds.ROWS
                 != ((k + 1) * batch_size - 1) // ds.ROWS)
    assert joined == (0 if batch_size == 4096 else 1)
    inside = "gathered" if shuffle else "sliced"
    want = {inside: len(batches) - joined}
    if joined:
        want["joined"] = joined
    assert cut == want


# ---------------------------------------------------------- estimator level
def _linear_df(session, n=1344):
    rng = np.random.RandomState(0)
    x = rng.random_sample((n, 2))
    y = x @ np.array([2.0, -3.0]) + 1.0
    return session.createDataFrame(
        pd.DataFrame({"x1": x[:, 0], "x2": x[:, 1], "y": y}),
        num_partitions=4)


@pytest.mark.slow
def test_flax_prefetch_to_device_parity(shared_session, monkeypatch):
    """prefetch_to_device=2 must be BIT-IDENTICAL to =0 (same seed, same
    shuffle): the async stage only overlaps placement with compute."""
    import optax

    from raydp_tpu.data import from_frame
    from raydp_tpu.models import MLP
    from raydp_tpu.train import FlaxEstimator

    monkeypatch.setenv("RDT_DEVICE_CACHE", "0")  # pin the streaming feed
    ds = from_frame(_linear_df(shared_session))

    def run(p2d):
        est = FlaxEstimator(
            model=MLP(features=(8,), use_batch_norm=False),
            optimizer=optax.adam(1e-2),
            loss="mse",
            feature_columns=["x1", "x2"],
            label_column="y",
            batch_size=64,
            num_epochs=2,
            shuffle=True,
            seed=0,
            prefetch_to_device=p2d,
        )
        return est.fit(ds)

    sync = run(0)
    pipelined = run(2)
    assert [r["steps"] for r in sync.history] == \
        [r["steps"] for r in pipelined.history]
    for a, b in zip(sync.history, pipelined.history):
        assert a["train_loss"] == b["train_loss"]  # bit-identical


@pytest.mark.slow
def test_keras_prefetch_to_device_parity(shared_session, monkeypatch):
    """The keras twin of the parity contract, over the jitted stateless
    loop."""
    import os

    os.environ.setdefault("KERAS_BACKEND", "jax")
    import keras

    from raydp_tpu.data import from_frame
    from raydp_tpu.train import KerasEstimator

    monkeypatch.setenv("RDT_DEVICE_CACHE", "0")
    ds = from_frame(_linear_df(shared_session, n=448))

    def run(p2d):
        model = keras.Sequential([
            keras.layers.Input(shape=(2,)),
            keras.layers.Dense(16, activation="relu"),
            keras.layers.Dense(1),
        ])
        est = KerasEstimator(model=model, optimizer="adam", loss="mse",
                             feature_columns=["x1", "x2"], label_column="y",
                             batch_size=64, num_epochs=2, shuffle=True,
                             seed=0, prefetch_to_device=p2d)
        return est.fit(ds)

    sync = run(0)
    pipelined = run(2)
    assert len(sync.history) == len(pipelined.history) == 2
    for a, b in zip(sync.history, pipelined.history):
        assert a["loss"] == b["loss"]  # bit-identical


@pytest.mark.slow
def test_timing_split_surfaced_in_reports(shared_session, monkeypatch):
    """Streaming epochs report a positive decode/h2d split; the
    device-resident path reports zeros (nothing streamed)."""
    import optax

    from raydp_tpu.data import from_frame
    from raydp_tpu.models import MLP
    from raydp_tpu.train import FlaxEstimator

    ds = from_frame(_linear_df(shared_session))

    def run():
        est = FlaxEstimator(
            model=MLP(features=(8,), use_batch_norm=False),
            optimizer=optax.adam(1e-2), loss="mse",
            feature_columns=["x1", "x2"], label_column="y",
            batch_size=64, num_epochs=2, shuffle=False)
        return est.fit(ds)

    monkeypatch.setenv("RDT_DEVICE_CACHE", "0")
    streamed = run()
    for r in streamed.history:
        assert r["decode_time_s"] > 0.0
        assert r["h2d_time_s"] > 0.0

    monkeypatch.setenv("RDT_DEVICE_CACHE", "1")
    resident = run()
    for r in resident.history:
        assert r["decode_time_s"] == 0.0
        assert r["h2d_time_s"] == 0.0


def test_device_feed_prefetch_knob_env_default(shared_session, monkeypatch):
    """prefetch_to_device falls back to RDT_PREFETCH_TO_DEVICE (default 2);
    an explicit argument wins."""
    from raydp_tpu.data import from_frame
    from raydp_tpu.data.feed import DeviceFeed

    ds = from_frame(_linear_df(shared_session, n=256))
    cols = {"features": (["x1", "x2"], np.float32),
            "label": ("y", np.float32)}
    assert DeviceFeed(ds, 64, cols).prefetch_to_device == 2
    monkeypatch.setenv("RDT_PREFETCH_TO_DEVICE", "5")
    assert DeviceFeed(ds, 64, cols).prefetch_to_device == 5
    assert DeviceFeed(ds, 64, cols,
                      prefetch_to_device=0).prefetch_to_device == 0
