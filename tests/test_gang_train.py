"""Gang-distributed FlaxEstimator training.

Parity: the reference trains in N Ray Train worker processes with
``FailureConfig`` (torch/estimator.py:312-356). Here ``fit_gang`` runs one
process per host under ``SPMDJob(jax_distributed=True)``: every rank feeds its
slice of each global batch through ``make_array_from_process_local_data``,
rank 0 writes orbax checkpoints, and a rank failure restarts the gang from the
last checkpoint. The core correctness claim — distributing changed nothing —
is asserted by matching per-epoch losses against the single-process run.
"""

import os

import numpy as np
import pandas as pd
import pytest

from raydp_tpu.models import MLP
from raydp_tpu.train import FlaxEstimator


def _linear_df(session, n=2048, parts=4):
    rng = np.random.RandomState(0)
    x = rng.random_sample((n, 2))
    y = x @ np.array([2.0, -3.0]) + 1.0 + rng.normal(0, 0.01, n)
    pdf = pd.DataFrame({"x1": x[:, 0], "x2": x[:, 1], "y": y})
    return session.createDataFrame(pdf, num_partitions=parts)


def _estimator(num_epochs=3, callbacks=None, ckpt_dir=None):
    import optax

    return FlaxEstimator(
        model=MLP(features=(16,), use_batch_norm=False),
        optimizer=optax.adam(1e-2),
        loss="mse",
        feature_columns=["x1", "x2"],
        label_column="y",
        batch_size=64,
        num_epochs=num_epochs,
        shuffle=False,
        checkpoint_dir=ckpt_dir,
        callbacks=callbacks,
    )


def test_gang_losses_match_single_process(shared_session, tmp_path):
    from raydp_tpu.data.dataset import from_frame

    df = _linear_df(shared_session)
    train_df, test_df = df.randomSplit([0.75, 0.25], seed=1)
    train_ds, test_ds = from_frame(train_df), from_frame(test_df)

    single = _estimator(ckpt_dir=str(tmp_path / "single"))
    r1 = single.fit(train_ds, test_ds)

    gang = _estimator(ckpt_dir=str(tmp_path / "gang"))
    r2 = gang.fit_gang(train_ds, test_ds, num_workers=2, run_timeout=900.0)

    assert len(r2.history) == len(r1.history)
    np.testing.assert_allclose(
        [h["train_loss"] for h in r2.history],
        [h["train_loss"] for h in r1.history], rtol=2e-5)
    np.testing.assert_allclose(
        [h["eval_loss"] for h in r2.history],
        [h["eval_loss"] for h in r1.history], rtol=2e-5)

    k1 = np.asarray(single.get_model()["params"]["Dense_0"]["kernel"])
    k2 = np.asarray(gang.get_model()["params"]["Dense_0"]["kernel"])
    np.testing.assert_allclose(k2, k1, rtol=1e-4, atol=1e-5)


def test_gang_rank_failure_restarts_from_checkpoint(shared_session, tmp_path):
    from raydp_tpu.data.dataset import from_frame

    flag = str(tmp_path / "crashed-once")

    def crash_once(report):
        # rank 1 dies mid-job exactly once; the gang must restart and resume
        import jax

        if (report["epoch"] == 1 and jax.process_index() == 1
                and not os.path.exists(flag)):
            open(flag, "w").close()
            os._exit(1)

    df = _linear_df(shared_session, n=1024)
    ds = from_frame(df)
    est = _estimator(num_epochs=4, callbacks=[crash_once],
                     ckpt_dir=str(tmp_path / "ck"))
    result = est.fit_gang(ds, num_workers=2, max_retries=1,
                          run_timeout=900.0)
    assert os.path.exists(flag), "the injected crash never fired"
    # every epoch appears exactly once: the restarted gang resumed from the
    # checkpoint (no replays) and restored the pre-crash history (no holes)
    assert [h["epoch"] for h in result.history] == [0, 1, 2, 3]
    # the checkpoint sidecar proves the second incarnation did not re-train
    # from scratch: at least one pre-crash epoch came from the restore
    import raydp_tpu.train.checkpoint as ckpt
    assert ckpt.restore_extra(str(tmp_path / "ck"))["history"]


def test_gang_rejects_indivisible_batch():
    from raydp_tpu.data.feed import GangShardIterator

    class _FakeDs:
        def block_sizes(self):
            return [10, 10]

    with pytest.raises(ValueError, match="divisible"):
        GangShardIterator(_FakeDs(), global_batch=10, world_size=3, rank=0,
                          columns={"x": ("x", np.float32)})


@pytest.mark.parametrize("sizes, pad", [
    ([7, 13, 5, 22, 1], False),   # 48 rows: three full global batches
    ([7, 13, 5, 22, 6], True),    # 53 rows: a fourth, 5 rows and padding
])
def test_gang_iterator_covers_rows_exactly_once(sizes, pad):
    """_runs boundary math: every global batch row is read exactly once per
    epoch, across uneven block boundaries and both ranks. With
    ``pad_remainder`` the rows past the last full batch come too, zero-padded
    and masked; rank 1's slice of that batch is padding alone."""
    import pyarrow as pa

    from raydp_tpu.data.feed import MASK_KEY, GangShardIterator

    total = sum(sizes)
    rows = np.arange(total, dtype=np.float64)
    starts = np.cumsum([0] + sizes)
    blocks = [pa.table({"x": rows[a:b]}) for a, b in zip(starts, starts[1:])]

    class _Ds:
        def block_sizes(self):
            return sizes

        def get_block(self, i, zero_copy=False):
            return blocks[i]

    got = []
    for rank in (0, 1):
        it = GangShardIterator(_Ds(), global_batch=16, world_size=2,
                               rank=rank, columns={"x": ("x", np.float64)},
                               pad_remainder=pad)
        assert len(it) == (4 if pad else 3)
        for batch in it:
            assert batch["x"].shape == (8,)
            assert (MASK_KEY in batch) == pad
            real = batch[MASK_KEY] > 0 if pad else slice(None)
            got.extend(batch["x"][real].tolist())
            if pad:
                assert not batch["x"][~real].any()
    # every row exactly once across both ranks, and no padding among them
    assert sorted(got) == list(range(total))


def test_gang_iterator_over_cap_decodes_slices_not_blocks(monkeypatch):
    """A block that exceeds the RDT_FEED_CACHE_MB budget is never decoded
    whole per batch: the iterator slices the Arrow table to the requested
    rows first, so over-cap feeds pay O(batch) decode work (advisor r4)."""
    import pyarrow as pa

    from raydp_tpu.data.feed import GangShardIterator

    rows = np.arange(64, dtype=np.float64)
    table = pa.table({"x": rows})
    log = []

    class _SpyTable:
        def slice(self, off, n):
            log.append(("slice", off, n))
            return table.slice(off, n)

        def column(self, c):
            log.append(("full-decode", c))
            return table.column(c)

    class _Ds:
        def block_sizes(self):
            return [64]

        def get_block(self, i, zero_copy=False):
            return _SpyTable()

    def run():
        log.clear()
        it = GangShardIterator(_Ds(), global_batch=16, world_size=2, rank=0,
                               columns={"x": ("x", np.float64)})
        out = [b["x"].copy() for b in it]
        return np.concatenate(out)

    monkeypatch.setenv("RDT_FEED_CACHE_MB", "0")   # block can never cache
    over = run()
    assert all(kind == "slice" for kind, *_ in log), log
    assert len(log) == 4                            # one slice per batch

    monkeypatch.setenv("RDT_FEED_CACHE_MB", "64")  # block caches on first use
    under = run()
    assert ("full-decode", "x") in log
    assert sum(1 for kind, *_ in log if kind == "full-decode") == 1
    np.testing.assert_array_equal(over, under)      # same rows either way
