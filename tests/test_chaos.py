"""Chaos matrix: seeded fault injection (raydp_tpu/faults.py) against the
lineage-recovery plane, proving *byte-identical* action results under
failures — not merely "it eventually returned something".

Matrix (ISSUE 3 acceptance criteria):
- executor killed mid-groupagg (between partial and merge)  → task retry
- shuffle bucket blob dropped before the reduce stage       → lineage rebuild
  (and the same schedule with recovery disabled must raise StageError,
  proving the injection actually bites)
- crash during cache() materialization                      → lineage rebuild
  of lost cached blocks on read
- estimator epoch failure                                   → checkpoint resume

Every schedule is pinned with ``nth=`` + a ``once=`` sentinel file, so the
injection is deterministic per session AND observable (the test asserts the
sentinel exists — a schedule that never fired would silently test nothing).
"""

import os
import threading
import time

import numpy as np
import pandas as pd
import pyarrow as pa
import pytest

import raydp_tpu
from raydp_tpu import faults
from raydp_tpu.etl import functions as F
from raydp_tpu.etl.engine import StageError
from raydp_tpu.runtime.object_store import ObjectRef


def _ipc_bytes(table: pa.Table) -> bytes:
    sink = pa.BufferOutputStream()
    with pa.ipc.new_stream(sink, table.schema) as w:
        w.write_table(table)
    return sink.getvalue().to_pybytes()


def _session(app):
    return raydp_tpu.init(app, num_executors=2, executor_cores=1,
                          executor_memory="512MB")


def _frame(s, n=4000):
    rng = np.random.RandomState(0)
    pdf = pd.DataFrame({
        "k": rng.randint(0, 50, n),
        # integer aggregates only: bit-identical under any partial/merge
        # order (float partials may differ in the last ulp)
        "v": rng.randint(0, 1000, n).astype(np.int64),
    })
    return s.createDataFrame(pdf, num_partitions=4)


def _run_groupagg(app):
    """One full session running the canonical two-phase groupagg; returns
    (result ipc bytes, row count, engine shuffle-stage report). The table is
    canonicalized by sorting on the group key before serializing: pyarrow's
    hash aggregation is threaded, so groupagg ROW ORDER is unspecified even
    between two fault-free runs (like Spark's) — the byte-identity contract
    is over the relation, each value bit-exact."""
    s = _session(app)
    try:
        df = _frame(s)
        out = df.groupBy("k").agg(F.sum("v").alias("s"),
                                  F.count("v").alias("n"))
        n = s.engine.count(out._plan)
        table = s.engine.collect(out._plan).sort_by([("k", "ascending")])
        return _ipc_bytes(table), n, s.engine.shuffle_stage_report()
    finally:
        raydp_tpu.stop()


def test_executor_crash_mid_groupagg_byte_identical(tmp_path, monkeypatch):
    """An injected transient raise on the first task AND an executor crash on
    its 3rd task (the merge stage, after the 2 map tasks) — task retry with
    backoff must deliver the exact fault-free bytes."""
    base, base_n, _ = _run_groupagg("chaos-crash-base")

    raise_s = str(tmp_path / "raise.sentinel")
    crash_s = str(tmp_path / "crash.sentinel")
    monkeypatch.setenv(
        "RDT_FAULTS",
        f"executor.run_task:raise:nth=1:once={raise_s};"
        f"executor.run_task:crash:nth=3:once={crash_s}")
    got, got_n, _ = _run_groupagg("chaos-crash")
    assert os.path.exists(raise_s), "injected raise never fired"
    assert os.path.exists(crash_s), "injected crash never fired"
    assert got_n == base_n
    assert got == base


def test_dropped_shuffle_bucket_lineage_recovery(tmp_path, monkeypatch):
    """A shuffle bucket blob silently dropped after the map stage (the
    store-host-died model): the reduce stage hits ObjectLostError, the engine
    re-executes the producer from the lineage ledger, re-homes the blob,
    patches the consumer refs, and the action result is byte-identical. The
    stage report records the regenerated intermediate."""
    base, base_n, _ = _run_groupagg("chaos-drop-base")

    sent = str(tmp_path / "drop.sentinel")
    monkeypatch.setenv("RDT_FAULTS", f"shuffle.write:drop:nth=2:once={sent}")
    got, got_n, report = _run_groupagg("chaos-drop")
    assert os.path.exists(sent), "injected drop never fired"
    assert got_n == base_n
    assert got == base
    assert sum(e.get("regenerated", 0) for e in report) >= 1, report
    assert sum(e.get("recovered", 0) for e in report) >= 1, report


def test_dropped_consolidated_map_blob_recovery(tmp_path, monkeypatch):
    """Consolidated shuffle path (explicitly pinned on): a map task's output
    is ONE blob holding every bucket, so ``shuffle.write:drop`` must target
    that single consolidated oid — and one regenerated producer restores all
    B buckets at once. The reduce stage hits ObjectLostError on its byte
    range, lineage reruns the producer (byte-identical, so the bucket index
    still addresses the fresh blob), and the action result matches the
    fault-free run exactly with the recovery surfaced in the ledger."""
    monkeypatch.setenv("RDT_SHUFFLE_CONSOLIDATE", "1")
    base, base_n, base_report = _run_groupagg("chaos-consol-base")
    assert all(e["consolidated"] for e in base_report), base_report

    sent = str(tmp_path / "consol-drop.sentinel")
    # bucket=3 would pick bucket 3 of a legacy map output; the consolidated
    # map has exactly one blob, so the victim index wraps onto it
    monkeypatch.setenv("RDT_FAULTS",
                       f"shuffle.write:drop:nth=2:bucket=3:once={sent}")
    got, got_n, report = _run_groupagg("chaos-consol-drop")
    assert os.path.exists(sent), "injected drop never fired"
    assert got_n == base_n
    assert got == base
    entries = [e for e in report if e.get("recovered", 0) >= 1]
    assert entries, report
    # the regenerated producer is a consolidated map task: ONE blob rebuilt
    # brings back every bucket, so a single recovery event suffices
    assert all(e["consolidated"] for e in entries)
    assert sum(e.get("regenerated", 0) for e in report) >= 1, report


def test_straggler_speculation_composes_with_lineage_recovery(tmp_path,
                                                              monkeypatch):
    """A seeded one-executor straggler (every task entering executor 0 sleeps
    at entry) COMBINED with a dropped shuffle blob in the same action:
    speculative backup tasks and lineage recovery must compose — results
    byte-identical to the fault-free run, the drop recovered through the
    ledger, at least one backup fired, and the store object count back at
    its pre-action value (no orphans from won/lost speculation races; the
    losers land late and free through the late-result path, so the audit
    polls). The drop is pinned to nth=1: the fast executor's first map
    write, deterministically a WINNING attempt's blob — the delayed
    executor's first write trails it by the full injected delay."""
    from raydp_tpu.runtime.object_store import get_client

    base, _, _ = _run_groupagg("chaos-straggler-base")

    sent = str(tmp_path / "straggler-drop.sentinel")
    victim = "rdt-executor-chaos-straggler-0"
    monkeypatch.setenv(
        "RDT_FAULTS",
        f"executor.run_task:delay:ms=600:match={victim}|;"
        f"shuffle.write:drop:nth=1:once={sent}")
    monkeypatch.setenv("RDT_SPECULATION_QUANTILE", "0.25")
    monkeypatch.setenv("RDT_SPECULATION_MIN_S", "0.15")
    s = _session("chaos-straggler")
    try:
        client = get_client()
        df = _frame(s)
        before = client.stats()["num_objects"]
        out = df.groupBy("k").agg(F.sum("v").alias("s"),
                                  F.count("v").alias("n"))
        table = s.engine.collect(out._plan).sort_by([("k", "ascending")])
        report = s.engine.shuffle_stage_report()
        assert os.path.exists(sent), "injected drop never fired"
        assert _ipc_bytes(table) == base
        assert sum(e.get("recovered", 0) for e in report) >= 1, report
        assert sum(e.get("regenerated", 0) for e in report) >= 1, report
        assert sum(e.get("speculated", 0) for e in report) >= 1, report
        deadline = time.time() + 30
        while time.time() < deadline \
                and client.stats()["num_objects"] != before:
            time.sleep(0.25)
        after = client.stats()["num_objects"]
        assert after == before, (
            f"speculation races orphaned {after - before} store objects")
    finally:
        raydp_tpu.stop()


def test_dropped_bucket_without_recovery_raises_stage_error(tmp_path,
                                                            monkeypatch):
    """Same drop schedule with lineage recovery disabled: the action must
    fail with StageError — proving the injection bites and the green run
    above is the recovery's doing, not an accident of scheduling."""
    sent = str(tmp_path / "drop-off.sentinel")
    monkeypatch.setenv("RDT_FAULTS", f"shuffle.write:drop:nth=2:once={sent}")
    monkeypatch.setenv("RDT_LINEAGE_RECOVERY", "0")
    s = _session("chaos-drop-off")
    try:
        df = _frame(s)
        out = df.groupBy("k").agg(F.sum("v").alias("s"))
        with pytest.raises(StageError):
            s.engine.collect(out._plan)
        assert os.path.exists(sent), "injected drop never fired"
    finally:
        raydp_tpu.stop()


def test_cache_crash_then_lineage_rebuild(tmp_path, monkeypatch):
    """Executor crash during cache() materialization: the cache stage retries
    onto the surviving/restarted executor, and blocks the crashed executor
    already cached are rebuilt from their lineage recipes on read — collect
    equals the fault-free run exactly."""
    from raydp_tpu.etl.expressions import col

    def run(app):
        s = _session(app)
        try:
            cached = _frame(s).withColumn("v2", col("v") * 2).persist()
            assert cached.count() == 4000
            table = s.engine.collect(cached._plan)
            return _ipc_bytes(table)
        finally:
            raydp_tpu.stop()

    base = run("chaos-cache-clean")
    sent = str(tmp_path / "cache-crash.sentinel")
    monkeypatch.setenv("RDT_FAULTS",
                       f"executor.run_task:crash:nth=2:once={sent}")
    got = run("chaos-cache-crash")
    assert os.path.exists(sent), "injected crash never fired"
    assert got == base


def test_cache_recover_recipes_survive_bucket_drop(tmp_path, monkeypatch):
    """A shuffle bucket dropped while persist() materializes: the cache
    stage recovers in-flight, and — the regression this pins — the persisted
    frame's recovery RECIPES must reference the regenerated blob, not the
    dead id (recipes are serialized after the stage, patched). Proven by
    wiping every executor cache afterwards and reading the frame back
    through lineage."""
    import time

    base, base_n, _ = _run_groupagg("chaos-recipe-base")

    sent = str(tmp_path / "recipe-drop.sentinel")
    monkeypatch.setenv("RDT_FAULTS", f"shuffle.write:drop:nth=2:once={sent}")
    s = _session("chaos-recipe")
    try:
        df = _frame(s)
        cached = df.groupBy("k").agg(F.sum("v").alias("s"),
                                     F.count("v").alias("n")).persist()
        assert os.path.exists(sent), "injected drop never fired"
        assert sum(e.get("regenerated", 0)
                   for e in s.engine.shuffle_stage_report()) >= 1

        # wipe every cache (crash-restart); reads must rebuild via recipes
        for h in s.executors:
            try:
                h.call("crash")
            except Exception:
                pass
        deadline = time.time() + 60
        got_n = None
        while time.time() < deadline:
            try:
                got_n = s.engine.count(cached._plan)
                break
            except Exception:
                time.sleep(0.5)
        assert got_n == base_n
        table = s.engine.collect(cached._plan).sort_by([("k", "ascending")])
        assert _ipc_bytes(table) == base
    finally:
        raydp_tpu.stop()


def test_estimator_epoch_failure_checkpoint_resume(tmp_path):
    """Epoch 1 dies (injected at the estimator.epoch site); with
    max_retries=1 the fit restores the epoch-0 checkpoint, replays, and the
    final weights are bit-identical to an uninterrupted fit."""
    import optax

    from raydp_tpu.data.dataset import from_frame
    from raydp_tpu.models import MLP
    from raydp_tpu.train import FlaxEstimator

    s = _session("chaos-estimator")
    try:
        rng = np.random.RandomState(0)
        x = rng.random_sample((1024, 2))
        y = x @ np.array([2.0, -3.0]) + 1.0
        pdf = pd.DataFrame({"x1": x[:, 0], "x2": x[:, 1], "y": y})
        ds = from_frame(s.createDataFrame(pdf, num_partitions=4))

        def make(ckpt):
            return FlaxEstimator(
                model=MLP(features=(8,), use_batch_norm=False),
                optimizer=optax.adam(1e-2), loss="mse",
                feature_columns=["x1", "x2"], label_column="y",
                batch_size=128, num_epochs=3, seed=0,
                checkpoint_dir=str(tmp_path / ckpt))

        clean = make("clean").fit(ds)
        assert len(clean.history) == 3

        faults.clear()
        try:
            rule = faults.inject("estimator.epoch", "raise",
                                 match="1", times=1)
            est = make("faulted")
            faulted = est.fit(ds, max_retries=1)
        finally:
            faults.clear()
        assert rule.fires == 1, "epoch fault never fired"
        assert len(faulted.history) == 3

        import jax
        a = jax.tree_util.tree_leaves(clean.state.params)
        b = jax.tree_util.tree_leaves(faulted.state.params)
        assert len(a) == len(b) and len(a) > 0
        for la, lb in zip(a, b):
            np.testing.assert_array_equal(np.asarray(la), np.asarray(lb))
    finally:
        raydp_tpu.stop()


def test_free_late_result_runs_off_callback_thread_unit():
    """The drain-abandonment callback fires on the executor connection's RPC
    read loop; its drop_blocks is a synchronous call over that SAME
    connection, so doing the work inline would block the only thread able to
    deliver the response — the callback must hand off and return at once."""
    import threading
    from concurrent.futures import Future

    from raydp_tpu.etl.engine import ExecutorPool

    release = threading.Event()
    dropped = threading.Event()

    class _Handle:
        def drop_blocks(self, keys, if_stamp=None):
            assert release.wait(5), "free thread never reached drop_blocks"
            assert keys == ["blk"]
            # the straggler's own generation stamp rides along, so the
            # executor only drops OUR stale entry, never a recovery
            # resubmit's fresh block cached under the same key
            assert if_stamp == "gen0"
            dropped.set()

    pool = ExecutorPool.__new__(ExecutorPool)
    pool.by_name = {"ex0": _Handle()}
    fut = Future()
    fut.set_result({"executor": "ex0", "cache_key": "blk",
                    "cache_stamp": "gen0"})

    t0 = time.monotonic()
    pool._free_late_result(fut)  # simulating the read-loop's callback call
    assert time.monotonic() - t0 < 1.0, \
        "callback blocked on the executor RPC instead of handing off"
    assert not dropped.is_set()
    release.set()
    assert dropped.wait(5), "handed-off free never ran"


def test_block_cache_stamp_conditioned_drop_unit():
    """A drain-abandoned CACHE straggler's deferred drop must not delete the
    live block a recovery resubmit cached under the same key: the drop is
    conditioned on the straggler's own generation stamp."""
    from raydp_tpu.etl.executor import BlockCache

    tbl = pa.table({"a": [1]})
    cache = BlockCache()
    cache.put("blk", tbl, stamp="old-gen")
    # the resubmit lands first, overwriting with a fresh generation
    cache.put("blk", tbl, stamp="new-gen")
    assert cache.drop(["blk"], if_stamp="old-gen") == 0
    assert cache.get("blk") is not None, "live resubmit block was dropped"
    # the straggler's drop DOES work when its generation is still current
    assert cache.drop(["blk"], if_stamp="new-gen") == 1
    assert cache.get("blk") is None
    # a lineage-rebuilt block (get_block re-put, no stamp) is also immune
    cache.put("blk", tbl)
    assert cache.drop(["blk"], if_stamp="old-gen") == 0
    # unconditional drops (persist sweeps) behave as before
    assert cache.drop(["blk"]) == 1


def test_patch_task_refs_surgery_unit():
    """Ref surgery (what recovery uses to point consumers at regenerated
    blobs) must reach every ref a task can hold — ArrowRefSource,
    HashJoinStep right side, a CachedSource's nested recovery task — and
    leave untouched tasks identity-equal. task_input_ids is the audit of the
    same traversal."""
    from raydp_tpu.etl import tasks as T
    from raydp_tpu.runtime.object_store import ObjectRef

    old = [ObjectRef(id=f"{i:032x}") for i in range(3)]
    new = ObjectRef(id="f" * 32)
    inner = T.Task(task_id="inner", source=T.ArrowRefSource([old[2]]))
    task = T.Task(
        task_id="outer",
        source=T.ArrowRefSource([old[0]]),
        steps=[T.HashJoinStep([old[1]], ["k"], ["k"]),
               T.CachedSource("key", recover=inner)])
    assert sorted(T.task_input_ids(task)) == sorted(r.id for r in old)

    patched = T.patch_task_refs(task, {old[0].id: new, old[2].id: new})
    ids = T.task_input_ids(patched)
    assert ids.count(new.id) == 2 and old[1].id in ids
    assert old[0].id not in ids and old[2].id not in ids
    # no-match mapping returns the identical object (no useless copies)
    assert T.patch_task_refs(task, {"e" * 32: new}) is task


def test_note_recovery_attribution_unit():
    """Recovery accounting must land on the entry of the stage that produced
    the lost blobs — not "the most recent entry with this label": concurrent
    actions interleave same-label entries in the engine deque, and one action
    can run the same label twice (two joins, two groupbys)."""
    import collections
    import threading

    from raydp_tpu.etl.engine import Engine, _ActionTemps, _Producer

    eng = Engine.__new__(Engine)
    eng._report_lock = threading.Lock()
    eng._stage_reports = collections.deque(maxlen=256)
    eng.tenant = "unit"

    def record(temps, label, ref_id):
        prod = _Producer(b"", [ref_id], label)
        temps.lineage[ref_id] = prod
        eng._record_stage(label, [{"num_rows": 1, "ref": ObjectRef(id=ref_id)}],
                          2, temps)
        return prod

    temps_a, temps_b = _ActionTemps(), _ActionTemps()
    prod_a = record(temps_a, "groupagg", "a" * 32)
    record(temps_b, "groupagg", "b" * 32)  # concurrent action, newer entry
    prod_a2 = record(temps_a, "groupagg", "c" * 32)  # same label, 2nd stage

    eng._note_recovery(prod_a, 3, temps_a)  # A's FIRST stage recovers
    report = eng.shuffle_stage_report()
    assert [e["regenerated"] for e in report] == [3, 0, 0], report
    assert [e["recovered"] for e in report] == [1, 0, 0], report
    eng._note_recovery(prod_a2, 2, temps_a)  # A's second stage, own entry
    assert [e["regenerated"] for e in eng.shuffle_stage_report()] == [3, 0, 2]

    # a label the action never recorded gets its own bare entry, and a
    # second recovery of the same label accumulates there (no duplicates)
    mat = _Producer(b"", ["d" * 32], "materialize")
    eng._note_recovery(mat, 1, temps_a)
    eng._note_recovery(mat, 2, temps_a)
    mats = [e for e in eng.shuffle_stage_report()
            if e["stage"] == "materialize"]
    assert len(mats) == 1
    assert mats[0]["regenerated"] == 3 and mats[0]["recovered"] == 2


def test_ref_patches_transitive_collapse_unit():
    """A second-generation loss (A regenerated as B, then B lost and
    regenerated as C) must leave ref_patches mapping A → C: cache() recover
    recipes are serialized through this map, and a recipe pointing at the
    freed intermediate B would be permanently unrecoverable (a later action
    has no lineage for B)."""
    from raydp_tpu.etl.engine import _ActionTemps

    a, b, c = ("a" * 32, "b" * 32, "c" * 32)
    temps = _ActionTemps()
    temps.apply_patches({a: ObjectRef(id=b)})
    temps.apply_patches({b: ObjectRef(id=c)})
    assert temps.ref_patches[a].id == c
    assert temps.ref_patches[b].id == c


def test_expand_lost_dead_host_unit(monkeypatch):
    """The multi-loss probe must share the read path's loss criterion: a
    reported-lost blob the store table still lists means its payload host is
    unreachable (purge_host lags a node death), so every ledgered candidate
    homed there is equally lost — while a head-local loss stays blob-specific
    and blobs on live hosts are left alone."""
    from raydp_tpu.etl import engine as E
    from raydp_tpu.etl import tasks as T

    # candidate inputs of the one unfinished task; L* are the reported losses
    c_dead, c_live, c_freed, c_head = ("c1" * 16, "c2" * 16, "c3" * 16,
                                       "c4" * 16)
    l_node, l_head = "f1" * 16, "f2" * 16
    locs = {l_node: "node-a", l_head: "head",  # table still lists both
            c_dead: "node-a", c_live: "node-b", c_head: "head"}
    # c_freed absent: freed/purged — lost via the plain presence check

    class _StubClient:
        def locations(self, refs):
            return {r.id: locs[r.id] for r in refs if r.id in locs}

    monkeypatch.setattr(E, "get_client", lambda: _StubClient())

    temps = E._ActionTemps()
    for cid in (c_dead, c_live, c_freed, c_head):
        temps.lineage[cid] = E._Producer(b"", [cid], "groupagg")
    task = T.Task(task_id="t0", source=T.ArrowRefSource(
        [ObjectRef(id=i) for i in (c_dead, c_live, c_freed, c_head)]))

    lost = E.Engine._expand_lost([l_node, l_head], [task], [None], temps)
    # node-a listed a blob whose read failed => node-a is dead => c_dead
    # joins; c_freed is absent from the table; head and node-b stay put
    assert lost == {l_node, l_head, c_dead, c_freed}


def test_failed_action_leaves_no_orphaned_store_objects():
    """Regression for the temps/abort lifecycle: an action that dies mid-map
    stage (a deterministic app error in ONE partition while the siblings'
    shuffle buckets are already written) must drain in-flight tasks and free
    every intermediate — the store object count returns to its pre-action
    value."""
    from raydp_tpu.etl.expressions import udf
    from raydp_tpu.runtime.object_store import get_client

    s = _session("chaos-orphans")
    try:
        rng = np.random.RandomState(1)
        vals = rng.randint(0, 100, 4000)
        vals[3600] = 777  # the poison pill lives in the LAST partition only
        pdf = pd.DataFrame({"k": rng.randint(0, 10, 4000), "v": vals})
        df = s.createDataFrame(pdf, num_partitions=4)

        client = get_client()
        before = client.stats()["num_objects"]

        @udf("int")
        def poison(v):
            if v == 777:
                raise ValueError("poison pill")
            return int(v)

        out = df.withColumn("p", poison("v")).groupBy("k").agg(
            F.sum("p").alias("s"))
        with pytest.raises(StageError):
            s.engine.collect(out._plan)

        after = client.stats()["num_objects"]
        assert after == before, (
            f"failed action leaked {after - before} store objects")
    finally:
        raydp_tpu.stop()


def test_failed_persist_leaves_no_cached_blocks():
    """Regression for the executor-RAM half of the abort contract: when
    persist() dies on one partition, the sibling partitions have already
    stored their tables in executor block caches — beyond the store-count
    audit above. The abort must sweep those blocks from every executor, or
    each retried persist of a failing plan pins more partition tables in the
    unbounded BlockCache."""
    from raydp_tpu.etl.expressions import udf
    from raydp_tpu.runtime.object_store import get_client

    s = _session("chaos-persist-abort")
    try:
        rng = np.random.RandomState(3)
        vals = rng.randint(0, 100, 4000)
        vals[3600] = 777  # poison only the LAST partition
        pdf = pd.DataFrame({"k": rng.randint(0, 10, 4000), "v": vals})
        df = s.createDataFrame(pdf, num_partitions=4)

        client = get_client()
        before = client.stats()["num_objects"]
        blocks_before = {h.name: set(h.list_blocks()) for h in s.executors}

        @udf("int")
        def poison(v):
            if v == 777:
                raise ValueError("poison pill")
            return int(v)

        with pytest.raises(StageError):
            df.withColumn("p", poison("v")).persist()

        assert client.stats()["num_objects"] == before
        for h in s.executors:
            assert set(h.list_blocks()) == blocks_before[h.name], (
                f"aborted persist left cached blocks on {h.name}")
    finally:
        raydp_tpu.stop()


def test_shuffle_write_raise_after_put_leaves_no_orphans(tmp_path,
                                                        monkeypatch):
    """An injected raise at shuffle.write fires AFTER the task's bucket blobs
    hit the store; the retry writes fresh copies, so the executor must free
    the first set — the action succeeds and the store count returns to its
    pre-action value (plus nothing: collect holds no refs at the end)."""
    from raydp_tpu.runtime.object_store import get_client

    sent = str(tmp_path / "wraise.sentinel")
    monkeypatch.setenv("RDT_FAULTS", f"shuffle.write:raise:nth=1:once={sent}")
    s = _session("chaos-wraise")
    try:
        df = _frame(s)
        client = get_client()
        before = client.stats()["num_objects"]
        out = df.groupBy("k").agg(F.sum("v").alias("s"))
        table = s.engine.collect(out._plan)
        assert table.num_rows > 0
        assert os.path.exists(sent), "injected shuffle.write raise never fired"
        after = client.stats()["num_objects"]
        assert after == before, (
            f"retried shuffle write leaked {after - before} store objects")
    finally:
        raydp_tpu.stop()


# ==== pipelined shuffle under chaos (ISSUE 8) ======================================
def _run_groupagg_pipelined(app, pipeline="1"):
    """The canonical groupagg with AQE pinned off so the pipelined mode
    actually engages (the AQE-wins rule barriers AQE-capable stages);
    ``pipeline="0"`` is the fault-free BARRIER baseline the pipelined chaos
    legs compare byte-identical against."""
    os.environ["RDT_ETL_AQE"] = "0"
    os.environ["RDT_SHUFFLE_PIPELINE"] = pipeline
    try:
        return _run_groupagg(app)
    finally:
        os.environ.pop("RDT_ETL_AQE", None)
        os.environ.pop("RDT_SHUFFLE_PIPELINE", None)


def test_pipelined_stale_range_regenerates_and_reseals(tmp_path,
                                                       monkeypatch):
    """Chaos leg (a): a map blob dropped AFTER its seal notification but
    BEFORE the reducer's fetch — ``shuffle.write:drop`` frees the
    consolidated blob executor-side, yet the winning result still reaches
    the driver, which publishes the seal; the streaming reducer's fetch of
    the now-stale range hits ObjectLostError, rides the existing lineage
    path (regenerate producer → RE-SEAL under the same map_id, next
    generation → resubmit), and the result is byte-identical to a
    fault-free BARRIER run."""
    base, base_n, _ = _run_groupagg_pipelined("chaos-pipe-base",
                                              pipeline="0")

    sent = str(tmp_path / "pipe-drop.sentinel")
    monkeypatch.setenv("RDT_FAULTS", f"shuffle.write:drop:nth=2:once={sent}")
    got, got_n, report = _run_groupagg_pipelined("chaos-pipe-drop")
    assert os.path.exists(sent), "injected drop never fired"
    assert got_n == base_n
    assert got == base
    assert any(e["pipelined"] for e in report), report
    assert sum(e.get("recovered", 0) for e in report) >= 1, report
    assert sum(e.get("regenerated", 0) for e in report) >= 1, report


def test_pipelined_speculation_losers_never_seal(tmp_path, monkeypatch):
    """Chaos leg (b): speculation loser seals racing the winner. A seeded
    one-executor straggler forces backup map tasks; only the FIRST
    finisher's result reaches the driver, so only the winner's blob is ever
    published to the seal stream — no duplicate bucket rows — and the
    losers' blobs free through the late-result path (store count back to
    the pre-action baseline)."""
    from raydp_tpu.runtime.object_store import get_client

    base, _, _ = _run_groupagg_pipelined("chaos-pipe-spec-base",
                                         pipeline="0")

    app = "chaos-pipe-spec"
    victim = f"rdt-executor-{app}-0"
    monkeypatch.setenv("RDT_FAULTS",
                       f"executor.run_task:delay:ms=600:match={victim}|")
    monkeypatch.setenv("RDT_SPECULATION_QUANTILE", "0.25")
    monkeypatch.setenv("RDT_SPECULATION_MIN_S", "0.15")
    monkeypatch.setenv("RDT_ETL_AQE", "0")
    monkeypatch.setenv("RDT_SHUFFLE_PIPELINE", "1")
    s = _session(app)
    try:
        client = get_client()
        df = _frame(s)
        before = client.stats()["num_objects"]
        out = df.groupBy("k").agg(F.sum("v").alias("s"),
                                  F.count("v").alias("n"))
        table = s.engine.collect(out._plan).sort_by([("k", "ascending")])
        report = s.engine.shuffle_stage_report()
        assert _ipc_bytes(table) == base, \
            "a loser's seal leaked duplicate bucket rows"
        assert any(e["pipelined"] for e in report), report
        assert sum(e.get("speculated", 0) for e in report) >= 1, report
        deadline = time.time() + 30
        while time.time() < deadline \
                and client.stats()["num_objects"] != before:
            time.sleep(0.2)
        after = client.stats()["num_objects"]
        assert after == before, (
            f"pipelined speculation races orphaned {after - before} blobs")
    finally:
        raydp_tpu.stop()


def test_pipelined_streamed_fetch_drop_recovery(tmp_path, monkeypatch):
    """Chaos leg (c): pipelining + ``shuffle.fetch:drop`` — the drop fires
    INSIDE a streaming reducer's fetch round (frees the backing blob, then
    the typed loss), mid-stream with other portions already decoded; the
    regenerated producer re-seals and the resubmitted reducer re-reads the
    whole bucket byte-identical to a fault-free barrier run."""
    base, base_n, _ = _run_groupagg_pipelined("chaos-pipe-fdrop-base",
                                              pipeline="0")

    sent = str(tmp_path / "pipe-fdrop.sentinel")
    monkeypatch.setenv("RDT_FAULTS",
                       f"shuffle.fetch:drop:nth=2:once={sent}")
    got, got_n, report = _run_groupagg_pipelined("chaos-pipe-fdrop")
    assert os.path.exists(sent), "injected streamed-fetch drop never fired"
    assert got_n == base_n
    assert got == base
    assert any(e["pipelined"] for e in report), report
    assert sum(e.get("recovered", 0) for e in report) >= 1, report


# ==== adaptive execution under chaos (ISSUE 7) =====================================
def _run_broadcast_join(app):
    """One session running the canonical broadcast join (small dim side →
    AQE replicates it, neither side shuffles); returns (result ipc bytes,
    row count, report)."""
    s = _session(app)
    try:
        rng = np.random.RandomState(2)
        n = 4000
        big = s.createDataFrame(
            pd.DataFrame({"k": rng.randint(0, 30, n),
                          "v": rng.randint(0, 1000, n).astype(np.int64)}),
            num_partitions=4)
        dim = s.createDataFrame(
            pd.DataFrame({"k": np.arange(30),
                          "lab": (np.arange(30) * 3).astype(np.int64)}),
            num_partitions=2)
        out = big.join(dim, on="k").select("k", "v", "lab")
        n_rows = s.engine.count(out._plan)
        table = s.engine.collect(out._plan).sort_by(
            [("k", "ascending"), ("v", "ascending")])
        return _ipc_bytes(table), n_rows, s.engine.shuffle_stage_report()
    finally:
        raydp_tpu.stop()


def test_dropped_broadcast_replica_blob_recovery(tmp_path, monkeypatch):
    """A broadcast side's store blob silently dropped before any executor
    fetched its replica (``shuffle.fetch:drop`` — the first RANGED read in
    an executor is a broadcast fetch, since a pre-shuffle broadcast join has
    no other ranged reads): the probe task hits ObjectLostError, lineage
    regenerates the small side's producer (ledgered under join-broadcast),
    the BroadcastJoinStep's parts are patched to the fresh blob (a new
    broadcast-cache key, so no executor probes stale bytes), and the join
    result is byte-identical. The report shows both the broadcast AND the
    recovery."""
    base, base_n, base_rep = _run_broadcast_join("chaos-bcast-base")
    assert sum(e.get("aqe_broadcast", 0) for e in base_rep) >= 1, base_rep

    sent = str(tmp_path / "bcast-drop.sentinel")
    monkeypatch.setenv("RDT_FAULTS", f"shuffle.fetch:drop:nth=1:once={sent}")
    got, got_n, report = _run_broadcast_join("chaos-bcast-drop")
    assert os.path.exists(sent), "injected broadcast-replica drop never fired"
    assert got_n == base_n
    assert got == base
    assert sum(e.get("aqe_broadcast", 0) for e in report) >= 1, report
    assert sum(e.get("recovered", 0) for e in report) >= 1, report
    assert sum(e.get("regenerated", 0) for e in report) >= 1, report


def _run_skew_groupagg(app):
    """One session running a skew-split groupby (hot key ~50%, unique-first
    chunks so row-wise partials carry the skew to the reduce side)."""
    s = _session(app)
    try:
        rng = np.random.RandomState(9)
        rows, parts = 16_000, 4
        per = rows // parts
        chunks, nxt = [], 1
        for _ in range(parts):
            nu = per // 2
            ks = np.concatenate([np.arange(nxt, nxt + nu) * 7 + 3,
                                 np.zeros(per - nu, dtype=np.int64)])
            nxt += nu
            chunks.append(pd.DataFrame(
                {"k": ks, "v": rng.randint(0, 1000, per).astype(np.int64)}))
        df = s.createDataFrame(pd.concat(chunks).reset_index(drop=True),
                               num_partitions=parts)
        out = df.groupBy("k").agg(F.sum("v").alias("sv"),
                                  F.count("v").alias("n"))
        table = s.engine.collect(out._plan).sort_by([("k", "ascending")])
        return _ipc_bytes(table), s.engine.shuffle_stage_report()
    finally:
        raydp_tpu.stop()


def test_dropped_split_read_source_mid_skew_recovery(tmp_path, monkeypatch):
    """A map blob dropped exactly when a SPLIT task's ranged read touches it
    (``shuffle.fetch:drop:nth=1`` — the split stage issues the first ranged
    reads of the action): lineage regenerates the map producer, the split
    task's RangeRefSource is patched (offsets survive: reruns are
    byte-identical), and the re-planned aggregation is byte-identical with
    both the split and the recovery in the ledger."""
    monkeypatch.setenv("RDT_AQE_COALESCE_MIN", "0")
    monkeypatch.setenv("RDT_AQE_SKEW_FACTOR", "2")
    base, base_rep = _run_skew_groupagg("chaos-skew-base")
    assert sum(e.get("aqe_split", 0) for e in base_rep) >= 1, base_rep

    sent = str(tmp_path / "split-drop.sentinel")
    monkeypatch.setenv("RDT_FAULTS", f"shuffle.fetch:drop:nth=1:once={sent}")
    got, report = _run_skew_groupagg("chaos-skew-drop")
    assert os.path.exists(sent), "injected split-read drop never fired"
    assert got == base
    assert sum(e.get("aqe_split", 0) for e in report) >= 1, report
    assert sum(e.get("recovered", 0) for e in report) >= 1, report


def test_broadcast_speculation_losers_leave_no_orphans(tmp_path,
                                                       monkeypatch):
    """The no-orphan store-count contract with BROADCAST replicas in the
    race: a seeded one-executor straggler makes the broadcast side's
    materialize tasks speculate; the losing copy's blob is a duplicate
    broadcast replica that reaches no caller and must free through the
    loser-drain path — after the action settles, the store count returns to
    its pre-action baseline and the result matches a straggler-free run."""
    from raydp_tpu.runtime.object_store import get_client

    base, base_n, _ = _run_broadcast_join("chaos-bcast-spec-base")

    app = "chaos-bcast-spec"
    victim = f"rdt-executor-{app}-0"
    monkeypatch.setenv("RDT_FAULTS",
                       f"executor.run_task:delay:ms=600:match={victim}|")
    monkeypatch.setenv("RDT_SPECULATION", "1")
    monkeypatch.setenv("RDT_SPECULATION_QUANTILE", "0.5")
    monkeypatch.setenv("RDT_SPECULATION_MIN_S", "0.2")
    s = _session(app)
    try:
        rng = np.random.RandomState(2)
        n = 4000
        big = s.createDataFrame(
            pd.DataFrame({"k": rng.randint(0, 30, n),
                          "v": rng.randint(0, 1000, n).astype(np.int64)}),
            num_partitions=4)
        dim = s.createDataFrame(
            pd.DataFrame({"k": np.arange(30),
                          "lab": (np.arange(30) * 3).astype(np.int64)}),
            num_partitions=2)
        client = get_client()
        before = client.stats()["num_objects"]
        out = big.join(dim, on="k").select("k", "v", "lab")
        n_rows = s.engine.count(out._plan)
        table = s.engine.collect(out._plan).sort_by(
            [("k", "ascending"), ("v", "ascending")])
        report = s.engine.shuffle_stage_report()
        assert n_rows == base_n
        assert _ipc_bytes(table) == base
        assert sum(e.get("aqe_broadcast", 0) for e in report) >= 1, report
        # losing duplicates land late and free through the loser path:
        # poll the store audit back to the pre-action baseline
        deadline = time.time() + 30
        while time.time() < deadline \
                and client.stats()["num_objects"] != before:
            time.sleep(0.2)
        orphans = client.stats()["num_objects"] - before
        assert orphans == 0, (
            f"broadcast speculation races orphaned {orphans} store objects")
    finally:
        raydp_tpu.stop()


# ==== elastic pool under chaos (ISSUE 13) ==========================================
def _session3(app):
    return raydp_tpu.init(app, num_executors=3, executor_cores=1,
                          executor_memory="512MB")


def _collect_groupagg_during_retire(app, victim_suffix="-2",
                                    retire_after_s=0.4):
    """Start the canonical groupagg on a background thread, retire one
    executor mid-action, join, and return (ipc bytes, report, session-level
    facts). The session is fully torn down before returning."""
    from raydp_tpu.runtime.object_store import get_client

    s = _session3(app)
    try:
        df = _frame(s)
        client = get_client()
        before = client.stats()["num_objects"]
        out = df.groupBy("k").agg(F.sum("v").alias("s"),
                                  F.count("v").alias("n"))
        box = {}

        def run():
            try:
                box["table"] = s.engine.collect(out._plan) \
                    .sort_by([("k", "ascending")])
            except Exception as e:  # noqa: BLE001 - asserted below
                box["error"] = e

        t = threading.Thread(target=run)
        t.start()
        time.sleep(retire_after_s)
        victim = f"rdt-executor-{app}{victim_suffix}"
        s.retire_executor(victim)
        t.join(timeout=300)
        assert not t.is_alive(), "action wedged across the retirement"
        if "error" in box:
            raise box["error"]
        # store-count audit: the drain + recovery leave zero orphans
        # (late losers/regenerations free asynchronously: poll)
        deadline = time.time() + 30
        while time.time() < deadline \
                and client.stats()["num_objects"] != before:
            time.sleep(0.25)
        orphans = client.stats()["num_objects"] - before
        return (_ipc_bytes(box["table"]), s.engine.shuffle_stage_report(),
                {"orphans": orphans, "pool": len(s.executors),
                 "survivors": [h.name for h in s.executors]})
    finally:
        raydp_tpu.stop()


def test_scale_down_races_lineage_recovery(tmp_path, monkeypatch):
    """Chaos leg (ISSUE 13a): a graceful scale-down races an in-flight
    lineage recovery round. A dropped map blob forces recovery while every
    task is slowed enough that the retirement lands mid-action: the drain
    takes the retiring executor out of rotation, its in-flight tasks finish
    or re-queue, and the recovery round re-runs producers on the shrunken
    pool — byte-identical to a fault-free FIXED-pool run, zero orphaned
    store objects, recovery surfaced in the ledger."""
    s = _session3("chaos-scaledown-base")
    try:
        df = _frame(s)
        out = df.groupBy("k").agg(F.sum("v").alias("s"),
                                  F.count("v").alias("n"))
        base = _ipc_bytes(s.engine.collect(out._plan)
                          .sort_by([("k", "ascending")]))
    finally:
        raydp_tpu.stop()

    sent = str(tmp_path / "scaledown-drop.sentinel")
    monkeypatch.setenv(
        "RDT_FAULTS",
        "executor.run_task:delay:ms=250;"
        f"shuffle.write:drop:nth=2:once={sent}")
    got, report, facts = _collect_groupagg_during_retire("chaos-scaledown")
    assert os.path.exists(sent), "injected drop never fired"
    assert got == base
    assert facts["pool"] == 2, facts
    assert facts["orphans"] == 0, (
        f"scale-down racing recovery orphaned {facts['orphans']} objects")
    assert sum(e.get("recovered", 0) for e in report) >= 1, report
    assert sum(e.get("regenerated", 0) for e in report) >= 1, report


def test_scale_down_drain_crash_races_pipelined_stream(tmp_path,
                                                      monkeypatch):
    """Chaos leg (ISSUE 13b): the retiring executor DIES mid-drain
    (``pool.drain:crash``) while a pipelined shuffle it feeds is
    mid-stream. Its unfinished map tasks fail and re-run on survivors,
    their seals publish (or re-seal under the next generation through the
    PR 7 machinery), streaming reducers keep decoding — byte-identical to
    a fault-free fixed-pool BARRIER run, zero orphans."""
    monkeypatch.setenv("RDT_ETL_AQE", "0")
    monkeypatch.setenv("RDT_SHUFFLE_PIPELINE", "0")
    s = _session3("chaos-draincrash-base")
    try:
        df = _frame(s)
        out = df.groupBy("k").agg(F.sum("v").alias("s"),
                                  F.count("v").alias("n"))
        base = _ipc_bytes(s.engine.collect(out._plan)
                          .sort_by([("k", "ascending")]))
    finally:
        raydp_tpu.stop()

    sent = str(tmp_path / "drain-crash.sentinel")
    monkeypatch.setenv("RDT_SHUFFLE_PIPELINE", "1")
    monkeypatch.setenv(
        "RDT_FAULTS",
        "executor.run_task:delay:ms=250:match=|mt-;"
        f"pool.drain:crash:once={sent}")
    got, report, facts = _collect_groupagg_during_retire(
        "chaos-draincrash", retire_after_s=0.3)
    assert os.path.exists(sent), "drain-crash schedule never fired"
    assert got == base
    assert any(e["pipelined"] for e in report), report
    assert facts["pool"] == 2, facts
    assert facts["orphans"] == 0, (
        f"drain-crash mid-stream orphaned {facts['orphans']} objects")


# A serving burst cut into batches by count, not by the clock: four two-row
# requests fill a batch and it leaves; the flush by age, which only the
# one-row tail waits for, stands beyond any stall of the submitting thread
# seen here (164 ms at twice as many busy processes as cores). A replica's
# program is compiled for its batch's row count and a row's last bits follow
# it (ROADMAP.md C13): a fault-free and a faulted run with equal batches are
# what "byte-identical" compares.
_PIN_BATCHES = {"RDT_SERVE_MAX_BATCH": "8",
                "RDT_SERVE_BATCH_TIMEOUT_MS": "250"}


def test_scale_down_races_live_serving_replica(tmp_path):
    """Chaos leg (ISSUE 13c): the executor hosting a live serving replica
    is retired mid-burst. In-flight dispatches re-route through the hedge
    path, the background reload routes through the pool's LIVE-member view
    and re-homes the replica onto a survivor (satellite fix — it used to
    probe the retired corpse until the grace expired) — zero dropped
    requests, results byte-identical to a fault-free fixed-pool run. Both
    runs cut the burst into the same batches (``_PIN_BATCHES``)."""
    import optax

    from raydp_tpu.models import MLP
    from raydp_tpu.serve import ServingSession
    from raydp_tpu.train import FlaxEstimator

    rng = np.random.RandomState(11)
    x = rng.random_sample((512, 2))
    y = x @ np.array([2.0, -3.0]) + 1.0
    pdf = pd.DataFrame({"x1": x[:, 0], "x2": x[:, 1], "y": y})
    export_dir = str(tmp_path / "scale-servable")
    results, reports = {}, {}

    for mode in ("clean", "retire"):
        os.environ.update(_PIN_BATCHES)
        s = raydp_tpu.init(f"serve_scale_{mode}", num_executors=3,
                           executor_cores=1, executor_memory="512MB")
        try:
            if mode == "clean":
                df = s.createDataFrame(pdf, num_partitions=2)
                est = FlaxEstimator(
                    model=MLP(features=(8,), use_batch_norm=False),
                    optimizer=optax.adam(1e-2), loss="mse",
                    feature_columns=["x1", "x2"], label_column="y",
                    batch_size=64, num_epochs=1)
                est.fit_on_frame(df)
                est.export_serving(export_dir)
            srv = ServingSession(export_dir, session=s, name="scalesrv")
            try:
                futs = [srv.predict_async({"x1": x[i:i + 2, 0],
                                           "x2": x[i:i + 2, 1]})
                        for i in range(0, 64, 2)]
                if mode == "retire":
                    # replica scalesrv-r0 lives on executor 0: retire it
                    # with the burst in flight
                    s.retire_executor(f"rdt-executor-serve_scale_{mode}-0")
                burst = [f.result(timeout=120.0) for f in futs]
                tail = [srv.predict({"x1": x[64 + i:65 + i, 0],
                                     "x2": x[64 + i:65 + i, 1]},
                                    timeout=120.0)
                        for i in range(16)]
                results[mode] = np.concatenate(burst + tail)
                # the re-homed replica's background reload may still be
                # jitting on the survivor: poll until it is back in rotation
                deadline = time.time() + 60
                while True:
                    reports[mode] = srv.serving_report()
                    if all(r["ready"] for r in reports[mode]["replicas"]) \
                            or time.time() > deadline:
                        break
                    time.sleep(0.25)
            finally:
                srv.close()
        finally:
            raydp_tpu.stop()
            for knob in _PIN_BATCHES:
                os.environ.pop(knob, None)

    assert reports["retire"]["failed"] == 0, reports["retire"]
    assert len(results["retire"]) == len(results["clean"]) == 80
    assert np.array_equal(results["clean"], results["retire"])
    # the replica re-homed off the retired executor onto a survivor
    r0 = next(r for r in reports["retire"]["replicas"]
              if r["replica"] == "scalesrv-r0")
    assert r0["executor"] != "rdt-executor-serve_scale_retire-0", r0
    assert r0["ready"], r0


def test_serving_replica_crash_reroutes_zero_dropped(tmp_path):
    """ISSUE 11 serving chaos leg: a replica crash mid-stream under seeded
    load re-routes the in-flight (and every later) request through the
    hedge path — ZERO dropped requests, results byte-identical to a
    fault-free run. The crashed executor restarts (max_restarts=-1) and the
    replica reloads in the background; the once= sentinel keeps the
    restarted process from re-crashing on the inherited spec."""
    import optax

    from raydp_tpu.models import MLP
    from raydp_tpu.serve import ServingSession
    from raydp_tpu.train import FlaxEstimator

    rng = np.random.RandomState(11)
    x = rng.random_sample((512, 2))
    y = x @ np.array([2.0, -3.0]) + 1.0
    pdf = pd.DataFrame({"x1": x[:, 0], "x2": x[:, 1], "y": y})
    export_dir = str(tmp_path / "chaos-servable")
    sentinel = str(tmp_path / "serve_crash.sentinel")
    results, reports = {}, {}

    for mode in ("clean", "crash"):
        if mode == "crash":
            # the 2nd batch entering replica chaos-r0's worker kills its
            # executor process abruptly, mid-request (env set BEFORE init so
            # the spawned executors inherit it)
            os.environ["RDT_FAULTS"] = (
                f"serve.predict:crash:nth=2:match=|chaos-r0:once={sentinel}")
        os.environ.update(_PIN_BATCHES)
        s = _session(f"serve_chaos_{mode}")
        try:
            if mode == "clean":
                df = s.createDataFrame(pdf, num_partitions=2)
                est = FlaxEstimator(
                    model=MLP(features=(8,), use_batch_norm=False),
                    optimizer=optax.adam(1e-2), loss="mse",
                    feature_columns=["x1", "x2"], label_column="y",
                    batch_size=64, num_epochs=1)
                est.fit_on_frame(df)
                est.export_serving(export_dir)
            srv = ServingSession(export_dir, session=s, name="chaos")
            try:
                # seeded load: a concurrent burst (coalesces, and is what
                # the crash lands in the middle of) + a sequential tail
                # (proves the plane keeps serving after the loss)
                futs = [srv.predict_async({"x1": x[i:i + 2, 0],
                                           "x2": x[i:i + 2, 1]})
                        for i in range(0, 64, 2)]
                burst = [f.result(timeout=120.0) for f in futs]
                tail = [srv.predict({"x1": x[64 + i:65 + i, 0],
                                     "x2": x[64 + i:65 + i, 1]},
                                    timeout=120.0)
                        for i in range(16)]
                results[mode] = np.concatenate(burst + tail)
                reports[mode] = srv.serving_report()
            finally:
                srv.close()
        finally:
            raydp_tpu.stop()
            os.environ.pop("RDT_FAULTS", None)
            for knob in _PIN_BATCHES:
                os.environ.pop(knob, None)

    # the injection actually fired, and every request still completed
    assert os.path.exists(sentinel), "crash schedule never fired"
    assert reports["crash"]["failed"] == 0
    assert reports["crash"]["rerouted"] >= 1, reports["crash"]
    assert len(results["crash"]) == len(results["clean"]) == 80
    # byte-identical to the fault-free run (row-independent jitted apply:
    # neither the crash nor the re-route may leak into the numbers; both
    # runs cut the burst into the same batches: ``_PIN_BATCHES``)
    assert np.array_equal(results["clean"], results["crash"])


# ==== guarded rollouts under chaos (ISSUE 18) ================================

def _guard_traffic(srv, x, n, out, timeout=120.0):
    """Sequential seeded load for the rollout legs: ``n`` 2-row predicts in
    a FIXED order, responses appended in that order — two runs (with and
    without a rollout in flight) produce position-comparable sequences."""
    for i in range(n):
        j = (2 * i) % 400
        out.append(srv.predict({"x1": x[j:j + 2, 0],
                                "x2": x[j:j + 2, 1]}, timeout=timeout))


def _settle_baseline_p99(srv, x, below_ms, timeout=120.0):
    """Predict until the primary's reported p99 is under ``below_ms``. The
    rollout judge reads a version's p99 off its whole latency window, and
    over a window of a hundred samples that is the slowest one: each
    replica's cold first predict (~0.2 s on a loaded host, more under a
    parallel suite). Waits on the report, not on a clock."""
    deadline = time.time() + timeout
    row = None
    while time.time() < deadline:
        _guard_traffic(srv, x, 50, [])
        row = next(v for v in srv.serving_report()["versions"]
                   if v["primary"])
        if row["p99_ms"] < below_ms:
            return
    raise AssertionError(f"baseline p99 never settled under {below_ms} ms: "
                         f"{row}")


def test_rollout_canary_latency_regression_rolls_back(tmp_path):
    """ISSUE 18 chaos leg (a): a canary whose every predict is stalled by a
    seeded ``serve.predict:delay`` (replica-id match ``-v2-`` pins the
    injection to the canary group alone) is judged unhealthy on the p99 arm
    and AUTO-ROLLS-BACK mid-traffic: zero dropped requests, results
    byte-identical to a rollout-free run, and the postmortem artifacts — a
    ``rollout_rollback`` event plus a flight-recorder blackbox bundle — are
    present. The delay rule has no once= sentinel (it must fire on every
    canary call to regress the p99 window); the ``"p99"`` rollback reason is
    the proof the injection bit."""
    import optax

    from raydp_tpu import metrics
    from raydp_tpu.models import MLP
    from raydp_tpu.runtime import head as head_mod
    from raydp_tpu.serve import ServingSession
    from raydp_tpu.train import FlaxEstimator

    rng = np.random.RandomState(11)
    x = rng.random_sample((512, 2))
    y = x @ np.array([2.0, -3.0]) + 1.0
    pdf = pd.DataFrame({"x1": x[:, 0], "x2": x[:, 1], "y": y})
    dir_v1 = str(tmp_path / "guard-v1")
    dir_v2 = str(tmp_path / "guard-v2")
    results, reports = {}, {}
    outcome = None

    for mode in ("clean", "rollout"):
        if mode == "rollout":
            # EVERY canary predict (replica ids guard-v2-r*) stalls 700ms —
            # a pure latency regression (no errors): only the p99 arm can
            # catch it (env set BEFORE init so executors inherit it). The
            # stall must clear the 2x judgment bar over the baseline's p99,
            # or the verdict flaps healthy and ramps a genuinely slow
            # canary: the baseline is settled to a quarter of it first.
            os.environ["RDT_FAULTS"] = \
                "serve.predict:delay:ms=700:match=-v2-"
        os.environ["RDT_SERVE_BATCH_TIMEOUT_MS"] = "10"
        os.environ["RDT_SERVE_HEDGE"] = "0"
        s = _session(f"serve_rollout_{mode}")
        try:
            if mode == "clean":
                df = s.createDataFrame(pdf, num_partitions=2)
                est = FlaxEstimator(
                    model=MLP(features=(8,), use_batch_norm=False),
                    optimizer=optax.adam(1e-2), loss="mse",
                    feature_columns=["x1", "x2"], label_column="y",
                    batch_size=64, num_epochs=1)
                est.fit_on_frame(df)
                est.export_serving(dir_v1)
                # the canary is the SAME weights exported again: responses
                # must be byte-identical whichever version answers, so the
                # identity assert covers requests served mid-ramp too
                est.export_serving(dir_v2)
            srv = ServingSession(dir_v1, session=s, name="guard")
            try:
                _settle_baseline_p99(srv, x, below_ms=700 / 4)
                got = []
                t = threading.Thread(target=_guard_traffic,
                                     args=(srv, x, 120, got))
                t.start()
                try:
                    if mode == "rollout":
                        outcome = srv.rollout(
                            dir_v2, tag="regressed", initial_weight=0.5,
                            steps=[0.5, 1.0], step_s=20.0, min_samples=6,
                            p99_factor=2.0, timeout=120.0)
                finally:
                    t.join(timeout=180.0)
                assert not t.is_alive(), "traffic thread hung"
                results[mode] = np.concatenate(got)
                reports[mode] = srv.serving_report()
                if mode == "rollout":
                    # postmortem artifacts, checked while the session (and
                    # its session_dir) is live
                    kinds = [e["kind"] for e in metrics.events()]
                    assert "rollout_rollback" in kinds, kinds
                    bb_dir = os.path.join(
                        head_mod.get_runtime().session_dir, "blackbox")
                    bundles = [f for f in os.listdir(bb_dir)
                               if f.startswith("blackbox-rollout-guard")
                               and f.endswith(".json")]
                    assert bundles, "rollback wrote no blackbox bundle"
            finally:
                srv.close()
        finally:
            raydp_tpu.stop()
            os.environ.pop("RDT_FAULTS", None)
            os.environ.pop("RDT_SERVE_BATCH_TIMEOUT_MS", None)
            os.environ.pop("RDT_SERVE_HEDGE", None)

    # the guard judged the latency regression, not an error burst
    assert outcome["outcome"] == "rolled_back", outcome
    assert "p99" in outcome["reason"], outcome
    # zero dropped: every seeded request completed, none failed terminally
    assert reports["rollout"]["failed"] == 0, reports["rollout"]
    assert len(results["rollout"]) == len(results["clean"]) == 240
    # byte-identical to the rollout-free run: neither the canary detour nor
    # the rollback re-home may leak into the numbers
    assert np.array_equal(results["clean"], results["rollout"])
    # the canary group is gone: the primary (v1) is the only live version
    # and no replica still carries the canary's bundle
    rep = reports["rollout"]
    assert rep["servable"]["version"] == 1, rep["servable"]
    assert [vr["version"] for vr in rep["versions"]] == [1], rep["versions"]
    assert all(r["version"] == 1 for r in rep["replicas"]), rep["replicas"]


def test_rollout_canary_executor_crash_mid_ramp_stays_unmixed(tmp_path):
    """ISSUE 18 chaos leg (b): the canary's executor CRASHES mid-ramp
    (``nth=2`` on replica guardb-v2-r0, once= sentinel). The in-flight
    dispatch re-routes VERSION-LOCALLY to the canary's surviving sibling —
    the ramp then continues or rolls back on its own judgment, but no
    response ever mixes versions: every answer is checked row-for-row
    against locally computed reference predictions of model A and model B
    (two genuinely different trainings) and must equal exactly one of
    them."""
    import optax

    from raydp_tpu.models import MLP
    from raydp_tpu.serve import ServingSession, load_servable
    from raydp_tpu.train import FlaxEstimator

    rng = np.random.RandomState(11)
    x = rng.random_sample((512, 2))
    y = x @ np.array([2.0, -3.0]) + 1.0
    pdf = pd.DataFrame({"x1": x[:, 0], "x2": x[:, 1], "y": y})
    dir_a = str(tmp_path / "guardb-a")
    dir_b = str(tmp_path / "guardb-b")
    sentinel = str(tmp_path / "rollout_crash.sentinel")

    # the 2nd batch entering canary replica guardb-v2-r0 kills its executor
    # abruptly mid-request; the primary replica colocated on that executor
    # dies with it (both groups must re-route, each within its own version)
    os.environ["RDT_FAULTS"] = (
        f"serve.predict:crash:nth=2:match=|guardb-v2-r0:once={sentinel}")
    os.environ["RDT_SERVE_BATCH_TIMEOUT_MS"] = "10"
    os.environ["RDT_SERVE_HEDGE"] = "0"
    s = _session("serve_rollout_crash")
    try:
        df = s.createDataFrame(pdf, num_partitions=2)
        # two genuinely different models: more epochs move the weights, and
        # the refs-differ assert below keeps the mixing check non-vacuous
        est_a = FlaxEstimator(
            model=MLP(features=(8,), use_batch_norm=False),
            optimizer=optax.adam(1e-2), loss="mse",
            feature_columns=["x1", "x2"], label_column="y",
            batch_size=64, num_epochs=1)
        est_a.fit_on_frame(df)
        est_a.export_serving(dir_a)
        est_b = FlaxEstimator(
            model=MLP(features=(8,), use_batch_norm=False),
            optimizer=optax.adam(1e-2), loss="mse",
            feature_columns=["x1", "x2"], label_column="y",
            batch_size=64, num_epochs=4)
        est_b.fit_on_frame(df)
        est_b.export_serving(dir_b)

        # per-request reference predictions, computed locally through the
        # SAME servable decode/place/apply path the replicas run
        sv_a, sv_b = load_servable(dir_a), load_servable(dir_b)
        batches = []
        refs_a, refs_b = [], []
        for i in range(120):
            j = (2 * i) % 400
            tbl = pa.table({"x1": x[j:j + 2, 0], "x2": x[j:j + 2, 1]})
            batches.append(j)
            refs_a.append(sv_a.predict_table(tbl))
            refs_b.append(sv_b.predict_table(tbl))
        assert not np.array_equal(refs_a[0], refs_b[0]), \
            "models A and B predict identically; mixing check is vacuous"

        srv = ServingSession(dir_a, session=s, name="guardb")
        try:
            got = []
            t = threading.Thread(target=_guard_traffic,
                                 args=(srv, x, 120, got))
            t.start()
            try:
                outcome = srv.rollout(
                    dir_b, tag="crashy-host", initial_weight=0.5,
                    steps=[0.5, 1.0], step_s=10.0, min_samples=4,
                    timeout=180.0)
            finally:
                t.join(timeout=240.0)
            assert not t.is_alive(), "traffic thread hung"
            report = srv.serving_report()
        finally:
            srv.close()
    finally:
        raydp_tpu.stop()
        os.environ.pop("RDT_FAULTS", None)
        os.environ.pop("RDT_SERVE_BATCH_TIMEOUT_MS", None)
        os.environ.pop("RDT_SERVE_HEDGE", None)

    # the injection actually fired, mid-ramp
    assert os.path.exists(sentinel), "crash schedule never fired"
    # zero dropped: the crashed dispatch re-routed (version-locally) and
    # completed; the ramp reached a terminal verdict on its own
    assert outcome["outcome"] in ("promoted", "rolled_back"), outcome
    assert report["failed"] == 0, report
    assert report["rerouted"] >= 1, report
    assert len(got) == 120
    # NO response mixes versions: each answer equals model A's reference or
    # model B's reference for its batch, entirely
    from_a = from_b = 0
    for i, ans in enumerate(got):
        if np.array_equal(ans, refs_a[i]):
            from_a += 1
        elif np.array_equal(ans, refs_b[i]):
            from_b += 1
        else:
            raise AssertionError(
                f"response {i} (batch offset {batches[i]}) matches neither "
                f"version's reference — versions mixed in one response")
    # both versions actually took traffic (the canary held >= min_samples
    # requests before any terminal verdict)
    assert from_a >= 1 and from_b >= 1, (from_a, from_b)


# ==== multi-tenant overload robustness (ISSUE 14) ============================

def _wide_pdf(n=16000):
    rng = np.random.RandomState(0)
    return pd.DataFrame({"k": rng.randint(0, 50, n),
                         "v": rng.randint(0, 1000, n).astype(np.int64)})


def test_spilled_blob_file_lost_mid_join_recovers(tmp_path, monkeypatch):
    """Chaos leg (ROADMAP item 4's missing fault proof): a spilled shuffle
    blob's DISK FILE is deleted mid-join (``store.spill:drop`` — the
    lost-disk model). The reduce side's transparent fault-in misses the
    file, ``_fault_in`` surfaces the typed ``ObjectLostError``, lineage
    recovery regenerates the map blob — byte-identical to a spill-free
    fault-free run, zero orphans. Parquet inputs keep the store holding
    ONLY intermediates, so every spill victim is lineage-recoverable."""
    from raydp_tpu import config as cfg

    monkeypatch.setenv("RDT_ETL_AQE", "0")  # a broadcast join skips spill
    rng = np.random.RandomState(0)
    for side, col in (("L", "v"), ("R", "w")):
        for i in range(2):
            pdf = pd.DataFrame(
                {"k": rng.randint(0, 200, 6000),
                 col: rng.randint(0, 1000, 6000).astype(np.int64)})
            pdf.to_parquet(str(tmp_path / f"{side}{i}.parquet"))

    def run(app, budget=None):
        from raydp_tpu.runtime.object_store import get_client

        s = raydp_tpu.init(
            app, num_executors=2, executor_cores=1, executor_memory="512MB",
            configs={cfg.SPILL_BUDGET_KEY: str(budget)} if budget else None)
        try:
            client = get_client()
            before = client.stats()["num_objects"]
            dfl = s.read.parquet([str(tmp_path / "L0.parquet"),
                                  str(tmp_path / "L1.parquet")])
            dfr = s.read.parquet([str(tmp_path / "R0.parquet"),
                                  str(tmp_path / "R1.parquet")])
            out = dfl.join(dfr, on="k")
            table = s.engine.collect(out._plan).sort_by(
                [("k", "ascending"), ("v", "ascending"), ("w", "ascending")])
            deadline = time.time() + 30
            while time.time() < deadline \
                    and client.stats()["num_objects"] != before:
                time.sleep(0.2)
            report = s.engine.shuffle_stage_report()
            return (_ipc_bytes(table),
                    client.stats()["num_objects"] - before, report)
        finally:
            raydp_tpu.stop()

    base, orphans0, _ = run("spill-join-base")
    assert orphans0 == 0

    sent = str(tmp_path / "spill-drop.sentinel")
    monkeypatch.setenv("RDT_FAULTS", f"store.spill:drop:nth=1:once={sent}")
    got, orphans, report = run("spill-join-chaos", budget=250_000)
    assert os.path.exists(sent), "store.spill drop never fired"
    assert got == base, "recovered join diverged from the fault-free run"
    assert orphans == 0, f"spill-loss recovery orphaned {orphans} objects"
    assert sum(e.get("recovered", 0) for e in report) >= 1, report
    assert sum(e.get("regenerated", 0) for e in report) >= 1, report


def test_flood_and_interactive_tenants_share_pool(tmp_path, monkeypatch):
    """Fairness chaos leg: a flooding tenant (a wide, per-map-delayed
    groupagg) and an interactive tenant (the canonical small groupagg)
    share ONE pool via two engines. The interactive action completes while
    the flood still has queued work (bounded latency — it never waits out
    the flood's queue), both tenants' results are byte-identical to
    uncontended runs, the per-tenant columns surface in load() and the
    stage report, and the store audit shows zero orphans."""
    from raydp_tpu.etl.engine import Engine

    # uncontended baselines (fault-free, fixed pool)
    s = _session3("chaos-fair-base")
    try:
        small = _frame(s)
        out_s = small.groupBy("k").agg(F.sum("v").alias("s"),
                                       F.count("v").alias("n"))
        base_small = _ipc_bytes(s.engine.collect(out_s._plan)
                                .sort_by([("k", "ascending")]))
        wide = s.createDataFrame(_wide_pdf(), num_partitions=48)
        out_w = wide.groupBy("k").agg(F.sum("v").alias("s"),
                                      F.count("v").alias("n"))
        base_wide = _ipc_bytes(s.engine.collect(out_w._plan)
                               .sort_by([("k", "ascending")]))
    finally:
        raydp_tpu.stop()

    # contended run: per-map delay stretches the flood (48 delayed maps
    # over 12 slots = several waves) so the interactive action demonstrably
    # overlaps it
    monkeypatch.setenv("RDT_FAULTS",
                       "executor.run_task:delay:ms=200:match=|mt-")
    s = _session3("chaos-fair")
    try:
        from raydp_tpu.runtime.object_store import get_client
        client = get_client()
        small = _frame(s)
        out_s = small.groupBy("k").agg(F.sum("v").alias("s"),
                                       F.count("v").alias("n"))
        # the flood is a SECOND tenant on the same pool: a second engine
        # over the session's executors, wide input (16 delayed maps)
        flood_eng = Engine(s.engine.pool,
                           shuffle_partitions=s.engine.shuffle_partitions,
                           owner=s.engine.owner, tenant="flood")
        wide = s.createDataFrame(_wide_pdf(), num_partitions=48)
        out_w = wide.groupBy("k").agg(F.sum("v").alias("s"),
                                      F.count("v").alias("n"))
        before = client.stats()["num_objects"]
        box = {}

        def flood():
            try:
                box["wide"] = _ipc_bytes(flood_eng.collect(out_w._plan)
                                         .sort_by([("k", "ascending")]))
            except Exception as e:  # noqa: BLE001 - asserted below
                box["error"] = e

        t = threading.Thread(target=flood)
        t.start()
        deadline = time.time() + 30
        while time.time() < deadline \
                and (s.engine.pool.load()["tenants"]
                     .get("flood", {}).get("queued", 0)) < 4:
            time.sleep(0.02)  # the flood has saturated + queued
        t0 = time.monotonic()
        got_small = _ipc_bytes(s.engine.collect(out_s._plan)
                               .sort_by([("k", "ascending")]))
        inter_wall = time.monotonic() - t0
        load_at_finish = s.engine.pool.load()
        t.join(timeout=300)
        assert "error" not in box, box.get("error")
        # bounded latency: the interactive action finished while the flood
        # still had queued work — it shared slots instead of queueing behind
        flood_row = load_at_finish["tenants"].get("flood", {})
        assert flood_row.get("queued", 0) > 0, load_at_finish
        assert inter_wall < 20.0, f"interactive starved: {inter_wall:.1f}s"
        # per-tenant observability: both tenants' dispatch counts surface,
        # and the stage report carries the tenant column
        tenants = load_at_finish["tenants"]
        assert tenants[s.master_name]["dispatched"] >= 1
        assert tenants["flood"]["dispatched"] >= 1
        rep = s.engine.shuffle_stage_report() + \
            flood_eng.shuffle_stage_report()
        assert {e["tenant"] for e in rep} >= {s.master_name, "flood"}
        # accepted results byte-identical to the uncontended runs
        assert got_small == base_small
        assert box["wide"] == base_wide
        deadline = time.time() + 30
        while time.time() < deadline \
                and client.stats()["num_objects"] != before:
            time.sleep(0.25)
        orphans = client.stats()["num_objects"] - before
        assert orphans == 0, f"contended run orphaned {orphans} objects"
    finally:
        raydp_tpu.stop()


def test_serving_overload_burst_sheds_typed(tmp_path, monkeypatch):
    """Serving overload chaos leg: a burst far past RDT_SERVE_MAX_QUEUE
    against a deliberately slowed replica sheds with the typed retriable
    ServingOverloaded — the dispatcher stays alive (accepted requests all
    complete, a post-burst request is served), accepted results are
    byte-identical to an uncontended run, and the report shows
    failed == shed only."""
    import optax

    from raydp_tpu.models import MLP
    from raydp_tpu.serve import ServingOverloaded, ServingSession
    from raydp_tpu.train import FlaxEstimator

    rng = np.random.RandomState(11)
    x = rng.random_sample((256, 2))
    y = x @ np.array([2.0, -3.0]) + 1.0
    pdf = pd.DataFrame({"x1": x[:, 0], "x2": x[:, 1], "y": y})
    export_dir = str(tmp_path / "overload-servable")

    monkeypatch.setenv("RDT_SERVE_BATCH_TIMEOUT_MS", "5")
    # armed BEFORE init so the spawned executors (where serve.predict
    # fires) inherit the delay; it slows every replica apply by 120ms,
    # which cannot change the jitted numbers — only the queue dynamics
    monkeypatch.setenv("RDT_FAULTS", "serve.predict:delay:ms=120")
    s = _session("serve_overload")
    try:
        df = s.createDataFrame(pdf, num_partitions=2)
        est = FlaxEstimator(model=MLP(features=(8,), use_batch_norm=False),
                            optimizer=optax.adam(1e-2), loss="mse",
                            feature_columns=["x1", "x2"], label_column="y",
                            batch_size=64, num_epochs=1)
        est.fit_on_frame(df)
        est.export_serving(export_dir)

        # uncontended reference predictions (shedding off)
        monkeypatch.setenv("RDT_SERVE_MAX_QUEUE", "0")
        with ServingSession(export_dir, session=s, name="ref",
                            num_replicas=1) as ref:
            expect = [ref.predict({"x1": x[i:i + 2, 0],
                                   "x2": x[i:i + 2, 1]}, timeout=60.0)
                      for i in range(0, 64, 2)]

        # overload run: the same slow replicas + a tight queue bound
        monkeypatch.setenv("RDT_SERVE_MAX_QUEUE", "6")
        srv = ServingSession(export_dir, session=s, name="overload",
                             num_replicas=1)
        try:
            accepted, shed = [], 0
            for i in range(0, 64, 2):
                try:
                    accepted.append(
                        (i // 2, srv.predict_async({"x1": x[i:i + 2, 0],
                                                    "x2": x[i:i + 2, 1]})))
                except ServingOverloaded:
                    shed += 1
            assert shed >= 1, "burst never shed"
            assert len(accepted) >= 6
            for idx, fut in accepted:
                got = fut.result(timeout=120.0)
                assert np.array_equal(got, expect[idx]), idx
            rep = srv.serving_report()
            assert rep["shed"] == shed
            assert rep["failed"] == rep["shed"], rep  # failed == shed ONLY
            # the dispatcher survived the burst: a fresh request serves
            tail = srv.predict({"x1": x[:2, 0], "x2": x[:2, 1]},
                               timeout=60.0)
            assert np.array_equal(tail, expect[0])
            from raydp_tpu import metrics
            assert "overload_shed" in [e["kind"] for e in metrics.events()]
        finally:
            srv.close()
    finally:
        raydp_tpu.stop()


def test_admission_composes_with_autoscale_and_drain(tmp_path, monkeypatch):
    """Admission chaos leg: a flooding tenant pushes the pool backlog past
    RDT_POOL_MAX_QUEUED so a second action PARKS at admission; the
    autoscaler (armed, fast cadence) sees the parked demand and grows the
    pool; a concurrent graceful drain retires an executor mid-flood. Both
    actions complete byte-identical to uncontended baselines, the parked
    action was admitted (never rejected), and the store audit shows zero
    orphans."""
    s = _session("chaos-admit-base")
    try:
        df = _frame(s)
        out = df.groupBy("k").agg(F.sum("v").alias("s"),
                                  F.count("v").alias("n"))
        base_small = _ipc_bytes(s.engine.collect(out._plan)
                                .sort_by([("k", "ascending")]))
        wide = s.createDataFrame(_wide_pdf(), num_partitions=48)
        out_w = wide.groupBy("k").agg(F.sum("v").alias("s"),
                                      F.count("v").alias("n"))
        base_wide = _ipc_bytes(s.engine.collect(out_w._plan)
                               .sort_by([("k", "ascending")]))
    finally:
        raydp_tpu.stop()

    monkeypatch.setenv("RDT_POOL_MAX_QUEUED", "8")
    monkeypatch.setenv("RDT_ADMIT_TIMEOUT_S", "120")
    monkeypatch.setenv("RDT_POOL_SCALE_INTERVAL_S", "0.2")
    monkeypatch.setenv("RDT_POOL_SCALE_UP_S", "0.3")
    monkeypatch.setenv("RDT_POOL_IDLE_S", "60")
    monkeypatch.setenv("RDT_POOL_COOLDOWN_S", "0.5")
    monkeypatch.setenv("RDT_FAULTS",
                       "executor.run_task:delay:ms=200:match=|mt-")
    s = _session3("chaos-admit")
    try:
        from raydp_tpu.runtime.object_store import get_client
        client = get_client()
        auto = s.autoscale(min_size=1, max_size=4)
        df = _frame(s)
        out = df.groupBy("k").agg(F.sum("v").alias("s"),
                                  F.count("v").alias("n"))
        wide = s.createDataFrame(_wide_pdf(), num_partitions=48)
        out_w = wide.groupBy("k").agg(F.sum("v").alias("s"),
                                      F.count("v").alias("n"))
        before = client.stats()["num_objects"]
        box = {}

        def flood():
            try:
                box["wide"] = _ipc_bytes(s.engine.collect(out_w._plan)
                                         .sort_by([("k", "ascending")]))
            except Exception as e:  # noqa: BLE001 - asserted below
                box["flood_error"] = e

        def late():
            try:
                box["small"] = _ipc_bytes(s.engine.collect(out._plan)
                                          .sort_by([("k", "ascending")]))
            except Exception as e:  # noqa: BLE001 - asserted below
                box["late_error"] = e

        tf = threading.Thread(target=flood)
        tf.start()
        deadline = time.time() + 30
        while time.time() < deadline \
                and s.engine.pool.load()["queued"] <= 8:
            time.sleep(0.02)  # flood backlog past the admission bound
        tl = threading.Thread(target=late)
        tl.start()
        # the late action parks at admission (visible in load())
        deadline = time.time() + 20
        parked_seen = 0
        while time.time() < deadline:
            parked_seen = max(parked_seen, s.engine.pool.load()["parked"])
            if parked_seen:
                break
            time.sleep(0.02)
        # concurrent drain while the flood runs and the late action parks
        s.retire_executor(s.executors[-1].name)
        tf.join(timeout=300)
        tl.join(timeout=300)
        assert "flood_error" not in box, box.get("flood_error")
        assert "late_error" not in box, box.get("late_error")
        assert parked_seen > 0, "late action never parked at admission"
        assert box["wide"] == base_wide
        assert box["small"] == base_small
        # the autoscaler grew for the parked/queued demand; it records the
        # decision once the executors it spawned have joined, which on a
        # loaded host is after both actions are done
        deadline = time.time() + 60
        while time.time() < deadline \
                and not any(e["direction"] == "up" for e in auto.events):
            time.sleep(0.05)
        assert any(e["direction"] == "up" for e in auto.events), auto.events
        deadline = time.time() + 30
        while time.time() < deadline \
                and client.stats()["num_objects"] != before:
            time.sleep(0.25)
        orphans = client.stats()["num_objects"] - before
        assert orphans == 0, f"admission+scale+drain orphaned {orphans}"
        from raydp_tpu import metrics
        snap = metrics.snapshot()["counters"]
        assert snap.get("pool_admission_parked_total", {}), snap
        assert not snap.get("pool_admission_rejects_total", {}), \
            "the parked action was rejected instead of admitted"
    finally:
        raydp_tpu.stop()


# ---------------------------------------------------------------------------
# continuous pipelines (ISSUE 15): the streaming fault matrix
# ---------------------------------------------------------------------------

def _run_stream_windows(app, epochs=5, rows=1200):
    """One full session driving a windowed continuous pipeline; returns
    (list of (start, end, window ipc bytes), epoch result bytes, report).
    Window tables are already key-sorted by the pipeline (the groupagg
    row-order caveat of _run_groupagg, handled once in _merge_window)."""
    from raydp_tpu import stream
    from raydp_tpu.etl.expressions import col

    def make(epoch):
        rng = np.random.RandomState(epoch)
        return pa.table({
            "k": rng.randint(0, 16, rows),
            "v": rng.randint(0, 1000, rows).astype(np.int64),
        })

    s = _session(app)
    try:
        from raydp_tpu.runtime.object_store import get_client
        client = get_client()
        before = client.stats()["num_objects"]
        pipe = stream.read_stream(
            stream.SyntheticSource(make, max_epochs=epochs)).transform(
            lambda df: df.filter(col("v") % 7 != 0)).window(
            size=3, slide=1, keys=["k"], aggs={"v": ["sum", "count"]})
        wins, epochs_b = [], []
        for er in pipe.epochs():
            epochs_b.append(_ipc_bytes(er.table()))
            wins.extend((w.start, w.end, _ipc_bytes(w.table))
                        for w in er.windows)
        rep = pipe.report()
        pipe.close()
        deadline = time.time() + 30
        while time.time() < deadline \
                and client.stats()["num_objects"] != before:
            time.sleep(0.25)
        orphans = client.stats()["num_objects"] - before
        return wins, epochs_b, rep, orphans
    finally:
        raydp_tpu.stop()


def test_stream_executor_crash_mid_epoch_byte_identical(tmp_path,
                                                        monkeypatch):
    """An executor crash in the middle of an epoch's engine action: the
    lineage plane re-runs the lost tasks INSIDE the epoch (the stream layer
    never notices), and every epoch result and window merge is
    byte-identical to the fault-free run with zero orphans."""
    base_w, base_e, _, orphans0 = _run_stream_windows("stream-crash-base")
    assert orphans0 == 0

    crash_s = str(tmp_path / "stream-crash.sentinel")
    monkeypatch.setenv(
        "RDT_FAULTS", f"executor.run_task:crash:nth=4:once={crash_s}")
    got_w, got_e, rep, orphans = _run_stream_windows("stream-crash")
    assert os.path.exists(crash_s), "injected crash never fired"
    assert got_e == base_e, "epoch results diverged after the crash"
    assert got_w == base_w, "window results diverged after the crash"
    assert orphans == 0, f"crash replay orphaned {orphans} store objects"


def test_stream_epoch_drop_replays_exactly_once(tmp_path, monkeypatch):
    """The stream's own fault site: ``stream.epoch:drop`` loses a freshly
    sealed epoch's partial blobs post-commit (the store-host-died model for
    streams). The window merges spanning the lost epoch must re-derive it
    from the source journal — results byte-identical to the unfaulted run,
    each epoch contributing exactly once, zero orphans."""
    base_w, base_e, base_rep, _ = _run_stream_windows("stream-drop-base")
    assert base_rep["replays"] == 0

    sent = str(tmp_path / "stream-drop.sentinel")
    monkeypatch.setenv("RDT_FAULTS",
                       f"stream.epoch:drop:nth=2:once={sent}")
    got_w, got_e, rep, orphans = _run_stream_windows("stream-drop")
    assert os.path.exists(sent), "injected drop never fired"
    assert rep["replays"] >= 1, "the lost epoch was never replayed"
    assert got_w == base_w, "window results diverged after the drop"
    assert got_e == base_e
    assert orphans == 0, f"epoch replay orphaned {orphans} store objects"
