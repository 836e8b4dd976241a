"""Elastic-pool bench: autoscale under a queued burst + chaos-hardened
scale-down (ISSUE 13 acceptance), plus multi-tenant fairness (ISSUE 14).

Three configs, each a fresh session:

1. ``autoscale`` — a 1-executor session with the controller armed
   (min=1, max=3, fast cadence) under a seeded per-task delay
   (``executor.run_task:delay`` — the queued-burst model): a burst of
   concurrent groupagg actions must GROW the pool, every action must
   succeed with identical results, and the idle window afterwards must
   DRAIN the pool back to min. The record carries the controller's event
   timeline, the peak size, and the action-failure count (must be 0).
2. ``chaos_scale`` — the scale-down chaos contract: a 3-executor session
   runs a PIPELINED groupagg (AQE off) with a seeded per-map delay, a
   dropped map blob (forcing a lineage-recovery round), and a
   ``pool.drain:crash`` rule that kills the retiring executor MID-DRAIN
   when the bench retires it mid-action. The action must return bytes
   identical to a fault-free fixed-pool BARRIER run, the store must end at
   its pre-action object count, and a flight-recorder bundle written at
   the end must carry the drain/recovery evidence chain
   (``executor_drain`` → ``executor_down`` → ``recovery_round``).

4. ``outofcore`` (``--outofcore``; records ``benchmarks/SPILL.json``) —
   the ROADMAP item 4c headroom proof: a full-row sort shuffle moving
   several× the store's configured shm budget, so the sealed input and map
   blobs MUST spill to disk mid-action and fault back in transparently.
   Asserted: the spill really engaged (``spilled_objects > 0``, measured
   peak bytes a recorded multiple of the budget), the result is
   byte-identical to the same action under a roomy budget, zero failed
   actions, zero orphans.

3. ``fairness`` (``--fairness``; the ``chaos-overload`` CI leg) — the
   multi-tenant overload contract on one fixed 2-executor pool under a
   seeded per-map delay: a FLOODING tenant (a second ``Engine`` over the
   session's pool, tenant="flood") loops wide groupaggs while the
   INTERACTIVE tenant runs a stream of small groupaggs — every
   interactive action must return bytes identical to its uncontended
   baseline with its p99 bounded (never queued behind the flood), zero
   failed accepted actions on either tenant, and a zero-orphan store
   audit; then two SATURATING tenants at weights 3:1 must show a
   per-tenant dispatch split within tolerance of the weight ratio.
   Recorded in ``benchmarks/FAIR.json``.

``--smoke`` shrinks the load, writes to /tmp (never the recorded
artifact), and ASSERTS the CI contract above; the full run records
``benchmarks/SCALE.json`` — or ``benchmarks/FAIR.json`` with
``--fairness`` (override with ``--out``).

Run: RDT_FAULTS_SEED=7 python benchmarks/scale_bench.py [--fairness]
     [--smoke] [--out P]
"""

import argparse
import json
import os
import sys
import tempfile
import threading
import time

import numpy as np
import pandas as pd

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _ipc_bytes(table):
    import pyarrow as pa
    sink = pa.BufferOutputStream()
    with pa.ipc.new_stream(sink, table.schema) as w:
        w.write_table(table)
    return sink.getvalue().to_pybytes()


def _frame(session, rows, parts):
    rng = np.random.RandomState(0)
    pdf = pd.DataFrame({
        "k": rng.randint(0, 50, rows),
        "v": rng.randint(0, 1000, rows).astype(np.int64),
    })
    return session.createDataFrame(pdf, num_partitions=parts)


def _groupagg_bytes(session, df):
    from raydp_tpu.etl import functions as F
    out = df.groupBy("k").agg(F.sum("v").alias("s"), F.count("v").alias("n"))
    return _ipc_bytes(session.engine.collect(out._plan)
                      .sort_by([("k", "ascending")]))


def run_autoscale_config(smoke):
    """Config 1: queued burst grows the pool, idle drains it back."""
    import raydp_tpu

    rows = 8_000 if smoke else 40_000
    parts = 8 if smoke else 16
    burst = 3 if smoke else 4
    os.environ.update({
        "RDT_POOL_SCALE_INTERVAL_S": "0.2",
        "RDT_POOL_SCALE_UP_S": "0.4",
        "RDT_POOL_IDLE_S": "1.5",
        "RDT_POOL_COOLDOWN_S": "1.0",
        "RDT_FAULTS": "executor.run_task:delay:ms=400",
    })
    t0 = time.time()
    s = raydp_tpu.init("scale-bench", num_executors=1, executor_cores=1,
                       executor_memory="512MB")
    try:
        auto = s.autoscale(min_size=1, max_size=3)
        df = _frame(s, rows, parts)
        results, errors = [], []

        def run():
            try:
                results.append(_groupagg_bytes(s, df))
            except Exception as e:  # noqa: BLE001 - counted below
                errors.append(repr(e))

        threads = [threading.Thread(target=run) for _ in range(burst)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=600)
        peak = max([1] + [e["size"] for e in auto.events
                          if e["direction"] == "up"])
        burst_wall = time.time() - t0
        deadline = time.time() + 60
        while time.time() < deadline and len(s.executors) > 1:
            time.sleep(0.3)
        final = len(s.executors)
        identical = len(set(results)) <= 1
        record = {
            "burst_actions": burst,
            "failed_actions": len(errors),
            "errors": errors,
            "results_identical": identical,
            "peak_pool_size": peak,
            "final_pool_size": final,
            "grew": peak > 1,
            "shrank_to_min": final == 1,
            "burst_wall_s": round(burst_wall, 2),
            "scale_events": [{"direction": e["direction"], "size": e["size"],
                              "reason": e["reason"]} for e in auto.events],
        }
    finally:
        raydp_tpu.stop()
        for k in ("RDT_POOL_SCALE_INTERVAL_S", "RDT_POOL_SCALE_UP_S",
                  "RDT_POOL_IDLE_S", "RDT_POOL_COOLDOWN_S", "RDT_FAULTS"):
            os.environ.pop(k, None)
    print(f"[autoscale] peak={record['peak_pool_size']} "
          f"final={record['final_pool_size']} "
          f"failed={record['failed_actions']} "
          f"identical={record['results_identical']}")
    return record


def run_chaos_scale_config(smoke):
    """Config 2: drain-crash racing a pipelined groupagg + recovery."""
    import raydp_tpu
    from raydp_tpu import metrics
    from raydp_tpu.runtime.object_store import get_client

    rows = 8_000 if smoke else 40_000

    # fault-free fixed-pool BARRIER baseline
    os.environ["RDT_ETL_AQE"] = "0"
    os.environ["RDT_SHUFFLE_PIPELINE"] = "0"
    s = raydp_tpu.init("scale-chaos-base", num_executors=3,
                       executor_cores=1, executor_memory="512MB")
    try:
        base = _groupagg_bytes(s, _frame(s, rows, 4))
    finally:
        raydp_tpu.stop()

    # chaos run: pipelined, slowed maps, a dropped map blob (recovery),
    # and a drain-crash fired when the bench retires executor -2
    sentinels = [os.path.join(tempfile.gettempdir(),
                              f"rdt_scale_bench_{os.getpid()}_{n}.sentinel")
                 for n in ("crash", "drop")]
    for p in sentinels:
        if os.path.exists(p):
            os.remove(p)
    os.environ["RDT_SHUFFLE_PIPELINE"] = "1"
    os.environ["RDT_FAULTS"] = (
        "executor.run_task:delay:ms=400:match=|mt-;"
        f"shuffle.write:drop:nth=2:once={sentinels[1]};"
        f"pool.drain:crash:once={sentinels[0]}")
    s = raydp_tpu.init("scale-chaos", num_executors=3, executor_cores=1,
                       executor_memory="512MB")
    try:
        metrics.reset()
        client = get_client()
        df = _frame(s, rows, 4)
        before = client.stats()["num_objects"]
        box = {}

        def run():
            try:
                box["bytes"] = _groupagg_bytes(s, df)
            except Exception as e:  # noqa: BLE001 - surfaced below
                box["error"] = repr(e)

        t = threading.Thread(target=run)
        t.start()
        # mid-map-stage: the 400ms per-map delay guarantees the victim has
        # in-flight work when the drain-crash kills it, so the blackbox
        # carries the full executor_drain → executor_down → recovery chain
        time.sleep(0.25)
        s.retire_executor("rdt-executor-scale-chaos-2")
        t.join(timeout=600)
        deadline = time.time() + 30
        while time.time() < deadline \
                and client.stats()["num_objects"] != before:
            time.sleep(0.25)
        orphans = client.stats()["num_objects"] - before
        report = s.engine.shuffle_stage_report()
        # the postmortem evidence chain: harvest every process's ring into
        # a blackbox bundle and read the drain/recovery sequence back
        bundle_path = metrics.write_blackbox("chaos-scale")
        with open(bundle_path) as fh:
            bundle = json.load(fh)
        driver_events = [e["kind"]
                         for e in bundle["processes"]["driver"]["events"]]
        record = {
            "failed_action": box.get("error"),
            "byte_identical": box.get("bytes") == base,
            "orphans": orphans,
            "pool_size_after": len(s.executors),
            "pipelined": any(e.get("pipelined") for e in report),
            "recovered": sum(e.get("recovered", 0) for e in report),
            "regenerated": sum(e.get("regenerated", 0) for e in report),
            "crash_fired": os.path.exists(sentinels[0]),
            "drop_fired": os.path.exists(sentinels[1]),
            "blackbox": bundle_path,
            "blackbox_has_drain": "executor_drain" in driver_events,
            "blackbox_has_executor_down": "executor_down" in driver_events,
            "blackbox_has_recovery_round": "recovery_round" in driver_events,
        }
    finally:
        raydp_tpu.stop()
        for k in ("RDT_ETL_AQE", "RDT_SHUFFLE_PIPELINE", "RDT_FAULTS"):
            os.environ.pop(k, None)
        for p in sentinels:
            if os.path.exists(p):
                os.remove(p)
    print(f"[chaos-scale] identical={record['byte_identical']} "
          f"orphans={record['orphans']} recovered={record['recovered']} "
          f"blackbox={os.path.basename(bundle_path)}")
    return record


def run_fairness_config(smoke):
    """Config 3: flood + interactive tenants on one pool, then a weighted
    3:1 saturation split (the ISSUE 14 fairness contract)."""
    import raydp_tpu
    from raydp_tpu.etl.engine import Engine

    rows_wide = 12_000 if smoke else 40_000
    parts_wide = 24 if smoke else 48
    inter_actions = 6 if smoke else 16
    # per-MAP delay (both tenants alike): stretches every map stage so the
    # flood holds a real backlog without inflating data volume
    os.environ["RDT_FAULTS"] = "executor.run_task:delay:ms=120:match=|mt-"
    s = raydp_tpu.init("fair-bench", num_executors=2, executor_cores=1,
                       executor_memory="512MB")
    try:
        from raydp_tpu.runtime.object_store import get_client
        client = get_client()
        pool = s.engine.pool
        small = _frame(s, 4_000 if smoke else 8_000, 4)
        rng = np.random.RandomState(1)
        wide = s.createDataFrame(pd.DataFrame({
            "k": rng.randint(0, 50, rows_wide),
            "v": rng.randint(0, 1000, rows_wide).astype(np.int64),
        }), num_partitions=parts_wide)
        before = client.stats()["num_objects"]

        # uncontended interactive baseline (bytes + wall)
        t0 = time.time()
        base_small = _groupagg_bytes(s, small)
        uncontended_s = time.time() - t0

        flood_eng = Engine(pool,
                           shuffle_partitions=s.engine.shuffle_partitions,
                           owner=s.engine.owner, tenant="flood")
        from raydp_tpu.etl import functions as F
        out_w = wide.groupBy("k").agg(F.sum("v").alias("s"),
                                      F.count("v").alias("n"))
        stop = threading.Event()
        flood_stats = {"actions": 0, "errors": []}

        def flood():
            while not stop.is_set():
                try:
                    _ipc_bytes(flood_eng.collect(out_w._plan)
                               .sort_by([("k", "ascending")]))
                    flood_stats["actions"] += 1
                except Exception as e:  # noqa: BLE001 - counted below
                    flood_stats["errors"].append(repr(e))
                    return

        tf = threading.Thread(target=flood)
        tf.start()
        deadline = time.time() + 60
        while time.time() < deadline and (pool.load()["tenants"]
                                          .get("flood", {})
                                          .get("queued", 0)) < 4:
            time.sleep(0.02)

        # the interactive stream under the flood
        walls, mismatches = [], 0
        flood_queued_seen = 0
        for _ in range(inter_actions):
            flood_queued_seen = max(
                flood_queued_seen,
                pool.load()["tenants"].get("flood", {}).get("queued", 0))
            t0 = time.time()
            got = _groupagg_bytes(s, small)
            walls.append(time.time() - t0)
            if got != base_small:
                mismatches += 1
        stop.set()
        tf.join(timeout=600)
        walls.sort()
        p50 = walls[len(walls) // 2]
        p99 = walls[min(len(walls) - 1, int(0.99 * len(walls)))]

        # weighted phase: two SATURATING tenants at 3:1, sampled when the
        # heavy one finishes (both still contending throughout its run)
        eng_a = Engine(pool, shuffle_partitions=s.engine.shuffle_partitions,
                       owner=s.engine.owner, tenant="wA", tenant_weight=1.0)
        eng_b = Engine(pool, shuffle_partitions=s.engine.shuffle_partitions,
                       owner=s.engine.owner, tenant="wB", tenant_weight=3.0)
        boxes = {}

        def run_w(tag, eng):
            try:
                boxes[tag] = _ipc_bytes(eng.collect(out_w._plan)
                                        .sort_by([("k", "ascending")]))
            except Exception as e:  # noqa: BLE001 - surfaced below
                boxes[tag + "_error"] = repr(e)

        ta = threading.Thread(target=run_w, args=("wA", eng_a))
        tb = threading.Thread(target=run_w, args=("wB", eng_b))
        ta.start()
        tb.start()
        # the split only means something WHILE both tenants contend (once
        # the heavy action's queue drains, the light one rightly floods the
        # freed slots): keep the last sample with both queues nonempty.
        # Note the per-stage in-flight caps bound the achievable ratio —
        # the heavy tenant can hold at most one stage's cap worth of slots
        # — so "tracks the weights" is a tolerance band, not an equality.
        sample = None
        deadline = time.time() + 600
        while tb.is_alive() and time.time() < deadline:
            t = pool.load()["tenants"]
            a, b = t.get("wA", {}), t.get("wB", {})
            if a.get("queued", 0) > 0 and b.get("queued", 0) > 0 \
                    and a.get("dispatched", 0) >= 4:
                sample = (a["dispatched"], b["dispatched"])
            time.sleep(0.05)
        tb.join(timeout=600)
        ta.join(timeout=600)
        disp_a, disp_b = sample if sample else (0, 0)
        ratio = (disp_b / disp_a) if disp_a else float("inf")

        deadline = time.time() + 30
        while time.time() < deadline \
                and client.stats()["num_objects"] != before:
            time.sleep(0.25)
        record = {
            "interactive_actions": inter_actions,
            "interactive_failed": mismatches,
            "results_identical": mismatches == 0,
            "uncontended_s": round(uncontended_s, 3),
            "contended_p50_s": round(p50, 3),
            "contended_p99_s": round(p99, 3),
            "p99_bounded": p99 < 10.0 * max(uncontended_s, 0.5) + 2.0,
            "flood_actions": flood_stats["actions"],
            "flood_failed": len(flood_stats["errors"]),
            "flood_errors": flood_stats["errors"],
            "flood_queued_seen": flood_queued_seen,
            "weight_ratio": 3.0,
            "observed_dispatch_ratio": round(ratio, 2),
            "ratio_within_tolerance": 1.5 <= ratio <= 6.0,
            "weighted_identical": boxes.get("wA") == boxes.get("wB"),
            "weighted_errors": [boxes[k] for k in boxes if "error" in k],
            "orphans": client.stats()["num_objects"] - before,
        }
    finally:
        raydp_tpu.stop()
        os.environ.pop("RDT_FAULTS", None)
    print(f"[fairness] p99={record['contended_p99_s']}s "
          f"(uncontended {record['uncontended_s']}s) "
          f"ratio={record['observed_dispatch_ratio']} "
          f"failed={record['interactive_failed']} "
          f"orphans={record['orphans']}")
    return record


def run_outofcore_config(smoke):
    """Config 4: sort-shuffle several× the store budget — spill engages,
    results stay byte-identical, nothing fails, nothing orphans."""
    import pandas as _pd

    import raydp_tpu
    from raydp_tpu import config as cfg
    from raydp_tpu.runtime.object_store import get_client

    rows = 60_000 if smoke else 240_000
    budget = (2 << 20) if smoke else (8 << 20)
    rng = np.random.RandomState(0)
    pdf = _pd.DataFrame({
        "k": rng.randint(0, 1_000_000, rows),
        "v": rng.randint(0, 1000, rows).astype(np.int64),
        # a fat payload column so the sort shuffle moves real bytes —
        # ~128 B/row of string data dominates the row's footprint
        "payload": ["x" * 96 + f"{i:032d}" for i in range(rows)],
    })

    def one_run(shm_budget):
        configs = None
        if shm_budget:
            configs = {cfg.OBJECT_STORE_MEMORY_KEY: str(shm_budget),
                       cfg.SPILL_BUDGET_KEY: str(shm_budget)}
            # this config DELIBERATELY oversubscribes the store — disk
            # spill is the mechanism under test, so the PR 14 memory
            # backpressure (which would pause dispatch at 1.25× budget and
            # deadlock an action whose own inputs hold the memory) steps
            # aside for the run
            os.environ["RDT_STORE_HIGH_WATERMARK"] = "1e9"
        s = raydp_tpu.init("spill-bench", num_executors=2, executor_cores=1,
                           executor_memory="512MB", configs=configs)
        try:
            client = get_client()
            df = s.createDataFrame(pdf, num_partitions=8)
            # the audit baseline includes the live input frame (its blocks
            # belong to df for the whole run); the ACTION must add nothing
            before = client.stats()["num_objects"]
            t0 = time.time()
            out = s.engine.collect(df.sort("k")._plan)
            wall = time.time() - t0
            stats = client.stats()
            peak = {
                "spilled_objects": stats.get("spilled_objects", 0),
                "spilled_bytes": stats.get("spilled_bytes", 0),
                "shm_bytes": stats.get("shm_bytes", 0),
            }
            data = _ipc_bytes(out)
            deadline = time.time() + 30
            while time.time() < deadline \
                    and client.stats()["num_objects"] != before:
                time.sleep(0.25)
            orphans = client.stats()["num_objects"] - before
            return data, wall, peak, orphans
        finally:
            raydp_tpu.stop()
            os.environ.pop("RDT_STORE_HIGH_WATERMARK", None)

    base, base_wall, _, orphans0 = one_run(None)  # roomy default budget
    got, wall, peak, orphans1 = one_run(budget)
    moved = peak["spilled_bytes"] + peak["shm_bytes"]
    record = {
        "rows": rows,
        "budget_bytes": budget,
        "result_bytes": len(base),
        "byte_identical": base == got,
        "spilled_objects": peak["spilled_objects"],
        "spilled_bytes": peak["spilled_bytes"],
        "store_bytes_over_budget": round(moved / budget, 2),
        "spill_engaged": peak["spilled_objects"] > 0,
        "wall_s": round(wall, 2),
        "incore_wall_s": round(base_wall, 2),
        "failed_actions": 0,  # one_run raises (and the bench fails) on any
        "orphans_incore": orphans0,
        "orphans_spill": orphans1,
    }
    print(f"[outofcore] spilled={record['spilled_objects']} objs "
          f"({record['store_bytes_over_budget']}x budget) "
          f"identical={record['byte_identical']} "
          f"wall={record['wall_s']}s (incore {record['incore_wall_s']}s) "
          f"orphans={record['orphans_spill']}")
    return record


def _assert_outofcore(rec):
    assert rec["byte_identical"], rec
    assert rec["spill_engaged"], rec
    assert rec["store_bytes_over_budget"] >= 2.0, rec
    assert rec["failed_actions"] == 0, rec
    assert rec["orphans_incore"] == 0 and rec["orphans_spill"] == 0, rec


def _assert_fairness(fair):
    assert fair["interactive_failed"] == 0, fair
    assert fair["results_identical"], fair
    assert fair["flood_failed"] == 0, fair
    assert fair["flood_queued_seen"] > 0, fair  # the flood really contended
    assert fair["p99_bounded"], fair
    assert fair["ratio_within_tolerance"], fair
    assert fair["weighted_identical"], fair
    assert not fair["weighted_errors"], fair
    assert fair["orphans"] == 0, fair


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n", 1)[0])
    ap.add_argument("--smoke", action="store_true",
                    help="CI contract: small load, asserts, writes to /tmp")
    ap.add_argument("--fairness", action="store_true",
                    help="run ONLY the multi-tenant fairness config "
                         "(records benchmarks/FAIR.json)")
    ap.add_argument("--outofcore", action="store_true",
                    help="run ONLY the out-of-core headroom config "
                         "(records benchmarks/SPILL.json)")
    ap.add_argument("--out", default=None, help="record path override")
    args = ap.parse_args()
    here = os.path.dirname(os.path.abspath(__file__))
    if args.outofcore:
        out = args.out or ("/tmp/SPILL_SMOKE.json" if args.smoke
                           else os.path.join(here, "SPILL.json"))
        ooc = run_outofcore_config(args.smoke)
        record = {
            "bench": "scale_bench",
            "metric": "outofcore_store_bytes_over_budget",
            "value": ooc["store_bytes_over_budget"],
            "smoke": args.smoke,
            "ts": time.strftime("%Y-%m-%dT%H:%M:%S"),
            "configs": {"outofcore": ooc},
        }
        with open(out, "w") as fh:
            json.dump(record, fh, indent=2, sort_keys=True)
        print(f"record written to {out}")
        _assert_outofcore(record["configs"]["outofcore"])
        print("outofcore bench contract: OK")
        return
    if args.fairness:
        out = args.out or ("/tmp/FAIR_SMOKE.json" if args.smoke
                           else os.path.join(here, "FAIR.json"))
        record = {
            "bench": "scale_bench",
            "smoke": args.smoke,
            "ts": time.strftime("%Y-%m-%dT%H:%M:%S"),
            "configs": {"fairness": run_fairness_config(args.smoke)},
        }
        with open(out, "w") as fh:
            json.dump(record, fh, indent=2, sort_keys=True)
        print(f"record written to {out}")
        _assert_fairness(record["configs"]["fairness"])
        print("fairness bench contract: OK")
        return
    out = args.out or ("/tmp/SCALE_SMOKE.json" if args.smoke else
                       os.path.join(here, "SCALE.json"))
    record = {
        "bench": "scale_bench",
        "smoke": args.smoke,
        "ts": time.strftime("%Y-%m-%dT%H:%M:%S"),
        "configs": {
            "autoscale": run_autoscale_config(args.smoke),
            "chaos_scale": run_chaos_scale_config(args.smoke),
        },
    }
    with open(out, "w") as fh:
        json.dump(record, fh, indent=2, sort_keys=True)
    print(f"record written to {out}")

    auto = record["configs"]["autoscale"]
    chaos = record["configs"]["chaos_scale"]
    # the contract holds for the recorded artifact too, not just CI
    assert auto["failed_actions"] == 0, auto
    assert auto["results_identical"], auto
    assert auto["grew"] and auto["shrank_to_min"], auto
    assert chaos["failed_action"] is None, chaos
    assert chaos["byte_identical"], chaos
    assert chaos["orphans"] == 0, chaos
    assert chaos["pipelined"], chaos
    assert chaos["crash_fired"] and chaos["drop_fired"], chaos
    assert chaos["recovered"] >= 1, chaos
    assert chaos["blackbox_has_drain"], chaos
    assert chaos["blackbox_has_executor_down"], chaos
    assert chaos["blackbox_has_recovery_round"], chaos
    print("scale bench contract: OK")


if __name__ == "__main__":
    main()
