"""Data-gravity bench: warm-start readiness + residency-aware locality
(ISSUE 19 acceptance).

Two configs, each a fresh session:

1. ``warm_start`` — executor readiness, cold spawn vs warm fork. A cold
   1-executor session times ``Session._grow_executor`` (fresh interpreter
   + the import chain); a warm session (``RDT_WARM_FORK=1``) times the
   same grow served by the pre-imported prototype. The warm session also
   carries the warm-fork-crash chaos leg: a ``pool.fork:crash`` rule
   kills one fresh fork BEFORE its readiness handshake — the half-started
   worker must be reaped (never admitted) or supervisor-restarted, the
   pool must still reach its target size, and results stay
   byte-identical. Asserted: warm readiness ≥2× faster than cold, every
   admitted executor reports ``warm_forked`` provenance, zero orphan
   processes after stop (prototype + workers audited by pid), zero store
   orphans, and the blackbox bundle carries ``warm_fork`` events
   (including the injected death).

2. ``gravity`` — residency-aware locality under a seeded spill +
   fault-in-delay storm, on a REAL two-host topology (the head plus one
   isolated node agent, one executor on each). The head's store budget is
   deliberately tiny, so the join's head-side bucket blobs spill
   (``store.spill:delay`` injects the slow-disk model); the agent host is
   roomy. The same join then runs under two knob settings of the SAME
   session: residency-aware (``RDT_LOCALITY_SPILLED_WEIGHT=0.5``, the
   default — spilled bytes pull half as hard, so reduce tasks tip to the
   host whose copy is fast) vs tier-blind (``=1.0``, the pre-PR
   behavior: the spilled host scores on raw bytes and the storm host
   wins). Asserted: the locality run's stage wall beats the tier-blind
   baseline, both byte-identical to each other and to a roomy-budget
   baseline, spill + fault-ins really engaged, zero orphans. The chaos
   leg retires the STORM-HOST executor mid-join (retire-during-fault-in):
   byte-identical, zero orphans, and the blackbox carries the
   ``store_fault_in`` / ``store_budget`` evidence.

``--smoke`` shrinks the load, writes to /tmp (never the recorded
artifact), and ASSERTS the contract above; the full run records
``benchmarks/GRAVITY.json`` (override with ``--out``).

Run: RDT_FAULTS_SEED=7 python benchmarks/gravity_bench.py [--smoke] [--out P]
"""

import argparse
import json
import os
import signal
import subprocess
import sys
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


def _groupagg_bytes(session, df):
    from raydp_tpu.etl import functions as F
    out = df.groupBy("k").agg(F.sum("v").alias("s"), F.count("v").alias("n"))
    return _ipc_bytes(session.engine.collect(out._plan)
                      .sort_by([("k", "ascending")]))


def _pid_alive(pid):
    try:
        os.kill(pid, 0)
        return True
    except (ProcessLookupError, PermissionError):
        return False


# ---- config 1: warm-start readiness ------------------------------------------


def _timed_grows(session, n):
    """Wall-clock of n sequential _grow_executor calls (spawn → admitted)."""
    times = []
    for _ in range(n):
        t0 = time.time()
        h = session._grow_executor()
        assert h is not None, "grow failed"
        times.append(time.time() - t0)
    return times


def run_warm_start_config(smoke):
    import raydp_tpu
    from raydp_tpu import faults, metrics
    from raydp_tpu.runtime import head as head_mod
    from raydp_tpu.runtime.object_store import get_client

    rows = 6_000 if smoke else 20_000
    grows = 2

    # cold baseline: every grow pays interpreter + import chain
    s = raydp_tpu.init("gravity-cold", num_executors=1, executor_cores=1,
                       executor_memory="512MB")
    try:
        df = None
        cold_times = _timed_grows(s, grows)
        rng = np.random.RandomState(0)
        df = s.createDataFrame(pd.DataFrame({
            "k": rng.randint(0, 50, rows),
            "v": rng.randint(0, 1000, rows).astype(np.int64),
        }), num_partitions=8)
        base = _groupagg_bytes(s, df)
    finally:
        raydp_tpu.stop()

    # warm: the prototype pays the imports once, grows fork from it
    os.environ["RDT_WARM_FORK"] = "1"
    os.environ["RDT_WARM_IMPORTS"] = "pyarrow,pandas,numpy,cloudpickle"
    s = raydp_tpu.init("gravity-warm", num_executors=1, executor_cores=1,
                       executor_memory="512MB")
    try:
        metrics.reset()
        client = get_client()

        # chaos leg: the next fork is killed BEFORE its readiness
        # handshake (dies-in-bootstrap). The half-started worker must be
        # reaped (grow returns None) or supervisor-restarted into a ready
        # executor — either way never a phantom member, and the plane
        # serves the retry.
        live_before = len(s.executors)
        faults.inject("pool.fork", "crash", times=1)
        try:
            h = s._grow_executor()
        finally:
            faults.clear()
        if h is None:  # reaped: the pool must be exactly where it was
            assert len(s.executors) == live_before, "phantom executor"
            h = s._grow_executor()
            assert h is not None, "warm plane did not serve the retry"
        crash_events = [e for e in metrics.events()
                        if e["kind"] == "warm_fork"
                        and e.get("injected_death")]

        warm_times = _timed_grows(s, grows)
        rng = np.random.RandomState(0)
        df = s.createDataFrame(pd.DataFrame({
            "k": rng.randint(0, 50, rows),
            "v": rng.randint(0, 1000, rows).astype(np.int64),
        }), num_partitions=8)
        # audit baseline includes the live input frame; the ACTION must
        # add nothing
        before = client.stats()["num_objects"]
        got = _groupagg_bytes(s, df)

        infos = [h.spawn_info() for h in s.executors]
        pids = [i["pid"] for i in infos]
        mgr = head_mod.get_runtime()._warm_fork[0]
        proto_pid = mgr._proc.pid if mgr is not None and mgr._proc else None
        bundle_path = metrics.write_blackbox("gravity-warm")
        with open(bundle_path) as fh:
            bundle = json.load(fh)
        driver_events = [e["kind"]
                        for e in bundle["processes"]["driver"]["events"]]
        deadline = time.time() + 30
        while time.time() < deadline \
                and client.stats()["num_objects"] != before:
            time.sleep(0.25)
        orphans = client.stats()["num_objects"] - before
    finally:
        raydp_tpu.stop()
        for k in ("RDT_WARM_FORK", "RDT_WARM_IMPORTS"):
            os.environ.pop(k, None)

    # zero-orphan process audit: workers AND the prototype died with stop
    # (executor exit is graceful — a shutdown RPC with a short grace
    # delay — so poll rather than snapshot)
    audit = pids + ([proto_pid] if proto_pid else [])
    deadline = time.time() + 15
    while time.time() < deadline and any(_pid_alive(p) for p in audit):
        time.sleep(0.25)
    leaked = [p for p in audit if _pid_alive(p)]
    speedup = min(cold_times) / max(min(warm_times), 1e-6)
    record = {
        "cold_grow_s": [round(t, 3) for t in cold_times],
        "warm_grow_s": [round(t, 3) for t in warm_times],
        "readiness_speedup": round(speedup, 2),
        "warm_forked_provenance": [bool(i["warm_forked"]) for i in infos],
        "crash_fired": len(crash_events) >= 1,
        "pool_size_after_chaos": len(pids),
        "byte_identical": got == base,
        "orphan_processes": leaked,
        "orphans": orphans,
        "blackbox": bundle_path,
        "blackbox_has_warm_fork": "warm_fork" in driver_events,
    }
    print(f"[warm-start] cold={record['cold_grow_s']} "
          f"warm={record['warm_grow_s']} speedup={speedup:.1f}x "
          f"crash_fired={record['crash_fired']} "
          f"identical={record['byte_identical']} orphans={orphans}")
    return record


# ---- config 2: residency-aware locality --------------------------------------


def _start_isolated_agent(head_url, cpus=4.0):
    """A node agent with its OWN payload plane on this machine — the
    second store host of the two-host gravity topology."""
    env = dict(os.environ)
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env["PYTHONPATH"] = repo + os.pathsep + env.get("PYTHONPATH", "")
    env["RDT_STORE_ISOLATED"] = "1"
    env["RDT_ARENA_FREE_GRACE_S"] = "0"
    return subprocess.Popen(
        [sys.executable, "-m", "raydp_tpu.runtime.node_agent",
         "--head", head_url, "--cpus", str(cpus)],
        env=env, stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
        start_new_session=True)


def _ensure_one_executor_per_host(session, agent_host):
    """Grow/retire until the pool is exactly one head-host + one
    agent-host executor (allocation is round-robin, so a grow may land on
    either node)."""
    for _ in range(6):
        hosts = session._executor_hosts()
        if any(h == agent_host for h in hosts.values()):
            break
        h = session._grow_executor()
        if h is None:
            continue
        if session._executor_hosts().get(h.name) != agent_host:
            session.retire_executor(h.name)
    hosts = session._executor_hosts()
    agent_execs = [n for n, h in hosts.items() if h == agent_host]
    head_execs = [n for n, h in hosts.items() if h != agent_host]
    assert agent_execs, f"no executor landed on the agent host: {hosts}"
    for name in head_execs[1:]:
        session.retire_executor(name)
    return head_execs[0], agent_execs[0]


def run_gravity_config(smoke):
    import raydp_tpu
    from raydp_tpu import metrics
    from raydp_tpu import config as cfg
    from raydp_tpu.runtime.head import get_runtime
    from raydp_tpu.runtime.object_store import get_client

    rows_a = 30_000 if smoke else 120_000
    rows_b = 10_000 if smoke else 40_000
    budget = 1 << 20 if smoke else 4 << 20
    parts = 12 if smoke else 16

    rng = np.random.RandomState(0)
    pdf_a = pd.DataFrame({
        "k": rng.randint(0, 200, rows_a),
        "v": rng.randint(0, 1000, rows_a).astype(np.int64),
        "payload": ["x" * 48 + f"{i:016d}" for i in range(rows_a)],
    })
    pdf_b = pd.DataFrame({
        "k": np.arange(200) % 200,
        "w": rng.randint(0, 1000, 200).astype(np.int64),
    })

    def join_bytes(s, df_a, df_b):
        from raydp_tpu.etl import functions as F
        out = (df_a.join(df_b, on="k")
               .groupBy("k").agg(F.sum("v").alias("s"),
                                 F.sum("w").alias("t"),
                                 F.count("v").alias("n")))
        return _ipc_bytes(s.engine.collect(out._plan)
                          .sort_by([("k", "ascending")]))

    # roomy single-host baseline: the correctness reference
    os.environ["RDT_ETL_AQE"] = "0"
    os.environ["RDT_SHUFFLE_PIPELINE"] = "1"
    s = raydp_tpu.init("gravity-base", num_executors=2, executor_cores=1,
                       executor_memory="512MB",
                       configs={cfg.SHUFFLE_PARTITIONS_KEY: str(parts)})
    try:
        base = join_bytes(s, s.createDataFrame(pdf_a, num_partitions=8),
                          s.createDataFrame(pdf_b, num_partitions=2))
    finally:
        raydp_tpu.stop()

    # the storm topology: tiny head budget + slow spill IO, roomy agent
    os.environ["RDT_STORE_HIGH_WATERMARK"] = "1e9"  # spill IS the test
    os.environ["RDT_FAULTS"] = "store.spill:delay:ms=25"
    s = raydp_tpu.init(
        "gravity", num_executors=1, executor_cores=1,
        executor_memory="512MB",
        configs={cfg.OBJECT_STORE_MEMORY_KEY: str(budget),
                 cfg.SPILL_BUDGET_KEY: str(budget),
                 cfg.SHUFFLE_PARTITIONS_KEY: str(parts)})
    agent = None
    try:
        rt = get_runtime()
        agent = _start_isolated_agent(rt.server.url)
        deadline = time.time() + 30
        while time.time() < deadline and not rt.store_hosts:
            time.sleep(0.2)
        assert rt.store_hosts, "agent never registered its store host"
        agent_host = next(iter(rt.store_hosts))
        head_exec, agent_exec = _ensure_one_executor_per_host(s, agent_host)

        metrics.reset()
        client = get_client()
        df_a = s.createDataFrame(pdf_a, num_partitions=8)
        df_b = s.createDataFrame(pdf_b, num_partitions=2)
        before = client.stats()["num_objects"]

        def run_variant(spilled_weight, repeats=2):
            """min wall over repeats; fault-in/spill deltas alongside."""
            os.environ["RDT_LOCALITY_SPILLED_WEIGHT"] = str(spilled_weight)
            walls, datas = [], []
            c0 = metrics.snapshot()["counters"]
            for _ in range(repeats):
                t0 = time.time()
                datas.append(join_bytes(s, df_a, df_b))
                walls.append(time.time() - t0)
            c1 = metrics.snapshot()["counters"]

            def delta(name):
                return (sum(c1.get(name, {}).values())
                        - sum(c0.get(name, {}).values()))
            return {"wall_s": round(min(walls), 3),
                    "walls_s": [round(w, 3) for w in walls],
                    "fault_ins": delta("store_fault_in_total"),
                    "locality_hits": delta("sched_locality_hits_total"),
                    "data": datas}

        blind = run_variant(1.0)     # tier-blind: raw bytes win
        aware = run_variant(0.5)     # residency-aware (the default)
        os.environ.pop("RDT_LOCALITY_SPILLED_WEIGHT", None)

        stats = client.stats()
        spilled = stats.get("spilled_objects", 0)

        # AQE-fed budget derivation over the measured join (the
        # store_budget evidence for the blackbox; derived budgets only
        # ever tighten, so the tiny head budget stands)
        derived = s.engine.derive_store_budgets()
        derived_stats = client.stats().get("derived_budgets", {})

        # chaos leg: retire the STORM-HOST executor mid-join, while its
        # spilled buckets are faulting in (the 25ms spill delay keeps the
        # storm alive long enough for the drain to race it)
        box = {}

        def run():
            try:
                box["bytes"] = join_bytes(s, df_a, df_b)
            except Exception as e:  # noqa: BLE001 - surfaced below
                box["error"] = repr(e)

        t = threading.Thread(target=run)
        t.start()
        time.sleep(0.4)
        s.retire_executor(head_exec)
        t.join(timeout=600)

        bundle_path = metrics.write_blackbox("gravity")
        with open(bundle_path) as fh:
            bundle = json.load(fh)
        driver_events = [e["kind"]
                         for e in bundle["processes"]["driver"]["events"]]
        deadline = time.time() + 30
        while time.time() < deadline \
                and client.stats()["num_objects"] != before:
            time.sleep(0.25)
        orphans = client.stats()["num_objects"] - before
        record = {
            "rows_join_side": rows_a,
            "head_budget_bytes": budget,
            "shuffle_partitions": parts,
            "blind_wall_s": blind["wall_s"],
            "blind_walls_s": blind["walls_s"],
            "locality_wall_s": aware["wall_s"],
            "locality_walls_s": aware["walls_s"],
            "stage_wall_win": round(blind["wall_s"]
                                    / max(aware["wall_s"], 1e-6), 2),
            "blind_fault_ins": blind["fault_ins"],
            "locality_fault_ins": aware["fault_ins"],
            "locality_hits": aware["locality_hits"],
            "spill_engaged": spilled > 0,
            "spilled_objects": spilled,
            "byte_identical": all(d == base
                                  for d in blind["data"] + aware["data"]),
            "budget_derived": bool(derived) and bool(derived_stats),
            "chaos_failed_action": box.get("error"),
            "chaos_byte_identical": box.get("bytes") == base,
            "pool_size_after_chaos": len(s.executors),
            "orphans": orphans,
            "blackbox": bundle_path,
            "blackbox_has_fault_in": "store_fault_in" in driver_events,
            "blackbox_has_store_budget": "store_budget" in driver_events,
            "blackbox_has_drain": "executor_drain" in driver_events,
        }
    finally:
        raydp_tpu.stop()
        if agent is not None:
            try:
                os.killpg(agent.pid, signal.SIGKILL)
            except (ProcessLookupError, PermissionError):
                agent.kill()
        for k in ("RDT_ETL_AQE", "RDT_SHUFFLE_PIPELINE", "RDT_FAULTS",
                  "RDT_STORE_HIGH_WATERMARK",
                  "RDT_LOCALITY_SPILLED_WEIGHT"):
            os.environ.pop(k, None)
    print(f"[gravity] blind={record['blind_wall_s']}s "
          f"locality={record['locality_wall_s']}s "
          f"win={record['stage_wall_win']}x "
          f"fault_ins={record['blind_fault_ins']}"
          f"->{record['locality_fault_ins']} "
          f"identical={record['byte_identical']} "
          f"orphans={record['orphans']}")
    return record


def _assert_warm(rec):
    assert rec["readiness_speedup"] >= 2.0, rec
    assert all(rec["warm_forked_provenance"]), rec
    assert rec["crash_fired"], rec
    assert rec["byte_identical"], rec
    assert not rec["orphan_processes"], rec
    assert rec["orphans"] == 0, rec
    assert rec["blackbox_has_warm_fork"], rec


def _assert_gravity(rec):
    assert rec["byte_identical"], rec
    assert rec["spill_engaged"], rec
    assert rec["locality_wall_s"] < rec["blind_wall_s"], rec
    assert rec["locality_hits"] > 0, rec
    assert rec["budget_derived"], rec
    assert rec["chaos_failed_action"] is None, rec
    assert rec["chaos_byte_identical"], rec
    assert rec["orphans"] == 0, rec
    assert rec["blackbox_has_fault_in"], rec
    assert rec["blackbox_has_store_budget"], rec
    assert rec["blackbox_has_drain"], rec


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n", 1)[0])
    ap.add_argument("--smoke", action="store_true",
                    help="CI contract: small load, asserts, writes to /tmp")
    ap.add_argument("--out", default=None, help="record path override")
    args = ap.parse_args()
    here = os.path.dirname(os.path.abspath(__file__))
    out = args.out or ("/tmp/GRAVITY_SMOKE.json" if args.smoke
                       else os.path.join(here, "GRAVITY.json"))
    warm = run_warm_start_config(args.smoke)
    grav = run_gravity_config(args.smoke)
    record = {
        "bench": "gravity_bench",
        "metric": "warm_readiness_speedup",
        "value": warm["readiness_speedup"],
        "smoke": args.smoke,
        "ts": time.strftime("%Y-%m-%dT%H:%M:%S"),
        "configs": {"warm_start": warm, "gravity": grav},
    }
    with open(out, "w") as fh:
        json.dump(record, fh, indent=2, sort_keys=True)
    print(f"record written to {out}")
    _assert_warm(warm)
    _assert_gravity(grav)
    print("gravity bench contract: OK")


if __name__ == "__main__":
    main()
