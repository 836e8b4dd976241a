"""Gang-SPMD job runner tests (parity model: reference test_mpi.py — start/run/
stop + restart of the same job object, rank addressing, env propagation,
placement-group variant; SURVEY.md §4)."""

import os

import numpy as np
import pytest

from raydp_tpu.spmd import create_spmd_job


def test_start_run_stop_restart():
    job = create_spmd_job("t-basic", world_size=3, timeout=60)
    job.start()
    try:
        results = job.run(lambda ctx: ctx.rank * 10)
        assert results == [0, 10, 20]
        # in-order sequencing: a second broadcast works
        results = job.run(lambda ctx: ctx.world_size)
        assert results == [3, 3, 3]
    finally:
        job.stop()
    # the same object restarts cleanly (parity: test_mpi.py restart case)
    job.start()
    try:
        assert job.run(lambda ctx: ctx.job_id) == ["t-basic"] * 3
    finally:
        job.stop()


def test_env_propagation():
    job = create_spmd_job("t-env", world_size=2,
                          env={"RDT_TEST_MARKER": "hello"}, timeout=60)
    job.start()
    try:
        # rdtlint: allow[knob-registry] probes extra_env propagation, not a knob
        got = job.run(lambda ctx: os.environ.get("RDT_TEST_MARKER"))
        assert got == ["hello", "hello"]
    finally:
        job.stop()


def test_rank_addresses():
    job = create_spmd_job("t-addr", world_size=2, timeout=60)
    job.start()
    try:
        addrs = job.rank_addresses()
        assert set(addrs) == {0, 1}
        assert all(len(a) == 2 for a in addrs.values())
    finally:
        job.stop()


def test_failure_surfaces_rank_and_traceback():
    job = create_spmd_job("t-fail", world_size=2, timeout=60)
    job.start()
    try:
        def boom(ctx):
            if ctx.rank == 1:
                raise ValueError("rank 1 exploded")
            return "ok"

        with pytest.raises(RuntimeError, match="rank 1"):
            job.run(boom)
        # the gang survives a function failure and keeps sequencing
        assert job.run(lambda ctx: ctx.rank) == [0, 1]
    finally:
        job.stop()


def test_placement_group_accounting(runtime):
    job = create_spmd_job("t-pg", world_size=2, cpus_per_process=1.0, timeout=60)
    job.start()
    try:
        assert job._placement_group_id is not None
        assert runtime.resource_manager.get_group(job._placement_group_id) is not None
    finally:
        job.stop()
    # pg removed on stop (parity: pg-leak check, test_spark_cluster.py:219-259)
    assert runtime.resource_manager.get_group("t-pg") is None


def test_ranks_share_object_store(runtime):
    """Ranks inherit the head env and can exchange data through the store —
    parity with every MPI rank joining Ray (mpi_worker.py:159-160)."""
    import pyarrow as pa

    table = pa.table({"x": np.arange(64, dtype=np.int64)})
    ref = runtime.store_client.put(table)

    job = create_spmd_job("t-store", world_size=2, timeout=60)
    job.start()
    try:
        def read_sum(ctx, ref=ref):
            from raydp_tpu.runtime.object_store import get_client
            t = get_client().get(ref)
            return int(np.asarray(t["x"]).sum())

        assert job.run(read_sum) == [2016, 2016]
    finally:
        job.stop()


def test_stop_escalation_sigkills_straggler_and_job_restarts():
    """Gang teardown robustness (parity: the reference's test_mpi restart
    case, mpi_job.py:344-395): (1) a rank SIGKILLed mid-life must not wedge
    ``stop()`` or the next ``start()``; (2) a rank that ignores the stop RPC
    (simulated with SIGSTOP) is SIGKILLed by the 5s escalation poll; (3) the
    same job object runs a full start→run→stop cycle after each."""
    import signal
    import time

    job = create_spmd_job("t-killrank", world_size=2, timeout=60)

    # cycle 1: kill a rank outright, then stop + restart
    job.start()
    try:
        assert job.run(lambda ctx: ctx.rank) == [0, 1]
        victim = job._procs[0]
        os.killpg(victim.pid, signal.SIGKILL)
        deadline = time.time() + 10
        while victim.poll() is None and time.time() < deadline:
            time.sleep(0.05)
        assert victim.poll() is not None
    finally:
        job.stop()

    # cycle 2: restart works after rank death; then wedge a rank so the stop
    # RPC is never processed — the escalation must SIGKILL it within ~5s
    job.start()
    try:
        assert job.run(lambda ctx: ctx.rank * 2) == [0, 2]
        straggler = job._procs[1]
        os.kill(straggler.pid, signal.SIGSTOP)
    finally:
        t0 = time.time()
        job.stop()
        elapsed = time.time() - t0
    assert elapsed < 30, f"stop() took {elapsed:.1f}s against a straggler"
    deadline = time.time() + 10
    while straggler.poll() is None and time.time() < deadline:
        time.sleep(0.05)
    assert straggler.poll() is not None, "straggler survived stop()"

    # cycle 3: the object still restarts cleanly after the escalated stop
    job.start()
    try:
        assert job.run(lambda ctx: ctx.job_id) == ["t-killrank"] * 2
    finally:
        job.stop()


def test_jax_distributed_gang():
    """world=2 ranks form one jax.distributed mesh; a psum across the global
    device set returns the world sum on every rank — the XLA-collective
    replacement for the reference's in-rank MPI allreduce."""
    job = create_spmd_job(
        "t-jaxdist", world_size=2, jax_distributed=True, timeout=180,
        env={"XLA_FLAGS": "--xla_force_host_platform_device_count=1",
             "JAX_PLATFORMS": "cpu"})
    job.start()
    try:
        def allreduce(ctx):
            import jax
            import jax.numpy as jnp
            from jax import shard_map
            from jax.sharding import Mesh, PartitionSpec as P

            devices = np.array(jax.devices())
            assert devices.size == ctx.world_size
            mesh = Mesh(devices, ("dp",))

            def f(x):
                return jax.lax.psum(x, "dp")

            shard = jnp.array([float(ctx.rank + 1)])
            out = jax.jit(shard_map(
                f, mesh=mesh, in_specs=P("dp"), out_specs=P()))(
                    jax.make_array_from_process_local_data(
                        jax.sharding.NamedSharding(mesh, jax.sharding.PartitionSpec("dp")),
                        shard, (ctx.world_size,)))
            return float(np.asarray(out)[0])

        assert job.run(allreduce, timeout=180) == [3.0, 3.0]
    finally:
        job.stop()


def test_gang_ring_attention_across_processes():
    """Sequence parallelism spanning PROCESS boundaries: a 2-process gang
    forms one global mesh with a 16-way seq axis; ring attention rotates K/V
    blocks through cross-process collectives and must match a locally
    computed dense reference on every rank (the long-context pillar running
    the way a TPU pod runs it — one process per host)."""
    from raydp_tpu.spmd import create_spmd_job

    def fn(ctx):
        import jax
        import numpy as np

        from jax.sharding import NamedSharding, PartitionSpec as P
        from raydp_tpu.ops.ring_attention import (
            dense_attention, ring_attention_sharded)
        from raydp_tpu.parallel import MeshSpec, make_mesh

        n = jax.device_count()
        mesh = make_mesh(MeshSpec(seq=n))
        B, T, H, D = 1, 16 * n, 2, 8
        rng = np.random.RandomState(0)   # same data on every rank
        q, k, v = (rng.randn(B, T, H, D).astype(np.float32) for _ in range(3))

        sh = NamedSharding(mesh, P(None, "seq"))
        rows = T // ctx.world_size
        lo = ctx.rank * rows
        qg, kg, vg = (jax.make_array_from_process_local_data(
            sh, a[:, lo:lo + rows]) for a in (q, k, v))

        with mesh:
            out = ring_attention_sharded(qg, kg, vg, mesh, causal=True)
        ref = np.asarray(dense_attention(*map(jax.numpy.asarray, (q, k, v)),
                                         causal=True))
        worst = 0.0
        for shard in out.addressable_shards:
            t0 = shard.index[1].start or 0
            got = np.asarray(shard.data)
            want = ref[:, t0:t0 + got.shape[1]]
            worst = max(worst, float(np.max(np.abs(got - want))))
        return worst

    job = create_spmd_job("ring-gang", world_size=2, jax_distributed=True,
                          timeout=180.0)
    job.start()
    try:
        errors = job.run(fn, timeout=600.0)
    finally:
        job.stop()
    assert len(errors) == 2
    assert all(e < 2e-5 for e in errors), errors


def test_gang_on_a_tpu_host_is_refused_not_hung(monkeypatch):
    """Ranks that would each open every local TPU chip cannot start: the
    launcher says so before spawning anything, instead of letting them fail
    (or hang) in backend init. Ranks held to the CPU are untouched."""
    from raydp_tpu.spmd import job as job_mod

    monkeypatch.setattr(job_mod, "_local_tpu_chips", lambda: 4)
    monkeypatch.setattr(
        job_mod.SPMDJob, "_spawn_rank",
        lambda self, rank: pytest.fail("a refused gang spawns nothing"))
    job = create_spmd_job("t-chips", world_size=2, jax_distributed=True,
                          env={"JAX_PLATFORMS": "tpu,cpu"})
    with pytest.raises(RuntimeError, match="one process at a time"):
        job.start()
    assert job._placement_group_id is None and job._server is None

    # the same gang held to CPU devices starts as before
    monkeypatch.undo()
    monkeypatch.setattr(job_mod, "_local_tpu_chips", lambda: 4)
    job = create_spmd_job("t-chips-cpu", world_size=2, jax_distributed=True,
                          env={"JAX_PLATFORMS": "cpu"}, timeout=120)
    job.start()
    try:
        assert job.run(lambda ctx: ctx.rank, timeout=60) == [0, 1]
    finally:
        job.stop()
