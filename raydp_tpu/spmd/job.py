"""Driver side of the gang-SPMD job runner.

Parity map (reference → here):

- ``MPIJob.start`` — gRPC DriverService + STRICT_SPREAD placement group +
  ``mpirun`` spawn + two-phase registration barrier
  (mpi_job.py:165-318) → an RPC driver service, a placement group over the
  runtime's nodes, a direct gang spawn of rank processes, and the same
  two-phase barrier (register → start worker service → register service).
- ``MPIJob.run(fn)`` — cloudpickle broadcast + world-size result gather
  (mpi_job.py:324-338) → synchronous fan-out over per-rank RPC stubs with
  in-order ``func_id`` sequencing enforced worker-side (mpi_worker.py:75-96).
- ``OpenMPIJob``/``IntelMPIJob``/``MPICHJob`` mpirun-flag variants
  (mpi_job.py:411-429) → ``jax_distributed=True`` wires a JAX coordinator
  (rank 0) so ranks form one global device mesh; ``False`` runs plain Python
  ranks (still gang-placed, still object-store-connected).
- each MPI rank also joins Ray (mpi_worker.py:159-160) → each rank inherits
  the head address + session env and connects an object-store client, so SPMD
  programs can read/write the Arrow data plane.
"""

from __future__ import annotations

import os
import signal
import socket
import subprocess
import sys
import threading
import time
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional

import cloudpickle

from concurrent.futures import Future, InvalidStateError

from raydp_tpu.log import get_logger
from raydp_tpu.runtime.rpc import (
    DeferredReply, MethodDispatcher, RpcClient, RpcServer)

logger = get_logger("spmd")

ENV_JOB_ID = "RDT_SPMD_JOB_ID"
ENV_DRIVER = "RDT_SPMD_DRIVER"
ENV_RANK = "RDT_SPMD_RANK"
ENV_WORLD = "RDT_SPMD_WORLD_SIZE"
ENV_COORDINATOR = "RDT_SPMD_COORDINATOR"
ENV_JAX_DIST = "RDT_SPMD_JAX_DISTRIBUTED"


@dataclass
class WorkerContext:
    """Handed to the user function on every rank (parity: mpi_worker.py
    ``WorkerContext`` — job name, rank, world size)."""

    job_id: str
    rank: int
    world_size: int

    def __repr__(self):
        return f"WorkerContext(job={self.job_id}, rank={self.rank}/{self.world_size})"


class _DriverService:
    """Registration + liveness endpoint the ranks call into
    (parity: DriverService in mpi/network/network.proto:22-30)."""

    def __init__(self, job: "SPMDJob"):
        self._job = job

    def register_worker(self, rank: int, pid: int) -> Dict[str, Any]:
        return self._job._on_register_worker(rank, pid)

    def register_worker_service(self, rank: int, host: str, port: int) -> bool:
        return self._job._on_register_service(rank, host, port)

    def set_coordinator(self, address: str) -> bool:
        return self._job._on_set_coordinator(address)

    def get_coordinator(self, timeout: float = 120.0):
        # DeferredReply-based: every non-zero rank long-polls here while
        # rank 0 is still importing jax — parking dispatchers on a condition
        # wait would make set_coordinator queue behind the very waiters it
        # must wake (pool exhaustion; rdtlint dispatcher-blocking)
        return self._job._coordinator_reply(timeout)

    def ping(self) -> str:
        return "pong"


class SPMDJob:
    """A restartable gang of SPMD rank processes under one control plane.

    ``start()`` → ``run(fn)``×N → ``stop()``; the same object can be started
    again after ``stop()`` (the reference's test restarts a job object,
    test_mpi.py start/run/stop/restart case).
    """

    def __init__(
        self,
        job_name: str,
        world_size: int,
        env: Optional[Dict[str, str]] = None,
        jax_distributed: bool = False,
        placement_strategy: str = "SPREAD",
        cpus_per_process: float = 1.0,
        timeout: float = 120.0,
    ):
        self.job_name = job_name
        self.world_size = world_size
        self.extra_env = dict(env or {})
        self.jax_distributed = jax_distributed
        self.placement_strategy = placement_strategy
        self.cpus_per_process = cpus_per_process
        self.timeout = timeout

        self._server: Optional[RpcServer] = None
        self._procs: List[subprocess.Popen] = []
        self._stubs: Dict[int, RpcClient] = {}
        self._registered: Dict[int, int] = {}
        self._services: Dict[int, tuple] = {}
        self._barrier = threading.Condition()
        self._func_id = 0
        self._started = False
        self._placement_group_id: Optional[str] = None
        self._coordinator: Optional[str] = None
        #: get_coordinator long-polls parked as futures — dispatcher threads
        #: return immediately; each waiter holds one short-lived daemon
        #: Timer for its deadline (gang-sized, never dispatcher-pool-sized)
        self._coord_waiters: List[Future] = []

    # -- registration callbacks (driver service) ------------------------------
    def _on_register_worker(self, rank: int, pid: int) -> Dict[str, Any]:
        with self._barrier:
            self._registered[rank] = pid
            self._barrier.notify_all()
        return {"job_id": self.job_name, "world_size": self.world_size}

    def _on_register_service(self, rank: int, host: str, port: int) -> bool:
        with self._barrier:
            self._services[rank] = (host, port)
            self._barrier.notify_all()
        return True

    def _on_set_coordinator(self, address: str) -> bool:
        """Rank 0 picks the JAX coordinator port on its own interface moments
        before ``jax.distributed`` binds it and reports it here — a far
        smaller reuse window than a driver-side pick that sits unclaimed
        through the whole gang spawn (and a gang restart retries it). The
        host is rank 0's routable address, so the gang is not limited to one
        machine."""
        with self._barrier:
            self._coordinator = address
            waiters, self._coord_waiters = self._coord_waiters, []
            self._barrier.notify_all()
        # complete OUTSIDE the lock: a done-callback (the RPC server's reply
        # submit) must never run under it
        for fut in waiters:
            try:
                fut.set_result(address)
            except InvalidStateError:
                pass  # lost the race to this waiter's timeout timer
        return True

    def _coordinator_reply(self, timeout: float):
        """The coordinator address immediately when known, else a
        :class:`~raydp_tpu.runtime.rpc.DeferredReply` completed by rank 0's
        ``set_coordinator`` (or failed at ``timeout``). Replaces a condition
        wait that parked one dispatcher PER WAITING RANK: with the pool
        sized below ``world_size - 1`` the ``set_coordinator`` call that
        wakes the waiters would queue behind them — deadlock until every
        waiter timed out."""
        with self._barrier:
            if self._coordinator is not None:
                return self._coordinator
            fut: Future = Future()
            self._coord_waiters.append(fut)
        timer = threading.Timer(timeout, self._coord_timeout, args=(fut,))
        timer.daemon = True
        timer.start()
        fut.add_done_callback(lambda _f: timer.cancel())
        return DeferredReply(fut)

    def _coord_timeout(self, fut: "Future") -> None:
        # claim the waiter under the lock: set_coordinator/_reset swap the
        # list out BEFORE completing futures, so a fut no longer listed is
        # theirs to complete — failing it here would turn a coordinator
        # that arrived exactly at the deadline into a spurious timeout
        with self._barrier:
            claimed = fut in self._coord_waiters
            if claimed:
                self._coord_waiters.remove(fut)
        if not claimed:
            return
        try:
            fut.set_exception(TimeoutError(
                "coordinator address never arrived "
                "(rank 0 dead before jax.distributed?)"))
        except InvalidStateError:
            pass  # completed while we were between lock and here

    def _wait_barrier(self, table: dict, phase: str) -> None:
        deadline = time.time() + self.timeout
        with self._barrier:
            while len(table) < self.world_size:
                remaining = deadline - time.time()
                if remaining <= 0 or not self._barrier.wait(timeout=min(1.0, remaining)):
                    self._check_procs_alive()
                if time.time() >= deadline and len(table) < self.world_size:
                    raise TimeoutError(
                        f"SPMD job {self.job_name}: {phase} barrier timed out "
                        f"({len(table)}/{self.world_size} ranks)")

    def _check_procs_alive(self) -> None:
        for i, p in enumerate(self._procs):
            code = p.poll()
            if code is not None and code != 0:
                raise RuntimeError(
                    f"SPMD job {self.job_name}: rank {i} exited with code "
                    f"{code} during startup (see {self._log_path(i)})")

    # -- lifecycle ------------------------------------------------------------
    def start(self) -> "SPMDJob":
        if self._started:
            raise RuntimeError(f"SPMD job {self.job_name} already started")
        # a restarted gang's rank 0 binds a FRESH coordinator port; serving
        # the previous gang's address would wedge every other rank's
        # jax.distributed.initialize against a dead socket
        self._coordinator = None
        self._reserve_placement()
        self._refuse_shared_chips()
        self._server = RpcServer(MethodDispatcher(_DriverService(self)),
                                 max_concurrency=max(4, self.world_size),
                                 name=f"spmd-{self.job_name}")
        for rank in range(self.world_size):
            self._procs.append(self._spawn_rank(rank))
        # two-phase barrier (parity: mpi_job.py:280-318)
        self._wait_barrier(self._registered, "register")
        self._wait_barrier(self._services, "service")
        for rank, addr in sorted(self._services.items()):
            self._stubs[rank] = RpcClient(addr)
        self._started = True
        logger.info("SPMD job %s started: %d ranks%s", self.job_name,
                    self.world_size,
                    " (jax.distributed mesh)" if self.jax_distributed else "")
        return self

    def _refuse_shared_chips(self) -> None:
        """A chip belongs to one process at a time, and this launcher hands
        every rank the driver's environment without selecting a device: each
        rank JAX does not hold to the CPU opens EVERY local chip. Several
        such ranks on one TPU host cannot start (nor can one beside a driver
        that has trained), so say so here instead of letting them die one by
        one in backend init. Ranks on other machines (node agents) own their
        machine's chips and are not this check's business."""
        if not self.jax_distributed:
            return
        platforms = self.extra_env.get(
            "JAX_PLATFORMS", os.environ.get("JAX_PLATFORMS")) or ""
        if platforms.strip().lower() == "cpu":
            return
        local = sum(1 for rank in range(self.world_size)
                    if self._rank_agent(rank)[0] is None)
        chips = _local_tpu_chips()
        if local > 1 and chips:
            self._reset()   # hand the placement reservation back
            raise RuntimeError(
                f"SPMD job {self.job_name}: {local} ranks on this host would "
                f"each open its TPU chips ({chips} on the PCI bus) — no "
                f"per-rank chip visibility is set, and a chip belongs to one "
                f"process at a time (the driver holds it once it has touched "
                f"JAX). Gangs on TPU chips are not brought up: train on "
                f"several chips from one process (mesh_spec=...), or hold "
                f"the ranks to CPU "
                f"devices with worker_env={{'JAX_PLATFORMS': 'cpu'}}.")

    def _reserve_placement(self) -> None:
        """Gang-reserve CPU bundles through the runtime when one is live
        (parity: STRICT_SPREAD pg pinning nodes, mpi_job.py:192-222); a bare
        job without a runtime still works — it is just unaccounted."""
        from raydp_tpu.runtime import head as head_mod

        if not head_mod.runtime_initialized():
            return
        rt = head_mod.get_runtime()
        bundles = [{"CPU": self.cpus_per_process}
                   for _ in range(self.world_size)]
        from raydp_tpu.runtime.placement import PlacementStrategy
        group = rt.resource_manager.create_group(
            bundles, PlacementStrategy(self.placement_strategy.upper()))
        self._placement_group_id = group.group_id

    def _log_path(self, rank: int) -> str:
        from raydp_tpu.runtime import head as head_mod

        if head_mod.runtime_initialized():
            base = os.path.join(head_mod.get_runtime().session_dir, "logs")
        else:
            base = "/tmp/raydp_tpu/spmd"
        os.makedirs(base, exist_ok=True)
        return os.path.join(base, f"spmd-{self.job_name}-rank{rank}.out")

    def _rank_agent(self, rank: int):
        """(agent client, node) serving this rank's placement bundle, when the
        bundle landed on a node-agent machine — gang ranks then spawn there,
        one process per host, the way `mpirun -hosts` fans ranks out
        (mpi_job.py:240-278)."""
        from raydp_tpu.runtime import head as head_mod

        if self._placement_group_id is None or not head_mod.runtime_initialized():
            return None, None
        rt = head_mod.get_runtime()
        group = rt.resource_manager.get_group(self._placement_group_id)
        if group is None or rank >= len(group.bundles):
            return None, None
        node_id = group.bundle_node(rank)
        agent = rt.node_agents.get(node_id) if node_id else None
        node = rt.resource_manager.get_node(node_id) if node_id else None
        return agent, node

    def _spawn_rank(self, rank: int):
        # an override valued None means "remove from the child env" —
        # honored by both the local spawn below and NodeAgent.spawn
        env_overrides: Dict[str, str] = dict(self.extra_env)
        from raydp_tpu.runtime import head as head_mod
        rt = None
        if head_mod.runtime_initialized():
            # hand ranks the session so they join the data plane
            # (parity: ray.init in every MPI rank, mpi_worker.py:159-160)
            rt = head_mod.get_runtime()
            env_overrides[head_mod.ENV_HEAD] = rt.server.url
            env_overrides[head_mod.ENV_SESSION] = rt.session_id
            env_overrides[head_mod.ENV_SESSION_DIR] = rt.session_dir
        env_overrides[ENV_JOB_ID] = self.job_name
        env_overrides[ENV_DRIVER] = self._server.url
        env_overrides[ENV_RANK] = str(rank)
        env_overrides[ENV_WORLD] = str(self.world_size)
        env_overrides[ENV_JAX_DIST] = "1" if self.jax_distributed else "0"
        driver_path = [p for p in sys.path if p]
        if env_overrides.get("PYTHONPATH"):  # user extra_env path first
            driver_path.insert(0, env_overrides["PYTHONPATH"])
        if os.environ.get("PYTHONPATH"):
            driver_path.append(os.environ["PYTHONPATH"])
        env_overrides["PYTHONPATH"] = os.pathsep.join(driver_path)

        agent, node = self._rank_agent(rank)
        if agent is not None:
            # None-valued overrides ride through: the agent applies them as
            # removals in the child env (NodeAgent.spawn). Data-plane env
            # (RDT_STORE_HOST_ID / PAYLOAD_ADDR / ARENA) is injected by the
            # agent itself when its machine hosts an isolated payload plane.
            pid = agent.call("spawn", env_overrides,
                             f"spmd-{self.job_name}-rank{rank}",
                             ["-u", "-m", "raydp_tpu.spmd.worker"],
                             timeout=30.0)
            from raydp_tpu.runtime.head import _RemoteProcess
            return _RemoteProcess(agent, pid, node.node_id if node else "")

        env = dict(os.environ)
        for k, v in env_overrides.items():
            if v is None:
                env.pop(k, None)
            else:
                env[k] = v
        out = open(self._log_path(rank), "ab")
        proc = subprocess.Popen(
            [sys.executable, "-u", "-m", "raydp_tpu.spmd.worker"],
            env=env, stdout=out, stderr=subprocess.STDOUT,
            start_new_session=True)
        out.close()
        return proc

    # -- execution ------------------------------------------------------------
    def run(self, fn: Callable[[WorkerContext], Any],
            timeout: Optional[float] = None) -> List[Any]:
        """Broadcast ``fn`` to every rank; return world-size results ordered by
        rank (parity: mpi_job.py:324-338)."""
        if not self._started:
            raise RuntimeError(f"SPMD job {self.job_name} not started")
        import concurrent.futures as cf

        self._func_id += 1
        payload = cloudpickle.dumps(fn)
        fut_to_rank = {
            stub.submit("run_function", self._func_id, payload): rank
            for rank, stub in self._stubs.items()
        }
        results: List[Any] = [None] * self.world_size
        # fail fast: a dead rank surfaces the moment its connection drops,
        # without waiting out ranks that are hung in a collective behind it
        for fut in cf.as_completed(fut_to_rank, timeout=timeout or self.timeout):
            rank = fut_to_rank[fut]
            ok, value = fut.result()
            if not ok:
                raise RuntimeError(
                    f"SPMD job {self.job_name} rank {rank} failed:\n{value}")
            results[rank] = value
        return results

    def rank_addresses(self) -> Dict[int, tuple]:
        """Rank → worker-service address (parity: the reference exposes
        worker addresses for tests, test_mpi.py rank-address query)."""
        return dict(self._services)

    def stop(self) -> None:
        for rank, stub in list(self._stubs.items()):
            try:
                stub.submit("stop")
            except Exception:
                pass
        deadline = time.time() + 5.0
        from raydp_tpu.runtime.head import _RemoteProcess
        for p in self._procs:
            while p.poll() is None and time.time() < deadline:
                time.sleep(0.05)
            if p.poll() is None:
                if isinstance(p, _RemoteProcess):
                    p.kill()
                    continue
                try:
                    os.killpg(p.pid, signal.SIGKILL)
                except (ProcessLookupError, PermissionError):
                    try:
                        p.kill()
                    except ProcessLookupError:
                        pass
        self._reset()

    def _reset(self) -> None:
        """Full teardown so the same job object can start again
        (parity: mpi_job.py:344-395 ``_reset``)."""
        with self._barrier:
            waiters, self._coord_waiters = self._coord_waiters, []
        for fut in waiters:  # a parked get_coordinator must not outlive us
            try:
                fut.set_exception(TimeoutError(
                    f"SPMD job {self.job_name} stopped before rank 0 "
                    "reported a coordinator"))
            except InvalidStateError:
                pass  # its timeout timer already failed it
        for stub in self._stubs.values():
            stub.close()
        self._stubs.clear()
        if self._server is not None:
            self._server.stop()
            self._server = None
        if self._placement_group_id is not None:
            from raydp_tpu.runtime import head as head_mod
            if head_mod.runtime_initialized():
                try:
                    head_mod.get_runtime().resource_manager.remove_group(
                        self._placement_group_id)
                except Exception:
                    pass
            self._placement_group_id = None
        self._procs.clear()
        self._registered.clear()
        self._services.clear()
        self._coordinator = None
        self._func_id = 0
        self._started = False
        logger.info("SPMD job %s stopped", self.job_name)


def create_spmd_job(
    job_name: str,
    world_size: int,
    env: Optional[Dict[str, str]] = None,
    jax_distributed: bool = False,
    placement_strategy: str = "SPREAD",
    cpus_per_process: float = 1.0,
    timeout: float = 120.0,
) -> SPMDJob:
    """Factory, shape-parity with ``raydp.mpi.create_mpi_job``
    (mpi/__init__.py:36-91)."""
    return SPMDJob(job_name=job_name, world_size=world_size, env=env,
                   jax_distributed=jax_distributed,
                   placement_strategy=placement_strategy,
                   cpus_per_process=cpus_per_process, timeout=timeout)


def _local_tpu_chips() -> int:
    """TPU chips on this machine's PCI bus, counted without opening one (a
    backend query would claim them for this process)."""
    from jax._src import hardware_utils

    return hardware_utils.num_available_tpu_chips_and_device_id()[0]


def _free_port(host: str = "127.0.0.1") -> int:
    s = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
    s.bind((host, 0))
    port = s.getsockname()[1]
    s.close()
    return port
