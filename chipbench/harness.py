"""One run of one cell: set-up, one measured ``fit_on_frame``, the checks, and
the result object. ``run.py`` is the command; this module holds what it does.

The clock is the benchmark's own. A callback the estimator calls with every
epoch report timestamps the report; each report follows a fetched loss, so
every tick ends in a host value. The window opens at the report of epoch 0
(trace and compile-cache load done) and closes at the report of the last
epoch; the final checkpoint save, the checks and the teardown lie outside it.
"""

from __future__ import annotations

import json
import math
import os
import shutil
import signal
import time
from dataclasses import dataclass, field
from typing import Optional

import numpy as np

from chipbench.manifest import BENCH_DIR, ROOT, Cell, peak_of

MIN_WINDOW_EPOCHS = 3
CALIBRATION_EPOCHS = 3
TRACE_EPOCHS, TRACE_SECONDS = 3, 5.0
LOWERING_EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"
CACHE_MISS_EVENT = "/jax/compilation_cache/cache_misses"
DEFAULT_CACHE_MB = 2048.0   # the program's residency budget (RDT_DEVICE_CACHE_MB)
REHEARSAL_PEAK = {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11,
                  "hbm_bytes": 1e10,
                  "source": "made up: a rehearsal has no peak"}


@dataclass
class Rehearsal:
    """Test-only: run a cell off the chip at tiny rows. The command line has
    no way to ask for this; a rehearsal's numbers are never results."""
    rows: int
    out_dir: str
    devices: int = 1
    peak: dict = field(default_factory=lambda: dict(REHEARSAL_PEAK))


def cut_for_cpu(cell: Cell, out_dir: str) -> Rehearsal:
    """The cell as a CPU rehearsal runs it. The cell's own pipeline says how
    it is cut (``cpu_cut(cfg, wl, chips)``: counts only, never a width; it
    edits this cell's ``cfg`` and ``wl`` and returns the rows of input); the
    loss band belongs to the real size and goes."""
    rows = cell.pipeline.cpu_cut(cell.cfg, cell.wl, cell.chips)
    cell.wl["first_window_loss_band"] = None
    return Rehearsal(rows=int(rows), out_dir=str(out_dir), devices=cell.chips)


class NoChip(RuntimeError):
    pass


class CompileCounter:
    """Counts jax's lowerings (every program jax compiles or loads from the
    persistent cache is lowered first) and persistent-cache misses."""

    def __init__(self):
        import jax.monitoring as mon
        self.lowerings = 0
        self.cache_misses = 0
        mon.register_event_duration_secs_listener(self._duration)
        mon.register_event_listener(self._event)

    def _duration(self, event, _secs, **_kw):
        if event == LOWERING_EVENT:
            self.lowerings += 1

    def _event(self, event, **_kw):
        if event == CACHE_MISS_EVENT:
            self.cache_misses += 1


class EpochClock:
    """The estimator callback: a timestamp (and the lowering count) at every
    epoch report and, in a traced run, the profiler's start and stop."""

    def __init__(self, compiles: CompileCounter, num_epochs: int,
                 trace_dir: Optional[str]):
        self.compiles = compiles
        self.ticks = []         # (perf_counter, lowerings so far)
        self.trace_dir = trace_dir
        self.trace_from = max(1, num_epochs - 1 - TRACE_EPOCHS)
        self.last = num_epochs - 1
        self.traced = None      # [first traced epoch, last traced epoch]
        self._t_trace = None

    def __call__(self, report: dict) -> None:
        now = time.perf_counter()
        self.ticks.append((now, self.compiles.lowerings))
        if self.trace_dir is None:
            return
        import jax
        idx = len(self.ticks) - 1
        if self._t_trace is None and idx == self.trace_from:
            jax.profiler.start_trace(self.trace_dir)
            self._t_trace = time.perf_counter()
            self.traced = [idx + 1, None]
        elif self.traced is not None and self.traced[1] is None and (
                idx - self.trace_from >= TRACE_EPOCHS or idx == self.last
                or now - self._t_trace >= TRACE_SECONDS):
            jax.profiler.stop_trace()
            self.traced[1] = idx


def adopt_orphans() -> None:
    """Make this process the one that orphaned descendants fall to (Linux
    ``PR_SET_CHILD_SUBREAPER``), so that ``reap_children`` can wait for them
    too and none is left to the machine's init."""
    import ctypes
    ctypes.CDLL(None, use_errno=True).prctl(36, 1, 0, 0, 0)


def _children() -> list:
    """This process's children, running or defunct - without the
    interpreter's own ``multiprocessing`` resource tracker, which Python
    itself stops and waits for when it exits."""
    me, found = os.getpid(), []
    for entry in os.listdir("/proc"):
        if not entry.isdigit():
            continue
        try:
            with open(f"/proc/{entry}/stat") as fh:
                parent = int(fh.read().rsplit(")", 1)[1].split()[1])
            with open(f"/proc/{entry}/cmdline", "rb") as fh:
                tracker = b"multiprocessing.resource_tracker" in fh.read()
        except (OSError, IndexError):
            continue            # it ended while we looked
        if parent == me and not tracker:
            found.append(int(entry))
    return found


def reap_children(grace_s: float = 10.0) -> int:
    """Wait until every process this one started has ended; returns how many
    were waited for. ``raydp_tpu.stop()`` ends the session's processes and
    does not wait for them, which leaves them defunct until this process
    exits and, where init reaps nothing, after it (seen on the chip machine).
    What still runs after ``grace_s`` is killed, then waited for."""
    reaped, killed = 0, False
    deadline = time.monotonic() + grace_s
    while True:
        children = _children()
        for pid in children:
            try:
                reaped += os.waitpid(pid, os.WNOHANG)[0] == pid
            except ChildProcessError:
                pass            # someone else waited for it
        if not children:
            return reaped
        if time.monotonic() < deadline:
            time.sleep(0.02)
        elif killed:
            raise RuntimeError(f"children {children} outlived SIGKILL")
        else:
            for pid in children:
                os.kill(pid, signal.SIGKILL)
            killed, deadline = True, time.monotonic() + grace_s


def find_devices(cell: Cell, rehearsal: Optional[Rehearsal]):
    import jax
    devices = jax.devices()
    if rehearsal is not None:
        return devices[:rehearsal.devices]
    platform = devices[0].platform
    if platform != "tpu" or len(devices) != cell.chips:
        raise NoChip(f"cell {cell.name} needs {cell.chips} TPU chip(s); jax "
                     f"found {len(devices)} device(s) of platform "
                     f"{platform!r} ({devices[0].device_kind})")
    return devices


def write_input(cell: Cell, rows: int, seed: int, out_dir: str,
                parts: int = 8) -> str:
    """The cell's raw input, from the seed, as ``parts`` Parquet files."""
    import pyarrow.parquet as pq
    table = cell.pipeline.generate(rows, seed, cell.cfg)
    path = os.path.join(out_dir, f"input-{rows}")
    shutil.rmtree(path, ignore_errors=True)
    os.makedirs(path)
    step = -(-rows // parts)
    for i in range(parts):
        pq.write_table(table.slice(i * step, step),
                       os.path.join(path, f"part-{i:05d}.parquet"))
    return path


def residency_env(cell: Cell, rows: int) -> dict:
    """``stream`` cuts the program's residency budget with the rows: the full
    table would not fit the default budget, so the cut one must not either.
    ``default`` leaves the program's routing alone."""
    mode = cell.wl["residency"]
    if mode == "default":
        return {}
    if mode == "stream":
        budget = DEFAULT_CACHE_MB * rows / cell.cfg["source_rows"]
        return {"RDT_DEVICE_CACHE_MB": repr(budget)}
    raise ValueError(f"residency {mode!r}: stream or default")


def global_batch(cell: Cell, mesh) -> int:
    from raydp_tpu.parallel.mesh import data_axes
    replicas = int(np.prod([mesh.shape[a] for a in data_axes(mesh)]))
    return cell.wl["batch_per_replica"] * replicas


def fit_once(cell: Cell, df, info: dict, mesh, num_epochs: int, callbacks):
    wl = cell.wl
    if wl["estimator"] != "flax":
        raise NotImplementedError(
            f"estimator {wl['estimator']!r}: the pipelines build flax only "
            f"(PERF.md, Open questions)")
    interval = wl["checkpoint_interval"]
    est = cell.pipeline.build_estimator(
        cell.cfg, wl, info, mesh=mesh, num_epochs=num_epochs,
        batch_size=global_batch(cell, mesh), callbacks=callbacks,
        checkpoint_interval=num_epochs if interval == "final" else interval,
        **wl["estimator_args"])
    t0 = time.perf_counter()
    result = est.fit_on_frame(df)
    t1 = time.perf_counter()
    shutil.rmtree(result.checkpoint_dir, ignore_errors=True)
    return est, result, t0, t1


def steady_epoch_s(cell: Cell, df, info, mesh, cache_dir: str) -> float:
    """``t_e``: from the file a run of this cell in this checkout left, else
    from a calibration fit (which also builds the native libraries and fills
    the compile cache): the shortest epoch after epoch 0 of
    ``CALIBRATION_EPOCHS``. A second epoch can still ramp (1.48 s against a
    steady 0.95 s on four chips, PERF.md), and a ``t_e`` read too long makes
    the window too short."""
    path = os.path.join(cache_dir, f"{cell.name}.json")
    if os.path.exists(path):
        with open(path) as fh:
            return float(json.load(fh)["t_e"])
    _, result, _, _ = fit_once(cell, df, info, mesh, CALIBRATION_EPOCHS, [])
    t_e = min(float(e["epoch_time_s"]) for e in result.history[1:])
    os.makedirs(cache_dir, exist_ok=True)
    with open(path, "w") as fh:
        json.dump({"t_e": t_e}, fh)
    return t_e


def relative_rms_error(program_out, reference_out) -> float:
    """The comparison that decides check (a): root-mean-square difference
    over the root mean square of the reference's outputs."""
    ref = np.asarray(reference_out, np.float64)
    diff = np.asarray(program_out, np.float64) - ref
    return float(np.sqrt(np.mean(diff * diff))
                 / max(np.sqrt(np.mean(ref * ref)), 1e-6))


def program_outputs(est, ds, batch_rows: int, compared=None) -> np.ndarray:
    """The program's outputs ``[rows, ...]`` on a dataset with the fit's
    parameters, pulled in batches of ``batch_rows``: the estimator's own
    model, batch preprocessor and cast, jitted with the parameters passed as
    arguments. ``compared`` (the pipeline's, optional) picks inside the jit
    what of a batch's output is compared, so that only that leaves the
    device. Not ``predict``: it closes over the parameters, so jax lowers
    them as constants - with 2.2 GB of tables that took 95 s a run on the
    chip, and at any size the constants are part of the compile-cache key, so
    every run would compile and add a cache entry (PERF.md, Findings)."""
    import jax
    import jax.numpy as jnp
    from raydp_tpu.data.feed import HostBatchIterator
    from raydp_tpu.train.flax_estimator import _cast_floating, _takes_train

    model = est._build_model()
    kwargs = {"train": False} if _takes_train(model) else {}

    @jax.jit
    def infer(variables, batch):
        inputs = _cast_floating(est._split_batch(batch)[0], est.compute_dtype)
        preds = model.apply(variables, inputs, **kwargs)
        if compared is not None:
            preds = compared(preds)
        return preds.astype(jnp.float32)

    variables = est.get_model()
    batches = HostBatchIterator(ds, batch_rows, est._columns(), shuffle=False,
                                drop_remainder=False)
    return np.concatenate([
        np.asarray(infer(variables, {k: jnp.asarray(v) for k, v in b.items()}))
        for b in batches])


def reference_outputs(cell: Cell, variables, table, info,
                      batch_rows: int) -> np.ndarray:
    """The plain reference's outputs on the same rows in the same batches
    (its ``forward`` returns what ``compared`` leaves of the program's)."""
    return np.concatenate([
        np.asarray(cell.reference.forward(
            variables, cell.pipeline.reference_inputs(
                table.slice(at, batch_rows), info), cell.cfg))
        for at in range(0, table.num_rows, batch_rows)])


def check_correct(cell: Cell, est, session, info, seed, out_dir, window,
                  streamed, lowerings_in_window):
    """The five checks; returns (all passed, what each found)."""
    import jax
    from raydp_tpu.data import from_frame_recoverable

    found = {}
    # (a) the program's outputs against the plain reference, same parameters,
    # on the sample the reference's file names beside its tolerance
    sample = cell.reference.SAMPLE
    path = write_input(cell, sample["rows"], seed + 1, out_dir, parts=1)
    df, _ = cell.pipeline.etl(session.read.parquet(path), cell.cfg, cell.wl)
    df = df.persist()
    table = df.to_arrow()
    t0 = time.perf_counter()
    pick = getattr(cell.pipeline, "compared", None)
    compared = (lambda out: pick(out, cell.cfg)) if pick else None
    got = program_outputs(est, from_frame_recoverable(df), sample["batch"],
                          compared)
    t1 = time.perf_counter()
    want = reference_outputs(cell, jax.device_get(est.get_model()), table,
                             info, sample["batch"])
    found["program_s"], found["reference_s"] = t1 - t0, time.perf_counter() - t1
    found["compared_shape"] = list(got.shape)
    same_shape = got.shape == want.shape and len(got) == table.num_rows > 0
    err = relative_rms_error(got, want) if same_shape else float("inf")
    found["reference_error"] = err
    found["reference_tolerance"] = cell.reference.TOLERANCE
    ok_a = same_shape and err <= cell.reference.TOLERANCE
    # (b) losses finite, the last below the first
    losses = [e["train_loss"] for e in window]
    ok_b = bool(np.all(np.isfinite(losses))) and losses[-1] < losses[0]
    found["window_losses"] = [losses[0], losses[-1]]
    # (c) the window's first loss inside the band three seeds gave
    band = cell.wl.get("first_window_loss_band")
    ok_c = band is None or band[0] <= losses[0] <= band[1]
    # (d) the path the cell is about
    want_stream = cell.wl["residency"] == "stream"
    ok_d = streamed == want_stream
    found["streamed"] = streamed
    # (e) nothing lowered (so nothing compiled) inside the window
    ok_e = lowerings_in_window == 0
    found["lowerings_in_window"] = lowerings_in_window
    found["checks"] = {"reference": ok_a, "loss_falls": ok_b,
                       "loss_band": ok_c, "path": ok_d, "no_compile": ok_e}
    # each number compared beside its limit: the result line's last key and
    # the run's last lines on standard error (``run.py``)
    found["compared"] = {
        "reference_error": {"value": err, "limit": cell.reference.TOLERANCE},
        "last_loss": {"value": losses[-1], "limit": losses[0]},
        "first_window_loss": {"value": losses[0], "limit": band},
        "lowerings_in_window": {"value": lowerings_in_window, "limit": 0}}
    return all(found["checks"].values()), found


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool,
             t_start: float, rehearsal: Optional[Rehearsal] = None) -> dict:
    """Run the cell once; returns the result object of the contract plus a
    ``detail`` key (which ``run.py`` prints on an earlier line)."""
    import jax

    import raydp_tpu
    from raydp_tpu import metrics as rdt_metrics
    from raydp_tpu.parallel import make_mesh

    devices = find_devices(cell, rehearsal)
    kind = devices[0].device_kind
    peak = rehearsal.peak if rehearsal is not None else peak_of(kind)
    base = rehearsal.out_dir if rehearsal is not None else os.path.join(
        ROOT, BENCH_DIR)
    out_dir = os.path.join(base, "out", cell.name)
    os.makedirs(out_dir, exist_ok=True)
    rows = rehearsal.rows if rehearsal is not None else int(cell.wl["rows"])
    compiles = CompileCounter()
    clock = {}

    # ---- set-up: input, session, ETL, the steady epoch wall
    env = residency_env(cell, rows)
    saved = {k: os.environ.get(k) for k in env}
    os.environ.update(env)
    t = time.perf_counter()
    path = write_input(cell, rows, seed, out_dir)
    clock["generate_s"] = time.perf_counter() - t
    t = time.perf_counter()
    session = raydp_tpu.init("chipbench", num_executors=2, executor_cores=2,
                             executor_memory="2GB")
    clock["session_s"] = time.perf_counter() - t
    try:
        t = time.perf_counter()
        df, info = cell.pipeline.etl(session.read.parquet(path), cell.cfg,
                                     cell.wl)
        df = df.persist()
        clock["etl_wall_s"] = time.perf_counter() - t
        mesh = make_mesh(cell.wl["mesh_spec"] or None, devices=devices)
        t = time.perf_counter()
        t_e = steady_epoch_s(cell, df, info, mesh,
                             os.path.join(base, ".cache"))
        clock["calibration_s"] = time.perf_counter() - t
        num_epochs = 1 + max(MIN_WINDOW_EPOCHS, math.ceil(seconds / t_e))
        trace_dir = os.path.join(out_dir, "trace") if trace else None
        if trace_dir:
            shutil.rmtree(trace_dir, ignore_errors=True)
        ticker = EpochClock(compiles, num_epochs, trace_dir)
        misses_before = compiles.cache_misses

        # ---- the measured fit
        est, result, t_fit0, t_fit1 = fit_once(cell, df, info, mesh,
                                               num_epochs, [ticker])
        history = result.history
        ticks = ticker.ticks
        t_open, t_close = ticks[0][0], ticks[-1][0]
        window_s = t_close - t_open
        window = history[1:]
        items_per_step = global_batch(cell, mesh) * (
            int(cell.wl.get("seq_len", 1))
            if cell.wl["unit_of_work"] == "tokens" else 1)
        steps = sum(e["steps"] for e in window)
        # the fit runs with max_retries=0: an epoch that fails ends the run,
        # so the only steps that can fail quietly are those of a lost loss
        bad_steps = sum(e["steps"] for e in window
                        if not np.isfinite(e["train_loss"]))
        clock["before_fit_s"] = t_fit0 - t_start
        clock["fit_startup_s"] = t_open - t_fit0
        clock["final_save_s"] = t_fit1 - t_close
        values = {
            "train_throughput": steps * items_per_step / window_s,
            # everything before the window: input, session, ETL and the
            # fit's own start-up (conversion, state, cache load, epoch 0)
            "setup_s": t_open - t_start,
        }

        # ---- after the window, outside every metric
        misses_in_fit = compiles.cache_misses - misses_before
        streamed = sum(e["feed_time_s"] + e["h2d_time_s"]
                       for e in history) > 0
        correct, found = check_correct(
            cell, est, session, info, seed, out_dir, window, streamed,
            ticks[-1][1] - ticks[0][1])
        counters = rdt_metrics.snapshot()["counters"]
    finally:
        raydp_tpu.stop()
        for k, v in saved.items():
            if v is None:
                del os.environ[k]
            else:
                os.environ[k] = v

    stats = [d.memory_stats() or {} for d in devices]
    device = {"platform": devices[0].platform, "kind": kind,
              "count": len(devices),
              "memory_peak_bytes": max(
                  int(s.get("peak_bytes_in_use", 0)) for s in stats)}
    detail = {"cell": cell.name, "seed": seed, "rows": rows,
              "num_epochs": num_epochs, "t_e": t_e, "window_s": window_s,
              "clock": clock, "found": found, "counters": counters,
              "cache_misses_in_fit": misses_in_fit,
              "cache_misses_total": compiles.cache_misses,
              "lowerings_total": compiles.lowerings,
              "epoch_walls_s": [e["epoch_time_s"] for e in history[:12]]}
    result_obj = {"correct": bool(correct), "attempted": int(steps),
                  "failed": int(bad_steps), "device": device,
                  "detail": detail}

    if not trace:
        result_obj["metrics"] = {
            m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
            for m in cell.end_to_end}
        return result_obj

    # ---- the traced run reports the per-layer metrics
    from chipbench.trace import host_spans, reduce as reducer
    xplane = reducer.find_xplane(trace_dir)
    reduced = reducer.reduce(xplane) if xplane else None
    if reduced is None and rehearsal is None:
        raise RuntimeError("the traced run holds no device operation")
    first, last = ticker.traced
    untraced = history[1:first]
    # host work between epochs: report-to-report time minus the later epoch's
    # own wall (no cell here is resident, so no reader takes it yet)
    gaps = [ticks[i][0] - ticks[i - 1][0] - history[i]["epoch_time_s"]
            for i in range(2, first)]
    # what a per-layer reader is given: the run's readings, and its cell
    # (the configuration and the traffic as their files have them, and the
    # family's count of operations), so that a reader names no configuration
    run = {
        "cell": cell.name, "cfg": cell.cfg, "wl": cell.wl,
        "flops": cell.flops,
        "epochs": untraced, "epoch_gaps_s": gaps, "clock": clock,
        "counters": counters, "trace": reduced, "xplane": xplane,
        "chips": len(devices),
        "peak": peak, "traced_items": items_per_step * sum(
            e["steps"] for e in history[first:last + 1]),
        "flops_per_item": cell.flops.train_flops_per_item(
            cell.cfg, cell.wl, info),
    }
    metrics = {}
    for m in cell.per_layer:
        value = cell.readers[m["name"]].read(run)
        if value is not None:       # a reader that finds nothing says nothing
            metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
    result_obj["metrics"] = metrics
    if reduced is not None:
        device["busy_s"] = reduced["busy_s"]
        device["window_s"] = reduced["window_s"]
        # idle seconds a chip by what the host did meanwhile (the loop's step
        # spans); a program without the spans leaves the device's own view
        # (inside a program or between two)
        by_span = host_spans.idle_seconds(xplane)
        result_obj["breakdown"] = {
            "device_ops": reduced["device_ops"],
            "idle_gaps": reducer.most_first(by_span) if by_span
            else reduced["idle_gaps"]}
        detail["per_chip"] = reduced["per_chip"]
        detail["traced_epochs"] = [first, last]
    return result_obj
