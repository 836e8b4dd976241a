"""The command's contract off the chip: it refuses to run anywhere but on the
chips a cell asks for, and a rehearsal of every cell at tiny rows (through the
test-only ``Rehearsal`` argument; the command line has no CPU mode) prints the
result object the contract describes. A rehearsal's numbers are never results.
"""

import json
import math
import os
import shutil
import statistics
import subprocess
import sys
import time
import types

import pytest

from chipbench import harness, manifest

REPO = manifest.ROOT
M = manifest.load_manifest()
CELLS = [w["name"] for w in M["workloads"]]
# the tests of the harness's own paths (residency, the loss band, the traced
# window) run in one cell, whichever the manifest has: the first that
# streams on one chip
STREAM = next(w["name"] for w in M["workloads"] if w["chips"] == 1
              and manifest.resolve(M, w["name"]).wl["residency"] == "stream")


def _command(cwd, *args, **env):
    env = dict({k: v for k, v in os.environ.items() if k != "PYTHONPATH"},
               **env)
    return subprocess.run(
        [sys.executable, "chipbench/run.py", "--workload",
         CELLS[0], "--seed", "1", "--seconds", "1",
         "--trace", "0", *args],
        cwd=cwd, capture_output=True, text=True, timeout=180, env=env)


def test_command_refuses_the_cpu_and_names_it():
    proc = _command(REPO, JAX_PLATFORMS="cpu")
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""            # no result line
    assert "TPU" in proc.stderr and "'cpu'" in proc.stderr


def test_command_alone_in_a_directory_fails(tmp_path):
    """BENCHMARK.json and the files under ``paths`` without the program
    measure nothing, and must say so."""
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), tmp_path)
    for p in M["paths"]:
        shutil.copytree(os.path.join(REPO, p), tmp_path / p,
                        ignore=shutil.ignore_patterns("out", ".cache",
                                                      "__pycache__"))
    proc = _command(tmp_path, JAX_PLATFORMS="cpu")
    assert proc.returncode != 0 and proc.stdout.strip() == ""
    assert "raydp_tpu" in proc.stderr


def _tiny(name, tmp_path):
    """The cell cut for a CPU test as its own pipeline says (``cpu_cut``:
    the widths it really has, far fewer rows), and the rehearsal of it."""
    cell = manifest.resolve(M, name)
    return cell, harness.cut_for_cpu(cell, tmp_path)


def _rehearse(cell, rehearsal, trace):
    t0 = time.perf_counter()
    return harness.run_cell(cell, seed=1, seconds=0.3, trace=trace,
                            t_start=t0, rehearsal=rehearsal)


def _check_contract(result, cell, trace):
    detail = result.pop("detail")
    line = json.loads(json.dumps(result))       # what the last line carries
    assert set(line) == {"correct", "attempted", "failed", "metrics", "device"}
    assert set(line["device"]) >= {"platform", "kind", "count",
                                   "memory_peak_bytes"}
    assert line["device"]["count"] == cell.chips
    assert line["attempted"] > 0 and line["failed"] == 0
    declared = {m["name"]: m for m in (cell.per_layer if trace
                                       else cell.end_to_end)}
    if trace:       # a reader that finds nothing (no TPU trace here) is left out
        assert set(line["metrics"]) <= set(declared)
        assert {"etl_wall_s", "fit_startup_s", "final_save_s",
                "dispatch_share"} <= set(line["metrics"])
    else:
        assert set(line["metrics"]) == set(declared)
    for name, got in line["metrics"].items():
        assert got["unit"] == declared[name]["unit"]
        assert math.isfinite(got["value"])
    if not trace:
        assert all(v["value"] > 0 for v in line["metrics"].values())
    # each number the checks compared stands beside its limit (``run.py``
    # prints them as the line's last key and on stderr), and the run's
    # counters are in its detail
    assert set(detail["found"]["compared"]) == {
        "reference_error", "last_loss", "first_window_loss",
        "lowerings_in_window"}
    assert all(set(pair) == {"value", "limit"}
               for pair in detail["found"]["compared"].values())
    assert detail["counters"] == json.loads(json.dumps(detail["counters"]))
    assert detail["counters"]
    return line, detail


@pytest.mark.parametrize("name", CELLS)
def test_rehearsal_prints_the_contract(name, tmp_path):
    cell, rehearsal = _tiny(name, tmp_path)
    line, detail = _check_contract(_rehearse(cell, rehearsal, False),
                                   cell, trace=False)
    assert line["correct"] is True, detail["found"]
    # check (a) compared every trailing dimension of the model's output, on
    # the rows the reference's file names
    assert detail["found"]["compared_shape"][0] == cell.reference.SAMPLE["rows"]
    assert detail["found"]["streamed"] == (cell.wl["residency"] == "stream")
    assert detail["found"]["lowerings_in_window"] == 0
    # the window holds whole epochs of whole steps
    assert line["attempted"] % (detail["num_epochs"] - 1) == 0
    # a second run of the cell here finds t_e and makes no calibration fit
    assert os.path.exists(tmp_path / ".cache" / f"{name}.json")


def _resident(cell):
    """The cell as a resident mix of the same configuration would be (no such
    cell is in the benchmark: PERF.md, Open questions): default routing, and
    a reader of its own for the host work between epochs, which the harness
    hands every reader. The feed's metrics hold for any cell; their readers
    find a feed that did nothing."""
    cell.wl["residency"] = "default"
    cell.per_layer.append({"name": "epoch_gap_ms", "unit": "ms",
                           "better": "lower", "source": "host_clock",
                           "layer": "train loop",
                           "moves": "train_throughput"})
    cell.readers["epoch_gap_ms"] = types.SimpleNamespace(
        read=lambda run: 1e3 * statistics.median(run["epoch_gaps_s"]))
    return cell


@pytest.mark.parametrize("residency", ["stream", "default"])
def test_traced_rehearsal_reports_the_layers(residency, tmp_path):
    cell, rehearsal = _tiny(STREAM, tmp_path)
    if residency == "default":
        cell = _resident(cell)
    # a run that finds the steady epoch wall makes no calibration fit; this
    # one gives the window 6 epochs on any machine: 3 untraced, then 3 traced
    os.makedirs(tmp_path / ".cache")
    (tmp_path / ".cache" / f"{cell.name}.json").write_text('{"t_e": 0.05}')
    line, detail = _check_contract(_rehearse(cell, rehearsal, True),
                                   cell, trace=True)
    assert line["correct"] is True, detail["found"]
    assert detail["num_epochs"] == 7 and detail["clock"]["calibration_s"] < 1
    assert detail["found"]["streamed"] == (residency == "stream")
    if residency == "stream":
        assert line["metrics"]["native_staged_share"]["value"] >= 0
        assert line["metrics"]["feed_wait_share"]["value"] > 0
    else:
        assert line["metrics"]["feed_wait_share"]["value"] == 0
        assert "epoch_gap_ms" in line["metrics"]


def test_a_stream_cell_that_ran_resident_is_not_correct(tmp_path, monkeypatch):
    cell, rehearsal = _tiny(STREAM, tmp_path)
    monkeypatch.setattr(harness, "residency_env", lambda cell, rows: {})
    result = _rehearse(cell, rehearsal, False)
    assert result["correct"] is False
    checks = result["detail"]["found"]["checks"]
    assert checks["path"] is False and checks["reference"] is True


def test_a_resident_cell_that_streamed_is_not_correct(tmp_path, monkeypatch):
    cell, rehearsal = _tiny(STREAM, tmp_path)
    cell = _resident(cell)
    monkeypatch.setenv("RDT_DEVICE_CACHE", "0")
    result = _rehearse(cell, rehearsal, False)
    assert result["correct"] is False
    assert result["detail"]["found"]["checks"]["path"] is False


def test_a_first_loss_outside_the_band_is_not_correct(tmp_path):
    cell, rehearsal = _tiny(STREAM, tmp_path)
    cell.wl["first_window_loss_band"] = [0.0, 1e-9]
    result = _rehearse(cell, rehearsal, False)
    assert result["correct"] is False
    checks = result["detail"]["found"]["checks"]
    assert checks["loss_band"] is False and checks["loss_falls"] is True


def test_an_estimator_the_pipelines_do_not_build_is_refused(tmp_path):
    cell, rehearsal = _tiny(STREAM, tmp_path)
    cell.wl["estimator"] = "gbdt"
    with pytest.raises(NotImplementedError, match="gbdt"):
        _rehearse(cell, rehearsal, False)


@pytest.mark.parametrize("name", CELLS)
def test_residency_budget_is_cut_with_the_rows(name):
    cell = manifest.resolve(M, name)
    if cell.wl["residency"] == "stream":
        env = harness.residency_env(cell, cell.wl["rows"])
        share = float(env["RDT_DEVICE_CACHE_MB"]) / harness.DEFAULT_CACHE_MB
        assert share == pytest.approx(cell.wl["rows"] / cell.cfg["source_rows"])
    cell.wl["residency"] = "default"
    assert harness.residency_env(cell, 10) == {}
    cell.wl["residency"] = "sometimes"
    with pytest.raises(ValueError, match="sometimes"):
        harness.residency_env(cell, 10)


def test_a_check_of_unlike_shapes_is_not_correct(tmp_path):
    """Check (a) compares ``[rows, ...]`` with ``[rows, ...]``: a reference
    that returns another shape than the program's compared output fails, it
    is not broadcast against it."""
    cell, rehearsal = _tiny(STREAM, tmp_path)
    forward = cell.reference.forward
    cell.reference = types.SimpleNamespace(
        TOLERANCE=cell.reference.TOLERANCE, SAMPLE=cell.reference.SAMPLE,
        forward=lambda *a: forward(*a).reshape(-1))
    result = _rehearse(cell, rehearsal, False)
    checks = result["detail"]["found"]["checks"]
    assert result["correct"] is False and checks["reference"] is False
    assert all(ok for name, ok in checks.items() if name != "reference")


REAP_SCRIPT = """
import os, signal, subprocess, sys, time
from chipbench import harness
harness.adopt_orphans()
ended = subprocess.Popen(["sleep", "60"])
ended.send_signal(signal.SIGKILL)            # ended, and nobody waits for it
stubborn = subprocess.Popen(["sleep", "60"])
# a grandchild whose parent exits at once: an orphan, which falls to us
subprocess.Popen([sys.executable, "-c", "import subprocess; "
                  "subprocess.Popen(['sleep', '60'])"]).wait()
time.sleep(0.2)
before = harness._children()
t0 = time.monotonic()
reaped = harness.reap_children(grace_s=0.3)
print(len(before), reaped, harness._children(), time.monotonic() - t0 < 5)
"""


def test_reap_children_waits_for_every_process_the_run_started():
    """``raydp_tpu.stop()`` ends the session's processes without waiting for
    them; the command waits for them, for adopted orphans too, and kills what
    still runs."""
    proc = subprocess.run([sys.executable, "-c", REAP_SCRIPT], cwd=REPO,
                          capture_output=True, text=True, timeout=60)
    assert proc.stdout.split(maxsplit=2)[:2] == ["3", "3"], proc.stderr
    assert proc.stdout.strip().endswith("[] True")
