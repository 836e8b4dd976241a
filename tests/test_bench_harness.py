"""Bench-harness control flow: a run without a chip or with a failed config
must fail, a CPU run must say it is one, and partial results survive a cap
kill. Probe and config children are faked so the logic is testable without
hardware."""

import importlib.util
import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture
def bench(monkeypatch, tmp_path):
    spec = importlib.util.spec_from_file_location(
        "bench_under_test", os.path.join(REPO, "bench.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    # keep the detail record and the compile cache away from the repo (and
    # out of the environment later tests' children inherit), and the default
    # run a default run (the suite itself runs under JAX_PLATFORMS=cpu)
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "jc"))
    monkeypatch.setenv("RDT_BENCH_DETAIL_PATH",
                       str(tmp_path / "BENCH_DETAIL.json"))
    monkeypatch.delenv("JAX_PLATFORMS", raising=False)
    monkeypatch.setattr(mod, "_probe_device",
                        lambda: ("tpu", "TPU v5 lite", 1))
    return mod


def _run_main(bench, capsys):
    """Run main(); return (exit code, the RICH record from
    BENCH_DETAIL.json). stdout's final line is a compact digest sized for a
    2000-char tail; the detail file carries the full per-config results —
    consistency of the two is asserted here so every test exercises both."""
    rc = bench.main()
    line = capsys.readouterr().out.strip().splitlines()[-1]
    compact = json.loads(line)
    assert len(line) <= 1900, f"stdout line too big for the driver: {len(line)}"
    # rdtlint: allow[knob-registry] test reads back the path it set above
    with open(os.environ["RDT_BENCH_DETAIL_PATH"]) as fh:
        detail = json.load(fh)
    for key in ("metric", "unit", "platform", "value", "vs_baseline"):
        assert compact.get(key) == detail.get(key), key
    return rc, detail


def test_no_chip_exits_nonzero_and_measures_nothing(bench, monkeypatch,
                                                    capsys):
    """A default run that finds no TPU fails before any config starts — it
    never records a CPU number under a device run's name."""
    monkeypatch.setattr(bench, "_probe_device", lambda: ("cpu", "cpu", 1))
    monkeypatch.setattr(bench, "_spawn_config",
                        lambda *a: pytest.fail("nothing should spawn"))
    assert bench.main() != 0
    captured = capsys.readouterr()
    assert captured.out.strip() == ""
    assert "no TPU" in captured.err and "cpu" in captured.err


def test_failed_config_makes_the_run_fail(bench, monkeypatch, capsys):
    """A config whose body raised, or that was killed at its cap, is on the
    record AND in the exit code; a failed headline is null, not 0.0."""
    monkeypatch.setenv("BENCH_CONFIGS", "nyctaxi,gbdt,keras")

    def fake_spawn(name, cap_s):
        if name == "nyctaxi":
            return {"error": "ValueError: boom"}
        if name == "gbdt":
            return {"timeout_s": cap_s, "error": "wall cap"}
        return {"samples_per_s_per_chip": 5.0}

    monkeypatch.setattr(bench, "_spawn_config", fake_spawn)
    rc, out = _run_main(bench, capsys)
    assert rc != 0
    assert out["failed"] == ["gbdt", "nyctaxi"]
    assert out["value"] is None and "boom" in out["error"]
    assert out["platform"] == "tpu"
    assert out["device"] == {"platform": "tpu", "kind": "TPU v5 lite",
                             "count": 1}
    assert out["extra"]["keras"]["samples_per_s_per_chip"] == 5.0


def test_cpu_run_only_when_asked_and_labelled_cpu(bench, monkeypatch, capsys):
    """JAX_PLATFORMS=cpu in the caller's environment is the one way onto the
    CPU shapes: no probe, and every entry says cpu."""
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    monkeypatch.setenv("BENCH_CONFIGS", "nyctaxi")
    monkeypatch.setattr(bench, "_probe_device",
                        lambda: pytest.fail("a CPU run does not probe"))
    monkeypatch.setattr(bench, "_spawn_config",
                        lambda name, cap_s: {"samples_per_s_per_chip": 9.0})
    rc, out = _run_main(bench, capsys)
    assert rc == 0
    assert out["platform"] == "cpu" and "device" not in out
    assert out["extra"]["nyctaxi"]["platform"] == "cpu"


def test_failing_child_end_to_end_and_parent_stays_off_jax(tmp_path):
    """Through the real subprocess path: a config child that raises makes
    bench.py exit non-zero after printing its JSON line, and the parent
    process never imports jax (one process owns the chip)."""
    code = (
        "import runpy, sys\n"
        "try:\n"
        "    runpy.run_path('bench.py', run_name='__main__')\n"
        "except SystemExit as e:\n"
        "    rc = e.code\n"
        "assert 'jax' not in sys.modules, 'parent imported jax'\n"
        "sys.exit(rc)\n")
    env = dict(os.environ, JAX_PLATFORMS="cpu", BENCH_CONFIGS="no_such_config",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "jc"),
               RDT_BENCH_DETAIL_PATH=str(tmp_path / "detail.json"))
    proc = subprocess.run([sys.executable, "-c", code], cwd=REPO, env=env,
                          capture_output=True, text=True, timeout=120)
    assert proc.returncode == 1, proc.stderr[-2000:]
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    assert out["failed"] == ["no_such_config"] and out["platform"] == "cpu"


def test_unknown_device_kind_has_no_peak(bench):
    """MFU against a guessed peak is worse than no MFU: an unlisted device is
    an error that names it."""
    class Dev:
        device_kind = "TPU v99 imaginary"

    with pytest.raises(RuntimeError, match="TPU v99 imaginary"):
        bench._peak_flops(Dev())
    Dev.device_kind = "TPU v5 lite"
    assert bench._peak_flops(Dev()) == 197e12


class _FakeProc:
    """Popen stub: first communicate may raise TimeoutExpired; the retry
    returns whatever stdout the child had printed before the kill."""

    pid = 4242
    returncode = 0

    def __init__(self, stdout, timeout_first=False):
        self._stdout = stdout
        self._timeout_first = timeout_first

    def communicate(self, timeout=None):
        import subprocess
        if self._timeout_first:
            self._timeout_first = False
            raise subprocess.TimeoutExpired(cmd="fake", timeout=timeout)
        return self._stdout, ""


def test_spawn_config_last_marker_line_wins(bench, monkeypatch):
    """Configs checkpoint partial matrices as marker lines; the final
    (most complete) line is the result."""
    lines = (bench.RESULT_MARK + json.dumps({"flash": 1}) + "\n"
             + bench.RESULT_MARK + json.dumps({"flash": 1, "dense": 2}) + "\n")
    monkeypatch.setattr(bench.subprocess, "Popen",
                        lambda *a, **k: _FakeProc(lines))
    out = bench._spawn_config("transformer", 60.0)
    assert out == {"flash": 1, "dense": 2}


def test_spawn_config_salvages_partial_on_cap_kill(bench, monkeypatch):
    """A cap kill mid-config keeps the entries measured before the stall
    (code-review r5: a fused2 compile stall must not erase flash/dense)."""
    lines = bench.RESULT_MARK + json.dumps({"flash": {"mfu": 0.59}}) + "\n"
    monkeypatch.setattr(bench.subprocess, "Popen",
                        lambda *a, **k: _FakeProc(lines, timeout_first=True))
    monkeypatch.setattr(bench, "_kill_group", lambda proc: None)
    out = bench._spawn_config("transformer", 60.0)
    assert out["flash"] == {"mfu": 0.59}
    assert out["partial"] is True and out["timeout_s"] == 60.0


def test_spawn_config_crashed_child_after_marker_tagged_partial(bench,
                                                                monkeypatch):
    """A child that dies AFTER printing a checkpoint marker must not read as
    a clean result — incremental checkpoints broke the old any-marker=success
    invariant, so the non-timeout path checks returncode."""
    lines = bench.RESULT_MARK + json.dumps({"flash": {"mfu": 0.59}}) + "\n"
    proc = _FakeProc(lines)
    proc.returncode = 137
    monkeypatch.setattr(bench.subprocess, "Popen", lambda *a, **k: proc)
    out = bench._spawn_config("transformer", 60.0)
    assert out["flash"] == {"mfu": 0.59}
    assert out["partial"] is True and "died rc=137" in out["error"]


def test_stdout_line_fits_driver_tail_and_detail_file_is_full(bench,
                                                              monkeypatch,
                                                              capsys,
                                                              tmp_path):
    """A driver that stores only the last 2000 chars of stdout parses the
    final line out of THAT. The stdout line must stay compact no matter how
    big the per-config results get; the full record goes to
    BENCH_DETAIL.json."""
    monkeypatch.setenv("BENCH_CONFIGS", "nyctaxi,transformer,gang")

    big = {"sweep": {str(w): {"samples_per_s": w * 1000.0,
                              "note": "x" * 400} for w in (1, 2, 4)},
           "scaling": {"1": 1.0, "2": 0.6, "4": 0.4},
           "collective_mechanism_ratio": 1.2}

    def fake_spawn(name, cap_s):
        if name == "nyctaxi":
            return {"samples_per_s_per_chip": 1000.0, "pad": "y" * 800}
        if name == "transformer":
            return {"flash": {"tokens_per_s": 83000.0, "mfu": 0.59,
                              "seq_len": 8192, "pad": "z" * 800},
                    "dense": {"tokens_per_s": 1000.0, "seq_len": 4096},
                    "flash_fused2": {"tokens_per_s": 80000.0, "mfu": 0.57,
                                     "seq_len": 8192}}
        return dict(big)

    monkeypatch.setattr(bench, "_spawn_config", fake_spawn)

    assert bench.main() == 0
    line = capsys.readouterr().out.strip().splitlines()[-1]
    assert len(line) <= 1900, len(line)
    out = json.loads(line)
    assert out["value"] == 1000.0 and out["metric"]
    assert out["extra"]["transformer"]["flash"]["mfu"] == 0.59
    assert out["extra"]["transformer"]["flash_fused2"]["tok_s"] == 80000.0
    assert out["extra"]["gang"]["mechanism_ratio"] == 1.2

    detail = json.loads((tmp_path / "BENCH_DETAIL.json").read_text())
    assert detail["extra"]["nyctaxi"]["pad"] == "y" * 800   # nothing lost
    assert detail["value"] == 1000.0
