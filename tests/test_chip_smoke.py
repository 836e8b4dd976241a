"""The chip smoke's contract off the chip, and the compile-cache helper it
shares with bench.py. What the smoke checks ON the chip only a chip run can
test; here: it refuses to run anywhere else, and it reads compiled HLO right.
"""

import importlib.util
import os
import shutil
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run_smoke(cwd, **env):
    return subprocess.run(
        [sys.executable, "chip_smoke.py"], cwd=cwd, capture_output=True,
        text=True, timeout=180, env=dict(os.environ, **env))


def test_chip_smoke_refuses_the_cpu_and_names_it():
    proc = _run_smoke(REPO, JAX_PLATFORMS="cpu")
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""            # no result line, no JSON
    assert "needs a TPU" in proc.stderr and "'cpu'" in proc.stderr


def test_chip_smoke_alone_in_a_directory_fails(tmp_path):
    """The script without the program proves nothing, and must say so."""
    shutil.copy(os.path.join(REPO, "chip_smoke.py"), tmp_path)
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    proc = subprocess.run([sys.executable, "chip_smoke.py"], cwd=tmp_path,
                          capture_output=True, text=True, timeout=180, env=env)
    assert proc.returncode != 0 and proc.stdout.strip() == ""
    assert "raydp_tpu" in proc.stderr


#: two of the three Mosaic calls of a compiled flash train step, as jax 0.9.0
#: / libtpu 0.0.34 print them on a TPU v5 lite (layouts and configs elided)
_HLO = '''
  %jvp__.1 = (bf16[16,8192,128]{2,1,0:T(8,128)(2,1)S(1)}, f32[16,1,8192]{2,1,0:T(1,128)S(1)}) custom-call(%bitcast.18, %bitcast.21, %bitcast.24), custom_call_target="tpu_custom_call", operand_layout_constraints={bf16[16,8192,128]{2,1,0}}, backend_config={}
  %transpose_jvp___.3 = bf16[16,8192,128]{2,1,0:T(8,128)(2,1)S(1)} custom-call(%bitcast.19, %bitcast.22), custom_call_target="tpu_custom_call", operand_layout_constraints={f32[16,1,8192]{2,1,0}}, backend_config={}
  %custom-call = bf16[2,1024,2,128]{3,2,1,0} custom-call(%a, %b), custom_call_target="ConcatBitcast"
'''


def test_kernel_calls_reads_the_mosaic_calls_out_of_compiled_hlo(
        monkeypatch, tmp_path):
    # importing the script places the compile cache; keep that out of the
    # environment the rest of the suite (and its child processes) inherits
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    spec = importlib.util.spec_from_file_location(
        "chip_smoke_under_test", os.path.join(REPO, "chip_smoke.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    assert mod.kernel_calls(_HLO) == [(("bf16", "f32"), 16), (("bf16",), 16)]


def test_compile_cache_dir_env_wins_else_fixed_path_in_the_checkout(
        monkeypatch, tmp_path):
    from raydp_tpu.utils import COMPILE_CACHE_ENV, compile_cache_dir

    placed = str(tmp_path / "placed-from-outside")
    monkeypatch.setenv(COMPILE_CACHE_ENV, placed)
    assert compile_cache_dir() == placed
    assert os.environ[COMPILE_CACHE_ENV] == placed
    assert not os.path.exists(placed)       # nothing is set or made in code

    monkeypatch.delenv(COMPILE_CACHE_ENV)
    want = os.path.join(REPO, ".jax_cache")
    # the same path on every call, in every process: never temp/pid/time
    assert compile_cache_dir() == want == os.environ[COMPILE_CACHE_ENV]
    monkeypatch.delenv(COMPILE_CACHE_ENV)
    assert compile_cache_dir() == want and os.path.isdir(want)


def test_compile_cache_is_configured_in_exactly_one_place():
    """``JAX_COMPILATION_CACHE_DIR`` / ``jax_compilation_cache_dir`` is set by
    the one helper; a second setter would move the cache under some runs."""
    hits = subprocess.run(
        ["git", "grep", "-l", "-i", "-e", "jax_compilation_cache_dir", "--",
         "*.py", ":!tests/"], cwd=REPO, capture_output=True, text=True)
    if hits.returncode not in (0, 1):
        pytest.skip("not a git checkout")
    assert hits.stdout.split() == ["raydp_tpu/utils.py"]
