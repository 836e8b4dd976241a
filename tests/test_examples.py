"""The entry points a user starts from (``examples/``), each run as the user
runs it: a script in a process of its own, on the CPU's 8-device mesh, from an
empty directory. A case passes when the script exits 0."""

import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

CASES = [
    "nyctaxi_mlp.py --rows 20000 --epochs 2 --batch-size 512 --trace",
    "gbdt_nyctaxi.py --rows 8000",
    "titanic_keras.py --rows 2000 --epochs 8",
    "keras_nyctaxi.py --rows 10000 --epochs 2 --batch-size 512",
    "stroke_pipeline.py --rows 6000 --epochs 4",
    "spmd_job.py",
    "torch_loop_nyctaxi.py --rows 10000 --epochs 2",
    # tables of odd cardinality over an expert extent of 2
    "dlrm_criteo.py --scale small --rows 4000 --epochs 1 --batch-size 512",
    "longcontext_lm.py --seq-len 512 --steps 5",
    # an evaluation set (a tenth of the rows) smaller than one batch
    "nyctaxi_mlp.py --rows 2000 --epochs 1 --batch-size 256",
]


@pytest.mark.parametrize("command", CASES, ids=lambda c: c.replace(" ", ""))
def test_example_runs_to_its_end(command, tmp_path):
    script, *args = command.split()
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=REPO,
               XLA_FLAGS="--xla_force_host_platform_device_count=8")
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "examples", script), *args],
        cwd=tmp_path, env=env, capture_output=True, text=True, timeout=150)
    assert proc.returncode == 0, proc.stdout[-2000:] + proc.stderr[-4000:]
