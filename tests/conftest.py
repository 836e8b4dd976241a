"""Test harness.

Parity with the reference's strategy (SURVEY.md §4): real local runtime, simulated
multi-host topology, kill-based fault injection. The JAX analogue of
``ray.cluster_utils.Cluster`` is a virtual 8-device CPU mesh: we force the host
platform before anything imports jax (must happen at conftest import time).
"""

import os

os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8").strip()
os.environ.setdefault("TF_CPP_MIN_LOG_LEVEL", "3")

import pytest  # noqa: E402


@pytest.fixture
def runtime():
    """A bare actor runtime (no ETL session), torn down after the test."""
    from raydp_tpu.runtime import init_runtime, shutdown_runtime

    rt = init_runtime()
    yield rt
    shutdown_runtime()


@pytest.fixture
def runtime_3nodes():
    """Three virtual nodes for placement/fault tests
    (parity: test_spark_cluster.py:90-110 heterogeneous virtual nodes)."""
    from raydp_tpu.runtime import init_runtime, shutdown_runtime

    rt = init_runtime(virtual_nodes=[
        {"CPU": 4.0, "memory": float(2 << 30)},
        {"CPU": 4.0, "memory": float(2 << 30)},
        {"CPU": 4.0, "memory": float(2 << 30), "accel": 1.0},
    ])
    yield rt
    shutdown_runtime()


@pytest.fixture
def session():
    """A 2-executor ETL session (parity: conftest.py spark_on_ray_2_executors)."""
    import raydp_tpu

    s = raydp_tpu.init("pytest", num_executors=2, executor_cores=1,
                       executor_memory="512MB")
    yield s
    raydp_tpu.stop()


@pytest.fixture
def forward_flash_kernels(monkeypatch):
    """A model's ``attention="flash"`` runs its Pallas kernels here,
    interpreted, in blocks of 16 (off the chip the op would take its jnp
    path); the fixture counts the forward kernels, full and windowed, in a
    program's jaxpr."""
    import functools
    import re

    from raydp_tpu.ops import flash_attention as fa

    monkeypatch.setattr(fa, "flash_attention", functools.partial(
        fa.flash_attention, interpret=True, block_q=16, block_k=16))
    return lambda program: len(re.findall(
        r"\bname=rdt_flash(?:_win)?_fwd\b", str(program)))


@pytest.fixture
def grouped_products():
    """Counts the grouped products (``jax.lax.ragged_dot`` and its two
    transposes: one primitive) in a traced program, through every loop,
    checkpoint and call it holds. The printed text would not do: it prints
    a body that several equations share once."""
    def count(jaxpr):
        jaxpr = getattr(jaxpr, "jaxpr", jaxpr)
        found = 0
        for eqn in jaxpr.eqns:
            found += eqn.primitive.name == "ragged_dot_general"
            for value in eqn.params.values():
                for inner in value if isinstance(value, (tuple, list)) \
                        else (value,):
                    if hasattr(getattr(inner, "jaxpr", inner), "eqns"):
                        found += count(inner)
        return found
    return count


@pytest.fixture
def policy_without_sublayer_out(monkeypatch):
    """Calling it leaves ``transformer.SUBLAYER_OUT`` out of every
    ``save_only_these_names`` policy built from then on: ``remat_blocks`` as
    it was before a recomputed block kept what its second norm reads."""
    import jax

    from raydp_tpu.models.transformer import SUBLAYER_OUT

    real = jax.checkpoint_policies.save_only_these_names

    def leave_out():
        monkeypatch.setattr(
            jax.checkpoint_policies, "save_only_these_names",
            lambda *names: real(*(n for n in names if n != SUBLAYER_OUT)))
    return leave_out
