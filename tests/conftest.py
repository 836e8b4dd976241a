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

# asserts in the LM families' shared helpers read like a test's own
pytest.register_assert_rewrite("tests.lm_testing")

# Under ``--dist loadfile`` a file is one unit of work, and xdist hands the
# units out by their count of tests: a long file of few tests starts late and
# the run waits for it (``test_chipbench_run.py``: 26 tests, on one worker
# 1,174 s of the parent's 1,618 s). Here the units leave by the seconds
# measured for their files, longest first (the unlisted after them, in
# xdist's order), and a file
# of ``SPLIT_BY_TEST`` a TEST at a time: it may stand there if it has no
# module- or class-scoped fixture and its tests share no state, and is worth
# it only above about a sixth of the run's wall (a split file pays its
# imports and traced programs once a worker). The seconds are a file's tests
# summed in a whole run of the driver's command (six workers on eight busy
# cores, PR 51's sandbox): list a new file that takes over ~200 s.
FILE_SECONDS = {
    "tests/chipbench_contract/test_chipbench_run.py": 1568,
    "tests/test_swa_moe_lm.py": 707,
    "tests/test_afmoe_lm.py": 637,
    "tests/chipbench_contract/test_chipbench_host_spans.py": 594,
    "tests/test_ssm_moe_lm.py": 462,
    "tests/test_rowwise_tables.py": 322,
    "tests/test_examples.py": 270,
    "tests/test_chaos.py": 265,
    "tests/chipbench_contract/test_chipbench_trinity_mini.py": 248,
    "tests/chipbench_contract/test_chipbench_kanana_2.py": 243,
    "tests/chipbench_contract/test_chipbench_smallthinker.py": 228,
    "tests/chipbench_contract/test_chipbench_nemotron_3_nano.py": 180,
}
SPLIT_BY_TEST = {
    "tests/chipbench_contract/test_chipbench_run.py",
    "tests/chipbench_contract/test_chipbench_host_spans.py",
}


def split_scope(nodeid):
    """The unit of work a test belongs to: itself in a file of
    ``SPLIT_BY_TEST``, its file otherwise."""
    path = nodeid.split("::", 1)[0]
    return nodeid if path in SPLIT_BY_TEST else path


def file_seconds(scope):
    return FILE_SECONDS.get(scope.split("::", 1)[0], 0)


@pytest.hookimpl(optionalhook=True)     # no such hook under ``-p no:xdist``
def pytest_xdist_make_scheduler(config, log):
    if config.getvalue("dist") != "loadfile":
        return None
    from xdist.scheduler.loadfile import LoadFileScheduling

    class LongestFileFirst(LoadFileScheduling):
        def _split_scope(self, nodeid):
            return split_scope(nodeid)

        def _assign_work_unit(self, node):
            # the first of the longest: ties keep xdist's order
            self.workqueue.move_to_end(
                max(self.workqueue, key=file_seconds), last=False)
            super()._assign_work_unit(node)

    return LongestFileFirst(config, log)


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
