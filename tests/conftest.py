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
# XLA:CPU's "machine feature ... not supported" on every executable it loads
# from the cache is an error-level line of 3 kB: silenced with the rest
os.environ.setdefault("TF_CPP_MIN_LOG_LEVEL", "3")

import functools  # noqa: E402
import re  # noqa: E402
import shutil  # noqa: E402
import tempfile  # noqa: E402

import pytest  # noqa: E402

# jax's name for its cache directory, as raydp_tpu.utils.COMPILE_CACHE_ENV
# has it (``test_lm_testing`` holds the two equal): importing the package
# here could import jax before the variables set above and in
# ``pytest_configure`` are read
COMPILE_CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"


def run_compile_cache(environ=os.environ):
    """ONE compile cache a run: the xdist controller (or the lone process)
    makes a new directory under the run's temporary one and names it in the
    environment, where the workers, the executors and every subprocess find
    it; a worker makes none. Never the checkout's ``.jax_cache`` nor a
    directory an earlier run filled: a run's seconds do not depend on what
    ran before it. Returns the directory where this process made it."""
    if "PYTEST_XDIST_WORKER" in environ:
        return None
    made = tempfile.mkdtemp(prefix="rdt-compile-cache-")
    environ[COMPILE_CACHE_ENV] = made
    # what took under half a second to compile costs more as a file than it
    # saves (measured: CHANGES.md, PR 54)
    environ["JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"] = "0.5"
    return made


_cache_made = None


def pytest_configure(config):
    # not at import: ``from tests import conftest`` is a second copy of this
    # module. Still before jax is imported (by a test file, at collection)
    # and before the controller starts its workers (at the session's start)
    global _cache_made
    _cache_made = run_compile_cache()


def pytest_unconfigure(config):
    if _cache_made:
        shutil.rmtree(_cache_made, ignore_errors=True)


# asserts in the LM families' shared helpers read like a test's own
pytest.register_assert_rewrite("tests.lm_testing")

# Under ``--dist loadfile`` a file is one unit of work and xdist hands the
# units out by their count of tests, most first. The files of
# ``tests/chipbench_contract/`` hold the longest single tests of the run (a
# cell's rehearsal, a chip-free compile: one to four minutes each under six
# workers) in files of few tests, which as whole units would start late and
# the run would wait for them: their tests leave one at a time instead, after
# the whole files (a unit of one test sorts last), where they fill the
# workers evenly to the end. There is no table of seconds to keep. A file
# may be split if its tests share nothing a worker would build again: no
# fixture wider than a test. That is read from the file's own source (the
# controller schedules node ids and holds no fixtures): a wide fixture that an
# import or a ``conftest.py`` of that directory brought in would not be seen,
# and the directory has neither.
SPLIT_DIR = "tests/chipbench_contract/"
_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_WIDER_THAN_A_TEST = re.compile(
    r"scope\s*=\s*[\"'](module|class|package|session)"
    r"|def (setup|teardown)_(module|class)\b")


@functools.lru_cache(maxsize=None)
def split_by_test(path):
    """Whether the tests of the collected file ``path`` leave one at a time."""
    if not path.startswith(SPLIT_DIR):
        return False
    with open(os.path.join(_ROOT, path)) as f:
        return not _WIDER_THAN_A_TEST.search(f.read())


def split_scope(nodeid):
    """The unit of work a test belongs to: itself in a file that
    ``split_by_test``, its file otherwise."""
    path = nodeid.split("::", 1)[0]
    return nodeid if split_by_test(path) else path


@pytest.hookimpl(optionalhook=True)     # no such hook under ``-p no:xdist``
def pytest_xdist_make_scheduler(config, log):
    if config.getvalue("dist") != "loadfile":
        return None
    from xdist.scheduler.loadfile import LoadFileScheduling

    class ContractTestsOneAtATime(LoadFileScheduling):
        def _split_scope(self, nodeid):
            return split_scope(nodeid)

    return ContractTestsOneAtATime(config, log)


class _ModuleSession:
    """The 2-executor ETL session that the tests of one file share, started
    at the first ``shared_session`` of the file and again after a test that
    took ``no_session``."""
    running = None

    def start(self):
        if self.running is None:
            self.running = _start_session()
        return self.running

    def stop(self):
        if self.running is not None:
            import raydp_tpu
            self.running = None
            raydp_tpu.stop()


def _start_session():
    import raydp_tpu
    return raydp_tpu.init("pytest", num_executors=2, executor_cores=1,
                          executor_memory="512MB")


@pytest.fixture(scope="module")
def _module_session():
    shared = _ModuleSession()
    yield shared
    shared.stop()


@pytest.fixture
def shared_session(_module_session):
    """The file's one session, for a test that only reads through it. What
    the test persisted is released at its end, so the file's last test sees
    the store its first saw. A test keeps ``session`` if it kills or
    restarts an actor, resizes or stops the session, must start its
    executors under an environment of its own, or asserts on executor-side
    counters or store contents that another test of the file moves."""
    s = _module_session.start()
    yield s
    for frame_id in s.cached_frames():
        s.release_cached(frame_id)


@pytest.fixture
def no_session(_module_session):
    """A process holds one session (or runtime) at a time: the file's shared
    one is stopped, for a test that starts its own in its body; the next
    ``shared_session`` starts it again."""
    _module_session.stop()


@pytest.fixture
def runtime(no_session):
    """A bare actor runtime (no ETL session), torn down after the test."""
    from raydp_tpu.runtime import init_runtime, shutdown_runtime

    rt = init_runtime()
    yield rt
    shutdown_runtime()


@pytest.fixture
def runtime_3nodes(no_session):
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
def session(no_session):
    """A 2-executor ETL session of the test's own (parity: conftest.py
    spark_on_ray_2_executors)."""
    import raydp_tpu

    yield _start_session()
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
