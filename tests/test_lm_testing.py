"""The LM family tests' shared helpers (``tests/lm_testing.py``) and the
harness of a run (``tests/conftest.py``): its one compile cache, the session
a file's tests share, and which files go to the workers a test at a time."""

import os
import types

import numpy as np
import pytest

from tests import conftest, lm_testing
from tests.lm_testing import F32_TOL, ROOT


def _model(**changed):
    from raydp_tpu.models import TransformerLM
    return TransformerLM(**{**dict(
        vocab_size=32, dim=16, num_heads=2, num_layers=1, num_experts=4,
        experts_per_token=2, ffn_dim=8, attention="dense"), **changed})


TOKENS = np.random.default_rng(0).integers(0, 32, (2, 8), dtype=np.int32)


def test_variables_are_initialised_once_and_copied_for_each_caller(
        monkeypatch):
    """The same model, shape and seed: one ``model.init``, equal trees, and
    a caller that writes into its copy leaves the next caller's alone."""
    from raydp_tpu.models import TransformerLM
    calls = []
    init = TransformerLM.init
    monkeypatch.setattr(TransformerLM, "init", lambda self, *a, **kw: (
        calls.append(self), init(self, *a, **kw))[1])
    model = _model(dim=24)          # a model no other test of this run builds
    first, state = lm_testing.variables(model, TOKENS)
    assert state is None and len(calls) == 1
    kept = {k: v.copy() for k, v in lm_testing.leaves(first).items()}
    first["embed"]["embedding"][:] = 7.0
    again, _ = lm_testing.variables(_model(dim=24), TOKENS[:1])
    assert len(calls) == 1
    got = lm_testing.leaves(again)
    assert set(got) == set(kept)
    for name in kept:
        np.testing.assert_array_equal(got[name], kept[name], name)
    assert not np.all(again["embed"]["embedding"] == 7.0)


def test_two_seeds_give_two_trees():
    a, _ = lm_testing.variables(_model(), TOKENS, seed=0)
    b, _ = lm_testing.variables(_model(), TOKENS, seed=1)
    assert all(np.abs(x - y).max() > 1e-3 for x, y in zip(
        lm_testing.leaves(a).values(), lm_testing.leaves(b).values())
        if x.ndim > 1)


def test_the_jitted_loss_and_logits_are_the_eager_ones():
    """``loss_and_grads`` and ``logits`` against the calls the family files
    made op by op, within their ``F32_TOL`` rule."""
    import jax
    model = _model()
    params, state = lm_testing.variables(model, TOKENS)
    w = np.full(2, 0.5, np.float32)
    (loss, counts), grads = lm_testing.loss_and_grads(model, params, state,
                                                      TOKENS, w)
    (want_loss, want_counts), want_grads = jax.value_and_grad(
        lambda p: model.apply({"params": p}, TOKENS, TOKENS, w,
                              method=model.loss_rows), has_aux=True)(params)
    assert abs(float(loss) - float(want_loss)) <= F32_TOL * float(want_loss)
    np.testing.assert_array_equal(counts, want_counts)
    lm_testing.close(grads, want_grads)
    np.testing.assert_allclose(
        lm_testing.logits(model, {"params": params}, TOKENS),
        model.apply({"params": params}, TOKENS), rtol=10 * F32_TOL,
        atol=10 * F32_TOL)
    with pytest.raises(AssertionError, match=r"^\w+/"):    # the leaf's name
        lm_testing.close(grads, jax.tree.map(lambda g: g + 1.0, want_grads))


def test_a_references_program_is_built_once_whatever_the_programs_options():
    cfg, _, _ = lm_testing.files("olmoe-1b-7b", {"layers": 2})
    a = lm_testing.reference_program("olmoe-1b-7b", cfg, "loss", grad=True)
    b = lm_testing.reference_program(
        "olmoe-1b-7b", dict(cfg, attention="flash", remat_blocks=True),
        "loss", grad=True)
    assert a is b
    assert lm_testing.reference_program(
        "olmoe-1b-7b", dict(cfg, layers=1), "loss", grad=True) is not a


def test_a_run_has_one_compile_cache_and_a_worker_makes_none(
        tmp_path, monkeypatch):
    """The controller (or the lone process) makes the run's directory, new
    and empty, under the run's temporary directory and never the checkout's
    ``.jax_cache``, whatever the environment named before; the workers it
    starts inherit the name and make nothing; it is gone at the end."""
    from raydp_tpu.utils import COMPILE_CACHE_ENV
    assert conftest.COMPILE_CACHE_ENV == COMPILE_CACHE_ENV
    monkeypatch.setattr(conftest.tempfile, "tempdir", str(tmp_path))
    controller = {COMPILE_CACHE_ENV: os.path.join(ROOT, ".jax_cache")}
    made = conftest.run_compile_cache(controller)
    assert controller[COMPILE_CACHE_ENV] == made
    assert os.path.dirname(made) == str(tmp_path) and os.listdir(made) == []
    workers = [dict(controller, PYTEST_XDIST_WORKER=f"gw{i}")
               for i in range(2)]
    for worker in workers:
        before = dict(worker)
        assert conftest.run_compile_cache(worker) is None
        assert worker == before and worker[COMPILE_CACHE_ENV] == made
    assert os.listdir(tmp_path) == [os.path.basename(made)]
    # a second run of the same checkout: another directory, empty
    again = conftest.run_compile_cache({})
    assert again != made and os.listdir(again) == []
    monkeypatch.setattr(conftest, "_cache_made", made)
    conftest.pytest_unconfigure(None)
    assert not os.path.exists(made)
    # this very run: not the checkout's, and the one its workers share
    here = os.environ[COMPILE_CACHE_ENV]
    assert os.path.isdir(here) and ".jax_cache" not in here


class _Session:
    """What ``shared_session`` asks of a session."""

    def __init__(self):
        self.frames = []

    def cached_frames(self):
        return list(self.frames)

    def release_cached(self, frame_id):
        self.frames.remove(frame_id)


def test_a_files_tests_share_one_session_and_leave_it_as_they_found_it(
        monkeypatch):
    """One session for two tests of a module, what the first persisted gone
    for the second; a test that needs the process's one session for itself
    stops it and the next test starts another; the module's end stops it, and
    the next module starts its own."""
    import raydp_tpu
    started, stopped = [], []
    monkeypatch.setattr(conftest, "_start_session", lambda: (
        started.append(_Session()), started[-1])[1])
    monkeypatch.setattr(raydp_tpu, "stop", lambda: stopped.append(1))
    shared_session = conftest.shared_session.__wrapped__
    module = conftest._module_session.__wrapped__()
    holder = module.send(None)
    assert started == []                    # nothing before the first asks

    first = shared_session(holder)
    session = first.send(None)
    session.frames += ["f1", "f2"]          # what a test persists
    with pytest.raises(StopIteration):
        first.send(None)
    second = shared_session(holder)
    assert second.send(None) is session and session.cached_frames() == []
    with pytest.raises(StopIteration):
        second.send(None)
    assert len(started) == 1 and stopped == []

    conftest.no_session.__wrapped__(holder)     # a test with its own
    assert stopped == [1]
    conftest.no_session.__wrapped__(holder)     # and one more: nothing to stop
    assert stopped == [1]
    third = shared_session(holder)
    assert third.send(None) is started[1] is not session
    with pytest.raises(StopIteration):
        module.send(None)
    assert stopped == [1, 1]
    other = conftest._module_session.__wrapped__()
    assert shared_session(other.send(None)).send(None) is started[2]


class _Node:
    """What the scheduler asks of a worker."""
    shutting_down = False

    def __init__(self, name):
        self.sent, self.gateway = [], types.SimpleNamespace(id=name)

    def send_runtest_some(self, indices):
        self.sent.append(list(indices))


SPLIT = "tests/chipbench_contract/test_chipbench_run.py"
SHARED = "tests/chipbench_contract/test_chipbench_pieces.py"


@pytest.mark.parametrize("path,singly", [
    (SPLIT, True), (SHARED, False),                 # a module-scoped fixture
    ("tests/test_swa_moe_lm.py", False)])           # programs kept a worker
def test_a_contract_file_without_a_shared_fixture_leaves_a_test_at_a_time(
        path, singly):
    assert conftest.split_by_test(path) is singly
    units = {conftest.split_scope(f"{path}::test_a[{i}]") for i in range(3)}
    assert len(units) == (3 if singly else 1)
    assert conftest.split_scope(f"{path}::TestX::test_b") == (
        f"{path}::TestX::test_b" if singly else path)


def test_the_scheduler_sends_whole_files_by_count_then_contract_tests_singly():
    """No table of seconds: the units leave in xdist's own order, most tests
    first, so the single tests of the contract files follow the whole files
    as they were collected; under another ``--dist`` the hook leaves the
    choice to xdist."""
    assert not hasattr(conftest, "FILE_SECONDS")
    option = {"dist": "loadfile", "tx": ["2*popen"]}
    config = types.SimpleNamespace(
        getvalue=option.get, option=types.SimpleNamespace(
            loadscopereorder=True))
    scheduler = conftest.pytest_xdist_make_scheduler(config, None)
    collection = [f"{SPLIT}::test_a[x]", f"{SPLIT}::test_a[y]",
                  f"{SHARED}::test_a", f"{SHARED}::test_b",
                  "tests/test_etl.py::test_a",
                  "tests/test_serve.py::test_a", "tests/test_serve.py::test_b",
                  "tests/test_serve.py::test_c"]
    nodes = [_Node("gw0"), _Node("gw1")]
    for node in nodes:
        scheduler.add_node(node)
        scheduler.add_node_collection(node, collection)
    scheduler.schedule()
    sent = [[collection[i] for i in batch] for node in nodes
            for batch in node.sent]
    # the files by their count of tests, then the units of one test as they
    # were collected (a node is sent more once it holds two tests or fewer)
    assert sent == [["tests/test_serve.py::test_a",
                     "tests/test_serve.py::test_b",
                     "tests/test_serve.py::test_c"],
                    [f"{SHARED}::test_a", f"{SHARED}::test_b"],
                    [f"{SPLIT}::test_a[x]"]]
    assert conftest.pytest_xdist_make_scheduler(types.SimpleNamespace(
        getvalue={"dist": "load"}.get), None) is None
