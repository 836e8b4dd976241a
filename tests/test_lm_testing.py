"""The LM family tests' shared helpers (``tests/lm_testing.py``) and the
scheduler's list of files that go to the workers a test at a time
(``tests/conftest.py``)."""

import os
import re
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


@pytest.mark.parametrize("path", sorted(conftest.SPLIT_BY_TEST))
def test_a_file_split_by_test_exists_and_shares_no_fixture(path):
    """What lets a file's tests run in any process: no fixture wider than a
    test (and no ``setup_module`` / ``setup_class``)."""
    with open(os.path.join(ROOT, path)) as f:
        source = f.read()
    assert not re.search(r"scope\s*=\s*[\"'](module|class|package|session)",
                         source)
    assert not re.search(r"def (setup|teardown)_(module|class)\b", source)
    assert path in conftest.FILE_SECONDS


def test_every_file_the_scheduler_lists_exists():
    assert all(os.path.exists(os.path.join(ROOT, path))
               for path in conftest.FILE_SECONDS)


class _Node:
    """What the scheduler asks of a worker."""
    shutting_down = False

    def __init__(self, name):
        self.sent, self.gateway = [], types.SimpleNamespace(id=name)

    def send_runtest_some(self, indices):
        self.sent.append(list(indices))


def test_the_scheduler_splits_listed_files_and_hands_out_the_longest_first():
    """A listed file's tests are a unit of work each and any other file is
    one; the units leave by their files' measured seconds, the unlisted
    after them in xdist's own order (by their count of tests); under another
    ``--dist`` the hook leaves the choice to xdist."""
    split = sorted(conftest.SPLIT_BY_TEST)[0]
    longest = max(conftest.FILE_SECONDS, key=conftest.FILE_SECONDS.get)
    assert longest in conftest.SPLIT_BY_TEST
    whole = "tests/test_swa_moe_lm.py"
    assert whole in conftest.FILE_SECONDS
    assert len({conftest.split_scope(f"{split}::test_a[{i}]")
                for i in range(3)}) == 3
    assert conftest.split_scope(
        "tests/test_etl.py::TestFrame::test_b") == "tests/test_etl.py"

    option = {"dist": "loadfile", "tx": ["2*popen"]}
    config = types.SimpleNamespace(
        getvalue=option.get, option=types.SimpleNamespace(
            loadscopereorder=True))
    scheduler = conftest.pytest_xdist_make_scheduler(config, None)
    collection = ["tests/test_serve.py::test_a", "tests/test_serve.py::test_b",
                  "tests/test_etl.py::test_a", f"{whole}::test_a",
                  f"{whole}::test_b", f"{longest}::test_a[x]",
                  f"{longest}::test_a[y]"]
    nodes = [_Node("gw0"), _Node("gw1")]
    for node in nodes:
        scheduler.add_node(node)
        scheduler.add_node_collection(node, collection)
    scheduler.schedule()
    sent = [[collection[i] for i in batch] for node in nodes
            for batch in node.sent]
    # a node starts with two units where it can: the longest file's two
    # tests, then the next longest file whole, then the unlisted by count
    assert sent == [[f"{longest}::test_a[x]"], [f"{whole}::test_a",
                                               f"{whole}::test_b"],
                    [f"{longest}::test_a[y]"],
                    ["tests/test_serve.py::test_a",
                     "tests/test_serve.py::test_b"]]
    assert conftest.pytest_xdist_make_scheduler(types.SimpleNamespace(
        getvalue={"dist": "load"}.get), None) is None
