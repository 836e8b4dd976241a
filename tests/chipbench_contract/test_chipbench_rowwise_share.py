"""``rowwise_table_share``: the reader on synthetic counters and on nothing (as
on a program without the counter), and its entry in the manifest: present,
once, with its reader, in the cells it lists."""

import os

import pytest

from chipbench import manifest

M = manifest.load_manifest()
NAME = "rowwise_table_share"
#: the cells of the configuration whose model declares its lookups
DLRM_CELLS = [w["name"] for w in M["workloads"]
              if w["config"] == "raydp-criteo-dlrm"]


def _reader():
    return manifest.load_module(manifest.ROOT, "layer_metrics", f"{NAME}.py")


@pytest.mark.parametrize("tables,expected", [
    ({"rowwise": 10, "dense": 16}, 100 * 10 / 26),    # the DLRM with Adagrad
    ({"rowwise": 20, "dense": 32}, 100 * 10 / 26),    # and a calibration fit
    ({"rowwise": 26}, 100.0),
    ({"dense": 26}, 0.0),                             # the DLRM with Adam
])
def test_reader_on_synthetic_counters(tables, expected):
    run = {"counters": {"train_table_updates_total": tables,
                        "feed_pulls_total": {"ready": 9, "empty": 1}}}
    assert _reader().read(run) == pytest.approx(expected)


@pytest.mark.parametrize("counters", [
    {}, {"feed_pulls_total": {"ready": 9, "empty": 1}},
    {"train_table_updates_total": {}}],
    ids=["no_counters", "parent_of_the_counter", "no_declared_table"])
def test_reader_that_finds_nothing_says_nothing(counters):
    assert _reader().read({"counters": counters}) is None


def test_the_entry_is_present_once_with_its_reader():
    """Where in ``per_layer`` it stands and how long the list is are later
    PRs' to change."""
    assert manifest.validate(M) == []
    (entry,) = [m for m in M["per_layer"] if m["name"] == NAME]
    # it lists the cells whose model declares tables (a metric with no list
    # has to print in every cell, and an LM declares none)
    assert entry == {"name": NAME, "unit": "%", "better": "higher",
                     "source": "program_counter", "layer": "model",
                     "moves": "train_throughput", "workloads": DLRM_CELLS}
    assert DLRM_CELLS == ["dlrm_criteo_stream", "dlrm_criteo_dp2ep2"]
    assert os.path.isfile(os.path.join(
        manifest.ROOT, manifest.BENCH_DIR, "layer_metrics", f"{NAME}.py"))


@pytest.mark.parametrize("cell", [w["name"] for w in M["workloads"]])
def test_it_resolves_in_the_cells_it_lists_and_in_no_other(cell):
    resolved = manifest.resolve(M, cell)
    listed = cell in DLRM_CELLS
    assert (NAME in resolved.readers) == listed
    assert (NAME in [m["name"] for m in resolved.per_layer]) == listed
    if listed:
        assert "train_throughput" in {m["name"] for m in resolved.end_to_end}


def test_counter_the_reader_reads_is_the_programs():
    from raydp_tpu import metrics

    m = metrics.METRICS["train_table_updates_total"]
    assert (m.kind, m.label) == (metrics.COUNTER, "path")
