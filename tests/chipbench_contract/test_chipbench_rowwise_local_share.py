"""``rowwise_local_share``: the reader on hand-made counters and on nothing (as
on a program without the counter), and the entry where the manifest lists it."""

import os

import pytest

from chipbench import manifest

M = manifest.load_manifest()
NAME = "rowwise_local_share"
#: the cells whose model declares tables: the only ones with a walk to count
CELLS = ["dlrm_criteo_stream", "dlrm_criteo_dp2ep2"]


def _reader():
    return manifest.load_module(manifest.ROOT, "layer_metrics", f"{NAME}.py")


@pytest.mark.parametrize("tables,expected", [
    ({"shard_local": 10}, 100.0),                  # four chips: all ten
    ({"shard_local": 20}, 100.0),                  # and a calibration fit
    ({"shard_local": 5, "global": 5}, 50.0),
    ({"global": 10}, 0.0),                         # one chip: nothing to split
])
def test_reader_on_hand_made_counters(tables, expected):
    run = {"counters": {"train_table_walk_total": tables,
                        "train_table_updates_total": {"rowwise": 10,
                                                      "dense": 16}}}
    assert _reader().read(run) == pytest.approx(expected)


@pytest.mark.parametrize("counters", [
    {}, {"train_table_updates_total": {"rowwise": 10, "dense": 16}},
    {"train_table_walk_total": {}}],
    ids=["no_counters", "parent_of_the_counter", "no_rowwise_table"])
def test_reader_that_finds_nothing_says_nothing(counters):
    assert _reader().read({"counters": counters}) is None


def test_the_entry_is_present_once_and_lists_the_cells_with_tables():
    """Where in ``per_layer`` it stands is a later PR's to change."""
    assert manifest.validate(M) == []
    (entry,) = [m for m in M["per_layer"] if m["name"] == NAME]
    assert entry == {"name": NAME, "unit": "%", "better": "higher",
                     "source": "program_counter", "layer": "model",
                     "moves": "train_throughput", "workloads": CELLS}
    assert os.path.isfile(os.path.join(
        manifest.ROOT, manifest.BENCH_DIR, "layer_metrics", f"{NAME}.py"))


@pytest.mark.parametrize("cell", [w["name"] for w in M["workloads"]])
def test_it_resolves_in_the_cells_it_lists_and_in_no_other(cell):
    resolved = manifest.resolve(M, cell)
    listed = cell in CELLS
    assert (NAME in resolved.readers) == listed
    assert (NAME in [m["name"] for m in resolved.per_layer]) == listed
    if listed:
        assert "train_throughput" in {m["name"] for m in resolved.end_to_end}


def test_counter_the_reader_reads_is_the_programs():
    from raydp_tpu import metrics

    m = metrics.METRICS["train_table_walk_total"]
    assert (m.kind, m.label) == (metrics.COUNTER, "path")
