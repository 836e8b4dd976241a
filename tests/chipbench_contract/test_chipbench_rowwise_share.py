"""``rowwise_table_share``: the reader on synthetic counters and on nothing (as
on a program without the counter), and the manifest grown by that one entry."""

import os

import pytest

from chipbench import manifest

M = manifest.load_manifest()
NAME = "rowwise_table_share"
#: the per-layer list as the PR before this one left it, in its order
BEFORE = ["etl_wall_s", "fit_startup_s", "fit_overhead_s", "feed_decode_share",
          "native_staged_share", "feed_wait_share", "h2d_share",
          "dispatch_share", "final_save_s", "collective_share",
          "model_flops_util", "device_idle_share", "fit_convert_s",
          "fit_state_s", "fit_epoch0_s", "fit_unattributed_s", "ckpt_d2h_s",
          "ckpt_import_s", "ckpt_write_s", "idle_feed_wait_share",
          "idle_dispatch_share", "idle_epoch_end_share", "feed_starved_share"]


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


def test_manifest_grew_by_the_one_entry_at_its_end():
    assert manifest.validate(M) == []
    names = [m["name"] for m in M["per_layer"]]
    assert names[:len(BEFORE)] == BEFORE
    assert names[len(BEFORE)] == NAME and len(names) == len(set(names))
    entry = M["per_layer"][len(BEFORE)]
    assert entry == {"name": NAME, "unit": "%", "better": "higher",
                     "source": "program_counter", "layer": "model",
                     "moves": "train_throughput",
                     "workloads": ["dlrm_criteo_stream",
                                   "dlrm_criteo_dp2ep2"]}
    assert os.path.isfile(os.path.join(
        manifest.ROOT, manifest.BENCH_DIR, "layer_metrics", f"{NAME}.py"))


@pytest.mark.parametrize("cell", [w["name"] for w in M["workloads"]])
def test_every_cell_reads_it(cell):
    resolved = manifest.resolve(M, cell)
    assert NAME in resolved.readers
    assert NAME in [m["name"] for m in resolved.per_layer]
    moved = {m["name"] for m in resolved.end_to_end}
    assert "train_throughput" in moved


def test_counter_the_reader_reads_is_the_programs():
    from raydp_tpu import metrics

    m = metrics.METRICS["train_table_updates_total"]
    assert (m.kind, m.label) == (metrics.COUNTER, "path")
