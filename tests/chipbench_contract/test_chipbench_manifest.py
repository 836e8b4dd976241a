"""BENCHMARK.json against the benchmark's contract, and the resolver that
finds a cell's files by name: a cell, a configuration and a per-layer metric
are each added as new files plus one manifest entry."""

import copy
import json
import os
import shutil

import pytest

from chipbench import manifest

REPO = manifest.ROOT


def test_manifest_is_sound():
    assert manifest.validate(manifest.load_manifest()) == []


def _break_unit(m):
    m["end_to_end"][0]["unit"] = "items per second"


def _break_name(m):
    m["workloads"][0]["name"] = "dlrm criteo,stream"


def _greek_unit(m):
    m["per_layer"][0]["unit"] = "μs"


def _second_four_chip_cell(m):
    m["workloads"][0]["chips"] = 4


def _moves_unreported(m):
    m["per_layer"][0]["moves"] = "ttft_p95_ms"


def _missing_file(m):
    m["per_layer"].append(dict(m["per_layer"][0], name="no_such_reader"))


def _loose_bound(m):
    m["end_to_end"][0]["bound"] = 0.2


def _width_reduced(m):
    m["configs"][0]["reduced"].append("embedding_dim")


def _no_setup(m):
    m["end_to_end"] = [e for e in m["end_to_end"] if e["name"] != "setup_s"]


def _extra_key(m):
    m["per_layer"][0]["why"] = "a metric takes no why"


def _long_why(m):
    m["workloads"][0]["why"] = "x" * 201


def _command_outside_paths(m):
    m["command"] = ["python3", "bench.py"]


@pytest.mark.parametrize("breaker", [
    _break_unit, _break_name, _greek_unit, _second_four_chip_cell,
    _moves_unreported, _missing_file, _loose_bound, _width_reduced,
    _no_setup, _extra_key, _long_why, _command_outside_paths],
    ids=lambda f: f.__name__.strip("_"))
def test_validate_refuses(breaker):
    m = copy.deepcopy(manifest.load_manifest())
    breaker(m)
    assert manifest.validate(m) != []


def test_every_cell_resolves_to_its_files():
    m = manifest.load_manifest()
    for entry in m["workloads"]:
        cell = manifest.resolve(m, entry["name"])
        assert cell.cfg["name"] == entry["config"]
        assert {x["name"] for x in cell.end_to_end} == {"setup_s",
                                                        "train_throughput"}
        assert set(cell.readers) == {x["name"] for x in cell.per_layer}
        assert all(callable(r.read) for r in cell.readers.values())
        for fn in ("generate", "etl", "build_estimator", "reference_inputs"):
            assert callable(getattr(cell.pipeline, fn))
        assert callable(cell.reference.forward) and cell.reference.TOLERANCE
        assert callable(cell.flops.train_flops_per_item)


def test_a_cell_a_config_and_a_metric_are_added_as_files(tmp_path):
    """Throw-away ones, in a copy: new files and one entry each in
    BENCHMARK.json; no existing file is edited."""
    root = str(tmp_path)
    bench = os.path.join(root, manifest.BENCH_DIR)
    shutil.copytree(os.path.join(REPO, manifest.BENCH_DIR), bench,
                    ignore=shutil.ignore_patterns("out", ".cache",
                                                  "__pycache__"))
    os.makedirs(os.path.join(root, "tests", "chipbench_contract"))
    before = {}
    for d, _, files in os.walk(bench):
        for f in files:
            p = os.path.join(d, f)
            before[p] = open(p, "rb").read()
    m = copy.deepcopy(manifest.load_manifest())

    for kind in ("configs", "pipelines", "reference"):      # a configuration
        ext = "json" if kind == "configs" else "py"
        shutil.copy(os.path.join(bench, kind, f"raydp-criteo-dlrm.{ext}"),
                    os.path.join(bench, kind, f"throwaway-dlrm.{ext}"))
    m["configs"].append({
        "name": "throwaway-dlrm", "source": "https://example.org/paper",
        "file": "chipbench/configs/throwaway-dlrm.json", "reduced": [],
        "why": "a throw-away configuration"})
    wl = manifest.load_json(root, "workloads", "dlrm_criteo_stream.json")
    wl.update(config="throwaway-dlrm", traffic="resident_small",
              residency="default", rows=65536)
    with open(os.path.join(bench, "workloads", "throwaway_cell.json"),
              "w") as fh:                                   # a cell
        json.dump(wl, fh)
    m["workloads"].append({"name": "throwaway_cell", "config": "throwaway-dlrm",
                           "traffic": "resident_small", "chips": 1,
                           "why": "a throw-away cell"})
    with open(os.path.join(bench, "layer_metrics", "throwaway_gap_ms.py"),
              "w") as fh:                                   # a per-layer metric
        fh.write("def read(run):\n"
                 "    return 1e3 * max(run['epoch_gaps_s'])\n")
    m["per_layer"].append({
        "name": "throwaway_gap_ms", "unit": "ms", "better": "lower",
        "source": "host_clock", "layer": "train loop",
        "moves": "train_throughput", "workloads": ["throwaway_cell"]})
    with open(os.path.join(root, "BENCHMARK.json"), "w") as fh:
        json.dump(m, fh)

    assert manifest.validate(m, root) == []
    cell = manifest.resolve(m, "throwaway_cell", root)
    assert cell.cfg["name"] == "raydp-criteo-dlrm"      # the copied file
    assert cell.wl["rows"] == 65536 and cell.wl["residency"] == "default"
    assert cell.readers["throwaway_gap_ms"].read(
        {"epoch_gaps_s": [0.001, 0.003]}) == 3.0
    # the feed's metrics name their cells, so the new cell does not get them
    assert "feed_wait_share" not in cell.readers
    old = manifest.resolve(m, "dlrm_criteo_stream", root)
    assert "throwaway_gap_ms" not in old.readers
    for p, content in before.items():
        assert open(p, "rb").read() == content, f"{p} was edited"


def test_resolve_refuses_a_file_that_disagrees_with_the_manifest(tmp_path):
    m = copy.deepcopy(manifest.load_manifest())
    m["workloads"][0]["chips"] = 4
    with pytest.raises(ValueError, match="chips"):
        manifest.resolve(m, m["workloads"][0]["name"])
    with pytest.raises(KeyError, match="no cell"):
        manifest.resolve(m, "no_such_cell")


def test_unknown_device_kind_is_an_error():
    assert manifest.peak_of("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    assert manifest.peak_of("TPU v5 lite")["source"]
    with pytest.raises(KeyError, match="never a default"):
        manifest.peak_of("TPU v9 imaginary")
    with pytest.raises(KeyError):
        manifest.peak_of("cpu")
