"""BENCHMARK.json against the benchmark's contract, and the resolver that
finds a cell's files by name: a cell, a configuration and a per-layer metric
are each added as new files plus one manifest entry."""

import copy
import json
import os
import shutil
import time

import pytest

from chipbench import harness, manifest

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
        for fn in ("generate", "etl", "build_estimator", "reference_inputs",
                   "describe", "batch_leaves", "cpu_cut"):
            assert callable(getattr(cell.pipeline, fn))
        assert callable(cell.reference.forward) and cell.reference.TOLERANCE
        sample = cell.reference.SAMPLE
        assert 0 < sample["batch"] <= sample["rows"]
        assert callable(cell.flops.train_flops_per_item)


def test_one_reader_a_metric_and_one_metric_a_reader():
    """Every per-layer entry has its reader file and no reader file stands
    without an entry; every cell reads every list-free metric; and a kernel's
    work answers to one contract in every family that has the kernel."""
    m = manifest.load_manifest()
    names = [x["name"] for x in m["per_layer"]]
    assert len(names) == len(set(names))
    files = {f[:-3] for f in os.listdir(os.path.join(
        REPO, manifest.BENCH_DIR, "layer_metrics")) if f.endswith(".py")}
    assert files == set(names)
    list_free = {x["name"] for x in m["per_layer"] if "workloads" not in x}
    for entry in m["workloads"]:
        cell = manifest.resolve(m, entry["name"])
        assert list_free <= set(cell.readers)
        if "flash_fwd_roofline" in cell.readers:
            for fn in ("flash_forward", "flash_backward", "num_experts"):
                assert callable(getattr(cell.flops, fn)), (entry["name"], fn)
    # no reader names a configuration: it is handed its cell
    for name in files:
        with open(os.path.join(REPO, manifest.BENCH_DIR, "layer_metrics",
                               f"{name}.py")) as fh:
            assert "CONFIG" not in fh.read(), name


def test_a_cell_a_config_and_a_metric_are_added_as_files(tmp_path):
    """Throw-away ones, in a copy: new files and one entry each in
    BENCHMARK.json; no existing file is edited. The second configuration is
    rehearsed end to end: what differs from the first (what is compared, on
    how much, the CPU cut) is said by its own files."""
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
    # the feed's metrics hold for any cell, so the new cell reads them too
    assert "feed_wait_share" in cell.readers
    old = manifest.resolve(m, "dlrm_criteo_stream", root)
    assert "throwaway_gap_ms" not in old.readers

    # a second configuration, not shaped like the first: a classifier whose
    # output is four numbers a row, checked on 64 rows in batches of 16 with
    # a selector that keeps three of the four, and a CPU cut of its own
    for path, text in OTHER_FILES.items():
        full = os.path.join(bench, path)
        assert not os.path.exists(full)
        with open(full, "w") as fh:
            fh.write(text)
    m["configs"].append({
        "name": "throwaway-classifier", "source": "https://example.org/other",
        "file": "chipbench/configs/throwaway-classifier.json", "reduced": [],
        "why": "a throw-away configuration that is no DLRM"})
    m["workloads"].append({
        "name": "throwaway_classes", "config": "throwaway-classifier",
        "traffic": "resident_rows", "chips": 1,
        "why": "a throw-away cell of it"})
    with open(os.path.join(root, "BENCHMARK.json"), "w") as fh:
        json.dump(m, fh)
    assert manifest.validate(m, root) == []
    other = manifest.resolve(m, "throwaway_classes", root)
    rehearsal = harness.cut_for_cpu(other, tmp_path / "rehearsal")
    assert other.wl["batch_per_replica"] == 32 and rehearsal.rows == 520
    assert other.cfg["model"]["features"] == [32, 16]       # no width is cut
    result = harness.run_cell(other, seed=3, seconds=0.2, trace=False,
                              t_start=time.perf_counter(),
                              rehearsal=rehearsal)
    found = result["detail"]["found"]
    assert result["correct"] is True, found
    assert found["compared_shape"] == [64, 3]
    assert found["reference_error"] <= other.reference.TOLERANCE
    assert set(result["metrics"]) == {"train_throughput", "setup_s"}
    for p, content in before.items():
        assert open(p, "rb").read() == content, f"{p} was edited"


OTHER_FILES = {
    "configs/throwaway-classifier.json": json.dumps({
        "name": "throwaway-classifier", "family": "throwaway_mlp",
        "model": {"features": [32, 16], "classes": 4, "num_features": 8},
        "compared_classes": 3, "loss": "cross_entropy",
        "source_rows": 1000000, "rows": 65536}),
    "workloads/throwaway_classes.json": json.dumps({
        "config": "throwaway-classifier", "traffic": "resident_rows",
        "chips": 1, "rows": 65536, "residency": "default", "mesh_spec": {},
        "batch_per_replica": 1024, "estimator": "flax", "estimator_args": {},
        "checkpoint_interval": "final", "unit_of_work": "samples",
        "first_window_loss_band": [0.1, 0.2]}),
    "flops/throwaway_mlp.py": '''
def train_flops_per_item(cfg, wl, info):
    m = cfg["model"]
    widths = [m["num_features"], *m["features"], m["classes"]]
    return 3.0 * sum(2 * a * b for a, b in zip(widths[:-1], widths[1:]))
''',
    "pipelines/throwaway-classifier.py": '''
import numpy as np
import pyarrow as pa

LABEL = "label"


def _features(cfg):
    return [f"f{i}" for i in range(cfg["model"]["num_features"])]


def generate(rows, seed, cfg):
    rng = np.random.default_rng(seed)
    x = rng.normal(size=(rows, cfg["model"]["num_features"]))
    cols = {LABEL: (2 * (x[:, 0] > 0) + (x[:, 1] > 0)).astype(np.int64)}
    cols.update({name: x[:, i] for i, name in enumerate(_features(cfg))})
    return pa.table(cols)


def describe(cfg, wl):
    return {"features": _features(cfg), "label": LABEL}


def etl(raw_df, cfg, wl):
    from raydp_tpu.etl.expressions import col
    return raw_df.withColumn("f0", col("f0") * 2.0), describe(cfg, wl)


def build_estimator(cfg, wl, info, **fit_args):
    import optax
    from raydp_tpu.models.mlp import MLP
    from raydp_tpu.train import FlaxEstimator
    m = cfg["model"]
    return FlaxEstimator(
        model=MLP(features=tuple(m["features"]), out_features=m["classes"],
                  use_batch_norm=False),
        optimizer=optax.adam(1e-2), loss=cfg["loss"],
        feature_columns=info["features"], label_column=info["label"],
        feature_dtype=np.float32, label_dtype=np.float32, shuffle=True,
        **fit_args)


def reference_inputs(table, info):
    return np.stack([table[c].to_numpy().astype(np.float32)
                     for c in info["features"]], 1)


def batch_leaves(cfg, wl, info, batch):
    return {"features": ((batch, len(info["features"])), "float32"),
            "label": ((batch,), "float32")}


def compared(outputs, cfg):
    return outputs[:, :cfg["compared_classes"]]


def cpu_cut(cfg, wl, chips):
    wl["batch_per_replica"] = 32
    return 32 * 16 + 8
''',
    "reference/throwaway-classifier.py": '''
import jax
import jax.numpy as jnp

TOLERANCE = 1e-4        # float32 against float32 under highest precision
SAMPLE = {"rows": 64, "batch": 16}


def forward(variables, x, cfg):
    params = variables["params"]
    layers = sorted(params, key=lambda k: int(k.split("_")[1]))
    with jax.default_matmul_precision("highest"):
        x = jnp.asarray(x, jnp.float32)
        for i, name in enumerate(layers):
            x = x @ params[name]["kernel"] + params[name]["bias"]
            if i < len(layers) - 1:
                x = jnp.maximum(x, 0)
    return x[:, :cfg["compared_classes"]]
''',
}


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
