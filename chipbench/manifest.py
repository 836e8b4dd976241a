"""Resolve a cell of ``BENCHMARK.json`` to the files that define it, and check
the manifest against the benchmark's contract.

A cell is found by name alone: ``workloads/<cell>.json`` (the traffic mix),
``configs/<config>.json`` (the sizes), ``pipelines/<config>.py`` (generator,
ETL plan, estimator), ``reference/<config>.py`` (the plain forward pass),
``flops/<family>.py`` and one ``layer_metrics/<metric>.py`` for each per-layer
metric the cell reports. A later PR adds any of these as new files plus one
entry in ``BENCHMARK.json``; nothing here names a cell, a configuration or a
metric.
"""

from __future__ import annotations

import importlib.util
import json
import os
import re
from dataclasses import dataclass
from typing import Dict, List

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH_DIR = "chipbench"

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
TRAFFIC_KEYS = {
    "config", "traffic", "chips", "rows", "residency", "mesh_spec",
    "table_row_divisor", "batch_per_replica", "estimator", "estimator_args",
    "checkpoint_interval", "unit_of_work", "seq_len",
    "first_window_loss_band"}


def load_manifest(root: str = ROOT) -> dict:
    with open(os.path.join(root, "BENCHMARK.json")) as fh:
        return json.load(fh)


def load_json(root: str, *parts: str) -> dict:
    with open(os.path.join(root, BENCH_DIR, *parts)) as fh:
        return json.load(fh)


def load_module(root: str, *parts: str):
    """Import one benchmark file by path (names may hold ``-`` and ``.``)."""
    path = os.path.join(root, BENCH_DIR, *parts)
    tag = re.sub(r"\W", "_", "_".join(parts))
    spec = importlib.util.spec_from_file_location(f"chipbench_file_{tag}", path)
    if spec is None or not os.path.exists(path):
        raise FileNotFoundError(path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def metrics_of(entries: List[dict], cell: str) -> List[dict]:
    """The entries of ``end_to_end`` or ``per_layer`` that a cell reports."""
    return [m for m in entries if cell in m.get("workloads", [cell])]


@dataclass
class Cell:
    name: str
    chips: int
    wl: dict                # workloads/<cell>.json
    cfg: dict               # configs/<config>.json
    pipeline: object        # pipelines/<config>.py
    reference: object       # reference/<config>.py
    flops: object           # flops/<family>.py
    end_to_end: List[dict]
    per_layer: List[dict]
    readers: Dict[str, object]      # per-layer metric name -> its module


def resolve(manifest: dict, cell_name: str, root: str = ROOT) -> Cell:
    entry = next((w for w in manifest["workloads"]
                  if w["name"] == cell_name), None)
    if entry is None:
        raise KeyError(f"no cell {cell_name!r} in BENCHMARK.json; have "
                       f"{[w['name'] for w in manifest['workloads']]}")
    wl = load_json(root, "workloads", f"{cell_name}.json")
    unknown = set(wl) - TRAFFIC_KEYS
    if unknown:
        raise ValueError(f"workloads/{cell_name}.json: unknown keys "
                         f"{sorted(unknown)}")
    for key in ("config", "traffic", "chips"):
        if wl[key] != entry[key]:
            raise ValueError(f"workloads/{cell_name}.json says {key}="
                             f"{wl[key]!r}, BENCHMARK.json {entry[key]!r}")
    config = entry["config"]
    cfg = load_json(root, "configs", f"{config}.json")
    per_layer = metrics_of(manifest["per_layer"], cell_name)
    return Cell(
        name=cell_name, chips=int(entry["chips"]), wl=wl, cfg=cfg,
        pipeline=load_module(root, "pipelines", f"{config}.py"),
        reference=load_module(root, "reference", f"{config}.py"),
        flops=load_module(root, "flops", f"{cfg['family']}.py"),
        end_to_end=metrics_of(manifest["end_to_end"], cell_name),
        per_layer=per_layer,
        readers={m["name"]: load_module(root, "layer_metrics",
                                        f"{m['name']}.py")
                 for m in per_layer})


def peak_of(device_kind: str, root: str = ROOT) -> dict:
    """The published peaks of a device; an unknown device is an error."""
    peaks = load_json(root, "peaks.json")
    if device_kind not in peaks:
        raise KeyError(f"device kind {device_kind!r} is not in "
                       f"{BENCH_DIR}/peaks.json (have {sorted(peaks)}); add it "
                       f"with its source, never a default")
    return peaks[device_kind]


# --------------------------------------------------------------- validation
def _line(text, limit: int = 200) -> bool:
    return (isinstance(text, str) and 1 <= len(text) <= limit
            and "\n" not in text and "\t" not in text)


def validate(manifest: dict, root: str = ROOT) -> List[str]:
    """Everything in the manifest that the benchmark's contract would refuse,
    as a list of sentences (empty when it is sound)."""
    bad: List[str] = []
    keys = {"command", "paths", "run_seconds", "configs", "workloads",
            "end_to_end", "per_layer"}
    if set(manifest) != keys:
        bad.append(f"top-level keys {sorted(manifest)} != {sorted(keys)}")
        return bad
    paths = manifest["paths"]
    if not 1 <= len(paths) <= 16:
        bad.append("paths: 1 to 16 directories")
    for p in paths:
        if (not re.fullmatch(r"[A-Za-z0-9_.\-/]{1,200}", p)
                or p.startswith("/") or ".." in p.split("/")
                or not os.path.isdir(os.path.join(root, p))):
            bad.append(f"path {p!r}")

    def under(f):
        return any(f == p or f.startswith(p + "/") for p in paths)

    cmd = manifest["command"]
    if not 1 <= len(cmd) <= 32 or not all(_line(w) for w in cmd):
        bad.append("command: 1 to 32 words of 1 to 200 characters")
    for w in cmd:
        if w.startswith("/") or ".." in w.split("/"):
            bad.append(f"command word {w!r} leaves the repo")
        elif os.path.exists(os.path.join(root, w)) and not under(w):
            bad.append(f"command names {w!r}, outside paths")
    rs = manifest["run_seconds"]
    if not (isinstance(rs, int) and 1 <= rs <= 51):
        bad.append("run_seconds: a whole number from 1 to 51")

    def names(entries, what):
        seen = set()
        for e in entries:
            n = e.get("name", "")
            if not NAME.match(n):
                bad.append(f"{what} name {n!r}")
            if n in seen:
                bad.append(f"{what} name {n!r} twice")
            seen.add(n)
        return seen

    configs = manifest["configs"]
    if not 1 <= len(configs) <= 24:
        bad.append("configs: 1 to 24")
    config_names = names(configs, "config")
    files = set()
    for c in configs:
        if set(c) != {"name", "source", "file", "reduced", "why"}:
            bad.append(f"config {c.get('name')}: keys {sorted(c)}")
            continue
        if not (_line(c["source"]) and _line(c["why"])):
            bad.append(f"config {c['name']}: source and why are one line of "
                       f"at most 200 characters")
        if not under(c["file"]) or c["file"] in files or not os.path.isfile(
                os.path.join(root, c["file"])):
            bad.append(f"config {c['name']}: file {c['file']!r}")
        files.add(c["file"])
        if len(c["reduced"]) > 16 or not all(NAME.match(k)
                                             for k in c["reduced"]):
            bad.append(f"config {c['name']}: reduced {c['reduced']}")
        for k in c["reduced"]:
            if re.search(r"(_dim|_rank|hidden|intermediate|width|head_size"
                         r"|latent|state_size|proj|expansion"
                         r"|experts_per_tok)", k):
                bad.append(f"config {c['name']}: reduced names a width, {k}")

    cells = manifest["workloads"]
    if not 2 <= len(cells) <= 24:
        bad.append("workloads: 2 to 24 cells")
    cell_names = names(cells, "cell")
    pairs = set()
    for w in cells:
        if set(w) != {"name", "config", "traffic", "chips", "why"}:
            bad.append(f"cell {w.get('name')}: keys {sorted(w)}")
            continue
        if w["config"] not in config_names:
            bad.append(f"cell {w['name']}: unknown config {w['config']!r}")
        if not NAME.match(w["traffic"]):
            bad.append(f"cell {w['name']}: traffic {w['traffic']!r}")
        if w["chips"] not in (1, 4):
            bad.append(f"cell {w['name']}: chips {w['chips']!r}")
        if not _line(w["why"]):
            bad.append(f"cell {w['name']}: why is one line of at most 200 "
                       f"characters")
        if (w["config"], w["traffic"]) in pairs:
            bad.append(f"cell {w['name']}: pair appears twice")
        pairs.add((w["config"], w["traffic"]))
    four = sum(1 for w in cells if w.get("chips") == 4)
    if four > max(1, len(cells) // 4):
        bad.append(f"{four} four-chip cells of {len(cells)}: at most a "
                   f"quarter, rounded down, and one always")
    for c in config_names - {w.get("config") for w in cells}:
        bad.append(f"config {c} is used by no cell")

    e2e, layers = manifest["end_to_end"], manifest["per_layer"]
    if not 1 <= len(e2e) <= 16 or not 1 <= len(layers) <= 128:
        bad.append("end_to_end: 1 to 16 metrics; per_layer: 1 to 128")
    names(e2e + layers, "metric")
    e2e_keys = {"name", "unit", "better", "bound", "source"}
    layer_keys = {"name", "unit", "better", "source", "layer", "moves"}
    for m, want in [(m, e2e_keys) for m in e2e] + [(m, layer_keys)
                                                   for m in layers]:
        if set(m) - {"workloads"} != want:
            bad.append(f"metric {m.get('name')}: keys {sorted(m)}")
            continue
        if not UNIT.match(m["unit"]):
            bad.append(f"metric {m['name']}: unit {m['unit']!r}")
        if m["better"] not in ("lower", "higher"):
            bad.append(f"metric {m['name']}: better {m['better']!r}")
        if m["source"] not in SOURCES:
            bad.append(f"metric {m['name']}: source {m['source']!r}")
        for w in m.get("workloads", []):
            if w not in cell_names:
                bad.append(f"metric {m['name']}: unknown cell {w!r}")
        if "bound" in want:
            if m["source"] not in ("host_clock", "device_trace"):
                bad.append(f"metric {m['name']}: an end-to-end metric is "
                           f"taken by the benchmark itself")
            if not 0.01 <= m["bound"] <= 0.1:
                bad.append(f"metric {m['name']}: bound {m['bound']}")
        else:
            if not _line(m["layer"]):
                bad.append(f"metric {m['name']}: layer")
    if "setup_s" not in {m.get("name") for m in e2e}:
        bad.append("end_to_end has no setup_s")
    for w in cell_names:
        mine = {m["name"] for m in metrics_of(e2e, w)}
        if "setup_s" not in mine or len(mine) < 2:
            bad.append(f"cell {w}: needs setup_s and one more end-to-end "
                       f"metric")
        mine_layers = metrics_of(layers, w)
        if not mine_layers:
            bad.append(f"cell {w}: no per-layer metric")
        for m in mine_layers:
            if m.get("moves") not in mine:
                bad.append(f"cell {w}: {m['name']} moves {m.get('moves')!r}, "
                           f"which the cell does not report")
        try:
            resolve(manifest, w, root)
        except (OSError, KeyError, ValueError) as e:
            bad.append(f"cell {w}: {e}")
    size = os.path.getsize(os.path.join(root, "BENCHMARK.json"))
    if size > 64 * 1024:
        bad.append(f"BENCHMARK.json is {size} bytes, over 64 KiB")
    return bad
