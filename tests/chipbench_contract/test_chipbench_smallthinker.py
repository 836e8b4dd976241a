"""The cell ``smallthinker_21ba3b_16k_train`` against the benchmark's
contract: its configuration's widths and the cut written into its file, the
parameter table to the parameter, the pairs a window leaves visible, its
operation counts against a hand count, its train step compiled chip-free at
the published widths, its rehearsal through ``harness.cut_for_cpu``, and each
of the per-layer readers that list it on a synthetic run handed the cell (and
on a DLRM run, where they say nothing).
"""

import copy
import json
import os
import re
import subprocess
import sys
import time

import numpy as np
import pyarrow as pa
import pytest

from chipbench import harness, manifest

REPO = manifest.ROOT
CELL = "smallthinker_21ba3b_16k_train"
CONFIG = "smallthinker-21b-a3b"
PEAK = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
WIDTHS = {"hidden_size": 2560, "head_dim": 128, "num_attention_heads": 28,
          "num_key_value_heads": 4, "moe_ffn_hidden_size": 768,
          "moe_num_active_primary_experts": 6, "sliding_window_size": 4096,
          "rope_theta": 1500000, "rms_norm_eps": 1e-06}
T = 16384
#: the per-layer metrics that list this cell
METRICS = ["flash_fwd_roofline", "flash_bwd_roofline", "window_attn_share",
           "attn_share", "expert_layer_share", "held_slot_share",
           "expert_load_imbalance", "head_loss_share"]


@pytest.fixture()
def cell():
    return manifest.resolve(manifest.load_manifest(), CELL)


def _catalog_row():
    path = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(path):
        return None
    with open(path) as fh:
        rows = [json.loads(line) for line in fh if line.strip()]
    return next(r for r in rows if r["name"] == "SmallThinker-21BA3B-Instruct")


def test_the_configuration_carries_the_source_whole_and_every_width(cell):
    cfg = cell.cfg
    for key, value in WIDTHS.items():
        assert cfg[key] == value, key
    # what the source states stays beside what is held here
    assert (cfg["num_hidden_layers"], cfg["moe_num_primary_experts"],
            cfg["vocab_size"], cfg["max_position_embeddings"]) == (
                52, 64, 151936, T)
    assert (cfg["layers"], cfg["first_expert"], cfg["experts_held"],
            cfg["vocab_rows_held"], cfg["chips_sharing_a_layer"]) == (
                4, 0, 16, 37984, 4)
    assert cfg["vocab_rows_held"] * 4 == cfg["vocab_size"]
    assert cfg["sliding_window_layout"] == cfg["rope_layout"] == [0, 1, 1, 1] * 13
    assert cfg["norm_topk_prob"] is True
    entry = next(c for c in manifest.load_manifest()["configs"]
                 if c["name"] == CONFIG)
    assert entry["reduced"] == cfg["reduced"] == [
        "layers", "experts_held", "vocab_rows_held"]
    assert entry["source"] == cfg["source"]
    assert "four chips share each layer" in cfg["deployment"]
    for key in ("biases", "router_input", "secondary_experts", "optimizer",
                "aux_loss", "init_std", "input", "compute_dtype",
                "remat_blocks", "parameters"):
        assert key in cfg["assumed"], key
    row = _catalog_row()
    if row is not None:     # every key of the catalog's config, unchanged
        assert row["source_url"] == cfg["source"]
        for key, value in row["config"].items():
            assert cfg[key] == value, key


def test_the_manifest_holds_the_cell_and_the_metrics_it_lists(cell):
    """Present, once, each with its reader, in the cells it lists: no place
    in ``per_layer`` and no length is asked of the manifest."""
    m = manifest.load_manifest()
    assert manifest.validate(m) == []
    entry = next(w for w in m["workloads"] if w["name"] == CELL)
    assert (entry["config"], entry["traffic"], entry["chips"]) == (
        CONFIG, "packed_16k_stream", 1)
    mine = [e for e in m["per_layer"] if CELL in e.get("workloads", [])]
    assert sorted(e["name"] for e in mine) == sorted(METRICS)
    assert all(e["moves"] == "train_throughput" for e in mine)
    assert all(e["unit"] == "%" for e in mine
               if e["name"] != "expert_load_imbalance")
    # one reader a measurement: the windowed kernels are this cell's alone,
    # the held share is read by the two cells that hold one, the rest by
    # every LM cell
    lists = {e["name"]: e["workloads"] for e in mine}
    assert lists["window_attn_share"] == [CELL]
    assert lists["held_slot_share"] == [CELL, "trinity_mini_8k_train"]
    assert all(lists[n] == ["olmoe_1b7b_train", CELL, "trinity_mini_8k_train"]
               for n in METRICS if n not in ("window_attn_share",
                                             "held_slot_share"))
    # every list-free metric is read here too, and no other cell's
    names = {e["name"] for e in cell.per_layer}
    assert {e["name"] for e in m["per_layer"] if "workloads" not in e} < names
    assert not names & {"expert_gemm_roofline", "shared_expert_share",
                        "rowwise_table_share", "collective_share"}
    assert set(cell.readers) == names
    assert (cell.wl["rows"], cell.wl["batch_per_replica"], cell.wl["seq_len"],
            cell.wl["residency"], cell.wl["estimator_args"]) == (
                8, 1, T, "stream", {})


def test_the_parameter_table_to_the_parameter(cell):
    """ISSUE 31's table, from the configuration's sizes and from the model's
    own tree at the published widths (shapes only: nothing is allocated)."""
    import jax

    parts = cell.flops.parameters(cell.cfg)
    assert parts["attention"] == 4 * 20971520
    assert parts["router_and_norms"] == 4 * 168960
    assert parts["experts"] == 4 * 94371840
    assert parts["embedding_head_final_norm"] == 194480640
    assert sum(parts.values()) == 656529920
    assert round(656529920 * 16 / 2 ** 30, 2) == 9.78       # GiB of state
    model = cell.pipeline.build_model(cell.cfg)
    shapes = jax.eval_shape(lambda: model.init(
        jax.random.PRNGKey(0), np.zeros((1, 8), np.int32))["params"])
    assert sum(int(np.prod(s.shape))
               for s in jax.tree.leaves(shapes)) == 656529920
    block = shapes["block_1"]
    assert block["attn"]["q"]["kernel"].shape == (2560, 28, 128)
    assert block["attn"]["k"]["kernel"].shape == (2560, 4, 128)
    assert block["attn"]["o"]["kernel"].shape == (28, 128, 2560)
    assert block["router"].shape == (2560, 64)
    assert block["moe"]["experts_down"].shape == (16, 768, 2560)
    assert shapes["lm_head"]["kernel"].shape == (2560, 37984)
    assert model.attention_layers == {"window": 3, "full": 1}


def test_the_pairs_a_window_leaves_visible(cell):
    work = cell.flops
    assert work.visible_pairs(T) == T * (T + 1) / 2 == 134225920
    assert work.visible_pairs(T, 4096) == 58722304
    assert round(work.visible_pairs(T) / T, 1) == 8192.5
    assert round(work.visible_pairs(T, 4096) / T, 1) == 3584.1
    # against a count of the mask itself, at a size that can be counted
    i, j = np.indices((256, 256))
    assert work.visible_pairs(256, 64) == ((j <= i) & (i - j < 64)).sum()
    assert work.visible_pairs(256, 300) == work.visible_pairs(256) == (
        j <= i).sum()
    assert work.layer_kinds(cell.cfg) == {"window": 3, "full": 1}
    # the kernels' blocks at 1024 x 1024: what the window skips
    from raydp_tpu.ops.flash_attention import _band_steps
    assert _band_steps(T, 1024, 1024, 4096) == (5, 5)


def test_flops_against_a_hand_count(cell):
    parts = cell.flops.forward_flops_per_token(cell.cfg, T)
    assert parts["attention_projections"] == 4 * 2 * 20971520      # 167.8 M
    full, windowed = 4 * 3584 * 134225920 / T, 4 * 3584 * 58722304 / T
    assert round(full / 1e6, 1) == 117.4 and round(windowed / 1e6, 1) == 51.4
    assert parts["attention_scores"] == full + 3 * windowed         # 271.6 M
    assert parts["router"] == 4 * 2 * 2560 * 64                     # 1.3 M
    assert parts["experts"] == 4 * 6 * 0.25 * 3 * 2 * 2560 * 768    # 70.8 M
    assert parts["head"] == 2 * 2560 * 37984                        # 194.5 M
    assert round(sum(parts.values()) / 1e6) == 706
    per_token = cell.flops.train_flops_per_item(cell.cfg, cell.wl, {})
    assert per_token == 3 * sum(parts.values())
    # 56.8%: the windowed layers' share of the pairs
    assert round(100 * 3 * windowed / (full + 3 * windowed), 1) == 56.8
    # one execution of one layer's kernels over 1 sequence of 16,384
    # tokens, by kind (the contract of ``trace/executions.py``)
    work, cfg, wl = cell.flops, cell.cfg, cell.wl
    assert work.num_experts(cfg) == 64
    for kind, pairs in (("full", 134225920), ("window", 58722304)):
        fwd, fwd_bytes = work.flash_forward(cfg, wl, kind, 1)
        assert fwd == 2 * 2 * 3584 * pairs
        assert fwd_bytes == T * ((2 * 3584 + 2 * 512) * 2 + 28 * 4)
        bwd, bwd_bytes = work.flash_backward(cfg, wl, kind, 1)
        assert bwd == 2.5 * fwd
        assert bwd_bytes == T * ((4 * 3584 + 4 * 512) * 2 + 2 * 28 * 4)
        for flops, moved in ((fwd, fwd_bytes), (bwd, bwd_bytes)):
            assert flops / PEAK["bf16_flops_per_s"] > 10 * moved / PEAK[
                "hbm_bytes_per_s"]      # compute-bound
    # twice the sequences, twice the work
    assert work.flash_forward(cfg, wl, "full", 2)[0] == 2 * 2 * 2 * 3584 \
        * 134225920


def test_a_batch_is_int32_tokens_drawn_from_the_slice(cell):
    info = cell.pipeline.describe(cell.cfg, cell.wl)
    assert cell.pipeline.batch_leaves(cell.cfg, cell.wl, info, 1) == {
        "tokens": ((1, T), "int32")}
    with pytest.raises(ValueError, match="seq_len"):
        cell.pipeline.describe(cell.cfg, dict(cell.wl, seq_len=4096))
    cfg = copy.deepcopy(cell.cfg)
    cell.pipeline.cpu_cut(cfg, copy.deepcopy(cell.wl), 1)
    a, b, c = (cell.pipeline.generate(32, s, cfg) for s in (7, 7, 2 ** 31 + 5))
    assert a.equals(b) and not a.equals(c)
    assert a.schema.field("tokens").type == pa.list_(pa.int32(), 256)
    tokens = cell.pipeline.reference_inputs(a, {"tokens": "tokens",
                                                "seq_len": 256})
    assert tokens.shape == (32, 256) and tokens.dtype == np.int32
    # the ids lie in the rows held, not in the whole vocabulary
    assert 0 <= tokens.min() and tokens.max() < cfg["vocab_rows_held"] == 512
    assert np.bincount(tokens.ravel(), minlength=512)[511] > 0      # eos


def test_the_cpu_cut_cuts_counts_and_never_a_width(cell):
    cfg, wl = copy.deepcopy(cell.cfg), copy.deepcopy(cell.wl)
    rows = cell.pipeline.cpu_cut(cfg, wl, cell.chips)
    for key in ("hidden_size", "head_dim", "moe_ffn_hidden_size",
                "rope_theta", "rms_norm_eps"):
        assert cfg[key] == WIDTHS[key], key
    assert (cfg["layers"], cfg["moe_num_primary_experts"],
            cfg["experts_held"], cfg["moe_num_active_primary_experts"]) == (
                4, 8, 2, 2)
    assert (cfg["vocab_size"], cfg["vocab_rows_held"]) == (2048, 512)
    assert (cfg["max_position_embeddings"], cfg["sliding_window_size"]) == (
        256, 64)
    # one group of the published seven query heads a K/V head
    assert cfg["num_attention_heads"] == 7 * cfg["num_key_value_heads"]
    assert rows == 4 and wl["batch_per_replica"] == 1 and wl["seq_len"] == 256


def test_the_train_step_compiles_chip_free_at_the_published_widths():
    """``rehearse.py compile``: the estimator's own train step for a described
    v5e chip, the grouped-query flash kernels (windowed and full), the held
    experts' grouped products and the recomputed blocks included. It must
    fit."""
    proc = subprocess.run(
        [sys.executable, "chipbench/rehearse.py", "compile", CELL], cwd=REPO,
        capture_output=True, text=True, timeout=900,
        env={k: v for k, v in os.environ.items() if k != "XLA_FLAGS"})
    if "REHEARSAL compile" not in proc.stdout and re.search(
            r"topolog|libtpu|lockfile", proc.stderr, re.IGNORECASE):
        pytest.skip(f"no v5e topology can be described here: "
                    f"{proc.stderr[-300:]}")
    assert proc.returncode == 0, proc.stderr[-2000:]
    line = next(x for x in proc.stdout.splitlines() if CELL in x)
    assert "656529920 parameters" in line and "global batch 1," in line
    gib = {k: float(v) for k, v in re.findall(
        r"(arguments|temporaries) ([0-9.]+) GiB", line)}
    assert 7.3 < gib["arguments"] < 7.4          # weights, mu, nu in float32
    assert gib["arguments"] + gib["temporaries"] < 15.75
    assert "collectives {}" in line


def test_the_rehearsal_through_cut_for_cpu_is_correct(cell, tmp_path):
    """The cell end to end on the CPU at its cut: the five checks, the
    counters of the share, and the one new metric a CPU run can read."""
    rehearsal = harness.cut_for_cpu(cell, tmp_path)
    rehearsal.rows = 2      # two steps an epoch: ``test_chipbench_run`` and
    # ``test_chipbench_host_spans`` rehearse the cell at its cut's four
    os.makedirs(tmp_path / ".cache")
    (tmp_path / ".cache" / f"{CELL}.json").write_text('{"t_e": 1.0}')
    t0 = time.perf_counter()
    result = harness.run_cell(cell, seed=2 ** 31 + 7, seconds=0.3, trace=True,
                              t_start=t0, rehearsal=rehearsal)
    found = result["detail"]["found"]
    assert result["correct"] is True, found
    assert found["compared_shape"] == [2, 32, 512]
    assert found["reference_error"] <= cell.reference.TOLERANCE
    assert found["streamed"] and found["lowerings_in_window"] == 0
    got = {k: v["value"] for k, v in result["metrics"].items()}
    assert 0 < got["held_slot_share"] < 100
    # no TPU plane off the chip: the device readers say nothing
    assert got["expert_load_imbalance"] >= 1.0
    assert not {"flash_fwd_roofline", "flash_bwd_roofline",
                "window_attn_share", "attn_share", "expert_layer_share",
                "head_loss_share"} & set(got)


def test_the_tolerance_separates_bfloat16_from_the_precision_below(cell):
    """The reference with every product's operands rounded to an 8-bit float
    (the nearest precision below the bfloat16 the configuration states) is
    not correct; rounded to bfloat16 it is far closer. At the CPU cut,
    seeded weights; the chip's readings at the published widths are in
    PERF.md."""
    import jax
    import jax.numpy as jnp

    from chipbench.harness import relative_rms_error
    cfg, ref = copy.deepcopy(cell.cfg), cell.reference
    cell.pipeline.cpu_cut(cfg, copy.deepcopy(cell.wl), 1)
    tokens = cell.pipeline.reference_inputs(
        cell.pipeline.generate(2, 11, cfg),
        {"tokens": "tokens", "seq_len": cfg["max_position_embeddings"]})
    variables = {"params": cell.pipeline.build_model(cfg).init(
        jax.random.PRNGKey(11), tokens[:1])["params"]}
    exact = np.asarray(ref.forward(variables, tokens, cfg))
    err = {dt: relative_rms_error(np.asarray(ref.at_precision(
        dt, ref.forward, variables, tokens, cfg)), exact)
        for dt in (jnp.bfloat16, jnp.float8_e5m2)}
    assert err[jnp.float8_e5m2] > 2 * ref.TOLERANCE > 2 * err[jnp.bfloat16]
    assert err[jnp.bfloat16] < err[jnp.float8_e5m2] / 8


# ---------------------------------------------------------------- readers
def _proto(fields):
    """Serialize ``[(number, value)]``: bytes length-delimited, ints varint."""
    def varint(n):
        out = bytearray()
        while True:
            out.append((n & 0x7F) | (0x80 if n > 0x7F else 0))
            n >>= 7
            if not n:
                return bytes(out)
    out = b""
    for number, value in fields:
        if isinstance(value, int):
            out += varint(number << 3) + varint(value)
        else:
            value = value.encode() if isinstance(value, str) else value
            out += varint(number << 3 | 2) + varint(len(value)) + value
    return out


def _xplane(path, instructions, events=()):
    """An ``.xplane.pb`` whose ``/host:metadata`` plane stores one program
    with the given ``{instruction name: op_name}`` and whose one device plane
    holds the given ``XLA Ops`` events ``(name, start_us, duration_us)``."""
    computation = _proto([(1, "main")] + [
        (2, _proto([(1, name), (2, "fusion"), (7, _proto([(2, op_name)]))]))
        for name, op_name in instructions.items()])
    hlo = _proto([(1, _proto([(1, "jit_train_step"), (3, computation)]))])
    stored = _proto([
        (2, "/host:metadata"),
        (5, _proto([(1, 9), (2, _proto([(1, 9), (2, "Hlo Proto")]))])),
        (4, _proto([(1, 1), (2, _proto([
            (1, 1), (2, "jit_train_step(1)"),
            (5, _proto([(1, 9), (6, hlo)]))]))]))])
    ids = {name: i + 1 for i, name in enumerate(
        dict.fromkeys(e[0] for e in events))}
    device = _proto(
        [(1, 1), (2, "/device:TPU:0"), (3, _proto(
            [(1, 1), (2, "XLA Ops"), (3, 1000)] + [
                (4, _proto([(1, ids[name]), (2, int(start * 1e6)),
                            (3, int(dur * 1e6))]))
                for name, start, dur in events]))] + [
            (4, _proto([(1, i), (2, _proto([(1, i), (2, f"%{name} = x")]))]))
            for name, i in ids.items()])
    path.write_bytes(_proto([(1, device), (1, stored)]))
    return str(path)


STEP = "jit(train_step)/jvp(TransformerLM.loss_rows)/TransformerLM/"
PROGRAM = {
    "rdt_flash_fwd.1": STEP + "block_0/attn/attn_full/pallas_call",
    "rdt_flash_win_fwd.2": STEP + "block_1/attn/attn_window/pallas_call",
    "rdt_flash_bwd_dkdv.1": STEP + "block_0/attn/attn_full/pallas_call",
    "rdt_flash_win_bwd_dkdv.3": STEP + "block_1/attn/attn_window/pallas_call",
    "fusion.2": STEP + "block_1/attn/q/dot_general",
    "fusion.1": STEP + "block_0/moe/router/dot_general",
    "ragged-dot-none.3": "ragged-dot-none",     # as the chip's compiler names it
    "fusion.7": STEP + "block_0/moe/combine/reduce_sum",
    "fusion.9": STEP + "lm_head_loss/while/body/dot_general",
    "fusion.11": "jit(train_step)/mul",
}
# one step's device events, microseconds: (name, start within the step, length)
STEP_EVENTS = [("rdt_flash_fwd.1", 0, 10000),
               ("rdt_flash_win_fwd.2", 10000, 15000),
               ("fusion.2", 25000, 5000), ("fusion.1", 30000, 1000),
               ("ragged-dot-none.3", 31000, 20000), ("fusion.7", 51000, 9000),
               ("fusion.9", 60000, 50000),
               ("rdt_flash_bwd_dkdv.1", 110000, 30000),
               ("rdt_flash_win_bwd_dkdv.3", 140000, 45000),
               ("fusion.11", 185000, 15000)]
BUSY = 0.2                  # seconds a step, every op a leaf


def _run(cell, tmp_path, steps=2):
    """A synthetic traced run of ``steps`` optimizer steps of the cell."""
    from chipbench.trace import reduce as reducer
    events = [(name, 250000 * i + start, dur) for i in range(steps)
              for name, start, dur in STEP_EVENTS]
    xplane = _xplane(tmp_path / f"t{steps}.xplane.pb", PROGRAM, events)
    return {"cell": CELL, "cfg": cell.cfg, "wl": cell.wl, "flops": cell.flops,
            "trace": reducer.reduce(xplane), "xplane": xplane, "chips": 1,
            "peak": PEAK, "traced_items": T * steps,
            "flops_per_item": cell.flops.train_flops_per_item(
                cell.cfg, cell.wl, {}),
            "counters": {"moe_slots_total": {
                "all": 393216.0 * steps, "max_expert": 40000.0 * steps,
                "held": 90000.0 * steps}}}


# what a run of the OLMoE cell counts (no held share), and a run of a DLRM
# cell as the harness hands it over: its own configuration and family
OLMOE = {"counters": {"moe_slots_total": {"all": 131072.0,
                                          "max_expert": 4096.0}}}
DLRM = manifest.resolve(manifest.load_manifest(), "dlrm_criteo_stream")
OTHER = {"cell": DLRM.name, "cfg": DLRM.cfg, "wl": DLRM.wl,
         "flops": DLRM.flops,
         "trace": {"op_seconds": {"fusion.114": 0.089}, "busy_s": 2.7},
         "xplane": None, "chips": 1, "peak": PEAK, "traced_items": 1 << 20,
         "flops_per_item": 1.4e6,
         "counters": {"train_table_updates_total": {"rowwise": 10}}}
FULL, WINDOW = 134225920, 58722304


@pytest.mark.parametrize("name,want", [
    # one execution of a full layer's kernels and one of a windowed layer's,
    # over one sequence each: what the trace holds, not the layers held
    ("flash_fwd_roofline",
     100 * (2 * 2 * 3584 * (FULL + WINDOW) / 197e12) / 0.025),
    ("flash_bwd_roofline",
     100 * (5 * 2 * 3584 * (FULL + WINDOW) / 197e12) / 0.075),
    ("window_attn_share", 100 * (0.015 + 0.045) / 0.1),
    ("attn_share", 100 * (0.1 + 0.005) / BUSY),
    ("expert_layer_share", 100 * (0.001 + 0.02 + 0.009) / BUSY),
    ("held_slot_share", 100 * 90000 / 393216),
    # over all 64 experts the router chooses among, not the 16 held
    ("expert_load_imbalance", 40000 / (393216 / 64)),
    ("head_loss_share", 100 * 0.05 / BUSY),
])
def test_a_reader_on_a_synthetic_run_and_on_another_cells(
        cell, tmp_path, name, want):
    reader = cell.readers[name]
    run = _run(cell, tmp_path)
    assert reader.read(run) == pytest.approx(want, rel=1e-6)
    # the same share whatever the number of traced steps
    assert reader.read(_run(cell, tmp_path, steps=5)) == pytest.approx(want)
    if name.endswith("_roofline"):
        assert want < 100
    # a program that holds every expert counts no held share
    if name == "held_slot_share":
        assert reader.read(dict(run, counters=OLMOE["counters"])) is None
    # a DLRM run has none of the kernels, scopes or counters
    assert reader.read(OTHER) is None
    assert reader.read(dict(OTHER, trace=None)) is None
    entry = next(m for m in cell.per_layer if m["name"] == name)
    assert CELL in entry["workloads"]
    assert sorted(METRICS) == sorted(
        m["name"] for m in cell.per_layer if "workloads" in m)


def test_a_trace_without_the_windowed_kernel_has_no_window_share(
        cell, tmp_path):
    from chipbench.trace import reduce as reducer
    events = [e for e in STEP_EVENTS if "_win_" not in e[0]]
    xplane = _xplane(tmp_path / "full.xplane.pb", PROGRAM, events)
    run = {"cell": CELL, "cfg": cell.cfg, "wl": cell.wl, "flops": cell.flops,
           "trace": reducer.reduce(xplane), "xplane": xplane, "chips": 1,
           "peak": PEAK, "traced_items": T, "counters": {},
           "flops_per_item": 1.07e9}
    assert cell.readers["window_attn_share"].read(run) is None
    # the full layers' kernels are read alone, one execution of each
    assert cell.readers["flash_fwd_roofline"].read(run) == pytest.approx(
        100 * (2 * 2 * 3584 * FULL / 197e12) / 0.01)
    assert cell.readers["flash_bwd_roofline"].read(run) == pytest.approx(
        100 * (5 * 2 * 3584 * FULL / 197e12) / 0.03)
    assert cell.readers["held_slot_share"].read(run) is None
    assert cell.readers["expert_load_imbalance"].read(run) is None
    assert cell.readers["attn_share"].read(run) == pytest.approx(
        100 * 0.045 / 0.14)


def test_the_counters_the_readers_read_are_the_programs():
    from raydp_tpu import metrics

    m = metrics.METRICS["moe_slots_total"]
    assert (m.kind, m.label) == (metrics.COUNTER, "kind") and "held" in m.doc
    for name, label in (("train_attention_layers_total", "kind"),
                        ("flash_blocks_total", "fate")):
        assert (metrics.METRICS[name].kind, metrics.METRICS[name].label) == (
            metrics.COUNTER, label)
    assert {"attn", "attn_full", "attn_window"} <= metrics.SCOPE_NAMES
    from raydp_tpu.ops import flash_attention as fa
    assert all(re.match(r"^rdt_flash_win_", n) for n in fa.WINDOW_KERNEL_NAMES)
    assert not any(re.match(r"^rdt_flash_win_", n) for n in fa.KERNEL_NAMES)
