"""The cell ``nemotron3_nano_30ba3b_16k_train`` against the benchmark's
contract: its configuration's widths and the cut written into its file, the
parameter table to the parameter (and the bias outside it), its operation
counts and the scan and flash kernels' operations and bytes against a hand
count, its train step compiled chip-free at the published widths, its
rehearsal through ``harness.cut_for_cpu``, and each of the per-layer readers
that list it on a synthetic run handed the cell (and on a DLRM run, where they
say nothing).
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
CELL = "nemotron3_nano_30ba3b_16k_train"
CONFIG = "nemotron-3-nano-30b-a3b"
PEAK = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
WIDTHS = {"hidden_size": 2688, "mamba_num_heads": 64, "mamba_head_dim": 64,
          "n_groups": 8, "ssm_state_size": 128, "conv_kernel": 4,
          "chunk_size": 128, "num_attention_heads": 32,
          "num_key_value_heads": 2, "head_dim": 128,
          "moe_intermediate_size": 1856,
          "moe_shared_expert_intermediate_size": 3712,
          "num_experts_per_tok": 6, "n_shared_experts": 1,
          "routed_scaling_factor": 2.5, "layer_norm_epsilon": 1e-05,
          "mlp_hidden_act": "relu2", "bias_update_rate": 0.001}
T = 16384
PAIRS = T * (T + 1) // 2            # 134,225,920 visible pairs a sequence
PARAMETERS = 666962944
M_LAYER, A_LAYER, E_LAYER = 38742208, 23396352, 100122624
#: the per-layer metrics that list this cell: the device's first, then the
#: counters'
METRICS = ["expert_layer_share", "attn_share", "ssm_share", "ssm_glue_share",
           "flash_fwd_roofline", "flash_bwd_roofline", "ssd_fwd_roofline",
           "ssd_bwd_roofline", "head_loss_share", "expert_load_imbalance",
           "held_slot_share"]
NEW = ["ssd_fwd_roofline", "ssd_bwd_roofline", "ssm_share", "ssm_glue_share"]


@pytest.fixture()
def cell():
    return manifest.resolve(manifest.load_manifest(), CELL)


def _catalog_row():
    path = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(path):
        return None
    with open(path) as fh:
        rows = [json.loads(line) for line in fh if line.strip()]
    return next(r for r in rows
                if r["name"] == "NVIDIA-Nemotron-3-Nano-30B-A3B-BF16")


def test_the_configuration_carries_the_source_whole_and_every_width(cell):
    cfg = cell.cfg
    for key, value in WIDTHS.items():
        assert cfg[key] == value, key
    # what the source states stays beside what is held here
    assert (cfg["num_hidden_layers"], cfg["n_routed_experts"],
            cfg["vocab_size"], cfg["max_position_embeddings"]) == (
                52, 128, 131072, 262144)
    pattern = cfg["hybrid_override_pattern"]
    assert (len(pattern), pattern.count("M"), pattern.count("E"),
            pattern.count("*")) == (52, 23, 23, 6)
    assert (cfg["layers"], cfg["layers_held"], cfg["layer_pattern_held"],
            cfg["first_expert"], cfg["experts_held"], cfg["vocab_rows_held"],
            cfg["chips_sharing_a_layer"], cfg["seq_len"]) == (
                9, list(range(9)), "MEMEM*EME", 0, 8, 16384, 16, T)
    assert "".join(pattern[i] for i in cfg["layers_held"]) == "MEMEM*EME"
    assert cfg["vocab_rows_held"] * 8 == cfg["vocab_size"]
    assert cfg["experts_held"] * 16 == cfg["n_routed_experts"]
    assert (cfg["norm_topk_prob"], cfg["n_group"], cfg["topk_group"],
            cfg["use_conv_bias"], cfg["family"]) == (
                True, 1, 1, True, "ssm_moe_lm")
    assert cfg["aux_loss"] == {"balance_weight": 0.0, "z_weight": 0.0}
    assert cfg["input"]["eos_id"] == cfg["vocab_rows_held"] - 1
    entry = next(c for c in manifest.load_manifest()["configs"]
                 if c["name"] == CONFIG)
    assert entry["reduced"] == cfg["reduced"] == [
        "layers", "experts_held", "vocab_rows_held"]
    assert not [k for k in cfg["reduced"] if re.search(
        r"(_dim|_rank|hidden|intermediate|width|head|latent|state|proj"
        r"|experts_per_tok)", k)]
    assert entry["source"] == cfg["source"]
    assert entry["file"] == f"chipbench/configs/{CONFIG}.json"
    assert "sixteen chips share each layer" in cfg["deployment"]
    for key in ("layers_of_one_sub_layer", "in_proj_split", "convolution",
                "scan", "gated_norm", "ssm_init", "attention", "routing",
                "experts", "bias_update", "aux_loss",
                "rescale_prenorm_residual", "optimizer", "input",
                "compute_dtype", "remat_blocks", "seq_len", "source_rows",
                "parameters", "layers", "experts_held", "vocab_rows_held"):
        assert key in cfg["assumed"], key
    row = _catalog_row()
    if row is not None:     # every key of the catalog's config, unchanged
        assert row["source_url"] == cfg["source"]
        for key, value in row["config"].items():
            assert cfg[key] == value, key


def test_the_manifest_holds_the_cell_and_the_metrics_it_lists(cell):
    """Present, once, each with its reader, in the cells it lists: no place
    in ``per_layer``, ``workloads`` or ``configs`` and no length is asked of
    the manifest, and of a list that other cells share only that it holds
    this cell and those it held (``<=``: the next cell does not break it)."""
    m = manifest.load_manifest()
    assert manifest.validate(m) == []
    entry = next(w for w in m["workloads"] if w["name"] == CELL)
    assert (entry["config"], entry["traffic"], entry["chips"]) == (
        CONFIG, "packed_16k_stream", 1)
    assert [c["name"] for c in m["configs"]].count(CONFIG) == 1
    mine = [e for e in m["per_layer"] if CELL in e.get("workloads", [])]
    assert set(METRICS) <= {e["name"] for e in mine}
    assert all(e["moves"] == "train_throughput" for e in mine)
    sources = {e["name"]: e["source"] for e in mine}
    assert all(sources[n] == "device_trace" for n in METRICS[:9])
    assert all(sources[n] == "program_counter" for n in METRICS[9:])
    lists = {e["name"]: e["workloads"] for e in mine}
    # the state-space readers are this cell's own so far; the held share is
    # read by those that hold one, the rest by every LM cell
    for name in NEW:
        assert CELL in lists[name]
        new = next(e for e in mine if e["name"] == name)
        assert (new["unit"], new["source"]) == ("%", "device_trace")
        assert (new["layer"], new["better"]) == (
            ("kernels", "higher") if name.endswith("_roofline")
            else ("model", "lower"))
    assert "olmoe_1b7b_train" not in lists["held_slot_share"]
    assert all({"olmoe_1b7b_train", "smallthinker_21ba3b_16k_train",
                "trinity_mini_8k_train", "kanana2_30ba3b_16k_train", CELL}
               <= set(lists[n]) for n in METRICS
               if n not in NEW + ["held_slot_share"])
    # every list-free metric is read here too, and no other family's
    names = {e["name"] for e in cell.per_layer}
    assert {e["name"] for e in m["per_layer"] if "workloads" not in e} < names
    assert not names & {"expert_gemm_roofline", "window_attn_share",
                        "latent_kv_share", "rowwise_table_share",
                        "collective_share"}
    assert set(cell.readers) == names
    wl = cell.wl
    assert (wl["seq_len"], wl["residency"], wl["checkpoint_interval"],
            wl["unit_of_work"]) == (T, "stream", "final", "tokens")
    assert 6 <= wl["rows"] <= 12
    # 16,384 tokens an optimizer step, as one row
    assert wl["batch_per_replica"] * T == 16384
    assert wl["estimator_args"] == {}


def test_the_parameter_table_to_the_parameter(cell):
    """ISSUE 47's table, from the configuration's sizes and from the model's
    own tree at the published widths (shapes only: nothing is allocated);
    the bias and its counts lie outside the parameters."""
    import jax

    parts = cell.flops.parameters(cell.cfg)
    assert 27697152 + 24576 + 6144 + 3 * 64 + 4096 + 11010048 == M_LAYER
    assert 2688 * 10304 == 27697152 and 4096 + 2 * 8 * 128 == 6144
    assert parts["ssm"] == 4 * M_LAYER
    assert 11010048 + 2 * 688128 + 11010048 == A_LAYER == parts["attention"]
    assert parts["norms"] == 9 * 2688
    assert (parts["router"], parts["shared_expert"], parts["experts"]) == (
        4 * 344064, 4 * 19955712, 4 * 8 * 9977856)
    assert 344064 + 19955712 + 8 * 9977856 == E_LAYER
    assert parts["embedding_head_final_norm"] == 88083072
    assert sum(parts.values()) == PARAMETERS
    assert 4 * M_LAYER + 4 * E_LAYER + A_LAYER + 9 * 2688 + 88083072 \
        == PARAMETERS
    assert round(PARAMETERS * 16 / 2 ** 30, 2) == 9.94      # GiB of state
    # uncut, one expert layer alone is more than a chip holds
    assert 344064 + 19955712 + 128 * 9977856 == 1297465344
    model = cell.pipeline.build_model(cell.cfg)
    shapes = jax.eval_shape(lambda: model.init(
        jax.random.PRNGKey(0), np.zeros((1, 8), np.int32)))
    count = lambda t: sum(int(np.prod(s.shape))  # noqa: E731
                          for s in jax.tree.leaves(t))
    assert count(shapes["params"]) == PARAMETERS
    for i, letter in enumerate("MEMEM*EME"):
        block = shapes["params"][f"block_{i}"]
        assert set(block) == {"norm", {"M": "ssm", "*": "attn",
                                       "E": "moe"}[letter]}
        assert count(block) == 2688 + {"M": M_LAYER, "*": A_LAYER,
                                       "E": E_LAYER}[letter]
    state = shapes["batch_stats"]
    assert sorted(state) == [f"block_{i}" for i in (1, 3, 6, 8)]
    assert sum(int(np.prod(b["moe"]["bias"].shape))
               for b in state.values()) == 4 * 128
    assert all(b["moe"]["counts"].shape == (128,) and
               b["moe"]["bias"].dtype == np.float32 for b in state.values())
    ssm = shapes["params"]["block_0"]["ssm"]
    assert sorted(ssm) == ["A_log", "D", "conv", "conv_bias", "dt_bias",
                           "in_proj", "norm", "out_proj"]
    assert ssm["in_proj"]["kernel"].shape == (2688, 10304)
    assert (ssm["conv"].shape, ssm["conv_bias"].shape) == ((4, 6144), (6144,))
    assert ssm["A_log"].shape == ssm["D"].shape == ssm["dt_bias"].shape \
        == (64,)
    assert all(ssm[n].dtype == np.float32 for n in ("A_log", "D", "dt_bias"))
    assert ssm["out_proj"]["kernel"].shape == (4096, 2688)
    attn = shapes["params"]["block_5"]["attn"]
    assert attn["q"]["kernel"].shape == (2688, 32, 128)
    assert attn["k"]["kernel"].shape == (2688, 2, 128)
    moe = shapes["params"]["block_1"]["moe"]
    assert sorted(moe) == ["experts_down", "experts_up", "router",
                           "shared_down", "shared_up"]       # no gate
    assert moe["experts_up"].shape == (8, 2688, 1856)
    assert moe["shared_down"]["kernel"].shape == (3712, 2688)
    assert shapes["params"]["lm_head"]["kernel"].shape == (2688, 16384)
    assert model.attention_layers == {"window": 0, "full": 1}
    assert model.ssm_layers == {"rescanned": 4}


def test_the_flops_and_the_kernels_work_by_hand(cell):
    work, cfg = cell.flops, cell.cfg
    assert work.visible_pairs(T) == PAIRS == 134225920
    assert work.layers_of(cfg) == {"M": 4, "*": 1, "E": 4}
    parts = work.forward_flops_per_token(cfg)
    # a state-space layer: both projections, then the scan
    assert parts["ssm_projections"] == 4 * 2 * (27697152 + 11010048)  # 309.7 M
    # in a chunk of 128 a position sees 64.5: C B^T once a group (8 x 128),
    # the decayed product once a head (64 x 64); then C . S and B (x) x
    scan = 2 * 64.5 * (8 * 128 + 64 * 64) + 2 * 2 * 64 * 128 * 64
    assert scan == 660480 + 2097152
    assert parts["ssm_scan"] == 4 * scan                            # 11.0 M
    assert round((parts["ssm_projections"] + parts["ssm_scan"]) / 4e6,
                 1) == 80.2
    assert parts["attention_projections"] == 2 * 2688 * (2 * 4096 + 2 * 256)
    assert parts["attention_scores"] == 2 * 2 * 4096 * (T + 1) / 2  # 134.2 M
    assert parts["router"] == 4 * 2 * 2688 * 128
    assert parts["shared_expert"] == 4 * 2 * 2 * 2688 * 3712        # 159.6 M
    assert parts["experts"] == 4 * 6 * (8 / 128) * 2 * 2 * 2688 * 1856
    assert parts["head"] == 2 * 2688 * 16384                        # 88.1 M
    assert round(sum(parts.values()) / 1e6) == 782
    assert round(100 * (parts["ssm_projections"] + parts["ssm_scan"])
                 / sum(parts.values())) == 41
    assert work.train_flops_per_item(cfg, cell.wl, {}) == 3 * sum(
        parts.values())
    assert work.num_experts(cfg) == 128
    # one execution of one layer's scan kernels over 2 sequences (the
    # contract of ``trace/executions.py``): x and y at 4096, B and C at 1024
    # each, bfloat16; dt float32
    fwd, fwd_bytes = work.ssd_forward(cfg, cell.wl, "scan", 2)
    assert fwd == 2 * T * scan
    assert fwd_bytes == 2 * T * ((2 * 4096 + 2 * 1024) * 2 + 64 * 4)
    assert round(fwd_bytes / 2 / 1e6) == 340        # ISSUE 47's 340 MB
    bwd, bwd_bytes = work.ssd_backward(cfg, cell.wl, "scan", 2)
    assert bwd == 2 * T * (2 * 64.5 * (3 * 1024 + 2 * 4096)
                           + 5 * 2 * 64 * 128 * 64)
    assert bwd_bytes == 2 * T * ((3 * 4096 + 4 * 1024) * 2 + 2 * 64 * 4)
    for flops, moved in ((fwd, fwd_bytes), (bwd, bwd_bytes)):   # memory-bound
        assert flops / PEAK["bf16_flops_per_s"] < moved / PEAK[
            "hbm_bytes_per_s"]
    with pytest.raises(ValueError, match="'scan'"):
        work.ssd_forward(cfg, cell.wl, "full", 1)
    # the flash kernels at a group of 16: K and V read once a group
    fwd, fwd_bytes = work.flash_forward(cfg, cell.wl, "full", 2)
    assert fwd == 2 * 2 * 2 * 4096 * PAIRS
    assert fwd_bytes == 2 * T * ((2 * 4096 + 2 * 256) * 2 + 32 * 4)
    bwd, bwd_bytes = work.flash_backward(cfg, cell.wl, "full", 2)
    assert bwd == 2.5 * fwd
    assert bwd_bytes == 2 * T * ((4 * 4096 + 4 * 256) * 2 + 2 * 32 * 4)
    with pytest.raises(ValueError, match="full causal"):
        work.flash_forward(cfg, cell.wl, "window", 1)


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
    for key in WIDTHS:
        if key not in ("mamba_num_heads", "n_groups", "num_attention_heads"):
            assert cfg[key] == WIDTHS[key], key
    # one layer of each kind, in the published order
    assert (cfg["layers"], cfg["layers_held"], cfg["layer_pattern_held"]) \
        == (3, [0, 1, 5], "ME*")
    assert "".join(cfg["hybrid_override_pattern"][i]
                   for i in cfg["layers_held"]) == "ME*"
    assert (cfg["n_routed_experts"], cfg["experts_held"],
            cfg["num_experts_per_tok"]) == (16, 1, 6)
    assert cfg["experts_held"] * 16 == cfg["n_routed_experts"]
    assert (cfg["vocab_size"], cfg["vocab_rows_held"]) == (4096, 512)
    # heads are counted, a group's eight and a head's widths stay
    assert (cfg["mamba_num_heads"], cfg["n_groups"]) == (16, 2)
    assert cfg["mamba_num_heads"] // cfg["n_groups"] == 64 // 8
    assert (cfg["seq_len"], cfg["num_attention_heads"]) == (256, 8)
    assert cfg["seq_len"] == 2 * cfg["chunk_size"]
    assert rows == 2 and wl["batch_per_replica"] == 1 and wl["seq_len"] == 256


def test_the_train_step_compiles_chip_free_at_the_published_widths():
    """``rehearse.py compile``: the estimator's own train step for a described
    v5e chip, both scan kernels at 64 heads of 64 in 8 groups with a state of
    128 and chunks of 128 (the tiling and the scoped-VMEM limit are the
    compiler's to refuse), the flash kernels at a group of 16, the held
    experts' walk with two grouped products, the shared expert, the bias's
    collection and the recomputed layers included, one 16,384-token row a
    step. The compiler refuses a program that does not fit the chip, so
    compiling is the check; the temporaries it reports (6.03 GiB) are the
    bound here, and the fit's peak on the chip is in PERF.md (PR 47)."""
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
    assert f"{PARAMETERS} parameters" in line and "global batch 1," in line
    gib = {k: float(v) for k, v in re.findall(
        r"(arguments|temporaries) ([0-9.]+) GiB", line)}
    assert 7.42 < gib["arguments"] < 7.49        # weights, mu, nu in float32
    assert gib["temporaries"] < 6.4
    assert "collectives {}" in line


def test_the_rehearsal_through_cut_for_cpu_is_correct(cell, tmp_path):
    """The cell end to end on the CPU at its cut: the five checks, the
    counters of the share and of the state-space layers, and the metrics a
    CPU run can read."""
    rehearsal = harness.cut_for_cpu(cell, tmp_path)
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
    counters = result["detail"]["counters"]
    assert counters["train_ssm_layers_total"]["rescanned"] >= 1
    assert counters["train_attention_layers_total"]["full"] >= 1
    got = {k: v["value"] for k, v in result["metrics"].items()}
    assert 0 < got["held_slot_share"] < 100
    # the imbalance counts with the experts of the configuration as it is
    # run: the cut's 16 here, the published 128 on the chip
    assert got["expert_load_imbalance"] >= 1.0
    # no TPU plane off the chip: the device readers say nothing
    assert not set(METRICS[:9]) & set(got)


def test_the_tolerance_separates_bfloat16_from_the_precision_below(cell):
    """The reference with every product's operands (the recurrence's state
    among them) rounded to an 8-bit float (the nearest precision below the
    bfloat16 the configuration states) is not correct; rounded to bfloat16 it
    is far closer. At the CPU cut, seeded weights; the chip's readings at the
    published widths are in PERF.md."""
    import jax
    import jax.numpy as jnp

    from chipbench.harness import relative_rms_error
    cfg, ref = copy.deepcopy(cell.cfg), cell.reference
    cell.pipeline.cpu_cut(cfg, copy.deepcopy(cell.wl), 1)
    tokens = cell.pipeline.reference_inputs(
        cell.pipeline.generate(2, 11, cfg),
        {"tokens": "tokens", "seq_len": cfg["seq_len"]})
    variables = dict(cell.pipeline.build_model(cfg).init(
        jax.random.PRNGKey(11), tokens[:1]))
    exact = np.asarray(ref.forward(variables, tokens, cfg))
    err = {dt: relative_rms_error(np.asarray(ref.at_precision(
        dt, ref.forward, variables, tokens, cfg)), exact)
        for dt in (jnp.bfloat16, jnp.float8_e5m2, jnp.float8_e4m3fn)}
    assert err[jnp.bfloat16] < ref.TOLERANCE / 4
    # three layers here read 0.13 (e4m3) and 0.20 (e5m2); the chip's nine
    # read 0.293 and 0.296 (PERF.md, PR 47)
    assert min(err[jnp.float8_e5m2], err[jnp.float8_e4m3fn]) > ref.TOLERANCE
    assert err[jnp.bfloat16] < err[jnp.float8_e4m3fn] / 4


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
BACK = "jit(train_step)/transpose(jvp(TransformerLM.loss_rows))/TransformerLM/"
PROGRAM = {
    "rdt_ssd_fwd.1": STEP + "block_0/ssm/scan/rdt_ssd_fwd/pallas_call",
    "rdt_ssd_fwd.2": BACK + "block_0/ssm/scan/rdt_ssd_fwd/pallas_call",
    "rdt_ssd_bwd.1": BACK + "block_0/ssm/scan/rdt_ssd_bwd/pallas_call",
    "rdt_flash_fwd.1": STEP + "block_5/attn/attn_full/pallas_call",
    "rdt_flash_bwd_dkdv_dq.1": BACK + "block_5/attn/attn_full/pallas_call",
    "fusion.2": STEP + "block_0/ssm/in_proj/dot_general",
    "fusion.3": STEP + "block_0/ssm/conv/mul",
    "fusion.4": STEP + "block_0/ssm/scan/cumsum",
    "fusion.5": STEP + "block_0/ssm/norm/rsqrt",
    "fusion.6": STEP + "block_0/ssm/out_proj/dot_general",
    "fusion.7": STEP + "block_5/attn/q/dot_general",
    "fusion.1": STEP + "block_1/moe/router/dot_general",
    "fusion.8": STEP + "block_1/moe/shared/shared_up/dot_general",
    "ragged-dot-none.3": "ragged-dot-none",     # the chip's compiler's name
    "fusion.9": STEP + "lm_head_loss/while/body/dot_general",
    "fusion.11": "jit(train_step)/mul",
}
# one step's device events, microseconds: (name, start within the step, length)
STEP_EVENTS = [("fusion.2", 0, 20000), ("fusion.3", 20000, 9000),
               ("fusion.4", 29000, 3000), ("rdt_ssd_fwd.1", 32000, 3000),
               ("fusion.5", 35000, 8000), ("fusion.6", 43000, 7000),
               ("fusion.7", 50000, 10000), ("rdt_flash_fwd.1", 60000, 17000),
               ("fusion.1", 77000, 1000), ("fusion.8", 78000, 12000),
               ("ragged-dot-none.3", 90000, 20000), ("fusion.9", 110000, 30000),
               ("rdt_flash_bwd_dkdv_dq.1", 140000, 35000),
               ("rdt_ssd_fwd.2", 175000, 3000),
               ("rdt_ssd_bwd.1", 178000, 10000), ("fusion.11", 188000, 12000)]
BUSY = 0.2                  # seconds a step, every op a leaf
SCAN = 2 * 64.5 * (8 * 128 + 64 * 64) + 2 * 2 * 64 * 128 * 64


def _run(cell, tmp_path, steps=2):
    """A synthetic traced run of ``steps`` optimizer steps of the cell (one
    sequence a step)."""
    from chipbench.trace import reduce as reducer
    events = [(name, 300000 * i + start, dur) for i in range(steps)
              for name, start, dur in STEP_EVENTS]
    xplane = _xplane(tmp_path / f"t{steps}.xplane.pb", PROGRAM, events)
    return {"cell": CELL, "cfg": cell.cfg, "wl": cell.wl, "flops": cell.flops,
            "trace": reducer.reduce(xplane), "xplane": xplane, "chips": 1,
            "peak": PEAK, "traced_items": T * steps,
            "flops_per_item": cell.flops.train_flops_per_item(
                cell.cfg, cell.wl, {}),
            "counters": {"moe_slots_total": {
                "all": 393216.0 * steps, "max_expert": 9000.0 * steps,
                "held": 24000.0 * steps, "moved": 24576.0 * steps}}}


# runs of two other cells as the harness hands them over: a DLRM's (its own
# configuration and family, none of the kernels, scopes or counters) and an
# older LM's, whose family counts no scan
DLRM = manifest.resolve(manifest.load_manifest(), "dlrm_criteo_stream")
OTHER = {"cell": DLRM.name, "cfg": DLRM.cfg, "wl": DLRM.wl,
         "flops": DLRM.flops,
         "trace": {"op_seconds": {"fusion.114": 0.089}, "busy_s": 2.7},
         "xplane": None, "chips": 1, "peak": PEAK, "traced_items": 1 << 20,
         "flops_per_item": 1.4e6,
         "counters": {"train_table_updates_total": {"rowwise": 10}}}


@pytest.mark.parametrize("name,want", [
    ("expert_layer_share", 100 * (0.001 + 0.012 + 0.020) / BUSY),
    ("attn_share", 100 * (0.010 + 0.017 + 0.035) / BUSY),
    # both projections, the convolution, the scan's scope with its three
    # kernel executions, the gated norm
    ("ssm_share", 100 * (0.020 + 0.009 + 0.003 + 0.003 + 0.008 + 0.007
                         + 0.003 + 0.010) / BUSY),
    # the convolution, the norm and the scan's scope without its kernels
    ("ssm_glue_share", 100 * (0.009 + 0.008 + 0.003) / BUSY),
    ("flash_fwd_roofline", 100 * (2 * 2 * 4096 * PAIRS / 197e12) / 0.017),
    ("flash_bwd_roofline", 100 * (5 * 2 * 4096 * PAIRS / 197e12) / 0.035),
    # two executions (the forward and the recomputed one), bound by memory
    ("ssd_fwd_roofline",
     100 * (2 * T * ((2 * 4096 + 2 * 1024) * 2 + 256) / 819e9) / 0.006),
    ("ssd_bwd_roofline",
     100 * (T * ((3 * 4096 + 4 * 1024) * 2 + 512) / 819e9) / 0.010),
    ("head_loss_share", 100 * 0.03 / BUSY),
    # over all 128 experts the router chooses among, not the 8 held
    ("expert_load_imbalance", 9000 / (393216 / 128)),
    ("held_slot_share", 100 * 24000 / 393216),
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
    assert reader.read(OTHER) is None
    assert reader.read(dict(OTHER, trace=None)) is None
    entry = next(m for m in cell.per_layer if m["name"] == name)
    assert CELL in entry["workloads"]


def test_a_program_without_the_scope_or_the_kernels_says_nothing(
        cell, tmp_path):
    """The parent of this PR has no ``ssm`` scope and no scan kernel (nor can
    it build this model), and an older LM cell's program has neither and its
    family counts no scan: the four new readers say nothing there and raise
    nothing."""
    from chipbench.trace import reduce as reducer
    program = {k: v.replace("/ssm/", "/mlp/")
               for k, v in PROGRAM.items() if not k.startswith("rdt_ssd")}
    events = [e for e in STEP_EVENTS if not e[0].startswith("rdt_ssd")]
    xplane = _xplane(tmp_path / "plain.xplane.pb", program, events)
    run = dict(_run(cell, tmp_path), trace=reducer.reduce(xplane),
               xplane=xplane)
    for name in NEW:
        assert cell.readers[name].read(run) is None, name
        assert cell.readers[name].read(dict(run, xplane=None)) is None
    assert cell.readers["attn_share"].read(run) is not None
    assert cell.readers["expert_layer_share"].read(run) is not None
    # an older LM cell's run: the scan kernels' names in a trace would still
    # read nothing, its family has no ``ssd_forward``
    older = manifest.resolve(manifest.load_manifest(),
                             "kanana2_30ba3b_16k_train")
    theirs = dict(_run(cell, tmp_path), cell=older.name, cfg=older.cfg,
                  wl=older.wl, flops=older.flops)
    assert cell.readers["ssd_fwd_roofline"].read(theirs) is None
    assert cell.readers["ssd_bwd_roofline"].read(theirs) is None
    assert cell.readers["held_slot_share"].read(
        dict(run, counters={})) is None


def test_the_counters_scopes_and_kernels_the_readers_read_are_the_programs():
    from raydp_tpu import metrics
    from raydp_tpu.ops import ssd_scan as ssd

    m = metrics.METRICS["train_ssm_layers_total"]
    assert (m.kind, m.label) == (metrics.COUNTER, "scan")
    m = metrics.METRICS["ssd_chunks_total"]
    assert (m.kind, m.label) == (metrics.COUNTER, "pass")
    assert {"ssm", "ssm/in_proj", "ssm/conv", "ssm/scan", "ssm/norm",
            "ssm/out_proj", "attn", "moe/router", "moe/shared",
            "moe/experts"} <= metrics.SCOPE_NAMES
    assert ssd.KERNEL_NAMES == ("rdt_ssd_fwd", "rdt_ssd_bwd")
    for reader, kernel in (("ssd_fwd_roofline", ssd.KERNEL_NAMES[0]),
                           ("ssd_bwd_roofline", ssd.KERNEL_NAMES[1])):
        module = manifest.load_module(REPO, "layer_metrics", f"{reader}.py")
        assert re.match(module.KERNEL, kernel + ".7")
    # the published shape reaches the compiled kernels; a step's program
    # names them (interpreted here: the names are the calls')
    assert ssd.kernel_ineligible(16384, 128, 8, 64, 128) is None
    import jax
    import jax.numpy as jnp
    shape = lambda *s: jax.ShapeDtypeStruct(s, jnp.float32)  # noqa: E731
    text = str(jax.make_jaxpr(jax.grad(lambda x, dt, a, b, c, d: ssd.ssd_scan(
        x, dt, a, b, c, d, chunk=8, interpret=True).sum()))(
            shape(1, 16, 4, 4), shape(1, 16, 4), shape(4), shape(1, 16, 2, 8),
            shape(1, 16, 2, 8), shape(4)))
    assert "name=rdt_ssd_fwd" in text and "name=rdt_ssd_bwd" in text
