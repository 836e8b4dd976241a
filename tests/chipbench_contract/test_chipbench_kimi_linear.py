"""The cell ``kimi_linear_48ba3b_16k_train`` against the benchmark's contract:
its configuration's widths, the source's ``config.json`` whole and the cut
written into its file; the manifest's entries (of a list other cells share
only ``<=``); its operation counts, its parameters and the kernels' and the
scan's operations and bytes against a hand count; the pipeline's generator
and CPU cut; its rehearsal through ``harness.cut_for_cpu``; the tolerance
against the precision below; and each of its two readers on a synthetic run
(and on a run of a program that lacks what they read, where they say
nothing).
"""

import copy
import os
import re
import time

import numpy as np
import pytest

from chipbench import harness, manifest

REPO = manifest.ROOT
CELL = "kimi_linear_48ba3b_16k_train"
CONFIG = "kimi-linear-48b-a3b"
PEAK = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
KDA = [1, 2, 3, 5, 6, 7, 9, 10, 11, 13, 14, 15, 17, 18, 19, 21, 22, 23, 25,
       26]
FULL = [4, 8, 12, 16, 20, 24, 27]
#: the source's config.json as the catalog copies it, whole
SOURCE = {
    "first_k_dense_replace": 1, "head_dim": 72, "hidden_act": "silu",
    "hidden_size": 2304, "intermediate_size": 9216, "kv_lora_rank": 512,
    "linear_attn_config": {
        "full_attn_layers": FULL, "head_dim": 128, "kda_layers": KDA,
        "num_heads": 32, "short_conv_kernel_size": 4},
    "mla_use_nope": True, "model_max_length": 1048576,
    "model_type": "kimi_linear", "moe_intermediate_size": 1024,
    "moe_layer_freq": 1, "moe_renormalize": True,
    "moe_router_activation_func": "sigmoid", "num_attention_heads": 32,
    "num_expert_group": 1, "num_experts": 256, "num_experts_per_token": 8,
    "num_hidden_layers": 27, "num_key_value_heads": 32,
    "num_nextn_predict_layers": 0, "num_shared_experts": 1,
    "q_lora_rank": None, "qk_nope_head_dim": 128, "qk_rope_head_dim": 64,
    "rms_norm_eps": 1e-05, "rope_scaling": None, "rope_theta": 10000,
    "routed_scaling_factor": 2.446, "tie_word_embeddings": False,
    "topk_group": 1, "use_grouped_topk": True, "v_head_dim": 128,
    "vocab_size": 163840}
L, LAYERS, KDAS, ATTNS = 16384, 5, 4, 1
PAIRS = L * (L + 1) // 2        # visible pairs a head a row, full causal
PARAMETERS = 602_433_408
SHARED = ["flash_fwd_roofline", "flash_bwd_roofline", "expert_layer_share",
          "head_loss_share", "expert_load_imbalance", "attn_share",
          "held_slot_share"]
NEW = ["kda_share", "kda_glue_share"]


@pytest.fixture()
def cell():
    return manifest.resolve(manifest.load_manifest(), CELL)


def test_the_configuration_carries_the_source_whole_and_every_width(cell):
    cfg = cell.cfg
    for key, value in SOURCE.items():
        assert cfg[key] == value, key
    path = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.exists(path):
        import json
        with open(path) as fh:
            row = next(r for r in map(json.loads, filter(str.strip, fh))
                       if r["name"] == "Kimi-Linear-48B-A3B-Instruct")
        assert row["source_url"] == cfg["source"]
        assert row["config"] == SOURCE
    assert (cfg["layers"], cfg["layers_held"], cfg["layer_pattern_held"],
            cfg["dense_layers"], cfg["seq_len"], cfg["family"]) == (
                LAYERS, [1, 2, 3, 4, 5], "KKKAK", 1, L, "kda_moe_lm")
    assert (cfg["first_expert"], cfg["experts_held"], cfg["vocab_rows_held"],
            cfg["chips_sharing_a_layer"], cfg["kda_chunk"]) == (
                0, 8, 20480, 32, 64)
    # the gates' rank is the KDA head_dim: no key of its own
    assert "kda_gate_rank" not in cfg
    # the guide's floors: a whole period and four layers after the dense
    # one, 8 experts, an eighth of the vocabulary
    assert cfg["layers"] - cfg["dense_layers"] >= 4
    assert cfg["experts_held"] >= 8
    assert cfg["vocab_rows_held"] * 8 >= cfg["vocab_size"]
    assert cfg["experts_held"] * cfg["chips_sharing_a_layer"] \
        == cfg["num_experts"]
    assert cfg["input"]["eos_id"] == cfg["vocab_rows_held"] - 1
    assert (cfg["compared_positions"], cfg["init_std"],
            cfg["bias_update_rate"], cfg["remat_blocks"],
            cfg["attention"], cfg["compute_dtype"]) == (
                256, 0.02, 0.001, True, "flash", "bfloat16")
    assert cfg["aux_loss"] == {"balance_weight": 0.0, "z_weight": 0.0}
    trinity = manifest.load_json(REPO, "configs", "trinity-mini.json")
    assert cfg["optimizer"] == trinity["optimizer"]
    assert {k: v for k, v in cfg["input"].items() if k != "eos_id"} == {
        k: v for k, v in trinity["input"].items() if k != "eos_id"}
    entry = next(c for c in manifest.load_manifest()["configs"]
                 if c["name"] == CONFIG)
    assert entry["reduced"] == cfg["reduced"] == [
        "layers", "experts_held", "vocab_rows_held"]
    assert not [k for k in cfg["reduced"] if re.search(
        r"(_dim|_rank|hidden|intermediate|width|head|latent|state|proj"
        r"|experts_per_tok)", k)]
    assert entry["source"] == cfg["source"]
    assert entry["file"] == f"chipbench/configs/{CONFIG}.json"
    assert "32 chips share each layer" in cfg["deployment"]
    assert "pipeline stage" in cfg["deployment"]
    published = cfg["published"]
    assert published["num_hidden_layers"] == 27
    assert (published["kda_layers"], published["full_attn_layers"]) == (
        KDA, FULL)
    assert published["layer_pattern"] == "KKKA" * 6 + "KKA"
    assert (published["num_experts"], published["vocab_size"]) == (
        256, 163840)
    for key in ("gate_rank", "fused_projection", "convolution",
                "l2_norm", "decay", "beta", "order", "output_stage",
                "output_gate_bias", "initialisation", "nope", "latent_norm",
                "routing", "bias_update", "numbering", "documents", "biases",
                "aux_loss", "layers", "experts_held", "vocab_rows_held",
                "parameters", "optimizer", "input", "compute_dtype",
                "remat_blocks", "kda_chunk", "seq_len", "source_rows",
                "batch"):
        assert key in cfg["assumed"], key
    assert "NO bias" in cfg["assumed"]["output_gate_bias"]
    assert "1e-20" in cfg["assumed"]["routing"]
    assert "32,768" in cfg["assumed"]["seq_len"]
    assert f"{PARAMETERS:,}" in cfg["assumed"]["parameters"]
    parts = cell.flops.parameters(cfg)
    assert sum(parts.values()) == PARAMETERS
    assert parts["kda"] == KDAS * 39_514_272
    assert parts["attention"] == ATTNS * 29_114_880
    assert parts["embedding_head_final_norm"] == 2 * 20480 * 2304 + 2304
    # the published arithmetic, from the published block
    p = published["parameters"]
    assert p["kda_operators_20"] == 20 * p["kda_operator"]
    assert p["attention_operators_7"] == 7 * p["attention_operator"]
    assert p["expert_layers_26_of_257"] == 26 * (p["router"]
                                                 + 257 * p["expert"])
    assert 49.0e9 < sum(p[k] for k in (
        "kda_operators_20", "attention_operators_7", "dense_feed_forward",
        "expert_layers_26_of_257", "embedding_and_head")) < 49.2e9


def test_the_manifest_holds_the_cell_and_the_metrics_it_lists(cell):
    """Present, once, each with its reader, in the cells it lists: no place
    in ``per_layer``, ``workloads`` or ``configs`` and no length is asked of
    the manifest, and of a list that other cells share only that it holds
    this cell (``<=``: the next cell does not break it)."""
    m = manifest.load_manifest()
    assert manifest.validate(m) == []
    entry = next(w for w in m["workloads"] if w["name"] == CELL)
    assert (entry["config"], entry["traffic"], entry["chips"]) == (
        CONFIG, "packed_16k_stream", 1)
    assert "512 slots" in entry["why"] and "32x" in entry["why"]
    assert [c["name"] for c in m["configs"]].count(CONFIG) == 1
    mine = {e["name"]: e for e in m["per_layer"]
            if CELL in e.get("workloads", [])}
    assert set(SHARED + NEW) <= set(mine)
    assert all(e["moves"] == "train_throughput" for e in mine.values())
    for name in SHARED:
        assert {"kanana2_30ba3b_16k_train", CELL} <= set(
            mine[name]["workloads"])
    for name in NEW:
        e = mine[name]
        assert e["workloads"] == [CELL]
        assert (e["layer"], e["better"], e["source"], e["unit"]) == (
            "model", "lower", "device_trace", "%")
    # every list-free metric is read here too; no reader of a kernel pair
    # that does not ship, and none that finds nothing in this program
    names = {e["name"] for e in cell.per_layer}
    assert {e["name"] for e in m["per_layer"] if "workloads" not in e} < names
    assert not names & {"kda_fwd_roofline", "kda_bwd_roofline",
                        "kda_kernel_share", "window_attn_share",
                        "expert_gemm_roofline", "ssd_fwd_roofline",
                        "ssm_share", "short_conv_share",
                        "rowwise_table_share", "collective_share",
                        "bd_flash_fwd_roofline", "loop_carry_share"}
    assert set(cell.readers) == names
    wl = cell.wl
    assert (wl["rows"], wl["seq_len"], wl["batch_per_replica"],
            wl["residency"], wl["checkpoint_interval"], wl["unit_of_work"],
            wl["estimator"], wl["estimator_args"], wl["mesh_spec"]) == (
                8, L, 1, "stream", "final", "tokens", "flax", {}, {})
    band = wl["first_window_loss_band"]
    assert band is None or (band[0] < band[1] and band[1] - band[0] <= 0.6)
    # the four-chip slots stay as they are
    assert sum(w["chips"] == 4 for w in m["workloads"]) <= max(
        1, len(m["workloads"]) // 4)


def test_the_flops_and_the_kernels_work_by_hand(cell):
    """A token's forward is 857 MFLOP, the four delta-rule operators its
    largest part; the flash kernels' and the scan's operations and bytes by
    hand."""
    cfg, flops = cell.cfg, cell.flops
    assert flops.visible_pairs(L) == PAIRS
    assert flops.layers_of(cfg) == {"K": KDAS, "A": ATTNS}
    parts = flops.forward_flops_per_token(cfg)
    d, w, heads = 2304, 4096, 32
    scan = heads * (2 * 2 * 128 * 32.5 + 64 * 256 + 3 * 2 * 128 * 128
                    + 2 * 128 * 32.5)
    assert parts == {
        "kda_projections": KDAS * 2 * (
            d * 3 * w + 2 * (d * 128 + 128 * w) + d * heads + w * d),
        "kda_scan": KDAS * scan,
        "attention_projections": 2 * (d * heads * 192 + d * 576
                                      + 512 * heads * 256 + w * d),
        "attention_scores": 2 * heads * 320 * (L + 1) / 2,
        "dense_ffn": 3 * 2 * d * 9216,
        "router": 4 * 2 * d * 256,
        "shared_experts": 4 * 3 * 2 * d * 1024,
        "experts": 4 * 8 * (8 / 256) * 3 * 2 * d * 1024,
        "head": 2 * d * 20480}
    total = sum(parts.values())
    assert 856e6 < total < 858e6
    assert parts["kda_projections"] == max(parts.values())
    assert 0.38 < (parts["kda_projections"] + parts["kda_scan"]) / total \
        < 0.40
    assert 0.26 < (parts["attention_projections"]
                   + parts["attention_scores"]) / total < 0.27
    assert flops.train_flops_per_item(cfg, cell.wl, {}) == 3.0 * total
    assert flops.num_experts(cfg) == 256
    ops, moved = flops.flash_forward(cfg, cell.wl, "full", 1.0)
    assert ops == 2 * heads * 320 * PAIRS
    assert moved == L * heads * ((2 * 192 + 2 * 128) * 2 + 4)
    ops_b, moved_b = flops.flash_backward(cfg, cell.wl, "full", 1.0)
    assert ops_b == 2 * heads * (3 * 192 + 2 * 128) * PAIRS
    assert moved_b == L * heads * ((4 * 192 + 4 * 128) * 2 + 2 * 4)
    with pytest.raises(ValueError, match="full causal"):
        flops.flash_forward(cfg, cell.wl, "window", 1.0)
    from chipbench.trace import roofline
    assert roofline.least_seconds(ops, moved, PEAK)[1] == "compute"
    # the scan: q, k, v read and o written at bfloat16, g and beta float32
    ops, moved = flops.kda_forward(cfg, cell.wl, "kda", 1.0)
    assert ops == L * scan
    assert moved == L * (4 * w * 2 + (w + heads) * 4) == 807_403_520
    ops_b, moved_b = flops.kda_backward(cfg, cell.wl, "kda", 1.0)
    assert moved_b == L * (7 * w * 2 + 2 * (w + heads) * 4)
    assert 2.4 < ops_b / ops < 2.6
    assert roofline.least_seconds(ops, moved, PEAK) == (
        pytest.approx(0.9858e-3, rel=1e-3), "memory")
    with pytest.raises(ValueError, match="'kda'"):
        flops.kda_forward(cfg, cell.wl, "full", 1.0)


def test_a_batch_is_int32_tokens_of_the_rows_held(cell):
    """Rows of ``seq_len`` ids over the 20,480 rows held, the end-of-text id
    at documents' ends, the same seed the same rows, a driver-sized seed
    taken."""
    cfg = copy.deepcopy(cell.cfg)
    cfg["seq_len"] = 4096
    table = cell.pipeline.generate(4, 2 ** 31 + 11, cfg)
    assert table.equals(cell.pipeline.generate(4, 2 ** 31 + 11, cfg))
    tokens = cell.pipeline.reference_inputs(
        table, {"tokens": "tokens", "seq_len": 4096})
    assert tokens.shape == (4, 4096) and tokens.dtype == np.int32
    assert 0 <= tokens.min() and 18000 < tokens.max() <= 20479
    assert 4 < (tokens == 20479).sum() < 60
    assert cell.pipeline.batch_leaves(cell.cfg, cell.wl, {"seq_len": L}, 1) \
        == {"tokens": ((1, L), "int32")}
    assert cell.pipeline.describe(cell.cfg, cell.wl) == {
        "tokens": "tokens", "seq_len": L}
    with pytest.raises(ValueError, match="seq_len"):
        cell.pipeline.describe(cell.cfg, dict(cell.wl, seq_len=4096))


def test_the_cpu_cut_cuts_counts_and_never_a_width(cell):
    cfg, wl = copy.deepcopy(cell.cfg), copy.deepcopy(cell.wl)
    rows = cell.pipeline.cpu_cut(cfg, wl, 1)
    assert rows == 2 and wl["seq_len"] == cfg["seq_len"] == 256
    for key in ("hidden_size", "intermediate_size", "moe_intermediate_size",
                "kv_lora_rank", "qk_nope_head_dim", "qk_rope_head_dim",
                "v_head_dim", "kda_chunk", "rms_norm_eps",
                "num_experts_per_token", "moe_renormalize",
                "routed_scaling_factor", "num_shared_experts", "init_std",
                "bias_update_rate", "first_k_dense_replace", "dense_layers"):
        assert cfg[key] == cell.cfg[key], key
    lin, was = cfg["linear_attn_config"], cell.cfg["linear_attn_config"]
    assert lin == dict(was, num_heads=2) and was["num_heads"] == 32
    assert cfg["num_attention_heads"] == 2
    assert (cfg["layers"], cfg["layers_held"], cfg["layer_pattern_held"]) \
        == (3, [1, 2, 4], "KKA")
    assert (cfg["num_experts"], cfg["experts_held"], cfg["vocab_size"],
            cfg["vocab_rows_held"]) == (16, 4, 2048, 512)
    model = cell.pipeline.build_model(cfg)
    assert (model.layer_kinds, model.rope_layers, model.dense_layers,
            model.routing, model.route_scale, model.normalize_top_k) == (
                "KKB", (0,), 1, "sigmoid", 2.446, True)
    assert (model.dim, model.ffn_dim, model.dense_ffn_dim,
            model.shared_expert_dim, model.kv_lora_rank) == (
                2304, 1024, 9216, 1024, 512)
    assert (model.kda.num_heads, model.kda.head_dim, model.kda.conv_taps,
            model.kda.gate_rank, model.kda.chunk) == (2, 128, 4, 128, 64)
    with pytest.raises(ValueError, match="sigmoid routing"):
        cell.pipeline.build_model(dict(cfg, mla_use_nope=False))


def test_the_rehearsal_through_cut_for_cpu_is_correct(cell, tmp_path):
    """The cell end to end on the CPU at its cut: the five checks, the
    counters of the pairs and of the scan's path, and the counter reader on
    them."""
    from raydp_tpu import metrics as rdt_metrics

    rehearsal = harness.cut_for_cpu(cell, tmp_path)
    os.makedirs(tmp_path / ".cache")
    (tmp_path / ".cache" / f"{CELL}.json").write_text('{"t_e": 1.0}')
    before = copy.deepcopy(rdt_metrics.snapshot()["counters"])
    t0 = time.perf_counter()
    result = harness.run_cell(cell, seed=2 ** 31 + 7, seconds=0.3,
                              trace=False, t_start=t0, rehearsal=rehearsal)
    found = result["detail"]["found"]
    assert result["correct"] is True, found
    assert found["compared_shape"] == [2, 32, 512]
    assert found["reference_error"] <= cell.reference.TOLERANCE
    assert found["streamed"] and found["lowerings_in_window"] == 0
    counters = {name: {label: value - before.get(name, {}).get(label, 0)
                       for label, value in by_label.items()}
                for name, by_label in result["detail"]["counters"].items()}
    assert counters["train_kda_layers_total"]["rescanned"] >= 2
    assert counters["train_attention_layers_total"]["latent"] >= 1
    assert counters["kda_scan_total"]["jnp"] >= 2
    chunks = counters["kda_chunks_total"]
    assert chunks["forward"] >= chunks["backward"] >= 2 * 2 * 4
    slots = counters["moe_slots_total"]
    assert 0 < slots["held"] < slots["all"]
    run = {"counters": counters, "flops": cell.flops, "cfg": cell.cfg}
    assert 0 < cell.readers["held_slot_share"].read(run) < 100
    assert set(result["metrics"]) == {"train_throughput", "setup_s"}


def test_the_tolerance_separates_bfloat16_from_the_precision_below(cell):
    """The reference with every product's operands (the convolution's taps
    and the scan's q, k, v among them) rounded to an 8-bit float (the nearest
    precision below the bfloat16 the configuration states) is not correct;
    rounded to bfloat16 it is far closer. At the CPU cut, seeded weights; the
    chip's readings at the published widths are in PERF.md."""
    import jax
    import jax.numpy as jnp

    from chipbench.harness import relative_rms_error
    cfg, ref = copy.deepcopy(cell.cfg), cell.reference
    cell.pipeline.cpu_cut(cfg, copy.deepcopy(cell.wl), 1)
    cfg["seq_len"] = 64
    inputs = cell.pipeline.reference_inputs(
        cell.pipeline.generate(2, 11, cfg),
        {"tokens": "tokens", "seq_len": 64})
    variables = dict(jax.jit(cell.pipeline.build_model(cfg).init)(
        jax.random.PRNGKey(11), inputs[:1]))
    exact = np.asarray(ref.forward(variables, inputs, cfg))
    err = {dt: relative_rms_error(np.asarray(ref.at_precision(
        dt, ref.forward, variables, inputs, cfg)), exact)
        for dt in (jnp.bfloat16, jnp.float8_e5m2, jnp.float8_e4m3fn)}
    assert err[jnp.bfloat16] < ref.TOLERANCE / 2
    assert min(err[jnp.float8_e5m2], err[jnp.float8_e4m3fn]) > ref.TOLERANCE
    assert err[jnp.bfloat16] < err[jnp.float8_e4m3fn] / 4


# ------------------------------------------------------------ the readers
def _run(cell, op_seconds, counters=None, items=L):
    """A synthetic run: ``op_seconds`` over a busy second, one row traced."""
    return {"cell": CELL, "cfg": cell.cfg, "wl": cell.wl, "flops": cell.flops,
            "counters": counters or {}, "chips": 1, "peak": PEAK,
            "traced_items": items, "xplane": None,
            "trace": {"op_seconds": op_seconds, "busy_s": 1.0}}


def test_the_new_readers_say_nothing_without_theirs(cell):
    """A parent's program (no scope) and a run without a trace: both new
    readers return None and raise nothing; the flash readers count this
    cell's one layer at keys of 192 beside values of 128."""
    for reader in (cell.readers[n] for n in NEW):
        assert reader.read(_run(cell, {"fusion.1": 1.0})) is None
        assert reader.read(dict(_run(cell, {}), trace=None)) is None
    ops, _ = cell.flops.flash_forward(cell.cfg, cell.wl, "full", 1.0)
    run = _run(cell, {"rdt_flash_fwd.1": 2 * ops / PEAK["bf16_flops_per_s"]})
    assert cell.readers["flash_fwd_roofline"].read(run) == pytest.approx(50.0)


def test_the_scope_readers_read_the_operator_and_its_stages(
        cell, monkeypatch):
    """``kda_share``: everything under ``kda``, projections and the scan
    included; ``kda_glue_share``: what lies under ``kda/conv``, ``kda/gate``
    and ``kda/norm`` alone; neither reads a state-space mixer's ``ssm/conv``
    nor is read by ``ssm_glue_share``; a program whose fusions left one
    stage no op of its own still reads the others."""
    from chipbench.trace import scopes

    base = "jit(train_step)/transpose(jvp(TransformerLM.loss_rows))/" \
           "TransformerLM/"
    names = {
        "fusion.1": base + "block_0/kda/in_proj/dot_general",
        "rdt_ssm_conv_fwd.1": base + "block_0/kda/conv/cond/branch_0_fun/"
                                     "rdt_ssm_conv_fwd/pallas_call",
        "fusion.2": base + "block_0/kda/gate/mul",
        "fusion.3": base + "block_0/kda/scan/while/body/dot_general",
        "fusion.4": base + "block_0/kda/norm/mul",
        "fusion.5": base + "block_0/kda/out_proj/dot_general",
        "fusion.6": base + "block_3/attn/q/dot_general",
        "fusion.7": base + "block_1/moe/router/dot_general",
        "fusion.8": base + "lm_head_loss/while/body/dot_general",
        "fusion.9": base + "block_9/ssm/conv/mul"}
    monkeypatch.setattr(scopes, "op_names", lambda path: names)
    run = dict(_run(cell, {name: 0.1 for name in names}), xplane="a trace")
    assert cell.readers["kda_share"].read(run) == pytest.approx(60.0)
    assert cell.readers["kda_glue_share"].read(run) == pytest.approx(30.0)
    assert cell.readers["attn_share"].read(run) == pytest.approx(10.0)
    assert cell.readers["head_loss_share"].read(run) == pytest.approx(10.0)
    glue = manifest.load_module(REPO, "layer_metrics", "ssm_glue_share.py")
    assert glue.read(run) == pytest.approx(10.0)
    del names["fusion.2"]
    run = dict(_run(cell, {name: 0.1 for name in names}), xplane="a trace")
    assert cell.readers["kda_glue_share"].read(run) == pytest.approx(20.0)


def test_the_counters_and_scopes_the_readers_read_are_the_programs():
    from raydp_tpu import metrics

    assert {"kda", "kda/in_proj", "kda/conv", "kda/gate", "kda/scan",
            "kda/norm", "kda/out_proj", "attn", "lm_head_loss"} \
        <= metrics.SCOPE_NAMES
    for name in ("train_kda_layers_total", "kda_scan_total",
                 "kda_chunks_total"):
        assert metrics.METRICS[name].kind == metrics.COUNTER
    for label in ("rescanned", "plain"):
        assert label in metrics.METRICS["train_kda_layers_total"].doc
    with open(os.path.join(REPO, "doc", "observability.md")) as fh:
        doc = fh.read()
    for name in ("kda/in_proj", "kda/conv", "kda/gate", "kda/scan",
                 "kda/norm", "kda/out_proj", "train_kda_layers_total",
                 "kda_scan_total", "kda_chunks_total"):
        assert f"`{name}`" in doc, name
