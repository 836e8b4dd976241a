"""The cell ``lfm2_8ba1b_8k_train`` against the benchmark's contract: its
configuration's widths, the source's ``config.json`` whole and the cut written
into its file; the manifest's entries (of a list other cells share only
``<=``); its operation counts, its parameters and the kernels' operations and
bytes against a hand count; the pipeline's refusals; its rehearsal through
``harness.cut_for_cpu``; the tolerance against the precision below; and each
of its five readers on a synthetic run (and on a run of a program that lacks
what they read, where they say nothing).
"""

import copy
import os
import re
import time

import numpy as np
import pytest

from chipbench import harness, manifest

REPO = manifest.ROOT
CELL = "lfm2_8ba1b_8k_train"
CONFIG = "lfm2-8b-a1b"
PEAK = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
C, A = "conv", "full_attention"
#: the source's config.json as the catalog copies it, whole
SOURCE = {
    "conv_L_cache": 3, "conv_bias": False, "hidden_size": 2048,
    "intermediate_size": 7168,
    "layer_types": [C, C, A, C, C, C, A, C, C, C, A, C, C, C, A, C, C, C, A,
                    C, C, A, C, C],
    "max_position_embeddings": 128000, "model_type": "lfm2_moe",
    "moe_intermediate_size": 1792, "norm_eps": 1e-05, "norm_topk_prob": True,
    "num_attention_heads": 32, "num_dense_layers": 2, "num_experts": 32,
    "num_experts_per_tok": 4, "num_hidden_layers": 24,
    "num_key_value_heads": 8, "rope_theta": 1000000,
    "routed_scaling_factor": 1, "use_expert_bias": True, "vocab_size": 65536}
L, LAYERS, CONVS, ATTNS = 8192, 7, 5, 2
PAIRS = L * (L + 1) // 2        # visible pairs a head a row, full causal
PARAMETERS = 711_389_440
SHARED = ["flash_fwd_roofline", "flash_bwd_roofline", "expert_layer_share",
          "head_loss_share", "expert_load_imbalance", "attn_share",
          "held_slot_share"]
NEW = ["short_conv_share", "short_conv_glue_share", "gated_conv_fwd_roofline",
       "gated_conv_bwd_roofline", "short_conv_kernel_share"]


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
                       if r["name"] == "LFM2-8B-A1B")
        assert row["source_url"] == cfg["source"]
        assert row["config"] == SOURCE
    assert (cfg["layers"], cfg["layers_held"], cfg["layer_pattern_held"],
            cfg["dense_layers"], cfg["seq_len"], cfg["family"]) == (
                LAYERS, [0, 2, 3, 4, 5, 6, 7], "CACCCAC", 1, L,
                "conv_moe_lm")
    assert (cfg["first_expert"], cfg["experts_held"], cfg["vocab_rows_held"],
            cfg["chips_sharing_a_layer"], cfg["head_dim"]) == (
                0, 8, 16384, 4, 64)
    assert cfg["head_dim"] == cfg["hidden_size"] // cfg["num_attention_heads"]
    # the guide's floors: a whole period and four layers after the dense
    # ones, 8 experts, an eighth of the vocabulary
    assert cfg["layers"] - cfg["dense_layers"] >= 4
    assert cfg["experts_held"] >= 8
    assert cfg["vocab_rows_held"] * 8 >= cfg["vocab_size"]
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
    assert "four chips share each layer" in cfg["deployment"]
    assert "pipeline stage" in cfg["deployment"]
    assert cfg["published"]["num_hidden_layers"] == 24
    assert cfg["published"]["layer_pattern"] == "".join(
        "C" if t == C else "A" for t in SOURCE["layer_types"])
    assert (cfg["published"]["num_experts"],
            cfg["published"]["vocab_size"]) == (32, 65536)
    for key in ("in_proj_split", "convolution", "documents", "qk_norm",
                "position_embedding", "routing", "bias_update",
                "tie_word_embeddings", "biases", "aux_loss", "init_std",
                "layers", "experts_held", "vocab_rows_held", "parameters",
                "optimizer", "input", "compute_dtype", "remat_blocks",
                "seq_len", "source_rows", "batch", "head_dim"):
        assert key in cfg["assumed"], key
    assert "8.34 B tied" in cfg["assumed"]["tie_word_embeddings"]
    assert "1e-6" in cfg["assumed"]["routing"]
    assert f"{PARAMETERS:,}" in cfg["assumed"]["parameters"]
    parts = cell.flops.parameters(cfg)
    assert sum(parts.values()) == PARAMETERS
    assert parts["conv"] == CONVS * 16_783_360
    assert parts["attention"] == ATTNS * 10_485_888
    assert parts["embedding_final_norm"] == 16384 * 2048 + 2048  # ONE array
    # the published arithmetic, from the published block
    p = cfg["published"]["parameters"]
    assert p["experts_22_layers_of_32"] == 22 * 32 * p["expert"]
    assert p["conv_operators_18"] == 18 * p["conv_operator"]
    assert p["attention_operators_6"] == 6 * p["attention_operator"]
    assert 8.33e9 < sum(p[k] for k in (
        "experts_22_layers_of_32", "conv_operators_18",
        "attention_operators_6", "dense_feed_forwards_2",
        "embedding")) < 8.35e9


def test_the_manifest_holds_the_cell_and_the_metrics_it_lists(cell):
    """Present, once, each with its reader, in the cells it lists: no place
    in ``per_layer``, ``workloads`` or ``configs`` and no length is asked of
    the manifest, and of a list that other cells share only that it holds
    this cell (``<=``: the next cell does not break it)."""
    m = manifest.load_manifest()
    assert manifest.validate(m) == []
    entry = next(w for w in m["workloads"] if w["name"] == CELL)
    assert (entry["config"], entry["traffic"], entry["chips"]) == (
        CONFIG, "packed_8k_stream", 1)
    assert "2,048 slots" in entry["why"] and "4x" in entry["why"]
    assert [c["name"] for c in m["configs"]].count(CONFIG) == 1
    mine = {e["name"]: e for e in m["per_layer"]
            if CELL in e.get("workloads", [])}
    assert set(SHARED + NEW) <= set(mine)
    assert all(e["moves"] == "train_throughput" for e in mine.values())
    for name in SHARED:
        assert {"trinity_mini_8k_train", CELL} <= set(mine[name]["workloads"])
    for name in NEW:
        e = mine[name]
        assert CELL in e["workloads"]
        assert (e["layer"], e["better"], e["source"], e["unit"]) == {
            "short_conv_share": ("model", "lower", "device_trace", "%"),
            "short_conv_glue_share": ("model", "lower", "device_trace", "%"),
            "gated_conv_fwd_roofline": ("kernels", "higher", "device_trace",
                                        "%"),
            "gated_conv_bwd_roofline": ("kernels", "higher", "device_trace",
                                        "%"),
            "short_conv_kernel_share": ("kernels", "higher",
                                        "program_counter", "%"),
        }[name]
    # every list-free metric is read here too, and no reader that finds
    # nothing in this program (no shared expert, no window, no scan, no loop)
    names = {e["name"] for e in cell.per_layer}
    assert {e["name"] for e in m["per_layer"] if "workloads" not in e} < names
    assert not names & {"shared_expert_share", "window_attn_share",
                        "latent_kv_share", "expert_gemm_roofline",
                        "ssd_fwd_roofline", "ssd_bwd_roofline", "ssm_share",
                        "ssm_glue_share", "rowwise_table_share",
                        "collective_share", "bd_flash_fwd_roofline",
                        "masked_token_share", "loop_carry_share"}
    assert set(cell.readers) == names
    wl = cell.wl
    assert (wl["rows"], wl["seq_len"], wl["batch_per_replica"],
            wl["residency"], wl["checkpoint_interval"], wl["unit_of_work"],
            wl["estimator"], wl["estimator_args"], wl["mesh_spec"]) == (
                16, L, 2, "stream", "final", "tokens", "flax", {}, {})
    band = wl["first_window_loss_band"]
    assert band is None or (band[0] < band[1] and band[1] - band[0] <= 0.5)
    # the four-chip slots stay as they are
    assert sum(w["chips"] == 4 for w in m["workloads"]) <= max(
        1, len(m["workloads"]) // 4)


def test_the_flops_and_the_kernels_work_by_hand(cell):
    """A token's forward is 565 MFLOP, the five convolution operators its
    largest part; the kernels' operations and bytes by hand."""
    cfg, flops = cell.cfg, cell.flops
    assert flops.visible_pairs(L) == PAIRS
    assert flops.layers_of(cfg) == {"C": CONVS, "A": ATTNS}
    parts = flops.forward_flops_per_token(cfg)
    d, q, kv = 2048, 32 * 64, 8 * 64
    assert parts == {
        "conv_projections": CONVS * 2 * (d * 3 * d + d * d),
        "attention_projections": ATTNS * 2 * d * (2 * q + 2 * kv),
        "attention_scores": ATTNS * 2 * 2 * q * (L + 1) / 2,
        "dense_ffn": 3 * 2 * d * 7168,
        "router": 6 * 2 * d * 32,
        "experts": 6 * 4 * (8 / 32) * 3 * 2 * d * 1792,
        "head": 2 * d * 16384}
    total = sum(parts.values())
    assert 564e6 < total < 566e6
    assert parts["conv_projections"] == max(parts.values())
    assert 0.29 < parts["conv_projections"] / total < 0.31
    assert 0.19 < (parts["attention_projections"]
                   + parts["attention_scores"]) / total < 0.20
    assert flops.train_flops_per_item(cfg, cell.wl, {}) == 3.0 * total
    assert flops.num_experts(cfg) == 32
    ops, moved = flops.flash_forward(cfg, cell.wl, "full", 2.0)
    assert ops == 2 * 2 * 2 * q * PAIRS
    assert moved == 2 * L * ((2 * q + 2 * kv) * 2 + 32 * 4)
    ops_b, moved_b = flops.flash_backward(cfg, cell.wl, "full", 2.0)
    assert ops_b == 2.5 * ops
    assert moved_b == 2 * L * ((4 * q + 4 * kv) * 2 + 2 * 32 * 4)
    with pytest.raises(ValueError, match="full causal"):
        flops.flash_forward(cfg, cell.wl, "window", 1.0)
    from chipbench.trace import roofline
    assert roofline.least_seconds(ops, moved, PEAK)[1] == "compute"
    # the gated convolution: 4 and 7 passes of [rows, 2048] bfloat16
    ops, moved = flops.gated_conv_forward(cfg, cell.wl, "conv", 2.0)
    assert moved == 2 * L * 4 * d * 2 == 268_435_456
    assert ops == 2 * L * d * 8
    ops_b, moved_b = flops.gated_conv_backward(cfg, cell.wl, "conv", 2.0)
    assert moved_b == 2 * L * 7 * d * 2 == 469_762_048
    assert roofline.least_seconds(ops, moved, PEAK) == (
        pytest.approx(0.3278e-3, rel=1e-3), "memory")
    assert roofline.least_seconds(ops_b, moved_b, PEAK) == (
        pytest.approx(0.5736e-3, rel=1e-3), "memory")
    with pytest.raises(ValueError, match="'conv'"):
        flops.gated_conv_forward(cfg, cell.wl, "full", 1.0)


def test_a_batch_is_int32_tokens_of_the_rows_held(cell):
    """Rows of ``seq_len`` ids over the 16,384 rows held, the end-of-text id
    at documents' ends, the same seed the same rows, a driver-sized seed
    taken."""
    cfg = copy.deepcopy(cell.cfg)
    cfg["seq_len"] = 4096
    table = cell.pipeline.generate(4, 2 ** 31 + 11, cfg)
    assert table.equals(cell.pipeline.generate(4, 2 ** 31 + 11, cfg))
    tokens = cell.pipeline.reference_inputs(
        table, {"tokens": "tokens", "seq_len": 4096})
    assert tokens.shape == (4, 4096) and tokens.dtype == np.int32
    assert 0 <= tokens.min() and 14000 < tokens.max() <= 16383
    assert 4 < (tokens == 16383).sum() < 60
    assert cell.pipeline.batch_leaves(cell.cfg, cell.wl, {"seq_len": L}, 2) \
        == {"tokens": ((2, L), "int32")}
    assert cell.pipeline.describe(cell.cfg, cell.wl) == {
        "tokens": "tokens", "seq_len": L}
    with pytest.raises(ValueError, match="seq_len"):
        cell.pipeline.describe(cell.cfg, dict(cell.wl, seq_len=4096))


def test_the_cpu_cut_cuts_counts_and_never_a_width(cell):
    cfg, wl = copy.deepcopy(cell.cfg), copy.deepcopy(cell.wl)
    rows = cell.pipeline.cpu_cut(cfg, wl, 1)
    assert rows == 2 and wl["seq_len"] == cfg["seq_len"] == 256
    for key in ("hidden_size", "head_dim", "intermediate_size",
                "moe_intermediate_size", "norm_eps", "rope_theta",
                "conv_L_cache", "num_experts_per_tok", "norm_topk_prob",
                "routed_scaling_factor", "init_std", "bias_update_rate",
                "num_dense_layers", "dense_layers"):
        assert cfg[key] == cell.cfg[key], key
    assert (cfg["num_attention_heads"], cfg["num_key_value_heads"]) == (8, 2)
    assert (cfg["layers"], cfg["layers_held"], cfg["layer_pattern_held"]) \
        == (3, [0, 2, 3], "CAC")
    assert (cfg["num_experts"], cfg["experts_held"], cfg["vocab_size"],
            cfg["vocab_rows_held"]) == (8, 2, 2048, 512)
    model = cell.pipeline.build_model(cfg)
    assert (model.layer_kinds, model.tie_embeddings, model.route_norm_eps,
            model.conv_taps, model.dense_layers, model.qk_norm) == (
                "CBC", True, 1e-6, 3, 1, "head")
    assert (model.dim, model.head_dim, model.ffn_dim, model.dense_ffn_dim) \
        == (2048, 64, 1792, 7168)


def test_the_pipeline_refuses_layers_that_are_not_the_pattern(cell):
    """A ``layers_held`` whose letters in the published ``layer_types`` are
    not ``layer_pattern_held``, and a ``dense_layers`` that is not the held
    layers below ``num_dense_layers``, as ``nemotron``'s pipeline refuses
    its pattern."""
    kinds = cell.pipeline.layer_kinds
    assert kinds(cell.cfg) == "CBCCCBC"
    assert kinds(dict(cell.cfg, layers=5, layers_held=[0, 2, 3, 4, 5],
                      layer_pattern_held="CACCC")) == "CBCCC"
    with pytest.raises(ValueError, match="layer_pattern_held"):
        kinds(dict(cell.cfg, layers_held=[0, 1, 3, 4, 5, 6, 7]))
    with pytest.raises(ValueError, match="layer_pattern_held"):
        kinds(dict(cell.cfg, layers=6))
    with pytest.raises(ValueError, match="dense_layers"):
        kinds(dict(cell.cfg, dense_layers=2))
    with pytest.raises(ValueError, match="dense_layers"):
        kinds(dict(cell.cfg, layers_held=[2, 3, 4, 5, 6, 7, 8],
                   layer_pattern_held="ACCCACC"))
    with pytest.raises(ValueError, match="dense_layers"):    # not leading
        kinds(dict(cell.cfg, layers_held=[3, 2, 0, 4, 5, 6, 7],
                   layer_pattern_held="CACCCAC"))


KERNELS_AT_THE_PUBLISHED_SHAPE = """
import os
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ.setdefault("TPU_LOG_DIR", "disabled")
import jax, jax.numpy as jnp
from jax.experimental import topologies
from jax.sharding import SingleDeviceSharding
from raydp_tpu.ops.flash_attention import flash_attention
from raydp_tpu.ops.short_conv import gated_conv
jax.config.update("jax_enable_compilation_cache", False)
topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
chip = SingleDeviceSharding(topo.devices[0])
a = lambda *shape, dtype=jnp.bfloat16: jax.ShapeDtypeStruct(
    shape, dtype, sharding=chip)
conv = lambda s, t: gated_conv(s, t, 2048)
both = jax.jit(lambda s, t, g: (conv(s, t), jax.vjp(conv, s, t)[1](g)))
text = both.lower(a(2, 8192, 6144), a(3, 2048, dtype=jnp.float32),
                  a(2, 8192, 2048)).compile().as_text()
for name in ("rdt_gated_conv_fwd", "rdt_gated_conv_bwd"):
    assert name in text, name
flash = lambda q, k, v: flash_attention(q, k, v, causal=True)
both = jax.jit(lambda q, k, v, g: jax.vjp(flash, q, k, v)[1](g))
q, kv = a(2, 8192, 32, 64), a(2, 8192, 8, 64)
text = both.lower(q, kv, kv, q).compile().as_text()
for name in ("rdt_flash_fwd", "rdt_flash_bwd_dkdv_dq"):
    assert name in text, name
print("KERNELS COMPILED")
"""


def test_the_kernels_compile_chip_free_at_the_published_shape():
    """The gated convolution's two kernels over ``W_in u [2, 8192, 6144]``
    bfloat16 (row tiles of 512 with their halos, the backward's grid of four
    axes and its five scratch buffers: the tiling, the index maps and the
    VMEM limit are the compiler's to refuse; a row tile of 1,024 is refused
    there, 18.3 MB of 16) and the flash kernels at 32 query heads on 8 K/V
    heads of 64, for a described v5e chip. (The whole train step is
    ``rehearse.py compile``'s: 7.95 GiB of arguments, 5.75 GiB of
    temporaries.)"""
    import subprocess
    import sys
    proc = subprocess.run(
        [sys.executable, "-c", KERNELS_AT_THE_PUBLISHED_SHAPE], cwd=REPO,
        capture_output=True, text=True, timeout=600,
        env={**{k: v for k, v in os.environ.items() if k != "XLA_FLAGS"},
             "PYTHONPATH": REPO})
    if "KERNELS COMPILED" not in proc.stdout and re.search(
            r"topolog|libtpu|lockfile", proc.stderr, re.IGNORECASE):
        pytest.skip(f"no v5e topology can be described here: "
                    f"{proc.stderr[-300:]}")
    assert proc.returncode == 0, proc.stderr[-2000:]


def test_the_rehearsal_through_cut_for_cpu_is_correct(cell, tmp_path):
    """The cell end to end on the CPU at its cut: the five checks, the
    counters of the pairs and of the convolution's path, and the counter
    reader on them."""
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
    assert counters["train_conv_layers_total"]["recomputed"] >= 2
    assert counters["train_attention_layers_total"]["full"] >= 1
    assert counters["short_conv_total"]["kernel"] >= 2
    assert counters["short_conv_total"].get("jnp", 0) == 0
    slots = counters["moe_slots_total"]
    assert 0 < slots["held"] < slots["all"]
    run = {"counters": counters, "flops": cell.flops, "cfg": cell.cfg}
    assert cell.readers["short_conv_kernel_share"].read(run) == 100.0
    assert 0 < cell.readers["held_slot_share"].read(run) < 100
    assert set(result["metrics"]) == {"train_throughput", "setup_s"}


def test_the_tolerance_separates_bfloat16_from_the_precision_below(cell):
    """The reference with every product's operands (the convolution's gates
    and taps among them) rounded to an 8-bit float (the nearest precision
    below the bfloat16 the configuration states) is not correct; rounded to
    bfloat16 it is far closer. At the CPU cut, seeded weights; the chip's
    readings at the published widths are in PERF.md."""
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
def _run(cell, op_seconds, counters=None, items=2 * L):
    """A synthetic run: ``op_seconds`` over a busy second, two rows traced."""
    return {"cell": CELL, "cfg": cell.cfg, "wl": cell.wl, "flops": cell.flops,
            "counters": counters or {}, "chips": 1, "peak": PEAK,
            "traced_items": items, "xplane": None,
            "trace": {"op_seconds": op_seconds, "busy_s": 1.0}}


def test_the_kernel_readers_count_executions_from_the_trace(cell):
    """Ten forward instructions (five layers and their recomputed forwards)
    and five backward ones over two traced rows, each in exactly its
    roofline's time, read 100; in twice the time 50; never clipped; the
    flash readers at heads of 64 likewise."""
    fwd, bwd = (cell.readers[n] for n in NEW[2:4])
    _, moved = cell.flops.gated_conv_forward(cell.cfg, cell.wl, "conv", 2.0)
    least = moved / PEAK["hbm_bytes_per_s"]
    run = _run(cell, {f"rdt_gated_conv_fwd.{i}": least for i in range(10)})
    assert fwd.read(run) == pytest.approx(100.0) and bwd.read(run) is None
    run = _run(cell, {f"rdt_gated_conv_fwd.{i}": 2 * least
                      for i in range(10)})
    assert fwd.read(run) == pytest.approx(50.0)
    run = _run(cell, {"rdt_gated_conv_fwd.1": least / 2})
    assert fwd.read(run) == pytest.approx(200.0)        # a fault shows
    _, moved_b = cell.flops.gated_conv_backward(cell.cfg, cell.wl, "conv",
                                                2.0)
    run = _run(cell, {f"rdt_gated_conv_bwd.{i}": moved_b
                      / PEAK["hbm_bytes_per_s"] for i in range(5)})
    assert bwd.read(run) == pytest.approx(100.0) and fwd.read(run) is None
    ops, _ = cell.flops.flash_forward(cell.cfg, cell.wl, "full", 2.0)
    run = _run(cell, {f"rdt_flash_fwd.{i}": 2 * ops / PEAK["bf16_flops_per_s"]
                      for i in range(ATTNS)})
    assert cell.readers["flash_fwd_roofline"].read(run) == pytest.approx(50.0)


def test_the_new_readers_say_nothing_without_theirs(cell):
    """A parent's program (no counter, no scope, no kernel) and a run without
    a trace: every new reader returns None and raises nothing."""
    share, glue, fwd, bwd, kernel = (cell.readers[n] for n in NEW)
    assert kernel.read(_run(cell, {}, {"short_conv_total": {
        "kernel": 10, "jnp": 0}})) == 100.0
    assert kernel.read(_run(cell, {}, {"short_conv_total": {"jnp": 5}})) == 0
    assert kernel.read(_run(cell, {})) is None
    for reader in (share, glue, fwd, bwd):
        assert reader.read(_run(cell, {"fusion.1": 1.0})) is None
        assert reader.read(dict(_run(cell, {}), trace=None)) is None


def test_the_scope_readers_read_the_operator_and_its_stage(
        cell, monkeypatch):
    """``short_conv_share``: everything under ``short_conv``, projections
    included; ``short_conv_glue_share``: what lies under ``short_conv/conv``
    alone; neither reads a state-space mixer's ``ssm/conv`` nor is read by
    ``ssm_glue_share``."""
    from chipbench.trace import scopes

    base = "jit(train_step)/transpose(jvp(TransformerLM.loss_rows))/" \
           "TransformerLM/"
    names = {
        "fusion.1": base + "block_0/short_conv/in_proj/dot_general",
        "rdt_gated_conv_fwd.1": base + "block_0/short_conv/conv/cond/"
                                       "branch_1_fun/rdt_gated_conv_fwd/"
                                       "pallas_call",
        "rdt_gated_conv_bwd.1": base + "block_0/short_conv/conv/cond/"
                                       "branch_1_fun/rdt_gated_conv_bwd/"
                                       "pallas_call",
        "fusion.2": base + "block_0/short_conv/out_proj/dot_general",
        "fusion.3": base + "block_1/attn/q/dot_general",
        "fusion.4": base + "block_1/moe/router/dot_general",
        "fusion.5": base + "lm_head_loss/while/body/dot_general",
        "fusion.6": base + "block_9/ssm/conv/mul"}
    monkeypatch.setattr(scopes, "op_names", lambda path: names)
    run = dict(_run(cell, {name: 0.1 for name in names}), xplane="a trace")
    assert cell.readers["short_conv_share"].read(run) == pytest.approx(40.0)
    assert cell.readers["short_conv_glue_share"].read(run) \
        == pytest.approx(20.0)
    assert cell.readers["attn_share"].read(run) == pytest.approx(10.0)
    assert cell.readers["head_loss_share"].read(run) == pytest.approx(10.0)
    glue = manifest.load_module(REPO, "layer_metrics", "ssm_glue_share.py")
    assert glue.read(run) == pytest.approx(10.0)


def test_the_counters_and_scopes_the_readers_read_are_the_programs():
    from raydp_tpu import metrics
    from raydp_tpu.ops import short_conv

    assert {"short_conv", "short_conv/in_proj", "short_conv/conv",
            "short_conv/out_proj", "attn", "lm_head_loss"} \
        <= metrics.SCOPE_NAMES
    for name in ("train_conv_layers_total", "short_conv_total"):
        assert metrics.METRICS[name].kind == metrics.COUNTER
    for label in ("recomputed", "plain"):
        assert label in metrics.METRICS["train_conv_layers_total"].doc
    for label in ("kernel", "jnp"):
        assert label in metrics.METRICS["short_conv_total"].doc
    for name in short_conv.KERNEL_NAMES:
        assert name in metrics.SPANS["short_conv/conv"].doc
    with open(os.path.join(REPO, "doc", "observability.md")) as fh:
        doc = fh.read()
    for name in ("short_conv/in_proj", "short_conv/conv",
                 "short_conv/out_proj", "train_conv_layers_total",
                 "short_conv_total"):
        assert f"`{name}`" in doc, name
