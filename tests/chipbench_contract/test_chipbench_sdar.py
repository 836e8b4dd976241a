"""The cell ``sdar_30ba3b_8k_blockdiff_train`` against the benchmark's
contract: its configuration's widths, the source's ``config.json`` whole and
the cut written into its file; the manifest's entries (of a list other cells
share only ``<=``); its operation counts and the two kernels' operations and
bytes against a hand count; the two kernels compiled chip-free at the
published shape; its rehearsal through ``harness.cut_for_cpu``; the tolerance
against the precision below; and each of its four readers on a synthetic run
(and on a run of a program that lacks what they read, where they say
nothing).
"""

import copy
import os
import re
import subprocess
import sys
import time

import numpy as np
import pytest

from chipbench import harness, manifest

REPO = manifest.ROOT
CELL = "sdar_30ba3b_8k_blockdiff_train"
CONFIG = "sdar-30b-a3b-chat"
PEAK = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
#: the source's config.json as the catalog copies it, whole
SOURCE = {
    "attention_bias": False, "decoder_sparse_step": 1, "head_dim": 128,
    "hidden_act": "silu", "hidden_size": 2048, "intermediate_size": 6144,
    "max_position_embeddings": 32768, "max_window_layers": 48,
    "mlp_only_layers": [], "model_type": "sdar_moe",
    "moe_intermediate_size": 768, "norm_topk_prob": True,
    "num_attention_heads": 32, "num_experts": 128, "num_experts_per_tok": 8,
    "num_hidden_layers": 48, "num_key_value_heads": 4, "rms_norm_eps": 1e-06,
    "rope_scaling": None, "rope_theta": 1000000, "sliding_window": None,
    "tie_word_embeddings": False, "use_sliding_window": False,
    "vocab_size": 151936}
L, BD = 8192, 4
PAIRS = L * L + L * BD          # 67,141,632 visible pairs a head a row
PARAMETERS = 645623296
SHARED = ["attn_share", "expert_layer_share", "head_loss_share",
          "expert_load_imbalance", "held_slot_share"]
NEW = ["bd_flash_fwd_roofline", "bd_flash_bwd_roofline",
       "diffusion_noise_share", "masked_token_share"]
OLDER_LM = {"olmoe_1b7b_train", "smallthinker_21ba3b_16k_train",
            "trinity_mini_8k_train", "kanana2_30ba3b_16k_train",
            "nemotron3_nano_30ba3b_16k_train"}


@pytest.fixture()
def cell():
    return manifest.resolve(manifest.load_manifest(), CELL)


def test_the_configuration_carries_the_source_whole_and_every_width(cell):
    cfg = cell.cfg
    for key, value in SOURCE.items():
        assert cfg[key] == value, key
    row = None
    path = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.exists(path):
        import json
        with open(path) as fh:
            row = next(r for r in map(json.loads, filter(str.strip, fh))
                       if r["name"] == "SDAR-30B-A3B-Chat")
        assert row["source_url"] == cfg["source"]
        assert row["config"] == SOURCE
    assert (cfg["layers"], cfg["first_expert"], cfg["experts_held"],
            cfg["vocab_rows_held"], cfg["chips_sharing_a_layer"],
            cfg["seq_len"], cfg["family"]) == (
                6, 0, 16, 18992, 8, L, "blockdiff_moe_lm")
    assert cfg["vocab_rows_held"] * 8 == cfg["vocab_size"]
    assert cfg["experts_held"] * 8 == cfg["num_experts"]
    noise = cfg["diffusion"]
    assert (noise["block_length"], noise["t_min"]) == (BD, 1e-3)
    assert sorted(noise) == ["block_length", "eval_noise_seed", "mask_id",
                             "t_min"]
    # matrices at the family's 0.02; the embedding's own std, and why, is
    # said under ``assumed.init_std``
    assert (cfg["init_std"], cfg["embed_init_std"]) == (0.02, 4.0)
    assert "embed_init_std" in cfg["assumed"]["init_std"]
    # the mask id is the last row but one of the slice, the end-of-text id
    # the last
    assert (noise["mask_id"], cfg["input"]["eos_id"]) == (18990, 18991)
    assert cfg["aux_loss"] == {"balance_weight": 0.0, "z_weight": 0.0}
    trinity = manifest.load_json(REPO, "configs", "trinity-mini.json")
    assert cfg["optimizer"] == trinity["optimizer"]
    entry = next(c for c in manifest.load_manifest()["configs"]
                 if c["name"] == CONFIG)
    assert entry["reduced"] == cfg["reduced"] == [
        "layers", "experts_held", "vocab_rows_held"]
    assert not [k for k in cfg["reduced"] if re.search(
        r"(_dim|_rank|hidden|intermediate|width|head|latent|state|proj"
        r"|experts_per_tok)", k)]
    assert entry["source"] == cfg["source"]
    assert entry["file"] == f"chipbench/configs/{CONFIG}.json"
    assert "eight chips share each layer" in cfg["deployment"]
    for key in ("layer", "routing", "block_length", "schedule", "objective",
                "mask", "mask_id", "noise_keys", "aux_loss", "optimizer",
                "input", "compute_dtype", "remat_blocks", "seq_len",
                "source_rows", "parameters", "layers", "experts_held",
                "vocab_rows_held", "batch"):
        assert key in cfg["assumed"], key
    assert f"{PARAMETERS:,}" in cfg["assumed"]["parameters"]
    assert sum(cell.flops.parameters(cfg).values()) == PARAMETERS


def test_the_manifest_holds_the_cell_and_the_metrics_it_lists(cell):
    """Present, once, each with its reader, in the cells it lists: no place
    in ``per_layer``, ``workloads`` or ``configs`` and no length is asked of
    the manifest, and of a list that other cells share only that it holds
    this cell and those it held (``<=``: the next cell does not break it)."""
    m = manifest.load_manifest()
    assert manifest.validate(m) == []
    entry = next(w for w in m["workloads"] if w["name"] == CELL)
    assert (entry["config"], entry["traffic"], entry["chips"]) == (
        CONFIG, "packed_8k_stream", 1)
    assert "TRAINED token" in entry["why"]
    assert [c["name"] for c in m["configs"]].count(CONFIG) == 1
    mine = {e["name"]: e for e in m["per_layer"]
            if CELL in e.get("workloads", [])}
    assert set(SHARED + NEW) <= set(mine)
    assert all(e["moves"] == "train_throughput" for e in mine.values())
    for name in SHARED:
        held = OLDER_LM - ({"olmoe_1b7b_train"} if name == "held_slot_share"
                           else set())
        assert held | {CELL} <= set(mine[name]["workloads"])
    for name in NEW:
        e = mine[name]
        assert CELL in e["workloads"] and e["unit"] == "%"
        assert (e["layer"], e["better"], e["source"]) == {
            "bd_flash_fwd_roofline": ("kernels", "higher", "device_trace"),
            "bd_flash_bwd_roofline": ("kernels", "higher", "device_trace"),
            "diffusion_noise_share": ("model", "lower", "device_trace"),
            "masked_token_share": ("model", "higher", "program_counter"),
        }[name]
    # every list-free metric is read here too, and no reader that finds
    # nothing in this program
    names = {e["name"] for e in cell.per_layer}
    assert {e["name"] for e in m["per_layer"] if "workloads" not in e} < names
    assert not names & {"flash_fwd_roofline", "flash_bwd_roofline",
                        "window_attn_share", "shared_expert_share",
                        "latent_kv_share", "expert_gemm_roofline",
                        "ssd_fwd_roofline", "ssd_bwd_roofline", "ssm_share",
                        "ssm_glue_share", "rowwise_table_share",
                        "collective_share"}
    assert set(cell.readers) == names
    wl = cell.wl
    assert (wl["seq_len"], wl["residency"], wl["checkpoint_interval"],
            wl["unit_of_work"], wl["estimator_args"]) == (
                L, "stream", "final", "tokens", {})
    assert 6 <= wl["rows"] <= 12
    # 8,192 trained tokens = 16,384 positions an optimizer step, as one row
    assert wl["batch_per_replica"] * L == 8192
    band = wl["first_window_loss_band"]
    assert band is None or (band[0] < band[1] and band[1] - band[0] <= 0.5)


def test_the_flops_and_the_kernels_work_by_hand(cell):
    """A trained token is two positions through every layer and one through
    the head; a row's visible pairs are counted pair by pair at a small size
    and are ``L^2 + L Bd`` at the published one; the kernels' operations and
    bytes by hand."""
    cfg, flops = cell.cfg, cell.flops
    for length, block in ((32, 4), (24, 1), (64, 32)):
        queries = np.arange(2 * length)
        assert cell.reference.visible(queries, length, block).sum() \
            == flops.visible_pairs(length, block) == length * (length + block)
    assert flops.visible_pairs(L, BD) == PAIRS == 67141632
    parts = flops.forward_flops_per_token(cfg)
    d, q, kv = 2048, 32 * 128, 4 * 128
    assert parts == {
        "attention_projections": 2 * 6 * 2 * d * (2 * q + 2 * kv),
        "attention_scores": 6 * 2 * 2 * q * (L + BD),
        "router": 2 * 6 * 2 * d * 128,
        "experts": 2 * 6 * 8 * (16 / 128) * 3 * 2 * d * 768,
        "head": 2 * d * 18992}
    per_item = flops.train_flops_per_item(cfg, cell.wl, {})
    assert per_item == 3.0 * sum(parts.values())
    # 4.37 GFLOP a trained token (a position of the older 2048-wide cells
    # is about 2): at the 14 k tokens/s of the chip's first reading 31% of
    # the bf16 peak
    assert 4.3e9 < per_item < 4.4e9
    assert flops.num_experts(cfg) == 128
    ops, moved = flops.bd_flash_forward(cfg, cell.wl, "blockdiff", 2.0)
    assert ops == 2 * 2 * 2 * q * PAIRS
    assert moved == 2 * 2 * L * ((2 * q + 2 * kv) * 2 + 32 * 4)
    ops_b, moved_b = flops.bd_flash_backward(cfg, cell.wl, "blockdiff", 2.0)
    assert ops_b == 2.5 * ops
    assert moved_b == 2 * 2 * L * ((3 * q + 4 * kv) * 2 + 2 * 32 * 4)
    # compute-bound by a wide margin: the roofline is the MXU's
    from chipbench.trace import roofline
    assert roofline.least_seconds(ops, moved, PEAK)[1] == "compute"
    assert roofline.least_seconds(ops_b, moved_b, PEAK)[1] == "compute"


def test_a_batch_is_int32_tokens_below_the_mask_id(cell):
    """Rows of ``seq_len`` ids drawn below the mask id, the end-of-text id at
    documents' ends, the same seed the same rows, a driver-sized seed taken;
    ``reference_inputs`` hands the reference the noised copy the program's
    plain call draws, and only masked tokens differ."""
    cfg = copy.deepcopy(cell.cfg)
    cfg["seq_len"] = 512
    table = cell.pipeline.generate(4, 2 ** 31 + 11, cfg)
    again = cell.pipeline.generate(4, 2 ** 31 + 11, cfg)
    assert table.equals(again) and table.num_rows == 4
    info = {"tokens": "tokens", "seq_len": 512,
            "diffusion": dict(cfg["diffusion"])}
    tokens, noised, level = cell.pipeline.reference_inputs(table, info)
    assert tokens.shape == noised.shape == (4, 512)
    assert tokens.dtype == np.int32 and level.shape == (4, 128)
    ends = tokens == 18991
    assert tokens[~ends].max() < 18990 and 0 < ends.sum() < 40
    masked = noised != tokens
    assert (noised[masked] == 18990).all() and 0.2 < masked.mean() < 0.8
    assert 1e-3 < level.min() and level.max() <= 1.0
    assert cell.pipeline.describe(cell.cfg, cell.wl)["diffusion"] \
        == cell.cfg["diffusion"]
    assert cell.pipeline.batch_leaves(cell.cfg, cell.wl, {"seq_len": L}, 1) \
        == {"tokens": ((1, L), "int32")}


def test_the_cpu_cut_cuts_counts_and_never_a_width(cell):
    cfg, wl = copy.deepcopy(cell.cfg), copy.deepcopy(cell.wl)
    rows = cell.pipeline.cpu_cut(cfg, wl, 1)
    assert rows == 4 and wl["seq_len"] == cfg["seq_len"] == 128
    for key in ("hidden_size", "head_dim", "moe_intermediate_size",
                "num_experts_per_tok", "rms_norm_eps", "rope_theta",
                "norm_topk_prob", "hidden_act"):
        assert cfg[key] == cell.cfg[key], key
    assert cfg["diffusion"]["block_length"] == BD
    assert cfg["num_attention_heads"] // cfg["num_key_value_heads"] == 8
    assert cfg["experts_held"] * 8 == cfg["num_experts"]
    # the one thing cut that is no count, and why, is said in its docstring
    assert cfg["diffusion"]["t_min"] == 0.5
    assert "lower clip" in cell.pipeline.cpu_cut.__doc__


KERNELS_AT_THE_PUBLISHED_SHAPE = """
import os
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ.setdefault("TPU_LOG_DIR", "disabled")
import jax, jax.numpy as jnp
from jax.experimental import topologies
from jax.sharding import SingleDeviceSharding
from raydp_tpu.ops.flash_attention import flash_attention
jax.config.update("jax_enable_compilation_cache", False)
topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
chip = SingleDeviceSharding(topo.devices[0])
a = lambda heads: jax.ShapeDtypeStruct((1, 16384, heads, 128), jnp.bfloat16,
                                       sharding=chip)
f = lambda q, k, v: flash_attention(q, k, v, blockdiff=4)
both = jax.jit(lambda q, k, v, g: jax.vjp(f, q, k, v)[1](g))
text = both.lower(a(32), a(4), a(4), a(32)).compile().as_text()
for name in ("rdt_flash_bd_fwd", "rdt_flash_bd_bwd_dkdv_dq"):
    assert name in text, name
print("KERNELS COMPILED")
"""


def test_the_two_kernels_compile_chip_free_at_the_published_shape():
    """The forward and the one-kernel backward under the block-diffusion
    mask at 16,384 positions, 32 query heads on 4 K/V heads of 128, bfloat16,
    for a described v5e chip: the tiling, the index maps of the compact walk
    and the VMEM limit are the compiler's to refuse. (The whole train step is
    ``rehearse.py compile``'s: 7.22 GiB of arguments, 8.46 GiB of
    temporaries; the fit's peak on the chip is in PERF.md, PR 50.)"""
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
    counters of the noise, of the share and of the mask, and the counter
    readers on them."""
    from raydp_tpu import metrics as rdt_metrics

    rehearsal = harness.cut_for_cpu(cell, tmp_path)
    os.makedirs(tmp_path / ".cache")
    (tmp_path / ".cache" / f"{CELL}.json").write_text('{"t_e": 1.0}')
    # the registry is the process's: what other tests of this worker counted
    # is taken off
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
    assert counters["train_attention_layers_total"]["blockdiff"] >= 1
    assert counters["flash_mask_total"]["blockdiff"] >= 2
    tokens = counters["train_diffusion_tokens_total"]
    assert tokens["all"] % 128 == 0 and 0 < tokens["masked"] < tokens["all"]
    # the one expert layer routes 2 x 128 positions, 8 experts a position,
    # for 128 trained tokens
    assert counters["moe_slots_total"]["all"] == 2 * 8 * tokens["all"]
    # the counter readers on the run's own counters (an untraced run prints
    # the end-to-end metrics alone)
    run = {"counters": counters, "flops": cell.flops, "cfg": cell.cfg}
    share = cell.readers["masked_token_share"].read(run)
    assert 60 < share < 90                      # the cut's t ~ U(0.5, 1]
    assert 0 < cell.readers["held_slot_share"].read(run) < 100
    assert cell.readers["expert_load_imbalance"].read(run) >= 1.0
    assert set(result["metrics"]) == {"train_throughput", "setup_s"}


def test_the_tolerance_separates_bfloat16_from_the_precision_below(cell):
    """The reference with every product's operands rounded to an 8-bit float
    (the nearest precision below the bfloat16 the configuration states) is
    not correct; rounded to bfloat16 it is far closer. At the CPU cut, seeded
    weights; the chip's readings at the published widths are in PERF.md."""
    import jax
    import jax.numpy as jnp

    from chipbench.harness import relative_rms_error
    cfg, ref = copy.deepcopy(cell.cfg), cell.reference
    cell.pipeline.cpu_cut(cfg, copy.deepcopy(cell.wl), 1)
    cfg["seq_len"], cfg["layers"] = 64, 1
    inputs = cell.pipeline.reference_inputs(
        cell.pipeline.generate(2, 11, cfg),
        {"tokens": "tokens", "seq_len": 64,
         "diffusion": dict(cfg["diffusion"])})
    variables = dict(jax.jit(cell.pipeline.build_model(cfg).init)(
        jax.random.PRNGKey(11), inputs[0][:1]))
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


def test_the_roofline_readers_count_executions_from_the_trace(cell):
    """Six layers' forward kernels over two traced rows in exactly the
    roofline's time read 100, in twice the time 50; the backward likewise,
    one execution a ``dkdv`` instruction whether the backward is one kernel
    or the pair; the causal and windowed kernels' names are not theirs, and
    theirs are not the accepted readers'."""
    fwd, bwd = (cell.readers[n] for n in NEW[:2])
    ops, _ = cell.flops.bd_flash_forward(cell.cfg, cell.wl, "blockdiff", 2.0)
    least = ops / PEAK["bf16_flops_per_s"]
    run = _run(cell, {f"rdt_flash_bd_fwd.{i}": least for i in range(6)})
    assert fwd.read(run) == pytest.approx(100.0)
    assert bwd.read(run) is None
    run = _run(cell, {f"rdt_flash_bd_fwd.{i}": 2 * least for i in range(6)})
    assert fwd.read(run) == pytest.approx(50.0)
    least_b = 2.5 * least
    one = _run(cell, {f"rdt_flash_bd_bwd_dkdv_dq.{i}": least_b
                      for i in range(6)})
    assert bwd.read(one) == pytest.approx(100.0) and fwd.read(one) is None
    pair = {f"rdt_flash_bd_bwd_dkdv.{i}": 0.6 * least_b for i in range(6)}
    pair.update({f"rdt_flash_bd_bwd_dq.{i}": 0.4 * least_b for i in range(6)})
    assert bwd.read(_run(cell, pair)) == pytest.approx(100.0)
    other = _run(cell, {"rdt_flash_fwd.1": 1.0, "rdt_flash_win_fwd.2": 1.0,
                        "rdt_flash_bwd_dkdv_dq.3": 1.0,
                        "rdt_flash_win_bwd_dkdv_dq.4": 1.0})
    assert fwd.read(other) is None and bwd.read(other) is None
    from raydp_tpu.ops import flash_attention as fa
    accepted = [r"^rdt_flash(_win)?_fwd", r"^rdt_flash(_win)?_bwd_"]
    assert not any(re.search(rx, name) for rx in accepted
                   for name in fa.BLOCKDIFF_KERNEL_NAMES)
    assert all(re.search(fwd.KERNEL if "fwd" in name else bwd.KERNEL, name)
               for name in fa.BLOCKDIFF_KERNEL_NAMES)
    # a parent's program (no such kernel), a run without a trace, a family
    # that counts none: nothing, and no error
    assert fwd.read(dict(run, trace=None)) is None
    dlrm = manifest.resolve(manifest.load_manifest(), "dlrm_criteo_stream")
    assert bwd.read(dict(one, flops=dlrm.flops)) is None


def test_the_counter_and_the_scope_readers_say_nothing_without_theirs(cell):
    share, noise = cell.readers["masked_token_share"], cell.readers[
        "diffusion_noise_share"]
    run = _run(cell, {}, {"train_diffusion_tokens_total": {
        "masked": 4100.0, "all": 8192.0}})
    assert share.read(run) == pytest.approx(100 * 4100 / 8192)
    assert share.read(_run(cell, {})) is None
    assert share.read(_run(cell, {}, {"train_diffusion_tokens_total": {
        "all": 8192.0}})) is None
    # no stored program names the scope (a parent's trace): nothing
    assert noise.read(_run(cell, {"fusion.1": 1.0})) is None
    assert noise.read(dict(_run(cell, {}), trace=None)) is None


def test_the_counters_scopes_and_kernels_the_readers_read_are_the_programs():
    from raydp_tpu import metrics
    from raydp_tpu.ops import flash_attention as fa

    assert {"diffusion", "attn_blockdiff", "attn", "lm_head_loss",
            "moe/experts"} <= metrics.SCOPE_NAMES
    assert fa.BLOCKDIFF_KERNEL_NAMES == (
        "rdt_flash_bd_fwd", "rdt_flash_bd_bwd_dkdv", "rdt_flash_bd_bwd_dq",
        "rdt_flash_bd_bwd_dkdv_dq")
    for name in ("train_diffusion_tokens_total", "flash_mask_total",
                 "flash_tiles_total", "moe_slots_total"):
        assert metrics.METRICS[name].kind == metrics.COUNTER
    for label in ("masked", "all"):
        assert label in metrics.METRICS["train_diffusion_tokens_total"].doc
    for label in ("causal", "window", "blockdiff"):
        assert label in metrics.METRICS["flash_mask_total"].doc
