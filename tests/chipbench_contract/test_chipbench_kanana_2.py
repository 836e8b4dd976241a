"""The cell ``kanana2_30ba3b_16k_train`` against the benchmark's contract: its
configuration's widths and the cut written into its file, the parameter table
to the parameter (and the bias outside it), its operation counts and the
flash kernels' operations and bytes at TWO widths against a hand count, its
train step compiled chip-free at the published widths, its rehearsal through
``harness.cut_for_cpu``, and each of the per-layer readers that list it on a
synthetic run handed the cell (and on a DLRM run, where they say nothing).
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
CELL = "kanana2_30ba3b_16k_train"
CONFIG = "kanana-2-30b-a3b"
PEAK = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
WIDTHS = {"hidden_size": 2048, "num_attention_heads": 32,
          "kv_lora_rank": 512, "qk_nope_head_dim": 128,
          "qk_rope_head_dim": 64, "qk_head_dim": 192, "v_head_dim": 128,
          "intermediate_size": 6144, "moe_intermediate_size": 768,
          "num_experts_per_tok": 6, "n_shared_experts": 2,
          "routed_scaling_factor": 2.448, "rope_theta": 1000000,
          "rms_norm_eps": 1e-06, "bias_update_rate": 0.001}
T = 16384
PAIRS = T * (T + 1) // 2            # 134,225,920 visible pairs a sequence
PARAMETERS = 687502336
#: the per-layer metrics that list this cell: the device's first, then the
#: counters'
METRICS = ["expert_layer_share", "shared_expert_share", "attn_share",
           "latent_kv_share", "flash_fwd_roofline", "flash_bwd_roofline",
           "head_loss_share", "expert_load_imbalance", "held_slot_share"]


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
                if r["name"] == "kanana-2-30b-a3b-instruct-2601")


def test_the_configuration_carries_the_source_whole_and_every_width(cell):
    cfg = cell.cfg
    for key, value in WIDTHS.items():
        assert cfg[key] == value, key
    assert cfg["q_lora_rank"] is None and cfg["rope_interleave"] is True
    # what the source states stays beside what is held here
    assert (cfg["num_hidden_layers"], cfg["first_k_dense_replace"],
            cfg["n_routed_experts"], cfg["vocab_size"],
            cfg["max_position_embeddings"]) == (48, 1, 128, 128256, 32768)
    assert (cfg["layers"], cfg["layers_held"], cfg["dense_layers"],
            cfg["first_expert"], cfg["experts_held"], cfg["vocab_rows_held"],
            cfg["chips_sharing_a_layer"], cfg["seq_len"]) == (
                6, [0, 1, 2, 3, 4, 5], 1, 0, 16, 16032, 8, T)
    assert cfg["vocab_rows_held"] * 8 == cfg["vocab_size"]
    assert cfg["experts_held"] * 8 == cfg["n_routed_experts"]
    assert (cfg["scoring_func"], cfg["norm_topk_prob"], cfg["topk_method"],
            cfg["n_group"], cfg["topk_group"], cfg["family"]) == (
                "sigmoid", True, "noaux_tc", 1, 1, "mla_moe_lm")
    assert cfg["aux_loss"] == {"balance_weight": 0.0, "z_weight": 0.0}
    assert cfg["input"]["eos_id"] == cfg["vocab_rows_held"] - 1
    entry = next(c for c in manifest.load_manifest()["configs"]
                 if c["name"] == CONFIG)
    assert entry["reduced"] == cfg["reduced"] == [
        "layers", "experts_held", "vocab_rows_held"]
    assert not [k for k in cfg["reduced"] if re.search(
        r"(_dim|_rank|hidden|intermediate|width|head|latent|proj"
        r"|experts_per_tok)", k)]
    assert entry["source"] == cfg["source"]
    assert entry["file"] == f"chipbench/configs/{CONFIG}.json"
    assert "eight chips share each layer" in cfg["deployment"]
    for key in ("latent_attention", "latent_norm", "rope_interleave",
                "softmax_scale", "routing", "bias_update", "shared_experts",
                "dense_layer", "norms", "biases", "aux_loss", "optimizer",
                "init_std", "input", "compute_dtype", "remat_blocks",
                "seq_len", "parameters", "layers", "experts_held",
                "vocab_rows_held"):
        assert key in cfg["assumed"], key
    row = _catalog_row()
    if row is not None:     # every key of the catalog's config, unchanged
        assert row["source_url"] == cfg["source"]
        for key, value in row["config"].items():
            assert cfg[key] == value, key


def test_the_manifest_holds_the_cell_and_the_metrics_it_lists(cell):
    """Present, once, each with its reader, in the cells it lists: no place
    in ``per_layer``, ``workloads`` or ``configs`` and no length is asked of
    the manifest, and no list is asked to END with this cell."""
    m = manifest.load_manifest()
    assert manifest.validate(m) == []
    entry = next(w for w in m["workloads"] if w["name"] == CELL)
    assert (entry["config"], entry["traffic"], entry["chips"]) == (
        CONFIG, "packed_16k_stream", 1)
    assert [c["name"] for c in m["configs"]].count(CONFIG) == 1
    mine = [e for e in m["per_layer"] if CELL in e.get("workloads", [])]
    assert sorted(e["name"] for e in mine) == sorted(METRICS)
    assert all(e["moves"] == "train_throughput" for e in mine)
    assert {e["name"]: e["source"] for e in mine} == {
        **{n: "device_trace" for n in METRICS[:7]},
        **{n: "program_counter" for n in METRICS[7:]}}
    assert all(e["unit"] == "%" for e in mine
               if e["name"] != "expert_load_imbalance")
    lists = {e["name"]: e["workloads"] for e in mine}
    # the latent path is this cell's alone; the shared expert is read by the
    # cells that have one, the held share by those that hold one, the rest
    # by every LM cell
    assert lists["latent_kv_share"] == [CELL]
    assert {"trinity_mini_8k_train", CELL} == set(
        lists["shared_expert_share"])
    assert "olmoe_1b7b_train" not in lists["held_slot_share"]
    assert all({"olmoe_1b7b_train", "smallthinker_21ba3b_16k_train",
                "trinity_mini_8k_train", CELL} <= set(lists[n])
               for n in METRICS if n not in (
                   "shared_expert_share", "held_slot_share",
                   "latent_kv_share"))
    latent = next(e for e in mine if e["name"] == "latent_kv_share")
    assert (latent["layer"], latent["better"]) == ("model", "lower")
    # every list-free metric is read here too, and no other cell's
    names = {e["name"] for e in cell.per_layer}
    assert {e["name"] for e in m["per_layer"] if "workloads" not in e} < names
    assert not names & {"expert_gemm_roofline", "window_attn_share",
                        "rowwise_table_share", "collective_share"}
    assert set(cell.readers) == names
    wl = cell.wl
    assert (wl["seq_len"], wl["residency"], wl["checkpoint_interval"],
            wl["unit_of_work"]) == (T, "stream", "final", "tokens")
    assert 4 <= wl["rows"] <= 8
    # 16,384 tokens an optimizer step, as one row
    assert wl["batch_per_replica"] * T == 16384
    assert wl["estimator_args"] == {}


def test_the_parameter_table_to_the_parameter(cell):
    """ISSUE 40's table, from the configuration's sizes and from the model's
    own tree at the published widths (shapes only: nothing is allocated);
    the bias and its counts lie outside the parameters."""
    import jax

    parts = cell.flops.parameters(cell.cfg)
    assert parts["attention"] == 6 * 26345984
    assert 12582912 + 1179648 + 512 + 4194304 + 8388608 == 26345984
    assert parts["norms"] == 6 * 4096
    assert parts["dense_ffn"] == 37748736
    assert parts["attention"] // 6 + 4096 + parts["dense_ffn"] == 64098816
    assert (parts["router"], parts["shared_experts"], parts["experts"]) == (
        5 * 262144, 5 * 9437184, 5 * 75497472)
    assert 26345984 + 4096 + 262144 + 9437184 + 75497472 == 111546880
    assert parts["embedding_head_final_norm"] == 65669120
    assert sum(parts.values()) == PARAMETERS
    assert 64098816 + 5 * 111546880 + 65669120 == PARAMETERS
    assert round(PARAMETERS * 16 / 2 ** 30, 2) == 10.24     # GiB of state
    # a query latent, which this model leaves null, would be counted too
    assert cell.flops.attention_projection_weights(
        dict(cell.cfg, q_lora_rank=1536)) == 26345472 - 12582912 + 1536 * (
            2048 + 6144)
    model = cell.pipeline.build_model(cell.cfg)
    shapes = jax.eval_shape(lambda: model.init(
        jax.random.PRNGKey(0), np.zeros((1, 8), np.int32)))
    assert sum(int(np.prod(s.shape))
               for s in jax.tree.leaves(shapes["params"])) == PARAMETERS
    state = shapes["batch_stats"]
    assert sorted(state) == [f"block_{i}" for i in range(1, 6)]
    assert sum(int(np.prod(b["moe"]["bias"].shape))
               for b in state.values()) == 5 * 128
    assert all(b["moe"]["counts"].shape == (128,) and
               b["moe"]["bias"].dtype == np.float32 for b in state.values())
    block = shapes["params"]["block_1"]
    assert sorted(block["attn"]) == ["kv_a", "kv_b", "kv_norm", "o", "q"]
    assert block["attn"]["q"]["kernel"].shape == (2048, 32, 192)
    assert block["attn"]["kv_a"]["kernel"].shape == (2048, 576)
    assert block["attn"]["kv_norm"]["scale"].shape == (512,)
    assert block["attn"]["kv_b"]["kernel"].shape == (512, 32, 256)
    assert block["attn"]["o"]["kernel"].shape == (32, 128, 2048)
    assert sorted(block) == ["attn", "ln1", "ln2", "moe"]
    assert block["moe"]["router"].shape == (2048, 128)
    assert block["moe"]["experts_down"].shape == (16, 768, 2048)
    assert block["moe"]["shared_up"]["kernel"].shape == (2048, 1536)
    assert shapes["params"]["block_0"]["gate"]["kernel"].shape == (2048, 6144)
    assert "moe" not in shapes["params"]["block_0"]
    assert shapes["params"]["lm_head"]["kernel"].shape == (2048, 16032)
    assert model.attention_layers == {"window": 0, "full": 6, "latent": 6}


def test_the_flops_and_the_kernels_work_at_two_widths_by_hand(cell):
    work, cfg = cell.flops, cell.cfg
    assert work.visible_pairs(T) == PAIRS == 134225920
    parts = work.forward_flops_per_token(cfg)
    assert parts["attention_projections"] == 6 * 2 * 26345472       # 316.1 M
    # QK^T at 192 and PV at 128 over (T + 1) / 2 keys a query, 32 heads
    a_layer = 2 * 32 * (192 + 128) * (T + 1) / 2
    assert round(a_layer / 1e6, 1) == 167.8
    assert parts["attention_scores"] == 6 * a_layer                 # 1,006.7 M
    assert parts["dense_ffn"] == 3 * 2 * 2048 * 6144                # 75.5 M
    assert parts["router"] == 5 * 2 * 2048 * 128                    # 2.6 M
    assert parts["shared_experts"] == 5 * 2 * 3 * 2 * 2048 * 768    # 94.4 M
    assert parts["experts"] == 5 * 6 * 0.125 * 3 * 2 * 2048 * 768   # 35.4 M
    assert parts["head"] == 2 * 2048 * 16032                        # 65.7 M
    assert round(sum(parts.values()) / 1e6) == 1596
    assert round(100 * parts["attention_scores"] / sum(parts.values())) == 63
    assert work.train_flops_per_item(cfg, cell.wl, {}) == 3 * sum(
        parts.values())
    # the sequence length is the configuration's, whatever a caller's
    # workload says
    assert work.train_flops_per_item(cfg, {"seq_len": 32768}, {}) == 3 * sum(
        parts.values())
    assert work.num_experts(cfg) == 128
    # one execution of one layer's kernels over 2 sequences (the contract of
    # ``trace/executions.py``): forward QK^T at 192 + PV at 128; q and k read
    # at 192, v read and the output written at 128, K and V once a head
    fwd, fwd_bytes = work.flash_forward(cfg, cell.wl, "full", 2)
    assert fwd == 2 * 32 * (2 * 192 + 2 * 128) * PAIRS
    assert fwd_bytes == 2 * T * 32 * ((2 * 192 + 2 * 128) * 2 + 4)
    # backward: scores again, dK, dQ at 192; dP, dV at 128
    bwd, bwd_bytes = work.flash_backward(cfg, cell.wl, "full", 2)
    assert bwd == 2 * 32 * 2 * (3 * 192 + 2 * 128) * PAIRS
    assert bwd_bytes == 2 * T * 32 * ((4 * 192 + 4 * 128) * 2 + 2 * 4)
    assert bwd / fwd == 2.6         # where one width gives 2.5
    for flops, moved in ((fwd, fwd_bytes), (bwd, bwd_bytes)):   # compute-bound
        assert flops / PEAK["bf16_flops_per_s"] > 5 * moved / PEAK[
            "hbm_bytes_per_s"]
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
    for key in ("hidden_size", "kv_lora_rank", "qk_nope_head_dim",
                "qk_rope_head_dim", "v_head_dim", "intermediate_size",
                "moe_intermediate_size", "num_experts_per_tok",
                "n_shared_experts", "routed_scaling_factor", "rope_theta",
                "rms_norm_eps", "bias_update_rate"):
        assert cfg[key] == WIDTHS[key], key
    # the dense layer and one expert layer
    assert (cfg["layers"], cfg["dense_layers"], cfg["layers_held"]) == (
        2, 1, [0, 1])
    assert (cfg["n_routed_experts"], cfg["experts_held"],
            cfg["num_experts_per_tok"]) == (16, 2, 6)
    assert cfg["experts_held"] * 8 == cfg["n_routed_experts"]
    assert (cfg["vocab_size"], cfg["vocab_rows_held"]) == (4096, 512)
    assert (cfg["seq_len"], cfg["num_attention_heads"]) == (256, 8)
    assert rows == 2 and wl["batch_per_replica"] == 1 and wl["seq_len"] == 256


def test_the_train_step_compiles_chip_free_at_the_published_widths():
    """``rehearse.py compile``: the estimator's own train step for a described
    v5e chip, the flash kernels at keys of 192 beside values of 128 in their
    1024 x 1024 blocks (the scoped-VMEM limit is the compiler's to refuse),
    the held experts' walk, the shared MLP, the bias's collection and the
    recomputed blocks included, one 16,384-token row a step. The compiler
    refuses a program that does not fit the chip, so compiling is the check;
    the temporaries it reports (5.96 GiB) are the bound here, and the fit's
    peak on the chip is in PERF.md (PR 40)."""
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
    assert 7.65 < gib["arguments"] < 7.72        # weights, mu, nu in float32
    assert gib["temporaries"] < 6.3
    assert "collectives {}" in line


def test_the_rehearsal_through_cut_for_cpu_is_correct(cell, tmp_path):
    """The cell end to end on the CPU at its cut: the five checks, the
    counters of the share, and the metrics a CPU run can read."""
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
    assert counters["train_attention_layers_total"]["latent"] >= 2
    got = {k: v["value"] for k, v in result["metrics"].items()}
    assert 0 < got["held_slot_share"] < 100
    # the imbalance counts with the experts of the configuration as it is
    # run: the cut's 16 here, the published 128 on the chip
    assert got["expert_load_imbalance"] >= 1.0
    # no TPU plane off the chip: the device readers say nothing
    assert not set(METRICS[:7]) & set(got)


def test_the_tolerance_separates_bfloat16_from_the_precision_below(cell):
    """The reference with every product's operands rounded to an 8-bit float
    (the nearest precision below the bfloat16 the configuration states) is
    not correct; rounded to bfloat16 it is far closer. At the CPU cut,
    seeded weights and biases; the chip's readings at the published widths
    are in PERF.md."""
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
    assert err[jnp.bfloat16] < ref.TOLERANCE
    assert min(err[jnp.float8_e5m2], err[jnp.float8_e4m3fn]) \
        > 1.5 * ref.TOLERANCE
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
PROGRAM = {
    "rdt_flash_fwd.1": STEP + "block_1/attn/attn_full/pallas_call",
    "rdt_flash_fwd.2": STEP + "block_2/attn/attn_full/pallas_call",
    "rdt_flash_bwd_dkdv.1": STEP + "block_1/attn/attn_full/pallas_call",
    "rdt_flash_bwd_dq.1": STEP + "block_1/attn/attn_full/pallas_call",
    "fusion.2": STEP + "block_1/attn/q/dot_general",
    "fusion.3": STEP + "block_1/attn/latent/kv_b/dot_general",
    "fusion.5": STEP + "block_1/attn/latent/concatenate",
    "fusion.1": STEP + "block_1/moe/router/dot_general",
    "fusion.4": STEP + "block_1/moe/shared/shared_up/dot_general",
    "ragged-dot-none.3": "ragged-dot-none",     # the chip's compiler's name
    "fusion.7": STEP + "block_1/moe/combine/reduce_sum",
    "fusion.8": STEP + "block_0/mlp/up/dot_general",
    "fusion.9": STEP + "lm_head_loss/while/body/dot_general",
    "fusion.11": "jit(train_step)/mul",
}
# one step's device events, microseconds: (name, start within the step, length)
STEP_EVENTS = [("rdt_flash_fwd.1", 0, 25000),
               ("rdt_flash_fwd.2", 25000, 25000),
               ("fusion.2", 50000, 14000), ("fusion.3", 64000, 6000),
               ("fusion.5", 70000, 4000),
               ("fusion.1", 74000, 1000), ("fusion.4", 75000, 6000),
               ("ragged-dot-none.3", 81000, 10000), ("fusion.7", 91000, 9000),
               ("fusion.8", 100000, 10000), ("fusion.9", 110000, 40000),
               ("rdt_flash_bwd_dkdv.1", 150000, 40000),
               ("rdt_flash_bwd_dq.1", 190000, 30000),
               ("fusion.11", 220000, 30000)]
BUSY = 0.25                 # seconds a step, every op a leaf


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
                "all": 491520.0 * steps, "max_expert": 9000.0 * steps,
                "held": 65000.0 * steps, "moved": 66560.0 * steps}}}


# runs of two other cells as the harness hands them over: a DLRM's (its own
# configuration and family, none of the kernels, scopes or counters) and an
# older LM's, whose program has attention and no latent path
DLRM = manifest.resolve(manifest.load_manifest(), "dlrm_criteo_stream")
OTHER = {"cell": DLRM.name, "cfg": DLRM.cfg, "wl": DLRM.wl,
         "flops": DLRM.flops,
         "trace": {"op_seconds": {"fusion.114": 0.089}, "busy_s": 2.7},
         "xplane": None, "chips": 1, "peak": PEAK, "traced_items": 1 << 20,
         "flops_per_item": 1.4e6,
         "counters": {"train_table_updates_total": {"rowwise": 10}}}


@pytest.mark.parametrize("name,want", [
    ("expert_layer_share", 100 * (0.001 + 0.006 + 0.010 + 0.009) / BUSY),
    ("shared_expert_share", 100 * 0.006 / BUSY),
    # projections, the latent path and the kernels, forward and backward
    ("attn_share", 100 * (0.05 + 0.024 + 0.07) / BUSY),
    ("latent_kv_share", 100 * (0.006 + 0.004) / BUSY),
    # two layers' forward kernels over one sequence each: QK^T at 192, PV at
    # 128
    ("flash_fwd_roofline",
     100 * (2 * 32 * (2 * 192 + 2 * 128) * PAIRS / 197e12) / 0.05),
    # one layer's pair of backward kernels: three products at 192, two at 128
    ("flash_bwd_roofline",
     100 * (32 * 2 * (3 * 192 + 2 * 128) * PAIRS / 197e12) / 0.07),
    ("head_loss_share", 100 * 0.04 / BUSY),
    # over all 128 experts the router chooses among, not the 16 held
    ("expert_load_imbalance", 9000 / (491520 / 128)),
    ("held_slot_share", 100 * 65000 / 491520),
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
    assert sorted(METRICS) == sorted(
        m["name"] for m in cell.per_layer if "workloads" in m)


def test_a_program_without_the_scope_or_the_kernels_says_nothing(
        cell, tmp_path):
    """The parent of this PR has no ``attn/latent`` scope (nor can it build
    this model), and an older LM cell's program has attention without it: the
    new reader says nothing there and raises nothing; a trace without flash
    kernels has no roofline."""
    from chipbench.trace import reduce as reducer
    program = {k: v.replace("/latent/", "/")
               for k, v in PROGRAM.items()}
    events = [e for e in STEP_EVENTS if not e[0].startswith("rdt_flash")]
    xplane = _xplane(tmp_path / "plain.xplane.pb", program, events)
    run = dict(_run(cell, tmp_path), trace=reducer.reduce(xplane),
               xplane=xplane)
    assert cell.readers["latent_kv_share"].read(run) is None
    assert cell.readers["flash_fwd_roofline"].read(run) is None
    assert cell.readers["flash_bwd_roofline"].read(run) is None
    assert cell.readers["attn_share"].read(run) is not None
    assert cell.readers["expert_layer_share"].read(run) is not None
    assert cell.readers["latent_kv_share"].read(dict(run, xplane=None)) \
        is None
    assert cell.readers["held_slot_share"].read(
        dict(run, counters={})) is None
    assert cell.readers["expert_load_imbalance"].read(
        dict(run, counters={})) is None


def test_the_counters_scopes_and_kernels_the_readers_read_are_the_programs():
    from raydp_tpu import metrics

    m = metrics.METRICS["train_attention_layers_total"]
    assert (m.kind, m.label) == (metrics.COUNTER, "kind") and "latent" in m.doc
    assert {"attn", "attn/latent", "mlp", "moe/router", "moe/shared",
            "moe/experts"} <= metrics.SCOPE_NAMES
    from raydp_tpu.ops import flash_attention as fa
    names = set(fa.KERNEL_NAMES) | set(fa.WINDOW_KERNEL_NAMES)
    for pattern in (r"^rdt_flash_fwd", r"^rdt_flash_bwd_dkdv",
                    r"^rdt_flash(_win)?_bwd_"):
        assert any(re.match(pattern, n) for n in names), pattern
    # the two widths reach the kernels as they are: nothing is padded to 192
    import jax
    import jax.numpy as jnp
    q = jax.ShapeDtypeStruct((1, 256, 2, 192), jnp.bfloat16)
    v = jax.ShapeDtypeStruct((1, 256, 2, 128), jnp.bfloat16)
    text = str(jax.make_jaxpr(jax.grad(lambda q, k, v: fa.flash_attention(
        q, k, v, interpret=True).astype(jnp.float32).sum(), (0, 1, 2)))(
            q, q, v))
    assert "bf16[2,256,192]" in text and "bf16[2,256,128]" in text
    assert fa.kernel_ineligible(16384, 192, d_v=128) is None
    assert "multiple of 8" in fa.kernel_ineligible(16384, 192, d_v=100)
