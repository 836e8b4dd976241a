"""The cell ``trinity_mini_8k_train`` against the benchmark's contract: its
configuration's widths and the cut written into its file, the parameter table
to the parameter (and the bias outside it), the pairs a window leaves
visible, its operation counts against a hand count, its train step compiled
chip-free at the published widths, its rehearsal through
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
CELL = "trinity_mini_8k_train"
CONFIG = "trinity-mini"
PEAK = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
WIDTHS = {"hidden_size": 2048, "head_dim": 128, "num_attention_heads": 32,
          "num_key_value_heads": 4, "intermediate_size": 6144,
          "moe_intermediate_size": 1024, "num_experts_per_tok": 8,
          "num_shared_experts": 1, "sliding_window": 2048,
          "route_scale": 2.826, "rope_theta": 10000, "rms_norm_eps": 1e-05,
          "load_balance_coeff": 0.001}
T = 8192
PARAMETERS = 705473792
#: the per-layer metrics that list this cell: the device's first, then the
#: counters'
METRICS = ["expert_layer_share", "shared_expert_share", "attn_share",
           "flash_fwd_roofline", "flash_bwd_roofline", "head_loss_share",
           "expert_load_imbalance", "held_slot_share"]


@pytest.fixture()
def cell():
    return manifest.resolve(manifest.load_manifest(), CELL)


def _catalog_row():
    path = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(path):
        return None
    with open(path) as fh:
        rows = [json.loads(line) for line in fh if line.strip()]
    return next(r for r in rows if r["name"] == "Trinity-Mini")


def test_the_configuration_carries_the_source_whole_and_every_width(cell):
    cfg = cell.cfg
    for key, value in WIDTHS.items():
        assert cfg[key] == value, key
    # what the source states stays beside what is held here
    assert (cfg["num_hidden_layers"], cfg["num_dense_layers"],
            cfg["num_experts"], cfg["vocab_size"],
            cfg["max_position_embeddings"]) == (32, 2, 128, 200192, 131072)
    assert (cfg["layers"], cfg["layers_held"], cfg["dense_layers"],
            cfg["first_expert"], cfg["experts_held"], cfg["vocab_rows_held"],
            cfg["chips_sharing_a_layer"], cfg["seq_len"]) == (
                5, [0, 4, 5, 6, 7], 1, 0, 16, 25024, 8, T)
    assert cfg["vocab_rows_held"] * 8 == cfg["vocab_size"]
    assert cfg["experts_held"] * 8 == cfg["num_experts"]
    assert [cfg["layer_types"][i] for i in cfg["layers_held"]] == [
        "sliding_attention"] * 4 + ["full_attention"]
    assert (cfg["score_func"], cfg["route_norm"], cfg["mup_enabled"],
            cfg["family"]) == ("sigmoid", True, True, "afmoe_lm")
    assert cfg["aux_loss"] == {"balance_weight": 0.0, "z_weight": 0.0}
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
    for key in ("attention_gate", "qk_norm", "sandwich_norms", "embed_scale",
                "position_embedding", "window", "routing", "bias_update",
                "shared_expert", "biases", "aux_loss", "optimizer",
                "init_std", "input", "compute_dtype", "remat_blocks",
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
    the manifest."""
    m = manifest.load_manifest()
    assert manifest.validate(m) == []
    entry = next(w for w in m["workloads"] if w["name"] == CELL)
    assert (entry["config"], entry["traffic"], entry["chips"]) == (
        CONFIG, "packed_8k_stream", 1)
    assert [c["name"] for c in m["configs"]].count(CONFIG) == 1
    mine = [e for e in m["per_layer"] if CELL in e.get("workloads", [])]
    assert sorted(e["name"] for e in mine) == sorted(METRICS)
    assert all(e["moves"] == "train_throughput" for e in mine)
    assert {e["name"]: e["source"] for e in mine} == {
        **{n: "device_trace" for n in METRICS[:6]},
        **{n: "program_counter" for n in METRICS[6:]}}
    assert all(e["unit"] == "%" for e in mine
               if e["name"] != "expert_load_imbalance")
    # one reader a measurement: the shared expert is this cell's alone, the
    # held share is read by the two cells that hold one, the rest by every
    # LM cell
    lists = {e["name"]: e["workloads"] for e in mine}
    assert lists["shared_expert_share"] == [CELL]
    assert lists["held_slot_share"] == ["smallthinker_21ba3b_16k_train", CELL]
    assert all(lists[n] == ["olmoe_1b7b_train",
                            "smallthinker_21ba3b_16k_train", CELL]
               for n in METRICS if n not in ("shared_expert_share",
                                             "held_slot_share"))
    # every list-free metric is read here too, and no other cell's
    names = {e["name"] for e in cell.per_layer}
    assert {e["name"] for e in m["per_layer"] if "workloads" not in e} < names
    assert not names & {"expert_gemm_roofline", "window_attn_share",
                        "rowwise_table_share", "collective_share"}
    assert set(cell.readers) == names
    wl = cell.wl
    assert (wl["rows"], wl["seq_len"], wl["residency"],
            wl["checkpoint_interval"], wl["unit_of_work"]) == (
                16, T, "stream", "final", "tokens")
    # 16,384 tokens an optimizer step, as two rows in one micro-batch
    assert wl["batch_per_replica"] * T == 16384
    assert wl["estimator_args"] == {}


def test_the_parameter_table_to_the_parameter(cell):
    """ISSUE 33's table, from the configuration's sizes and from the model's
    own tree at the published widths (shapes only: nothing is allocated);
    the bias and its counts lie outside the parameters."""
    import jax

    parts = cell.flops.parameters(cell.cfg)
    assert parts["attention"] == 5 * 27263232
    assert parts["norms"] == 5 * 8192
    assert parts["dense_ffn"] == 37748736
    assert parts["attention"] // 5 + 8192 + parts["dense_ffn"] == 65020160
    assert (parts["router"], parts["shared_expert"], parts["experts"]) == (
        4 * 262144, 4 * 6291456, 4 * 100663296)
    assert 27263232 + 8192 + 262144 + 6291456 + 100663296 == 134488320
    assert parts["embedding_head_final_norm"] == 102500352
    assert sum(parts.values()) == PARAMETERS
    assert round(PARAMETERS * 16 / 2 ** 30, 2) == 10.51     # GiB of state
    model = cell.pipeline.build_model(cell.cfg)
    shapes = jax.eval_shape(lambda: model.init(
        jax.random.PRNGKey(0), np.zeros((1, 8), np.int32)))
    assert sum(int(np.prod(s.shape))
               for s in jax.tree.leaves(shapes["params"])) == PARAMETERS
    state = shapes["batch_stats"]
    assert sorted(state) == ["block_1", "block_2", "block_3", "block_4"]
    assert sum(int(np.prod(b["moe"]["bias"].shape))
               for b in state.values()) == 4 * 128
    assert all(b["moe"]["counts"].shape == (128,) and
               b["moe"]["bias"].dtype == np.float32 for b in state.values())
    block = shapes["params"]["block_1"]
    assert block["attn"]["q"]["kernel"].shape == (2048, 32, 128)
    assert block["attn"]["k"]["kernel"].shape == (2048, 4, 128)
    assert block["attn"]["gate"]["kernel"].shape == (2048, 32, 128)
    assert block["attn"]["o"]["kernel"].shape == (32, 128, 2048)
    assert block["attn"]["q_norm"]["scale"].shape == (128,)
    assert block["moe"]["router"].shape == (2048, 128)
    assert block["moe"]["experts_down"].shape == (16, 1024, 2048)
    assert block["moe"]["shared_up"]["kernel"].shape == (2048, 1024)
    assert shapes["params"]["block_0"]["gate"]["kernel"].shape == (2048, 6144)
    assert "moe" not in shapes["params"]["block_0"]
    assert shapes["params"]["lm_head"]["kernel"].shape == (2048, 25024)
    assert model.attention_layers == {"window": 4, "full": 1}


def test_the_pairs_a_window_leaves_visible_and_the_flops(cell):
    work, cfg = cell.flops, cell.cfg
    assert work.visible_pairs(T) == T * (T + 1) / 2 == 33558528
    assert work.visible_pairs(T, 2048) == 14681088
    i, j = np.indices((256, 256))
    assert work.visible_pairs(256, 64) == ((j <= i) & (i - j < 64)).sum()
    assert work.layer_kinds(cfg) == {"window": 4, "full": 1}
    assert work.pairs_by_kind(cfg) == {"window": 14681088, "full": 33558528}
    # the kernels' blocks at 1024 x 1024: what the window skips
    from raydp_tpu.ops.flash_attention import _band_steps
    assert _band_steps(T, 1024, 1024, 2048) == (3, 3)
    parts = work.forward_flops_per_token(cfg)
    assert parts["attention_projections"] == 5 * 2 * 27262976      # 272.6 M
    full, windowed = 4 * 4096 * 33558528 / T, 4 * 4096 * 14681088 / T
    assert round(full / 1e6, 1) == 67.1 and round(windowed / 1e6, 1) == 29.4
    assert parts["attention_scores"] == full + 4 * windowed         # 184.6 M
    assert parts["dense_ffn"] == 3 * 2 * 2048 * 6144                # 75.5 M
    assert parts["router"] == 4 * 2 * 2048 * 128                    # 2.1 M
    assert parts["shared_expert"] == 4 * 3 * 2 * 2048 * 1024        # 50.3 M
    assert parts["experts"] == 4 * 8 * 0.125 * 3 * 2 * 2048 * 1024  # 50.3 M
    assert parts["head"] == 2 * 2048 * 25024                        # 102.5 M
    assert round(sum(parts.values()) / 1e6) == 738
    assert work.train_flops_per_item(cfg, cell.wl, {}) == 3 * sum(
        parts.values())
    # the sequence length is the configuration's, whatever a caller's
    # workload says
    assert work.train_flops_per_item(cfg, {"seq_len": 131072}, {}) == 3 * sum(
        parts.values())
    assert work.num_experts(cfg) == 128
    # one execution of one layer's kernels over 2 sequences (the contract of
    # ``trace/executions.py``)
    fwd, fwd_bytes = work.flash_forward(cfg, cell.wl, "full", 2)
    assert fwd == 2 * 2 * 2 * 4096 * 33558528
    assert fwd_bytes == 2 * T * ((2 * 4096 + 2 * 512) * 2 + 32 * 4)
    bwd, bwd_bytes = work.flash_backward(cfg, cell.wl, "window", 2)
    assert bwd == 2 * 5 * 2 * 4096 * 14681088
    assert bwd_bytes == 2 * T * ((4 * 4096 + 4 * 512) * 2 + 2 * 32 * 4)
    for flops, moved in ((fwd, fwd_bytes), (bwd, bwd_bytes)):   # compute-bound
        assert flops / PEAK["bf16_flops_per_s"] > 5 * moved / PEAK[
            "hbm_bytes_per_s"]


def test_a_batch_is_int32_tokens_drawn_from_the_slice(cell):
    info = cell.pipeline.describe(cell.cfg, cell.wl)
    assert cell.pipeline.batch_leaves(cell.cfg, cell.wl, info, 2) == {
        "tokens": ((2, T), "int32")}
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
    for key in ("hidden_size", "head_dim", "intermediate_size",
                "moe_intermediate_size", "num_experts_per_tok",
                "num_shared_experts", "route_scale", "rope_theta",
                "rms_norm_eps", "load_balance_coeff"):
        assert cfg[key] == WIDTHS[key], key
    # the dense layer (window, RoPE) and one expert layer (full, no RoPE)
    assert (cfg["layers"], cfg["dense_layers"], cfg["layers_held"]) == (
        2, 1, [0, 7])
    assert [cfg["layer_types"][i] for i in cfg["layers_held"]] == [
        "sliding_attention", "full_attention"]
    assert (cfg["num_experts"], cfg["experts_held"],
            cfg["num_experts_per_tok"]) == (16, 2, 8)
    assert cfg["experts_held"] * 8 == cfg["num_experts"]
    assert (cfg["vocab_size"], cfg["vocab_rows_held"]) == (4096, 512)
    assert (cfg["seq_len"], cfg["sliding_window"]) == (256, 64)
    # one group of the published eight query heads a K/V head
    assert cfg["num_attention_heads"] == 8 * cfg["num_key_value_heads"]
    assert rows == 2 and wl["batch_per_replica"] == 1 and wl["seq_len"] == 256


def test_the_train_step_compiles_chip_free_at_the_published_widths():
    """``rehearse.py compile``: the estimator's own train step for a described
    v5e chip, the grouped-query flash kernels (windowed and full), the held
    experts' walk, the shared expert, the bias's collection and the
    recomputed blocks included, two 8,192-token rows a step. The compiler
    refuses a program that does not fit the chip (with ``accum_steps`` 2 it
    does: 9.17 GiB of program beside 7.88 of arguments), so compiling is the
    check; the temporaries it reports for this step (8.84 GiB) overstate what
    the fit takes: its peak on the chip is 14.08-14.09 GiB (PERF.md, PR 33)."""
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
    assert f"{PARAMETERS} parameters" in line and "global batch 2," in line
    gib = {k: float(v) for k, v in re.findall(
        r"(arguments|temporaries) ([0-9.]+) GiB", line)}
    assert 7.85 < gib["arguments"] < 7.95        # weights, mu, nu in float32
    assert gib["temporaries"] < 9.0
    assert "collectives {}" in line


def test_the_rehearsal_through_cut_for_cpu_is_correct(cell, tmp_path):
    """The cell end to end on the CPU at its cut: the five checks, the
    counters of the share, and the one new metric a CPU run can read."""
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
    got = {k: v["value"] for k, v in result["metrics"].items()}
    assert 0 < got["held_slot_share"] < 100
    # the imbalance counts with the experts of the configuration as it is
    # run: the cut's 16 here, the published 128 on the chip
    assert got["expert_load_imbalance"] >= 1.0
    # no TPU plane off the chip: the device readers say nothing
    assert not set(METRICS[:6]) & set(got)


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
    "rdt_flash_fwd.1": STEP + "block_4/attn/attn_full/pallas_call",
    "rdt_flash_fwd.2": STEP + "block_4/attn/attn_full/pallas_call",
    "rdt_flash_win_fwd.2": STEP + "block_1/attn/attn_window/pallas_call",
    "rdt_flash_bwd_dkdv.1": STEP + "block_4/attn/attn_full/pallas_call",
    "rdt_flash_bwd_dq.1": STEP + "block_4/attn/attn_full/pallas_call",
    "rdt_flash_win_bwd_dkdv.3": STEP + "block_1/attn/attn_window/pallas_call",
    "fusion.2": STEP + "block_1/attn/gate/dot_general",
    "fusion.3": STEP + "block_1/attn/attn_gate/mul",
    "fusion.1": STEP + "block_1/moe/router/dot_general",
    "fusion.4": STEP + "block_1/moe/shared/shared_up/dot_general",
    "ragged-dot-none.3": "ragged-dot-none",     # as the chip's compiler names it
    "fusion.7": STEP + "block_1/moe/combine/reduce_sum",
    "fusion.8": STEP + "block_0/mlp/up/dot_general",
    "fusion.9": STEP + "lm_head_loss/while/body/dot_general",
    "fusion.11": "jit(train_step)/mul",
}
# one step's device events, microseconds: (name, start within the step, length)
STEP_EVENTS = [("rdt_flash_fwd.1", 0, 5000), ("rdt_flash_fwd.2", 5000, 5000),
               ("rdt_flash_win_fwd.2", 10000, 15000),
               ("fusion.2", 25000, 4000), ("fusion.3", 29000, 1000),
               ("fusion.1", 30000, 1000), ("fusion.4", 31000, 6000),
               ("ragged-dot-none.3", 37000, 14000), ("fusion.7", 51000, 9000),
               ("fusion.8", 60000, 10000), ("fusion.9", 70000, 40000),
               ("rdt_flash_bwd_dkdv.1", 110000, 20000),
               ("rdt_flash_bwd_dq.1", 130000, 10000),
               ("rdt_flash_win_bwd_dkdv.3", 140000, 45000),
               ("fusion.11", 185000, 15000)]
BUSY = 0.2                  # seconds a step, every op a leaf


def _run(cell, tmp_path, steps=2):
    """A synthetic traced run of ``steps`` optimizer steps of the cell (two
    sequences a step)."""
    from chipbench.trace import reduce as reducer
    events = [(name, 250000 * i + start, dur) for i in range(steps)
              for name, start, dur in STEP_EVENTS]
    xplane = _xplane(tmp_path / f"t{steps}.xplane.pb", PROGRAM, events)
    return {"cell": CELL, "cfg": cell.cfg, "wl": cell.wl, "flops": cell.flops,
            "trace": reducer.reduce(xplane), "xplane": xplane, "chips": 1,
            "peak": PEAK, "traced_items": 2 * T * steps,
            "flops_per_item": cell.flops.train_flops_per_item(
                cell.cfg, cell.wl, {}),
            "counters": {"moe_slots_total": {
                "all": 524288.0 * steps, "max_expert": 6000.0 * steps,
                "held": 70000.0 * steps, "moved": 73728.0 * steps}}}


# a run of a DLRM cell as the harness hands it over: its own configuration
# and family, none of the kernels, scopes or counters
DLRM = manifest.resolve(manifest.load_manifest(), "dlrm_criteo_stream")
OTHER = {"cell": DLRM.name, "cfg": DLRM.cfg, "wl": DLRM.wl,
         "flops": DLRM.flops,
         "trace": {"op_seconds": {"fusion.114": 0.089}, "busy_s": 2.7},
         "xplane": None, "chips": 1, "peak": PEAK, "traced_items": 1 << 20,
         "flops_per_item": 1.4e6,
         "counters": {"train_table_updates_total": {"rowwise": 10}}}
FULL, WINDOW = 33558528, 14681088


@pytest.mark.parametrize("name,want", [
    ("expert_layer_share", 100 * (0.001 + 0.006 + 0.014 + 0.009) / BUSY),
    ("shared_expert_share", 100 * 0.006 / BUSY),
    ("attn_share", 100 * (0.1 + 0.005) / BUSY),
    # two executions of the full layer's forward kernel (as a block that
    # recomputed its kernel would run it) and one of a windowed layer's,
    # over two sequences each
    ("flash_fwd_roofline",
     100 * (2 * 2 * 2 * 4096 * (2 * FULL + WINDOW) / 197e12) / 0.025),
    # one full and one windowed layer's pair of kernels
    ("flash_bwd_roofline",
     100 * (2 * 5 * 2 * 4096 * (FULL + WINDOW) / 197e12) / 0.075),
    ("head_loss_share", 100 * 0.04 / BUSY),
    # over all 128 experts the router chooses among, not the 16 held
    ("expert_load_imbalance", 6000 / (524288 / 128)),
    ("held_slot_share", 100 * 70000 / 524288),
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
    """The parent of this PR under these files has no ``moe/shared`` scope;
    a trace without flash kernels has no roofline."""
    from chipbench.trace import reduce as reducer
    program = {k: v for k, v in PROGRAM.items() if "/shared/" not in v}
    events = [e for e in STEP_EVENTS
              if not e[0].startswith("rdt_flash") and e[0] != "fusion.4"]
    xplane = _xplane(tmp_path / "plain.xplane.pb", program, events)
    run = dict(_run(cell, tmp_path), trace=reducer.reduce(xplane),
               xplane=xplane)
    assert cell.readers["shared_expert_share"].read(run) is None
    assert cell.readers["flash_fwd_roofline"].read(run) is None
    assert cell.readers["flash_bwd_roofline"].read(run) is None
    assert cell.readers["expert_layer_share"].read(run) is not None
    assert cell.readers["held_slot_share"].read(
        dict(run, counters={})) is None
    assert cell.readers["expert_load_imbalance"].read(
        dict(run, counters={})) is None


def test_the_counters_scopes_and_gauge_the_readers_read_are_the_programs():
    from raydp_tpu import metrics

    m = metrics.METRICS["moe_slots_total"]
    assert (m.kind, m.label) == (metrics.COUNTER, "kind") and "held" in m.doc
    gauge = metrics.METRICS["moe_router_bias_spread"]
    assert gauge.kind == metrics.GAUGE and "bias" in gauge.doc
    assert {"attn", "attn_gate", "mlp", "moe/router", "moe/shared",
            "moe/experts"} <= metrics.SCOPE_NAMES
    from raydp_tpu.ops import flash_attention as fa
    names = set(fa.KERNEL_NAMES) | set(fa.WINDOW_KERNEL_NAMES)
    for pattern in (r"^rdt_flash_win_fwd", r"^rdt_flash_fwd",
                    r"^rdt_flash_win_bwd_dkdv", r"^rdt_flash_bwd_dkdv",
                    r"^rdt_flash(_win)?_bwd_"):
        assert any(re.match(pattern, n) for n in names), pattern
