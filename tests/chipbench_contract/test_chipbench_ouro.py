"""The cell ``ouro_2p6b_8k_train`` against the benchmark's contract: its
configuration's widths, the source's ``config.json`` whole and the cut written
into its file; the manifest's entries (of a list other cells share only
``<=``); its operation counts and the two kernels' operations and bytes
against a hand count; the factor of ``total_ut_steps`` executions a kernel
instruction held to the lowered step itself; its rehearsal through
``harness.cut_for_cpu``; the tolerance against the precision below; and each
of its three readers on a synthetic run (and on a run of a program that lacks
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
CELL = "ouro_2p6b_8k_train"
CONFIG = "ouro-2.6b"
PEAK = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
#: the source's config.json as the catalog copies it, whole
SOURCE = {
    "head_dim": 128, "hidden_act": "silu", "hidden_size": 2048,
    "intermediate_size": 5632, "layer_types": ["full_attention"] * 48,
    "max_position_embeddings": 65536, "max_window_layers": 48,
    "model_type": "ouro", "num_attention_heads": 16,
    "num_hidden_layers": 48, "num_key_value_heads": 16,
    "rms_norm_eps": 1e-06, "rope_scaling": None, "rope_theta": 1000000,
    "sliding_window": None, "tie_word_embeddings": False,
    "total_ut_steps": 4, "early_exit_threshold": 1,
    "use_sliding_window": False, "vocab_size": 49152}
L, PASSES, LAYERS = 8192, 4, 8
PAIRS = L * (L + 1) // 2        # visible pairs a head a row, full causal
PARAMETERS = 612438017
SHARED = ["flash_fwd_roofline", "flash_bwd_roofline", "head_loss_share",
          "attn_share"]
NEW = ["loop_carry_share", "exit_gate_share", "exit_pass_mean"]
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
    path = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.exists(path):
        import json
        with open(path) as fh:
            row = next(r for r in map(json.loads, filter(str.strip, fh))
                       if r["name"] == "Ouro-2.6B")
        assert row["source_url"] == cfg["source"]
        assert row["config"] == SOURCE
    assert (cfg["layers"], cfg["seq_len"], cfg["family"],
            cfg["exit_entropy_weight"], cfg["init_std"]) == (
                LAYERS, L, "looped_lm", 0.1, 0.02)
    # nothing of the vocabulary is sliced: the end-of-text id is its last row
    assert cfg["input"]["eos_id"] == cfg["vocab_size"] - 1
    assert (cfg["compared_positions"], cfg["compared_vocab"]) == (256, 512)
    trinity = manifest.load_json(REPO, "configs", "trinity-mini.json")
    assert cfg["optimizer"] == trinity["optimizer"]
    assert {k: v for k, v in cfg["input"].items() if k != "eos_id"} == {
        k: v for k, v in trinity["input"].items() if k != "eos_id"}
    entry = next(c for c in manifest.load_manifest()["configs"]
                 if c["name"] == CONFIG)
    assert entry["reduced"] == cfg["reduced"] == ["layers"]
    assert not [k for k in cfg["reduced"] if re.search(
        r"(_dim|_rank|hidden|intermediate|width|head|latent|state|proj"
        r"|experts_per_tok)", k)]
    assert entry["source"] == cfg["source"]
    assert entry["file"] == f"chipbench/configs/{CONFIG}.json"
    assert "pipeline stage" in cfg["deployment"]
    for key in ("layer", "attention", "mlp", "biases", "loop", "exit_gate",
                "exit_distribution", "objective", "exit_entropy_weight",
                "early_exit", "layers", "parameters", "optimizer",
                "init_std", "input", "compute_dtype", "remat_blocks",
                "seq_len", "source_rows", "batch", "compared"):
        assert key in cfg["assumed"], key
    assert f"{PARAMETERS:,}" in cfg["assumed"]["parameters"]
    assert sum(cell.flops.parameters(cfg).values()) == PARAMETERS
    # the passes share every parameter: the count does not know them
    assert cell.flops.parameters(dict(cfg, total_ut_steps=1)) \
        == cell.flops.parameters(cfg)


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
    assert "4 passes" in entry["why"]
    assert [c["name"] for c in m["configs"]].count(CONFIG) == 1
    mine = {e["name"]: e for e in m["per_layer"]
            if CELL in e.get("workloads", [])}
    assert set(SHARED + NEW) <= set(mine)
    assert all(e["moves"] == "train_throughput" for e in mine.values())
    for name in SHARED:
        assert OLDER_LM | {CELL} <= set(mine[name]["workloads"])
    for name in NEW:
        e = mine[name]
        assert e["workloads"] == [CELL] or CELL in e["workloads"]
        assert (e["layer"], e["better"], e["source"], e["unit"]) == {
            "loop_carry_share": ("model", "lower", "device_trace", "%"),
            "exit_gate_share": ("model", "lower", "device_trace", "%"),
            "exit_pass_mean": ("model", "lower", "program_counter",
                               "passes"),
        }[name]
    # every list-free metric is read here too, and no reader that finds
    # nothing in this program (a dense model: no experts, no window, no scan)
    names = {e["name"] for e in cell.per_layer}
    assert {e["name"] for e in m["per_layer"] if "workloads" not in e} < names
    assert not names & {"expert_layer_share", "expert_load_imbalance",
                        "held_slot_share", "window_attn_share",
                        "shared_expert_share", "latent_kv_share",
                        "expert_gemm_roofline", "ssd_fwd_roofline",
                        "ssd_bwd_roofline", "ssm_share", "ssm_glue_share",
                        "rowwise_table_share", "collective_share",
                        "bd_flash_fwd_roofline", "masked_token_share"}
    assert set(cell.readers) == names
    wl = cell.wl
    assert (wl["rows"], wl["seq_len"], wl["batch_per_replica"],
            wl["residency"], wl["checkpoint_interval"], wl["unit_of_work"],
            wl["estimator"], wl["estimator_args"], wl["mesh_spec"]) == (
                8, L, 1, "stream", "final", "tokens", "flax", {}, {})
    band = wl["first_window_loss_band"]
    assert band is None or (band[0] < band[1] and band[1] - band[0] <= 0.5)


def test_the_flops_and_the_kernels_work_by_hand(cell):
    """A token costs 32 layer executions and four heads; the kernels'
    operations and bytes by hand, for one instruction of the program: four
    executions a sequence."""
    cfg, flops = cell.cfg, cell.flops
    assert flops.visible_pairs(L) == PAIRS
    parts = flops.forward_flops_per_token(cfg)
    d, q = 2048, 16 * 128
    assert parts == {
        "attention_projections": 32 * 2 * d * 4 * q,
        "attention_scores": 32 * 2 * 2 * q * (L + 1) / 2,
        "dense_ffn": 32 * 3 * 2 * d * 5632,
        "exit_gate": 4 * 2 * d,
        "head": 4 * 2 * d * 49152}
    # a layer 136.3 MFLOP, a head 201.3
    assert 136.2e6 < sum(parts[k] for k in (
        "attention_projections", "attention_scores", "dense_ffn")) / 32 \
        < 136.4e6
    assert parts["head"] / 4 == pytest.approx(201.3e6, rel=1e-3)
    per_item = flops.train_flops_per_item(cfg, cell.wl, {})
    assert per_item == 3.0 * sum(parts.values())
    assert 15.4e9 < per_item < 15.6e9
    # one pass and one head would be a quarter of it, to the gate's rounding
    once = flops.train_flops_per_item(dict(cfg, total_ut_steps=1),
                                      cell.wl, {})
    assert once == pytest.approx(per_item / 4)
    ops, moved = flops.flash_forward(cfg, cell.wl, "full", 2.0)
    assert ops == PASSES * 2 * 2 * 2 * q * PAIRS
    assert moved == PASSES * 2 * L * (4 * q * 2 + 16 * 4)
    ops_b, moved_b = flops.flash_backward(cfg, cell.wl, "full", 2.0)
    assert ops_b == 2.5 * ops
    assert moved_b == PASSES * 2 * L * (8 * q * 2 + 2 * 16 * 4)
    from chipbench.trace import roofline
    assert roofline.least_seconds(ops, moved, PEAK)[1] == "compute"
    assert roofline.least_seconds(ops_b, moved_b, PEAK)[1] == "compute"


def test_a_batch_is_int32_tokens_of_the_whole_vocabulary(cell):
    """Rows of ``seq_len`` ids over all 49,152 rows, the end-of-text id at
    documents' ends, the same seed the same rows, a driver-sized seed
    taken."""
    cfg = copy.deepcopy(cell.cfg)
    cfg["seq_len"] = 4096
    table = cell.pipeline.generate(4, 2 ** 31 + 11, cfg)
    assert table.equals(cell.pipeline.generate(4, 2 ** 31 + 11, cfg))
    tokens = cell.pipeline.reference_inputs(
        table, {"tokens": "tokens", "seq_len": 4096})
    assert tokens.shape == (4, 4096) and tokens.dtype == np.int32
    assert 0 <= tokens.min() and 40000 < tokens.max() <= 49151
    assert 4 < (tokens == 49151).sum() < 60
    assert cell.pipeline.batch_leaves(cell.cfg, cell.wl, {"seq_len": L}, 1) \
        == {"tokens": ((1, L), "int32")}
    assert cell.pipeline.describe(cell.cfg, cell.wl) == {
        "tokens": "tokens", "seq_len": L}


def test_the_cpu_cut_cuts_counts_and_never_a_width(cell):
    cfg, wl = copy.deepcopy(cell.cfg), copy.deepcopy(cell.wl)
    rows = cell.pipeline.cpu_cut(cfg, wl, 1)
    assert rows == 2 and wl["seq_len"] == cfg["seq_len"] == 128
    for key in ("hidden_size", "head_dim", "intermediate_size",
                "rms_norm_eps", "rope_theta", "hidden_act",
                "total_ut_steps", "exit_entropy_weight", "init_std",
                "compared_vocab"):
        assert cfg[key] == cell.cfg[key], key
    assert cfg["num_attention_heads"] == cfg["num_key_value_heads"] == 4
    assert (cfg["layers"], cfg["vocab_size"]) == (2, 1024)
    model = cell.pipeline.build_model(cfg)
    assert (model.total_ut_steps, model.exit_entropy_weight,
            model.sandwich_norms, model.num_layers) == (4, 0.1, True, 2)


def test_a_kernel_instruction_runs_total_ut_steps_times_a_sequence(cell):
    """The factor ``flops/looped_lm.flash_forward|backward`` multiply by,
    held to the lowered step itself: the step of the CPU cut (2 layers, 4
    passes), lowered for the TPU platform so that the flash op takes its
    kernels, shows ONE forward and ONE backward kernel call a layer (not one
    a layer and pass), and both loops over the passes have
    ``total_ut_steps`` trips; the model says it executes layers x passes
    attention layers a step, so each instruction runs ``total_ut_steps``
    times a sequence. A program that unrolled its passes fails here, before
    a roofline share read four times too high."""
    import jax
    import optax

    from tests import lm_testing
    cfg, wl = copy.deepcopy(cell.cfg), copy.deepcopy(cell.wl)
    cell.pipeline.cpu_cut(cfg, wl, 1)
    cfg["seq_len"] = wl["seq_len"] = 512    # a length the kernels take
    model = cell.pipeline.build_model(cfg)
    tokens = np.zeros((1, 512), np.int32)
    shapes = jax.eval_shape(lambda: model.init(
        jax.random.PRNGKey(0), tokens[:, :8]))["params"]
    step, create, arguments = lm_testing.train_step(model, optax.sgd(0.05))
    state = jax.eval_shape(lambda: create(jax.tree.map(
        lambda s: np.zeros(s.shape, s.dtype), shapes)))
    text = jax.jit(step).trace(*arguments(state, tokens)).lower(
        lowering_platforms=("tpu",)).as_text()
    kernels = re.findall(r'kernel_name = "([^"]+)"', text)
    layers, passes = cfg["layers"], cfg["total_ut_steps"]
    assert kernels.count("rdt_flash_fwd") == layers
    assert kernels.count("rdt_flash_bwd_dkdv_dq") == layers
    assert len(kernels) == 2 * layers
    trips = re.findall(r"cond \{\s*%\S+ = stablehlo\.constant dense<(\d+)> : "
                       r"tensor<i32>\s*%\S+ = stablehlo\.compare  LT", text)
    assert trips.count(str(passes)) == 2, trips
    assert model.attention_layers["full"] == layers * passes
    assert model.attention_forward == {"once": layers * passes}
    ops, _ = cell.flops.flash_forward(cfg, wl, "full", 1.0)
    one, _ = cell.flops.flash_forward(dict(cfg, total_ut_steps=1), wl,
                                      "full", 1.0)
    assert ops == passes * one


def test_the_rehearsal_through_cut_for_cpu_is_correct(cell, tmp_path):
    """The cell end to end on the CPU at its cut: the five checks, the
    counters of the loop and of the exit distribution, and the counter
    reader on them."""
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
    assert found["compared_shape"] == [2, 32, 512 + PASSES]
    assert found["reference_error"] <= cell.reference.TOLERANCE
    assert found["streamed"] and found["lowerings_in_window"] == 0
    counters = {name: {label: value - before.get(name, {}).get(label, 0)
                       for label, value in by_label.items()}
                for name, by_label in result["detail"]["counters"].items()}
    assert counters["train_loop_passes_total"]["recomputed"] >= 2 * PASSES
    assert counters["train_attention_layers_total"]["full"] >= 2 * PASSES
    mass = counters["train_exit_mass_total"]
    positions = counters["train_exit_positions_total"][""]
    assert sorted(mass) == ["1", "2", "3", "4"]
    assert positions % 127 == 0 and positions > 0
    assert sum(mass.values()) == pytest.approx(positions, rel=1e-4)
    run = {"counters": counters, "flops": cell.flops, "cfg": cell.cfg}
    # 1.875 at a fresh gate (p = 1/2, 1/4, 1/8, 1/8); the cut's 64-step
    # warm-up moves it within the rehearsal's few steps
    mean = cell.readers["exit_pass_mean"].read(run)
    assert mean == pytest.approx(sum(int(t) * v for t, v in mass.items())
                                 / positions)
    assert 1.2 < mean < 3.0
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


def test_the_accepted_roofline_readers_count_four_executions_an_instruction(
        cell):
    """Eight layers' forward kernel instructions over two traced rows, each
    run four times a row in exactly the roofline's time, read 100 (a factor
    left out would read 25, one applied to an unrolled program's 32
    instructions 400); the backward likewise."""
    fwd, bwd = (cell.readers[n] for n in SHARED[:2])
    ops, _ = cell.flops.flash_forward(cell.cfg, cell.wl, "full", 2.0)
    least = ops / PEAK["bf16_flops_per_s"]          # four executions' time
    run = _run(cell, {f"rdt_flash_fwd.{i}": least for i in range(LAYERS)})
    assert fwd.read(run) == pytest.approx(100.0)
    assert bwd.read(run) is None
    run = _run(cell, {f"rdt_flash_fwd.{i}": 2 * least for i in range(LAYERS)})
    assert fwd.read(run) == pytest.approx(50.0)
    one = _run(cell, {f"rdt_flash_bwd_dkdv_dq.{i}": 2.5 * least
                      for i in range(LAYERS)})
    assert bwd.read(one) == pytest.approx(100.0) and fwd.read(one) is None


def test_the_new_readers_say_nothing_without_theirs(cell):
    carry, gate, mean = (cell.readers[n] for n in NEW)
    counters = {"train_exit_mass_total": {"1": 400.0, "2": 200.0,
                                          "3": 100.0, "4": 100.0},
                "train_exit_positions_total": {"": 800.0}}
    assert mean.read(_run(cell, {}, counters)) == pytest.approx(1.875)
    assert mean.read(_run(cell, {})) is None
    assert mean.read(_run(cell, {}, {"train_exit_mass_total": {
        "1": 1.0}})) is None
    # no stored program names the scopes (a parent's trace, or none): nothing
    for reader in (carry, gate):
        assert reader.read(_run(cell, {"fusion.1": 1.0})) is None
        assert reader.read(dict(_run(cell, {}), trace=None)) is None


def test_the_loop_reader_leaves_the_layers_the_gate_and_the_head_out(
        cell, monkeypatch):
    """Under ``loop`` and under no ``block_<i>``, ``exit_gate`` or
    ``lm_head_loss`` scope: the carry's own ops."""
    from chipbench.trace import scopes

    base = "jit(train_step)/transpose(jvp(TransformerLM.loss_rows))/"
    names = {
        "fusion.1": base + "TransformerLM/loop/while/body/closed_call/"
                           "block_0/attn/q/dot_general",
        "fusion.2": base + "TransformerLM/loop/while/body/closed_call/ln_f/mul",
        "fusion.3": base + "TransformerLM/loop/while/body/dynamic_update_slice",
        "fusion.4": base + "TransformerLM/exit_gate/exit_gate/dot_general",
        "fusion.5": base + "TransformerLM/lm_head_loss/while/body/dot_general",
        "fusion.6": base + "TransformerLM/embed/gather"}
    monkeypatch.setattr(scopes, "op_names", lambda path: names)
    run = dict(_run(cell, {f"fusion.{i}": 0.1 for i in range(1, 7)}),
               xplane="a trace")
    assert cell.readers["loop_carry_share"].read(run) == pytest.approx(20.0)
    assert cell.readers["exit_gate_share"].read(run) == pytest.approx(10.0)
    assert cell.readers["head_loss_share"].read(run) == pytest.approx(10.0)
    assert cell.readers["attn_share"].read(run) == pytest.approx(10.0)


def test_the_counters_and_scopes_the_readers_read_are_the_programs():
    from raydp_tpu import metrics

    assert {"loop", "exit_gate", "attn", "lm_head_loss"} <= metrics.SCOPE_NAMES
    for name in ("train_loop_passes_total", "train_exit_mass_total",
                 "train_exit_positions_total", "train_attention_layers_total"):
        assert metrics.METRICS[name].kind == metrics.COUNTER
    assert "total_ut_steps" in metrics.METRICS[
        "train_attention_layers_total"].doc
    for label in ("recomputed", "plain"):
        assert label in metrics.METRICS["train_loop_passes_total"].doc
