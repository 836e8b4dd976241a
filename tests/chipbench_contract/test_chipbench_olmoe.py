"""The cell ``olmoe_1b7b_train`` against the benchmark's contract: its
configuration's widths, what its pipeline says of a batch and of a CPU cut,
its operation counts against a hand count, its train step compiled chip-free
at the published widths, and each of its per-layer readers on a synthetic
run (and on a run of another configuration, where they say nothing). The
rehearsal of the cell end to end is ``test_chipbench_run``'s, which takes
every cell of the manifest.
"""

import copy
import os
import re
import subprocess
import sys

import numpy as np
import pyarrow as pa
import pytest

from chipbench import manifest

REPO = manifest.ROOT
CELL = "olmoe_1b7b_train"
PEAK = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
WIDTHS = {"hidden_size": 2048, "intermediate_size": 1024,
          "num_attention_heads": 16, "num_key_value_heads": 16}


@pytest.fixture()
def cell():
    return manifest.resolve(manifest.load_manifest(), CELL)


def test_the_configuration_carries_every_published_width(cell):
    cfg = cell.cfg
    published = {"num_experts": 64, "num_experts_per_tok": 8,
                 "vocab_size": 50304, "max_position_embeddings": 4096,
                 "num_hidden_layers": 16, "rms_norm_eps": 1e-5,
                 "rope_theta": 10000, "tie_word_embeddings": False,
                 "norm_topk_prob": False, "model_type": "olmoe", **WIDTHS}
    assert {k: cfg[k] for k in published} == published
    assert cfg["reduced"] == ["layers"] and cfg["layers"] == 1
    assert cfg["hidden_size"] // cfg["num_attention_heads"] == 128
    assert cfg["family"] == "moe_lm"
    # 16,384 tokens an optimizer step
    assert cell.wl["batch_per_replica"] * cell.wl["seq_len"] == 16384
    assert cell.wl["unit_of_work"] == "tokens" and cell.chips == 1
    assert cell.wl["seq_len"] == cfg["max_position_embeddings"]


def test_the_cpu_cut_cuts_counts_and_never_a_width(cell):
    cfg, wl = copy.deepcopy(cell.cfg), copy.deepcopy(cell.wl)
    rows = cell.pipeline.cpu_cut(cfg, wl, cell.chips)
    assert {k: cfg[k] for k in WIDTHS} == WIDTHS
    assert cfg["layers"] == 1
    assert cfg["num_experts"] < 64 and cfg["vocab_size"] < 50304
    assert cfg["num_experts_per_tok"] < cfg["num_experts"]
    assert wl["seq_len"] == cfg["max_position_embeddings"] < 4096
    assert rows == 4 * wl["batch_per_replica"]       # four steps an epoch
    info = cell.pipeline.describe(cfg, wl)
    assert cell.pipeline.batch_leaves(cfg, wl, info, 2) == {
        "tokens": ((2, wl["seq_len"]), "int32")}


def test_a_batch_is_int32_tokens_of_the_sequence_length(cell):
    info = cell.pipeline.describe(cell.cfg, cell.wl)
    assert cell.pipeline.batch_leaves(cell.cfg, cell.wl, info, 4) == {
        "tokens": ((4, 4096), "int32")}
    wrong = dict(cell.wl, seq_len=2048)
    with pytest.raises(ValueError, match="seq_len"):
        cell.pipeline.describe(cell.cfg, wrong)


def test_the_generator_packs_token_rows_from_the_seed(cell):
    cfg = copy.deepcopy(cell.cfg)
    cell.pipeline.cpu_cut(cfg, copy.deepcopy(cell.wl), 1)
    a, b, c = (cell.pipeline.generate(64, s, cfg) for s in (7, 7, 2 ** 31 + 5))
    assert a.equals(b) and not a.equals(c)
    t = cfg["max_position_embeddings"]
    assert a.schema.field("tokens").type == pa.list_(pa.int32(), t)
    tokens = cell.pipeline.reference_inputs(a, {"tokens": "tokens",
                                                "seq_len": t})
    assert tokens.shape == (64, t) and tokens.dtype == np.int32
    assert 0 <= tokens.min() and tokens.max() < cfg["vocab_size"]
    counts = np.bincount(tokens.ravel(), minlength=cfg["vocab_size"])
    # Zipf(1.1): eight ids of 512 take a fifth of the mass; documents end
    assert np.sort(counts)[-8:].sum() > 0.2 * tokens.size
    assert counts[cfg["input"]["eos_id"]] > 0
    assert (a["n_tokens"].to_numpy() == t).all()


def test_flops_against_a_hand_count(cell):
    parts = cell.flops.forward_flops_per_token(cell.cfg, 4096)
    assert parts["head"] == 2 * 2048 * 50304                    # 206.0 M
    assert parts["experts"] == 8 * 3 * 2 * 2048 * 1024          # 100.7 M
    assert parts["attention_projections"] == 4 * 2 * 2048 ** 2  # 33.6 M
    assert parts["attention_scores"] == 4 * 2048 * 4097 / 2     # 16.8 M
    assert parts["router"] == 2 * 2048 * 64
    per_token = cell.flops.train_flops_per_item(cell.cfg, cell.wl, {})
    assert per_token == 3 * sum(parts.values())
    assert 1.06e9 < per_token < 1.08e9
    # one execution of the one layer's kernels over a step's 4 sequences,
    # 16,384 tokens (the contract of ``trace/executions.py``); every layer
    # of this family is full causal attention
    fwd, fwd_bytes = cell.flops.flash_forward(cell.cfg, cell.wl, "full", 4)
    assert fwd == 4 * 16 * 4 * 128 * 4096 * 4097 / 2
    assert fwd_bytes == 4 * 16384 * 2048 * 2 + 16384 * 16 * 4
    bwd, _ = cell.flops.flash_backward(cell.cfg, cell.wl, "full", 4)
    assert bwd == 2.5 * fwd
    with pytest.raises(ValueError, match="full causal"):
        cell.flops.flash_forward(cell.cfg, cell.wl, "window", 4)
    assert cell.flops.num_experts(cell.cfg) == 64
    gemm, gemm_bytes = cell.flops.expert_gemms(cell.cfg, 16384)
    assert gemm == 9 * 2 * 131072 * 2048 * 1024
    assert gemm / PEAK["bf16_flops_per_s"] > gemm_bytes / PEAK[
        "hbm_bytes_per_s"]                                # compute-bound


def test_the_train_step_compiles_chip_free_at_the_published_widths():
    """``rehearse.py compile``: the estimator's own train step for a described
    v5e chip, flash kernels and grouped products included. It must fit."""
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
    assert "625616896 parameters" in line and "global batch 4," in line
    gib = {k: float(v) for k, v in re.findall(
        r"(arguments|temporaries) ([0-9.]+) GiB", line)}
    assert 6.9 < gib["arguments"] < 7.1          # weights, mu, nu in float32
    assert gib["arguments"] + gib["temporaries"] < 15.75
    assert "collectives {}" in line


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
    assert err[jnp.float8_e5m2] > 4 * ref.TOLERANCE
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
    "rdt_flash_fwd.1": STEP + "block_0/attn/pallas_call",
    "rdt_flash_bwd_dkdv.1": STEP + "block_0/attn/pallas_call",
    "rdt_flash_bwd_dq.1": STEP + "block_0/attn/pallas_call",
    "fusion.1": STEP + "block_0/moe/router/dot_general",
    "ragged-dot-none.3": "ragged-dot-none",     # as the chip's compiler names it
    "ragged-dot-metadata": "ragged-dot-metadata",
    "fusion.7": STEP + "block_0/moe/combine/reduce_sum",
    "fusion.9": STEP + "lm_head_loss/while/body/dot_general",
    "fusion.11": "jit(train_step)/mul",
}
# one step's device events, microseconds: (name, start within the step, length)
STEP_EVENTS = [("rdt_flash_fwd.1", 0, 4000), ("fusion.1", 4000, 1000),
               ("ragged-dot-metadata", 5000, 100),
               ("ragged-dot-none.3", 5100, 40000),
               ("ragged-dot-none", 45100, 10000), ("fusion.7", 55100, 9000),
               ("fusion.9", 64100, 90000), ("rdt_flash_bwd_dkdv.1", 154100, 9000),
               # a zero-length custom-call at the kernel's start, as the chip
               # writes one (target ConcatBitcast): the kernel stays a leaf
               ("rdt_flash_bwd_dq.1", 163100, 6000), ("custom-call.64", 163100, 0),
               ("fusion.11", 169100, 20000)]
BUSY = 0.1891               # seconds a step, every op of non-zero length a leaf


def _run(cell, tmp_path, steps=2):
    """A synthetic traced run of ``steps`` optimizer steps of the cell."""
    from chipbench.trace import reduce as reducer
    events = [(name, 200000 * i + start, dur) for i in range(steps)
              for name, start, dur in STEP_EVENTS]
    xplane = _xplane(tmp_path / f"t{steps}.xplane.pb", PROGRAM, events)
    trace = reducer.reduce(xplane)
    assert trace["op_seconds"]["rdt_flash_bwd_dq.1"] == pytest.approx(
        0.006 * steps)
    assert "custom-call.64" not in trace["op_seconds"]
    assert trace["busy_s"] == pytest.approx(BUSY * steps)
    return {"cell": CELL, "cfg": cell.cfg, "wl": cell.wl, "flops": cell.flops,
            "trace": trace, "xplane": xplane, "chips": 1, "peak": PEAK,
            "traced_items": 16384 * steps,
            "flops_per_item": cell.flops.train_flops_per_item(
                cell.cfg, cell.wl, {}),
            "counters": {"moe_slots_total": {"all": 131072.0 * steps,
                                             "max_expert": 4096.0 * steps}}}


# a run of a DLRM cell as the harness hands it over: its own configuration
# and family, a trace with none of the kernels and no program that names a
# scope, and none of the counters
DLRM = manifest.resolve(manifest.load_manifest(), "dlrm_criteo_stream")
OTHER = {"cell": DLRM.name, "cfg": DLRM.cfg, "wl": DLRM.wl,
         "flops": DLRM.flops,
         "trace": {"op_seconds": {"fusion.114": 0.089, "all-reduce.102": 0.2},
                   "busy_s": 2.7},
         "xplane": None, "chips": 1, "peak": PEAK, "traced_items": 1 << 20,
         "flops_per_item": 1.4e6,
         "counters": {"train_table_updates_total": {"rowwise": 10}}}
#: the per-layer metrics that list this cell
METRICS = ["flash_fwd_roofline", "flash_bwd_roofline", "expert_gemm_roofline",
           "expert_layer_share", "head_loss_share", "expert_load_imbalance",
           "attn_share"]


@pytest.mark.parametrize("name,want", [
    # least seconds of the hand count above over the kernel's seconds: one
    # forward instruction, one pair of backward kernels
    ("flash_fwd_roofline",
     100 * (4 * 16 * 4 * 128 * 4096 * 4097 / 2 / 197e12) / 0.004),
    ("flash_bwd_roofline",
     100 * (2.5 * 4 * 16 * 4 * 128 * 4096 * 4097 / 2 / 197e12) / 0.015),
    ("expert_gemm_roofline",
     100 * (9 * 2 * 131072 * 2048 * 1024 / 197e12) / 0.05),
    # the moe scopes and every ragged-dot op over the busy seconds
    ("expert_layer_share",
     100 * (0.001 + 0.04 + 0.01 + 0.0001 + 0.009) / BUSY),
    ("head_loss_share", 100 * 0.09 / BUSY),
    ("expert_load_imbalance", 4096 / (131072 / 64)),
    # the three flash kernels: this trace names no projection
    ("attn_share", 100 * (0.004 + 0.009 + 0.006) / BUSY),
])
def test_a_reader_on_a_synthetic_run_and_on_another_cells(
        cell, tmp_path, name, want):
    reader = cell.readers[name]
    run = _run(cell, tmp_path)
    assert reader.read(run) == pytest.approx(want, rel=1e-6)
    # the same share whatever the number of traced steps
    assert reader.read(_run(cell, tmp_path, steps=5)) == pytest.approx(want)
    assert reader.read(OTHER) is None
    assert reader.read(dict(OTHER, trace=None)) is None
    entry = next(m for m in cell.per_layer if m["name"] == name)
    assert CELL in entry["workloads"]
    assert sorted(METRICS) == sorted(
        m["name"] for m in cell.per_layer if "workloads" in m)
    if name.endswith("_roofline"):
        assert entry["unit"] == "%" and want < 100


def test_scopes_reads_the_programs_a_trace_stores(tmp_path):
    from chipbench.trace import scopes
    path = _xplane(tmp_path / "t.xplane.pb", PROGRAM)
    assert scopes.op_names(path) == PROGRAM
    # a trace that stores no program: nothing, not an error
    sample = os.path.join(REPO, "chipbench", "trace", "sample.xplane.pb")
    assert scopes.op_names(sample) == {}
    assert scopes.seconds_under({"trace": {"op_seconds": {"a": 1.0}},
                                 "xplane": sample}, "/moe/") is None
