"""The yardstick's parts, each against something worked out by hand: the
generators, the FLOP counts, the plain references against the flax models at
tiny widths, and the trace reduction on a hand-written and a recorded trace."""

import os

import numpy as np
import pytest

from chipbench import manifest
from chipbench.harness import relative_rms_error

M = manifest.load_manifest()


def _cell(name):
    return manifest.resolve(M, name)


# ------------------------------------------------------------- generators
def test_generator_is_a_function_of_the_seed():
    cell = _cell("dlrm_criteo_stream")
    a = cell.pipeline.generate(2048, 7, cell.cfg)
    b = cell.pipeline.generate(2048, 7, cell.cfg)
    c = cell.pipeline.generate(2048, 8, cell.cfg)
    assert a.equals(b) and not a.equals(c) and a.num_rows == 2048


def test_criteo_generator_has_the_schema_and_a_signal():
    cell = _cell("dlrm_criteo_stream")
    t = cell.pipeline.generate(20000, 0, cell.cfg)
    assert t.num_columns == 1 + 13 + 26
    nulls = t["_c1"].null_count / t.num_rows
    assert 0.07 < nulls < 0.13
    label = t["_c0"].to_numpy()
    assert 0.1 < label.mean() < 0.5
    d0 = t["_c1"].to_numpy(zero_copy_only=False)
    hi, lo = label[d0 > 10].mean(), label[d0 < 6].mean()
    assert hi > lo + 0.1        # the planted signal a gradient can find
    # one chip's share is half of every table, padded to an even row count
    full = cell.cfg["model"]["table_rows"]
    share = cell.pipeline.table_rows(cell.cfg, {"table_row_divisor": 2})
    assert share == [(n + 1) // 2 for n in full]
    whole = cell.pipeline.table_rows(cell.cfg, {"table_row_divisor": 1})
    assert all(w % 2 == 0 and 0 <= w - n <= 1 for w, n in zip(whole, full))
    assert sum(full) == 33762577


def test_criteo_pipeline_says_what_a_batch_and_a_cpu_cut_are():
    """What the harness, the rehearsals and the chip-free compile ask of a
    configuration's own files: the frame without a frame, a global batch's
    leaves, the sample of check (a), and a CPU cut that cuts counts only."""
    from chipbench import harness
    cell = _cell("dlrm_criteo_dp2ep2")
    info = cell.pipeline.describe(cell.cfg, cell.wl)
    assert len(info["features"]) == 39 and info["label"] == "_c0"
    assert info["table_rows"] == cell.pipeline.table_rows(cell.cfg, cell.wl)
    assert cell.pipeline.batch_leaves(cell.cfg, cell.wl, info, 8192) == {
        "features": ((8192, 39), "float32"), "label": ((8192,), "float32")}
    assert cell.reference.SAMPLE == {"rows": 4096, "batch": 4096}
    assert not hasattr(cell.pipeline, "compared")   # one logit a row: all of it
    widths = (cell.cfg["model"]["embedding_dim"],
              cell.cfg["model"]["bottom_mlp"], cell.cfg["model"]["top_mlp"])
    rehearsal = harness.cut_for_cpu(cell, "unused")
    assert rehearsal.devices == 4 and rehearsal.rows == 2 * 1024 + 128
    assert cell.wl["batch_per_replica"] == 256
    assert cell.wl["first_window_loss_band"] is None
    assert max(cell.cfg["model"]["table_rows"]) == 1000
    assert widths == (32, [512, 128, 32], [1024, 1024, 512, 256, 1])
    one = _cell("dlrm_criteo_stream")
    assert harness.cut_for_cpu(one, "unused").rows == 1024 + 64


def test_criteo_stream_budget_does_not_hold_the_cut_table():
    """``stream`` cuts the program's residency budget with the rows: 13 dense
    and 26 categorical features of 8 bytes and a float32 label a row must not
    fit it, cut or whole."""
    from chipbench import harness
    cell = _cell("dlrm_criteo_stream")
    env = harness.residency_env(cell, cell.wl["rows"])
    budget_bytes = float(env["RDT_DEVICE_CACHE_MB"]) * 2 ** 20
    row_bytes = 39 * 8 + 4
    assert budget_bytes < cell.wl["rows"] * row_bytes
    assert cell.cfg["source_rows"] * row_bytes > \
        harness.DEFAULT_CACHE_MB * 2 ** 20


# ------------------------------------------------------------------ flops
def test_dlrm_flops_against_a_hand_count():
    cell = _cell("dlrm_criteo_stream")
    bottom = 2 * (13 * 512 + 512 * 128 + 128 * 32)
    interaction = 2 * 27 * 27 * 32
    top = 2 * (384 * 1024 + 1024 * 1024 + 1024 * 512 + 512 * 256 + 256 * 1)
    assert (bottom, interaction, top) == (152576, 46656, 4194816)
    got = cell.flops.train_flops_per_item(cell.cfg, cell.wl, {})
    assert got == 3 * (bottom + interaction + top) == 13182144


# ------------------------------------------------------------- references
def test_dlrm_reference_matches_the_flax_model():
    import jax

    from raydp_tpu.models import DLRM
    cell = _cell("dlrm_criteo_stream")
    cfg = {"model": {"bottom_mlp": [16, 8]}}
    sizes = (5, 3, 11)
    rng = np.random.default_rng(1)
    dense = rng.normal(size=(32, 4)).astype(np.float32)
    sparse = np.stack([rng.integers(0, n, 32) for n in sizes], 1)
    for dtype, tol in ((np.float32, 1e-5), ("bfloat16",
                                            cell.reference.TOLERANCE)):
        model = DLRM(categorical_sizes=sizes, num_dense=4, embedding_dim=8,
                     bottom_mlp=(16, 8), top_mlp=(16, 8, 1),
                     dtype=jax.numpy.dtype(dtype))
        v = model.init(jax.random.PRNGKey(0),
                       {"dense": dense[:1], "sparse": sparse[:1]})
        # flax's default embedding scale is tiny: make the lookups matter
        v = jax.tree.map(lambda a: a * 3.0, v)
        got = np.asarray(model.apply(v, {"dense": dense, "sparse": sparse}))
        want = np.asarray(cell.reference.forward(
            jax.device_get(v), (dense, sparse), cfg))
        assert got.shape == want.shape == (32, 1)   # [rows, ...] as the model
        assert relative_rms_error(got, want) <= tol
    shuffled = (dense, sparse[:, ::-1] % np.array(sizes))
    wrong = np.asarray(cell.reference.forward(jax.device_get(v), shuffled,
                                              cfg))
    assert relative_rms_error(got, wrong) > cell.reference.TOLERANCE


# ------------------------------------------------------- trace reduction
HAND_TRACE = """
planes {
  id: 1 name: "/device:TPU:0"
  lines { id: 1 name: "XLA Modules" timestamp_ns: 1000
    events { metadata_id: 10 offset_ps: 0 duration_ps: 4000000 }
    events { metadata_id: 10 offset_ps: 6000000 duration_ps: 4000000 } }
  lines { id: 2 name: "XLA Ops" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 2000000 }
    events { metadata_id: 2 offset_ps: 3000000 duration_ps: 1000000 }
    events { metadata_id: 1 offset_ps: 6000000 duration_ps: 2000000 }
    events { metadata_id: 2 offset_ps: 7000000 duration_ps: 3000000 }
    events { metadata_id: 3 offset_ps: 6000000 duration_ps: 4000000 } }
  event_metadata { key: 1 value { id: 1 name: "%fusion.1 = f32[8]{0} fusion(p)" } }
  event_metadata { key: 3 value { id: 3 name: "%while.3 = (s32[]) while(t)" } }
  event_metadata { key: 2 value { id: 2 name: "all-reduce.2" } }
  event_metadata { key: 10 value { id: 10 name: "jit_train_step(123)" } }
}
planes {
  id: 2 name: "/device:TPU:1"
  lines { id: 2 name: "XLA Ops" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 10000000 } }
  event_metadata { key: 1 value { id: 1 name: "fusion.1" } }
}
planes { id: 3 name: "/host:CPU"
  lines { id: 1 name: "python" timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 0 duration_ps: 99000000 } }
  event_metadata { key: 1 value { id: 1 name: "not a device op" } }
}
"""


@pytest.fixture(scope="module")
def reducer():
    return manifest.load_module(manifest.ROOT, "trace", "reduce.py")


def test_reduce_on_a_hand_written_trace(tmp_path, reducer):
    from jax.profiler import ProfileData
    path = tmp_path / "hand.xplane.pb"
    path.write_bytes(ProfileData.text_proto_to_serialized_xspace(HAND_TRACE))
    r = reducer.reduce(str(path))
    # chip 0: ops [0,2] [3,4] [6,8] [7,10] us -> busy 2+1+4 = 7 of 10 us;
    # while.3 spans [6,10] and holds the last two, so it does not count
    assert r["window_s"] == pytest.approx(10e-6)
    c0, c1 = r["per_chip"]
    assert c0["busy_s"] == pytest.approx(7e-6)
    assert c0["idle_share"] == pytest.approx(0.3)
    assert c0["collective_s"] == pytest.approx(4e-6)
    assert c1["busy_s"] == pytest.approx(10e-6) and c1["idle_share"] == 0
    assert r["busy_s"] == pytest.approx(8.5e-6)
    ops = dict(r["device_ops"])
    assert ops["fusion.1"] == pytest.approx((4e-6 + 10e-6) / 2)
    assert ops["all-reduce.2"] == pytest.approx(4e-6 / 2)
    assert "while.3" not in ops and c0["ops"] == 4
    # every op's seconds by name, not the longest only
    assert r["op_seconds"] == pytest.approx(ops)
    assert reducer.most_first({"a": 1.0, "b": 3.0, "c": 2.0}, top=2) == [
        ["b", 3.0], ["c", 2.0]]
    gaps = dict(r["idle_gaps"])
    assert gaps["inside jit_train_step(123)"] == pytest.approx(1e-6 / 2)
    assert gaps["between programs, before jit_train_step(123)"] == \
        pytest.approx(2e-6 / 2)


K, Z, C, W, B1, B2 = "kernel.1", "custom-call.64", "copy-done.4", \
    "while.3", "fusion.1", "fusion.2"


@pytest.mark.parametrize("ops,leaves,held", [
    # a kernel whose event holds a zero-length custom-call at its start (as
    # the chip writes them: target ConcatBitcast): the kernel is the leaf
    ([(0, 10, K), (0, 0, Z)], [(0, 10, K)], []),
    # ... in its middle and at its end, and one that stands alone
    ([(0, 10, K), (4, 4, Z), (10, 10, Z), (12, 12, Z)], [(0, 10, K)], []),
    # a real nested op (an asynchronous copy's -done with a length): the
    # holder goes, what it holds counts
    ([(0, 10, K), (3, 5, C)], [(3, 5, C)], [(0, 10, K)]),
    # a loop goes and its body stays, the gap in it too
    ([(0, 10, W), (0, 4, B1), (6, 10, B2)], [(0, 4, B1), (6, 10, B2)],
     [(0, 10, W)]),
    # a loop round a kernel that holds a zero-length event
    ([(0, 10, W), (1, 9, K), (1, 1, Z), (20, 25, B1)],
     [(1, 9, K), (20, 25, B1)], [(0, 10, W)]),
    # a loop in a loop: both go
    ([(0, 20, W), (2, 12, "while.5"), (2, 6, B1), (8, 12, B2), (14, 20, K)],
     [(2, 6, B1), (8, 12, B2), (14, 20, K)], [(0, 20, W), (2, 12, "while.5")]),
], ids=["zero_length_at_the_start", "zero_length_inside_at_the_end_alone",
        "real_nested_op", "loop", "loop_round_a_kernel", "loop_in_a_loop"])
def test_a_leaf_holds_no_op_of_non_zero_length(reducer, ops, leaves, held):
    assert reducer.leaf_ops(ops) == leaves
    assert reducer.leaf_ops(list(reversed(ops))) == leaves   # any order
    assert reducer.held_ops(ops, leaves) == held
    # busy time is the leaves' union; a zero-length event adds nothing
    assert reducer.union_seconds(reducer.leaf_ops(ops)) == pytest.approx(
        sum(e - s for s, e, _ in leaves) / 1e9)


def test_the_by_hand_listing_names_what_the_leaf_rule_leaves_out(
        tmp_path, reducer):
    """``python3 -m chipbench.trace.reduce <trace>``: the ops that hold
    another, with their executions, their seconds and how much of them lies
    under no leaf (the loop's own control)."""
    from jax.profiler import ProfileData
    path = tmp_path / "hand.xplane.pb"
    path.write_bytes(ProfileData.text_proto_to_serialized_xspace(HAND_TRACE))
    # while.3 spans [6,10] and its body [6,8] [7,10] covers it whole
    assert reducer.dropped(str(path)) == [
        ["while.3", 1, pytest.approx(4e-6), pytest.approx(0.0)]]
    # with a zero-length custom-call at the start of every fusion.1 the
    # reduction is the same to the last digit
    marked = HAND_TRACE.replace(
        "events { metadata_id: 1 offset_ps: 0 duration_ps: 2000000 }",
        "events { metadata_id: 1 offset_ps: 0 duration_ps: 2000000 }\n"
        "    events { metadata_id: 4 offset_ps: 0 duration_ps: 0 }").replace(
        "events { metadata_id: 1 offset_ps: 6000000 duration_ps: 2000000 }",
        "events { metadata_id: 1 offset_ps: 6000000 duration_ps: 2000000 }\n"
        "    events { metadata_id: 4 offset_ps: 6000000 duration_ps: 0 }"
    ).replace(
        'event_metadata { key: 3 value',
        'event_metadata { key: 4 value { id: 4 name: "custom-call.64" } }\n'
        '  event_metadata { key: 3 value')
    assert marked.count("duration_ps: 0 }") == 2
    other = tmp_path / "marked.xplane.pb"
    other.write_bytes(ProfileData.text_proto_to_serialized_xspace(marked))
    assert reducer.reduce(str(other)) == reducer.reduce(str(path))
    assert reducer.dropped(str(other)) == reducer.dropped(str(path))


PEAK = {"bf16_flops_per_s": 100e12, "hbm_bytes_per_s": 1e12}


@pytest.mark.parametrize("flops,bytes_moved,bound,least_us", [
    (140e6, 1e6, "compute", 1.4),       # 140 MFLOP at 100 TFLOP/s: 1.4 us
    (10e6, 3.5e6, "memory", 3.5),       # 3.5 MB at 1 TB/s: 3.5 us
], ids=["compute_bound", "memory_bound"])
def test_roofline_share_of_an_op_of_the_hand_written_trace(
        tmp_path, reducer, flops, bytes_moved, bound, least_us):
    """``fusion.1`` takes 7 us a chip in the hand-written trace (4 us on chip
    0, 10 on chip 1). The least the made-up chip could take for the made-up
    work, over that: what every ``<kernel>_roofline`` reader does."""
    from jax.profiler import ProfileData

    from chipbench.trace import roofline
    path = tmp_path / "hand.xplane.pb"
    path.write_bytes(ProfileData.text_proto_to_serialized_xspace(HAND_TRACE))
    ops = reducer.reduce(str(path))["op_seconds"]
    seconds = roofline.seconds_of(ops, r"^fusion\.")
    assert seconds == pytest.approx(7e-6)
    assert roofline.seconds_of(ops, r"all-reduce") == pytest.approx(2e-6)
    assert roofline.seconds_of(ops, r"flash_attention") == 0
    least, by = roofline.least_seconds(flops, bytes_moved, PEAK)
    assert by == bound and least == pytest.approx(least_us * 1e-6)
    assert roofline.share(seconds, flops, bytes_moved, PEAK) == \
        pytest.approx(100 * least_us / 7)
    # a kernel the trace holds no time for says nothing; over 100 is not cut
    assert roofline.share(0.0, flops, bytes_moved, PEAK) is None
    assert roofline.share(1e-6, 140e6, 1e6, PEAK) == pytest.approx(140.0)


def test_reduce_finds_nothing_without_a_device(tmp_path, reducer):
    from jax.profiler import ProfileData
    path = tmp_path / "host.xplane.pb"
    host_only = HAND_TRACE[HAND_TRACE.index('planes { id: 3'):]
    path.write_bytes(ProfileData.text_proto_to_serialized_xspace(host_only))
    assert reducer.reduce(str(path)) is None
    assert reducer.find_xplane(str(tmp_path)) is None


def test_reduce_on_the_recorded_trace(reducer):
    """A trace recorded on the chip (TPU v5 lite, PR 23), kept beside the
    reducer: real plane, line and op names."""
    path = os.path.join(manifest.ROOT, manifest.BENCH_DIR, "trace",
                        "sample.xplane.pb")
    r = reducer.reduce(path)
    assert r is not None and len(r["per_chip"]) >= 1
    assert 0 < r["busy_s"] <= r["window_s"]
    assert 1 <= len(r["device_ops"]) <= 10 <= len(r["op_seconds"])
    assert all(sec > 0 for _, sec in r["device_ops"])
    assert r["device_ops"] == sorted(r["device_ops"], key=lambda kv: -kv[1])
