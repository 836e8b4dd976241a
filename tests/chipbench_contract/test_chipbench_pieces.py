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
        got = np.asarray(model.apply(v, {"dense": dense,
                                         "sparse": sparse}))[:, 0]
        want = np.asarray(cell.reference.forward(
            jax.device_get(v), (dense, sparse), cfg))
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
    gaps = dict(r["idle_gaps"])
    assert gaps["inside jit_train_step(123)"] == pytest.approx(1e-6 / 2)
    assert gaps["between programs, before jit_train_step(123)"] == \
        pytest.approx(2e-6 / 2)


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
    assert 1 <= len(r["device_ops"]) <= 10
    assert all(sec > 0 for _, sec in r["device_ops"])
    assert r["device_ops"] == sorted(r["device_ops"], key=lambda kv: -kv[1])
