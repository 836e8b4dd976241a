"""ETL engine tests (parity: reference test_spark_cluster.py dataframe paths)."""

import numpy as np
import pandas as pd
import pyarrow as pa
import pytest

from raydp_tpu.etl import functions as F
from raydp_tpu.etl.expressions import col, lit, udf, when


@pytest.fixture
def people(shared_session):
    return shared_session.createDataFrame(
        [{"name": "alice", "age": 30, "city": "nyc"},
         {"name": "bob", "age": 25, "city": "sf"},
         {"name": "carol", "age": 35, "city": "nyc"},
         {"name": "dave", "age": 28, "city": "sf"},
         {"name": "erin", "age": 41, "city": "nyc"}])


def test_create_and_collect(shared_session, people):
    assert people.count() == 5
    rows = people.collect()
    assert {r["name"] for r in rows} == {"alice", "bob", "carol", "dave", "erin"}
    assert set(people.columns) == {"name", "age", "city"}


def test_select_withcolumn_filter(shared_session, people):
    df = people.withColumn("age2", col("age") * 2).filter(col("age") > 27)
    rows = {r["name"]: r["age2"] for r in df.collect()}
    assert rows == {"alice": 60, "carol": 70, "dave": 56, "erin": 82}

    df2 = people.select("name", (col("age") + 1).alias("age_next"))
    assert set(df2.columns) == {"name", "age_next"}


def test_expressions(shared_session, people):
    df = people.withColumn(
        "senior", when(col("age") >= 35, 1).otherwise(0)).filter(
        col("city") == "nyc")
    rows = {r["name"]: r["senior"] for r in df.collect()}
    assert rows == {"alice": 0, "carol": 1, "erin": 1}


def test_udf(shared_session, people):
    @udf("int")
    def is_sf(city):
        return 1 if city == "sf" else 0

    df = people.withColumn("sf", is_sf("city"))
    rows = {r["name"]: r["sf"] for r in df.collect()}
    assert rows["bob"] == 1 and rows["alice"] == 0


def test_groupby_agg(shared_session, people):
    out = people.groupBy("city").agg(
        F.mean("age").alias("avg_age"), F.count("age").alias("n")).to_pandas()
    out = out.set_index("city")
    assert out.loc["nyc", "n"] == 3
    assert abs(out.loc["nyc", "avg_age"] - (30 + 35 + 41) / 3) < 1e-9
    assert out.loc["sf", "n"] == 2


def test_join(shared_session, people):
    cities = shared_session.createDataFrame(
        [{"city": "nyc", "state": "NY"}, {"city": "sf", "state": "CA"}])
    joined = people.join(cities, on="city").to_pandas()
    assert len(joined) == 5
    assert set(joined.columns) >= {"name", "age", "city", "state"}
    assert (joined[joined.city == "sf"].state == "CA").all()


def test_repartition_and_coalesce(shared_session, monkeypatch):
    # AQE's tiny-partition coalescing deliberately fuses kilobyte-sized
    # reduce buckets (doc/etl.md "Adaptive execution"), so the EXACT
    # partition count only holds with it off — rows are identical either way
    monkeypatch.setenv("RDT_ETL_AQE", "0")
    df = shared_session.range(1000, num_partitions=2)
    rep = df.repartition(5)
    assert rep.num_partitions() == 5
    assert rep.count() == 1000
    co = rep.coalesce(2)
    assert co.num_partitions() == 2
    assert co.count() == 1000
    # with AQE on, these tiny buckets fuse into fewer dispatches — the
    # row-count contract (what repartition is FOR in a pipeline) survives
    monkeypatch.setenv("RDT_ETL_AQE", "1")
    assert 1 <= rep.num_partitions() <= 5
    assert rep.count() == 1000


def test_random_split_disjoint(shared_session):
    df = shared_session.range(2000, num_partitions=4)
    a, b = df.randomSplit([0.8, 0.2], seed=3)
    na, nb = a.count(), b.count()
    assert na + nb == 2000
    assert 0.7 * 2000 < na < 0.9 * 2000
    # determinism
    assert a.count() == na


def test_sort(shared_session):
    rng = np.random.RandomState(0)
    df = shared_session.createDataFrame(
        pd.DataFrame({"x": rng.permutation(500), "y": np.arange(500)}),
        num_partitions=4)
    out = df.sort("x").to_pandas()
    assert list(out["x"]) == sorted(out["x"])
    assert len(out) == 500


def test_sort_multikey_heavy_duplicates(shared_session):
    """Global order with a heavily-duplicated primary key: rows tying on
    key[0] must stay contiguous and ordered by the secondary key across
    range-partition boundaries (VERDICT r2 weak #3)."""
    rng = np.random.RandomState(0)
    n = 5000
    a = rng.randint(0, 3, n)  # only 3 distinct primaries → massive ties
    b = rng.randint(0, 1000, n)
    df = shared_session.createDataFrame(pd.DataFrame({"a": a, "b": b}),
                                 num_partitions=8)
    out = df.sort("a", "b").to_pandas().reset_index(drop=True)
    exp = pd.DataFrame({"a": a, "b": b}).sort_values(["a", "b"]) \
        .reset_index(drop=True)
    pd.testing.assert_frame_equal(out, exp)


def test_sort_nulls_land_at_end(shared_session):
    """Null keys must land at the global end (Arrow at_end semantics), not
    in the middle where the first range bucket happens to sit — both
    directions, with a secondary key."""
    rng = np.random.RandomState(1)
    n = 3000
    a = rng.randint(0, 50, n).astype(float)
    a[rng.rand(n) < 0.15] = np.nan
    b = rng.randint(0, 100, n)
    pdf = pd.DataFrame({"a": a, "b": b})
    df = shared_session.createDataFrame(pdf, num_partitions=6)

    out = df.sort("a", "b").to_pandas().reset_index(drop=True)
    exp = pdf.sort_values(["a", "b"], na_position="last") \
        .reset_index(drop=True)
    pd.testing.assert_frame_equal(out, exp)

    out_d = df.sort(("a", "descending"), ("b", "descending")) \
        .to_pandas().reset_index(drop=True)
    exp_d = pdf.sort_values(["a", "b"], ascending=False,
                            na_position="last").reset_index(drop=True)
    pd.testing.assert_frame_equal(out_d, exp_d)


def test_csv_roundtrip(shared_session, tmp_path):
    rng = np.random.RandomState(1)
    pdf = pd.DataFrame({
        "a": rng.randint(0, 100, 5000),
        "b": rng.random_sample(5000),
        "s": [f"row{i}" for i in range(5000)],
    })
    path = tmp_path / "data.csv"
    pdf.to_csv(path, index=False)
    df = shared_session.read.csv(str(path), num_partitions=4)
    assert df.num_partitions() >= 2
    assert df.count() == 5000
    got = df.to_pandas().sort_values("s").reset_index(drop=True)
    want = pdf.sort_values("s").reset_index(drop=True)
    assert (got["a"].values == want["a"].values).all()


def test_parquet_roundtrip(shared_session, tmp_path):
    pdf = pd.DataFrame({"x": np.arange(100), "y": np.arange(100) * 1.5})
    df = shared_session.createDataFrame(pdf, num_partitions=3)
    out_dir = str(tmp_path / "out")
    df.write.parquet(out_dir)
    back = shared_session.read.parquet(out_dir)
    assert back.count() == 100
    assert back.to_pandas().sort_values("x")["y"].iloc[-1] == 99 * 1.5


def test_datetime_functions(shared_session):
    pdf = pd.DataFrame({
        "ts": pd.to_datetime(["2024-01-07 13:45:00",   # a Sunday
                              "2024-06-03 02:10:00"]), # a Monday
        "v": [1.0, 2.0],
    })
    df = shared_session.createDataFrame(pdf)
    out = df.select(
        F.hour(col("ts")).alias("h"),
        F.dayofweek(col("ts")).alias("dow"),
        F.month(col("ts")).alias("m"),
        F.year(col("ts")).alias("y"),
        F.weekofyear(col("ts")).alias("w"),
    ).to_pandas().sort_values("h").reset_index(drop=True)
    assert list(out["h"]) == [2, 13]
    # Spark semantics: Sunday=1, Monday=2
    assert list(out["dow"]) == [2, 1]
    assert list(out["m"]) == [6, 1]


def test_persist_and_release(shared_session):
    df = shared_session.range(1000, num_partitions=4).withColumn(
        "sq", col("id") * col("id"))
    cached = df.persist()
    assert cached.count() == 1000
    frame_id = cached._plan.frame_id
    assert frame_id in shared_session.cached_frames()
    # blocks live on executors
    keys = set()
    for h in shared_session.executors:
        keys.update(h.list_blocks())
    assert any(k.startswith(f"block_{frame_id}_") for k in keys)
    cached.unpersist()
    assert frame_id not in shared_session.cached_frames()


def test_block_recovery_after_executor_crash(session):
    """Kill an executor holding cached blocks; lineage recomputes on fetch.

    Parity: the recoverable-dataset fault test (test_spark_cluster.py:262-299)
    and the recache protocol (RayDPExecutor.scala:312-355)."""
    import time

    df = session.range(400, num_partitions=4).withColumn("sq", col("id") * 2)
    cached = df.persist()
    plan = cached._plan
    # crash (not deliberate-kill) every executor: caches are wiped
    for h in session.executors:
        try:
            h.call("crash")
        except Exception:
            pass

    def try_count():
        return cached.count()

    deadline = time.time() + 60
    value = None
    while time.time() < deadline:
        try:
            value = try_count()
            break
        except Exception:
            time.sleep(0.5)
    assert value == 400


def test_dropna_fillna(shared_session):
    df = shared_session.createDataFrame(pd.DataFrame({
        "a": [1.0, None, 3.0, None], "b": ["x", "y", None, "w"]}))
    assert df.dropna().count() == 1
    assert df.dropna(subset=["a"]).count() == 2
    filled = df.fillna(0.0, subset=["a"]).to_pandas()
    assert filled["a"].isna().sum() == 0


def test_global_limit(shared_session):
    # regression: limit() must be global, not per-partition
    df = shared_session.range(1000, num_partitions=4)
    assert df.limit(5).count() == 5
    assert len(df.limit(5).collect()) == 5
    assert df.limit(5000).count() == 1000


def test_sort_string_column(shared_session):
    # regression: orderBy on non-numeric keys (no float cast)
    import pandas as pd
    pdf = pd.DataFrame({"s": [f"key{i:04d}" for i in range(300)][::-1],
                        "v": range(300)})
    df = shared_session.createDataFrame(pdf, num_partitions=3)
    out = df.sort("s").to_pandas()
    assert list(out["s"]) == sorted(out["s"])


def test_join_then_sort(shared_session):
    # regression: a Sort nested beside another shuffle must not free the
    # sibling shuffle's intermediates mid-plan
    left = shared_session.createDataFrame(
        [{"k": i % 5, "a": i} for i in range(100)], num_partitions=2)
    right = shared_session.createDataFrame(
        [{"k": k, "b": k * 10} for k in range(5)], num_partitions=2)
    out = left.join(right.sort("k"), on="k").to_pandas()
    assert len(out) == 100


def test_modulo_semantics(shared_session):
    import pandas as pd

    from raydp_tpu.etl.expressions import col
    big = 9_007_199_254_740_995  # > 2^53: float64 round-trip would corrupt
    df = shared_session.createDataFrame(pd.DataFrame({
        "x": [10, -7, big, 5], "y": [3, 3, 1000, 0]}))
    rows = df.withColumn("m", col("x") % col("y")).to_pandas()
    m = {int(x): v for x, v in zip(rows["x"], rows["m"])}
    assert m[10] == 1
    assert m[-7] == 2  # Python semantics
    assert m[big] == big % 1000
    import math
    assert rows["m"].isna().iloc[3] or math.isnan(rows["m"].iloc[3])  # div by 0 -> null


def test_sort_sorted_input_balanced_ranges(shared_session):
    # regression (sort sampling skew): already-sorted input used to have its
    # boundaries sampled from the first blocks only, collapsing every row
    # into one range partition
    df = shared_session.createDataFrame(
        pd.DataFrame({"x": np.arange(2000)}), num_partitions=4)
    out = df.sort("x").to_pandas()
    assert list(out["x"]) == list(range(2000))


def test_concurrent_actions(shared_session, people):
    # two shuffling actions racing on one session must not cross-free each
    # other's shuffle intermediates (Engine tracks temps per action)
    import threading

    errors = []
    results = {}

    def _agg(tag):
        try:
            out = people.groupBy("city").agg(
                F.count("age").alias("n")).to_pandas().set_index("city")
            results[tag] = int(out.loc["nyc", "n"])
        except Exception as e:  # noqa: BLE001
            errors.append(e)

    threads = [threading.Thread(target=_agg, args=(i,)) for i in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert not errors, errors
    assert all(v == 3 for v in results.values())


def test_dynamic_allocation_shrink_grow(session):
    from raydp_tpu.data.dataset import from_frame_recoverable

    pdf = pd.DataFrame({"x": np.arange(4000), "y": np.arange(4000) % 7})
    df = session.createDataFrame(pdf, num_partitions=4)
    ds = from_frame_recoverable(df, fetch=False)  # cached across 2 executors

    # shrink: the killed executor's cached blocks must recover via lineage
    # on the survivor (parity: RayCoarseGrainedSchedulerBackend.scala:278-301)
    assert session.request_total_executors(1) == 1
    total = sum(ds.get_block(i).num_rows for i in range(ds.num_blocks()))
    assert total == 4000

    # grow back up; new executors serve fresh work
    assert session.request_total_executors(3) == 3
    df2 = session.createDataFrame(pdf, num_partitions=6)
    assert df2.count() == 4000
    out = df2.groupBy("y").agg(F.count("x").alias("n")).to_pandas()
    assert int(out["n"].sum()) == 4000


def test_distinct_and_drop_duplicates(shared_session):
    """distinct/dropDuplicates parity (reference examples/data_process.py):
    executor-side hash-shuffle dedupe, exact global result."""
    pdf = pd.DataFrame({
        "a": [1, 1, 2, 2, 3] * 40,
        "b": ["x", "x", "y", "z", "x"] * 40,
    })
    df = shared_session.createDataFrame(pdf, num_partitions=4)
    out = df.distinct().to_pandas().sort_values(["a", "b"]).reset_index(drop=True)
    exp = pdf.drop_duplicates().sort_values(["a", "b"]).reset_index(drop=True)
    pd.testing.assert_frame_equal(out, exp)

    # subset dedupe keeps one full row per key value
    by_a = df.dropDuplicates(["a"]).to_pandas()
    assert sorted(by_a["a"]) == [1, 2, 3]
    assert set(by_a.columns) == {"a", "b"}

    # dedupe after a transform, with nulls (null is a distinct value)
    pdf2 = pd.DataFrame({"k": [1.0, None, 1.0, None, 2.0]})
    df2 = shared_session.createDataFrame(pdf2, num_partitions=2)
    assert df2.distinct().count() == 3


def test_describe(shared_session):
    rng = np.random.RandomState(7)
    pdf = pd.DataFrame({"x": rng.normal(10, 3, 2000),
                        "y": rng.randint(0, 5, 2000),
                        "s": ["t"] * 2000})
    df = shared_session.createDataFrame(pdf, num_partitions=4)
    out = df.describe().to_pandas().set_index("summary")
    assert "s" not in out.columns  # non-numeric skipped
    assert out.loc["count", "x"] == 2000
    np.testing.assert_allclose(out.loc["mean", "x"], pdf["x"].mean(), rtol=1e-9)
    np.testing.assert_allclose(out.loc["stddev", "x"], pdf["x"].std(ddof=1),
                               rtol=1e-9)
    assert out.loc["min", "y"] == pdf["y"].min()
    assert out.loc["max", "y"] == pdf["y"].max()
    # explicit column selection
    one = df.describe("y").to_pandas()
    assert list(one.columns) == ["summary", "y"]


def test_sort_mixed_directions(shared_session):
    """Composite-key range sort with per-key direction mix: ascending primary,
    descending secondary — the boundary comparison must honor each key's
    direction (single-key bucketing reversed globally and broke this)."""
    rng = np.random.RandomState(3)
    n = 3000
    a = rng.randint(0, 4, n)
    b = rng.randint(0, 500, n)
    df = shared_session.createDataFrame(pd.DataFrame({"a": a, "b": b}),
                                 num_partitions=6)
    out = df.sort(("a", "ascending"), ("b", "descending")) \
        .to_pandas().reset_index(drop=True)
    exp = pd.DataFrame({"a": a, "b": b}).sort_values(
        ["a", "b"], ascending=[True, False]).reset_index(drop=True)
    pd.testing.assert_frame_equal(out, exp)


def test_sort_low_cardinality_primary_balanced(shared_session):
    """With 2 distinct primary values, composite boundaries must still spread
    rows over >2 range partitions (single-key boundaries collapse to 1)."""
    rng = np.random.RandomState(5)
    n = 4000
    pdf = pd.DataFrame({"a": rng.randint(0, 2, n), "b": rng.permutation(n)})
    df = shared_session.createDataFrame(pdf, num_partitions=8)
    sorted_df = df.sort("a", "b")
    assert sorted_df.num_partitions() > 2
    out = sorted_df.to_pandas().reset_index(drop=True)
    exp = pdf.sort_values(["a", "b"]).reset_index(drop=True)
    pd.testing.assert_frame_equal(out, exp)


def test_sort_float_with_nans(shared_session):
    """NaN sort keys must land at the global end (Arrow orders NaN above all
    numbers), not in the first range partition (code-review r4 finding)."""
    rng = np.random.RandomState(11)
    vals = rng.rand(2000) * 100
    vals[rng.choice(2000, 25, replace=False)] = np.nan
    df = shared_session.createDataFrame(pd.DataFrame({"x": vals}),
                                        num_partitions=6)
    out = df.sort("x").to_pandas()["x"].to_numpy()
    finite = out[~np.isnan(out)]
    assert len(finite) == 2000 - 25
    assert (np.diff(finite) >= 0).all()
    assert np.isnan(out[-25:]).all()  # NaNs contiguous at the end
