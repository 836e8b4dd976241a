"""Continuous-pipeline bench: the ISSUE 15 acceptance record (STREAM.json).

Three configs, each a fresh session, together covering the full
ingest → window → partial_fit → hot-swap loop (doc/streaming.md):

1. ``sustained`` — a synthetic-rate source drives N micro-batch epochs
   through a filter + sliding windowed aggregation; the record carries the
   per-epoch wall quantiles (p50/p99/max — the "bounded per-epoch latency"
   claim), rows/s, windows closed, and the zero-orphan store audit after
   close.
2. ``fault_replay`` — the exactly-once contract: the same windowed
   pipeline runs once unfaulted (the baseline window bytes) and once with
   a seeded mid-stream ``stream.epoch:drop`` losing a freshly sealed
   epoch's partials; the faulted run must REPLAY the epoch from the source
   journal and produce window results byte-identical to the unfaulted run,
   with ``replays >= 1`` proving the fault actually fired and a
   zero-orphan audit after close.
3. ``hot_swap`` — online training under live traffic: a bootstrap
   servable takes an open-loop predict burst while ``partial_fit``
   consumes a stream and hot-swaps freshly exported servables into the
   SAME serving session mid-burst. Zero dropped requests (every future
   resolves with a prediction), ``hot_swaps >= 2``, and the final
   ``serving_report`` names the active servable version/tag.

``--smoke`` shrinks the load, writes to /tmp (never the recorded
artifact), and ASSERTS the contract above; the full run records
``benchmarks/STREAM.json`` (override with ``--out``).

Run: RDT_FAULTS_SEED=7 python benchmarks/stream_bench.py [--smoke] [--out P]
"""

import argparse
import json
import os
import sys
import tempfile
import threading
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _make_batch(rows):
    def make(epoch):
        import pyarrow as pa
        rng = np.random.RandomState(epoch)
        return pa.table({
            "k": rng.randint(0, 8, rows),
            "v": rng.randint(0, 1000, rows).astype(np.int64),
        })
    return make


def _train_batch(rows):
    def make(epoch):
        import pyarrow as pa
        rng = np.random.RandomState(epoch)
        x = rng.random_sample((rows, 2))
        y = x @ np.array([2.0, -3.0]) + 1.0
        return pa.table({"x1": x[:, 0], "x2": x[:, 1], "y": y})
    return make


def _windowed_pipeline(session, make, epochs):
    from raydp_tpu import stream
    from raydp_tpu.etl.expressions import col

    return stream.read_stream(
        stream.SyntheticSource(make, max_epochs=epochs), session=session
    ).transform(lambda df: df.filter(col("v") >= 0)).window(
        size=3, slide=1, keys=["k"], aggs={"v": ["sum", "mean", "count"]})


def _drive(pipe):
    """Run the pipeline dry; return (window bytes in close order, report)."""
    import pyarrow as pa

    wins = []
    for er in pipe.epochs():
        for w in er.windows:
            sink = pa.BufferOutputStream()
            with pa.ipc.new_stream(sink, w.table.schema) as wr:
                wr.write_table(w.table)
            wins.append((w.start, w.end, sink.getvalue().to_pybytes()))
    return wins, pipe.report()


def run_sustained_config(smoke):
    """Config 1: sustained epochs, bounded per-epoch latency, no orphans."""
    import raydp_tpu
    from raydp_tpu.runtime.object_store import get_client

    rows = 2_000 if smoke else 20_000
    epochs = 8 if smoke else 40
    s = raydp_tpu.init("stream-bench", num_executors=2, executor_cores=1,
                       executor_memory="512MB")
    try:
        before = get_client().stats()["num_objects"]
        pipe = _windowed_pipeline(s, _make_batch(rows), epochs)
        t0 = time.time()
        wins, rep = _drive(pipe)
        wall = time.time() - t0
        pipe.close()
        deadline = time.time() + 30
        while time.time() < deadline \
                and get_client().stats()["num_objects"] != before:
            time.sleep(0.2)
        record = {
            "epochs": rep["epochs"],
            "rows_in": rep["rows_in"],
            "rows_per_s": round(rep["rows_in"] / wall, 1) if wall else 0.0,
            "windows_closed": rep["windows_closed"],
            "replays": rep["replays"],
            "epoch_p50_s": rep["epoch_p50_s"],
            "epoch_p99_s": rep["epoch_p99_s"],
            "epoch_max_s": rep["epoch_max_s"],
            "latency_bounded": rep["epoch_p99_s"] < 10.0,
            "orphans": get_client().stats()["num_objects"] - before,
        }
    finally:
        raydp_tpu.stop()
    print(f"[sustained] epochs={record['epochs']} "
          f"p50={record['epoch_p50_s']}s p99={record['epoch_p99_s']}s "
          f"windows={record['windows_closed']} orphans={record['orphans']}")
    return record


def run_fault_replay_config(smoke):
    """Config 2: a dropped epoch blob replays exactly-once — window results
    byte-identical to the unfaulted run, zero orphans."""
    import raydp_tpu
    from raydp_tpu import faults
    from raydp_tpu.runtime.object_store import get_client

    rows = 2_000 if smoke else 10_000
    epochs = 6 if smoke else 16

    def one_run(fault):
        s = raydp_tpu.init("stream-chaos", num_executors=2,
                           executor_cores=1, executor_memory="512MB")
        try:
            before = get_client().stats()["num_objects"]
            if fault:
                # lose the SECOND epoch's freshly sealed partials — the
                # sliding window that includes it must replay from the
                # source journal
                faults.inject("stream.epoch", "drop", nth=2)
            pipe = _windowed_pipeline(s, _make_batch(rows), epochs)
            wins, rep = _drive(pipe)
            pipe.close()
            deadline = time.time() + 30
            while time.time() < deadline \
                    and get_client().stats()["num_objects"] != before:
                time.sleep(0.2)
            orphans = get_client().stats()["num_objects"] - before
            return wins, rep, orphans
        finally:
            faults.clear()
            raydp_tpu.stop()

    base, _, orphans0 = one_run(fault=False)
    got, rep, orphans1 = one_run(fault=True)
    record = {
        "epochs": epochs,
        "windows": len(base),
        "byte_identical": base == got,
        "replays": rep["replays"],
        "fault_fired": rep["replays"] >= 1,
        "orphans_baseline": orphans0,
        "orphans_faulted": orphans1,
    }
    print(f"[fault-replay] identical={record['byte_identical']} "
          f"replays={record['replays']} orphans={record['orphans_faulted']}")
    return record


def run_hot_swap_config(smoke):
    """Config 3: partial_fit hot-swaps servables into a live session under
    an open-loop predict burst — zero dropped requests."""
    import optax

    import raydp_tpu
    from raydp_tpu import stream
    from raydp_tpu.models import MLP
    from raydp_tpu.runtime.object_store import get_client
    from raydp_tpu.serve import ServingSession
    from raydp_tpu.train import FlaxEstimator

    rows = 512 if smoke else 4_096
    epochs = 4 if smoke else 12
    os.environ["RDT_SERVE_BATCH_TIMEOUT_MS"] = "10"
    s = raydp_tpu.init("stream-serve", num_executors=2, executor_cores=1,
                       executor_memory="512MB")
    try:
        est = FlaxEstimator(
            model=MLP(features=(8,), use_batch_norm=False),
            optimizer=optax.adam(1e-2), loss="mse",
            feature_columns=["x1", "x2"], label_column="y",
            batch_size=128, num_epochs=1)
        boot = _train_batch(rows)(10_000).to_pandas()
        est.fit_on_frame(s.createDataFrame(boot, num_partitions=2))
        root = tempfile.mkdtemp(prefix="rdt-stream-bench-")
        v0 = os.path.join(root, "v0")
        est.export_serving(v0)
        srv = ServingSession(v0, session=s, name="stream-bench")
        before = get_client().stats()["num_objects"]

        stop = threading.Event()
        burst = {"sent": 0, "ok": 0, "errors": []}
        rng = np.random.RandomState(5)

        def fire():
            futs = []
            while not stop.is_set():
                x = rng.random_sample((4, 2))
                try:
                    futs.append(srv.predict_async(
                        {"x1": x[:, 0], "x2": x[:, 1]}))
                    burst["sent"] += 1
                except Exception as e:  # noqa: BLE001 - counted below
                    burst["errors"].append(repr(e))
                time.sleep(0.002)
            for f in futs:
                try:
                    preds = f.result(timeout=120.0)
                    assert preds.shape == (4,)
                    burst["ok"] += 1
                except Exception as e:  # noqa: BLE001 - counted below
                    burst["errors"].append(repr(e))

        t = threading.Thread(target=fire)
        t.start()
        pipe = stream.read_stream(
            stream.SyntheticSource(_train_batch(rows), max_epochs=epochs),
            session=s)
        res = est.partial_fit(pipe, export_every=2, export_dir=root,
                              serving=srv)
        time.sleep(0.3)  # a few more requests against the final servable
        stop.set()
        t.join(timeout=600)
        rep = srv.serving_report()
        pipe.close()
        srv.close()
        deadline = time.time() + 30
        while time.time() < deadline \
                and get_client().stats()["num_objects"] != before:
            time.sleep(0.2)
        record = {
            "train_epochs": res.epochs,
            "exports": len(res.exports),
            "hot_swaps": rep["hot_swaps"],
            "active_servable": rep["servable"],
            "requests_sent": burst["sent"],
            "requests_ok": burst["ok"],
            "dropped": burst["sent"] - burst["ok"],
            "errors": burst["errors"][:5],
            "serve_failed": rep["failed"],
            "final_train_loss": round(
                res.history[-1]["train_loss"], 6) if res.history else None,
            "orphans": get_client().stats()["num_objects"] - before,
        }
    finally:
        raydp_tpu.stop()
        os.environ.pop("RDT_SERVE_BATCH_TIMEOUT_MS", None)
    print(f"[hot-swap] swaps={record['hot_swaps']} "
          f"sent={record['requests_sent']} dropped={record['dropped']} "
          f"active=v{record['active_servable']['version']} "
          f"orphans={record['orphans']}")
    return record


def _assert_contract(record):
    sus = record["configs"]["sustained"]
    assert sus["epochs"] > 0 and sus["windows_closed"] > 0, sus
    assert sus["latency_bounded"], sus
    assert sus["orphans"] == 0, sus
    rep = record["configs"]["fault_replay"]
    assert rep["byte_identical"], rep
    assert rep["fault_fired"], rep
    assert rep["orphans_baseline"] == 0 and rep["orphans_faulted"] == 0, rep
    hs = record["configs"]["hot_swap"]
    assert hs["hot_swaps"] >= 2, hs
    assert hs["requests_sent"] > 0, hs
    assert hs["dropped"] == 0 and not hs["errors"], hs
    assert hs["serve_failed"] == 0, hs
    assert hs["active_servable"]["version"] == hs["hot_swaps"] + 1, hs
    assert hs["orphans"] == 0, hs


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n", 1)[0])
    ap.add_argument("--smoke", action="store_true",
                    help="CI contract: small load, asserts, writes to /tmp")
    ap.add_argument("--out", default=None, help="record path override")
    args = ap.parse_args()
    here = os.path.dirname(os.path.abspath(__file__))
    out = args.out or ("/tmp/STREAM_SMOKE.json" if args.smoke
                       else os.path.join(here, "STREAM.json"))
    configs = {
        "sustained": run_sustained_config(args.smoke),
        "fault_replay": run_fault_replay_config(args.smoke),
        "hot_swap": run_hot_swap_config(args.smoke),
    }
    record = {
        "bench": "stream_bench",
        "metric": "stream_sustained_rows_per_s",
        "value": configs["sustained"]["rows_per_s"],
        "smoke": args.smoke,
        "ts": time.strftime("%Y-%m-%dT%H:%M:%S"),
        "configs": configs,
    }
    with open(out, "w") as fh:
        json.dump(record, fh, indent=2, sort_keys=True)
    print(f"record written to {out}")
    _assert_contract(record)
    print("stream bench contract: OK")


if __name__ == "__main__":
    main()
