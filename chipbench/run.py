"""The benchmark's one command. From the root of the checkout:

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process; it owns the chip(s); the ETL executors are CPU children that
``raydp_tpu.init`` starts. It needs the TPU chips the cell asks for: anywhere
else it exits non-zero, naming what it found, and no flag changes that. The
last line of stdout is the result object (``correct``, ``attempted``,
``failed``, ``metrics``, ``device``, traced ``breakdown``, and last
``compared``: each number the checks compared beside its limit, which are
also the last lines of stderr); the run's detail (its ``counters`` among it)
goes on the line before it and into ``chipbench/out/<cell>/``.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()       # set-up is counted from here

import argparse     # noqa: E402
import json         # noqa: E402
import os           # noqa: E402
import sys          # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), required=True)
    args = ap.parse_args(argv)

    sys.path.insert(0, ROOT)
    try:
        from raydp_tpu.utils import compile_cache_dir
    except ImportError as e:
        print(f"chipbench measures the raydp_tpu checkout it sits in, and "
              f"found none at {ROOT}: {e}", file=sys.stderr)
        return 2
    # the compile cache goes where the environment names a directory for it,
    # else to the fixed <checkout>/.jax_cache (the program's one helper decides;
    # nothing here sets it); before jax is imported, inherited by children
    compile_cache_dir()
    # libtpu's logs go inside the checkout, not to its fixed /tmp/tpu_logs
    os.environ.setdefault("TPU_LOG_DIR", os.path.join(
        ROOT, "chipbench", "out", "tpu_logs"))
    os.makedirs(os.environ["TPU_LOG_DIR"], exist_ok=True)
    # cache every program, also the small ones a fit's set-up is made of
    os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "0")
    os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES", "-1")

    from chipbench import harness, manifest

    cell = manifest.resolve(manifest.load_manifest(), args.workload)
    harness.adopt_orphans()
    try:
        result = harness.run_cell(cell, args.seed, args.seconds,
                                  bool(args.trace), T_START)
    except harness.NoChip as e:
        print(f"chipbench: {e}", file=sys.stderr)
        return 3
    finally:
        # every process the run started has ended before the result is printed
        harness.reap_children()
    detail = result.pop("detail")
    out = os.path.join(ROOT, manifest.BENCH_DIR, "out", cell.name,
                       f"detail-trace{args.trace}.json")
    with open(out, "w") as fh:
        json.dump(detail, fh, indent=1)
    print("detail " + json.dumps(detail), flush=True)
    # each number the checks compared, beside its limit: the line's last key
    result["compared"] = detail["found"]["compared"]
    print(json.dumps(result), flush=True)
    for name, pair in result["compared"].items():
        print(f"compared {name}: {pair['value']} limit {pair['limit']}",
              file=sys.stderr, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
