"""The on-chip benchmark of raydp_tpu: one command, cells driven by data.
See ``run.py`` (the command), ``BENCHMARK.json`` at the root and ``PERF.md``."""
