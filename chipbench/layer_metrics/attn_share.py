"""Model: attention's share of the device's busy time. Busy seconds of the
ops whose ``op_name`` lies under an ``attn`` scope (every layer's projections,
RoPE and flash kernels, windowed and full, forward, recomputed and backward)
over all busy seconds (``trace/scopes.py`` reads the programs the trace
stores). It sums ``op_seconds``, whose leaf rule drops a kernel execution
that holds an async copy's ``-done`` (PERF.md section 3), from both sides."""

from chipbench.trace import scopes


def read(run):
    under = scopes.seconds_under(run, "/attn/")
    if under is None or not run["trace"]["busy_s"]:
        return None
    return 100.0 * under / run["trace"]["busy_s"]
