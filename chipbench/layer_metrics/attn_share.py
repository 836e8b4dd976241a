"""Model: attention's share of the device's busy time. Busy seconds of the
ops whose ``op_name`` lies under an ``attn`` scope (every layer's projections,
an output gate's among them, QK norms, RoPE, a gate's sigmoid, and the flash
kernels, windowed and full, forward, recomputed and backward) over all busy
seconds (``trace/scopes.py`` reads the programs the trace stores). A program
without the scope says nothing."""

from chipbench.trace import scopes


def read(run):
    under = scopes.seconds_under(run, "/attn/")
    if under is None or not run["trace"]["busy_s"]:
        return None
    return 100.0 * under / run["trace"]["busy_s"]
