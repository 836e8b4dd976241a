"""Model: attention's share of the device's busy time in an ``afmoe`` model:
busy seconds of the ops whose ``op_name`` lies under an ``attn`` scope (every
layer's five projections, the output gate among them, the head norms, RoPE,
the gate's sigmoid and the flash kernels, windowed and full, forward,
recomputed and backward) over all busy seconds. It sums ``op_seconds``, whose
leaf rule drops a kernel execution that holds an async copy's ``-done``
(PERF.md section 3), from both sides. The configuration is the one whose
cell this metric lists: a run of another says nothing."""

from chipbench.trace import kernels, scopes

CONFIG = "trinity-mini"


def read(run):
    if not run.get("trace") or kernels.sizes_of(CONFIG, run) is None:
        return None
    under = scopes.seconds_under(run, "/attn/")
    if under is None or not run["trace"]["busy_s"]:
        return None
    return 100.0 * under / run["trace"]["busy_s"]
