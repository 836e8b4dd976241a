"""Model: the sparse expert layer's share of the device's busy time. Busy
seconds of the ops whose ``op_name`` lies under a ``moe`` scope (router,
dispatch, SiLU and gate, combine, forward and backward) and of the grouped
products themselves (``ragged-dot``: the compiler's kernel keeps no op_name
of the model's) over all busy seconds; the optimizer's update of the expert
kernels is outside it (``trace/scopes.py`` reads the programs the trace
stores)."""

from chipbench.trace import scopes


def read(run):
    under = scopes.seconds_under(run, "/moe/", kernels=r"^ragged-dot")
    if under is None or not run["trace"]["busy_s"]:
        return None
    return 100.0 * under / run["trace"]["busy_s"]
