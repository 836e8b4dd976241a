"""Model: the state-space mixers' share of the device's busy time. Busy
seconds of the ops whose ``op_name`` lies under an ``ssm`` scope (every
state-space layer's two projections, its convolution, the scan kernels and
what lies round them, the gated norm; forward, recomputed and backward) over
all busy seconds (``trace/scopes.py`` reads the programs the trace stores).
A program without the scope says nothing."""

from chipbench.trace import scopes


def read(run):
    under = scopes.seconds_under(run, "/ssm/")
    if under is None or not run["trace"]["busy_s"]:
        return None
    return 100.0 * under / run["trace"]["busy_s"]
