"""Model: the Kimi Delta Attention operators' share of the device's busy time.
Busy seconds of the ops whose ``op_name`` lies under a ``kda`` scope (every
``K`` pair's operator: the fused projection ``in_proj``, the convolution under
``conv``, the decay, ``beta`` and the L2 norms under ``gate``, the chunked
scan under ``scan``, the gated norm with its gate's two products under
``norm``, and ``out_proj``; forward, recomputed and backward) over all busy
seconds (``trace/scopes.py`` reads the programs the trace stores). A program
without the scope says nothing."""

from chipbench.trace import scopes


def read(run):
    under = scopes.seconds_under(run, "/kda/")
    if under is None or not run["trace"]["busy_s"]:
        return None
    return 100.0 * under / run["trace"]["busy_s"]
