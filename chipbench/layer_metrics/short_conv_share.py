"""Model: the convolution operators' share of the device's busy time. Busy
seconds of the ops whose ``op_name`` lies under a ``short_conv`` scope (every
``C`` pair's operator: both projections, ``in_proj`` and ``out_proj``, and
the gated convolution between them under ``conv``; forward, recomputed and
backward) over all busy seconds (``trace/scopes.py`` reads the programs the
trace stores). A program without the scope says nothing."""

from chipbench.trace import scopes


def read(run):
    under = scopes.seconds_under(run, "/short_conv/")
    if under is None or not run["trace"]["busy_s"]:
        return None
    return 100.0 * under / run["trace"]["busy_s"]
