"""Model: the sparse expert layers' share of the device's busy time, whole or
the share of them one chip holds. Busy seconds of the ops whose ``op_name``
lies under a ``moe`` scope (router, the sort and dispatch of the slots, the
experts' activation and gate, a shared expert, combine; forward, recomputed
and backward) and of the grouped products themselves (``ragged-dot``: the
compiler's kernel keeps no op_name of the model's; with a share held they run
over the held experts' slots alone) over all busy seconds; the optimizer's
update of the expert kernels is outside it (``trace/scopes.py`` reads the
programs the trace stores). A program without the scope says nothing."""

from chipbench.trace import scopes


def read(run):
    under = scopes.seconds_under(run, "/moe/", kernels=r"^ragged-dot")
    if under is None or not run["trace"]["busy_s"]:
        return None
    return 100.0 * under / run["trace"]["busy_s"]
