"""Model: the share of the device's busy time that one chip's share of an
``afmoe`` expert layer takes: the router (sigmoid, bias, top-k, the counts the
bias update reads), the shared expert, and the held experts' walk. Busy
seconds of the ops whose ``op_name`` lies under a ``moe`` scope (forward,
recomputed and backward) and of the grouped products themselves
(``ragged-dot``, which run over the held experts' slots alone and keep no
op_name of the model's) over all busy seconds. The configuration is the one
whose cell this metric lists: a run of another says nothing
(``trace/kernels.sizes_of``)."""

from chipbench.trace import kernels, scopes

CONFIG = "trinity-mini"


def read(run):
    if not run.get("trace") or kernels.sizes_of(CONFIG, run) is None:
        return None
    under = scopes.seconds_under(run, "/moe/", kernels=r"^ragged-dot")
    if under is None or not run["trace"]["busy_s"]:
        return None
    return 100.0 * under / run["trace"]["busy_s"]
