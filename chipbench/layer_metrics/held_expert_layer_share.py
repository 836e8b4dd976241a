"""Model: the share of the device's busy time that one chip's share of the
expert layers takes. Busy seconds of the ops whose ``op_name`` lies under a
``moe`` scope (router, the sort and dispatch of all slots, ReLU and gate,
combine, forward, recomputed and backward) and of the grouped products
themselves (``ragged-dot``, which run over the held experts' slots alone and
keep no op_name of the model's) over all busy seconds. Only a program whose
expert layer holds a share counts ``moe_slots_total{held}``: without it (the
parent, or a cell whose layers hold every expert, which ``expert_layer_share``
reads) this says nothing."""

from chipbench.trace import scopes


def read(run):
    if not run["counters"].get("moe_slots_total", {}).get("held"):
        return None
    under = scopes.seconds_under(run, "/moe/", kernels=r"^ragged-dot")
    if under is None or not run["trace"]["busy_s"]:
        return None
    return 100.0 * under / run["trace"]["busy_s"]
