"""Model: the shared expert's share of the device's busy time. Busy seconds
of the ops whose ``op_name`` lies under the scope ``moe/shared`` (the three
products every token takes in every expert layer, SiLU and the gate; forward,
recomputed and backward) over all busy seconds. A program without the scope
says nothing."""

from chipbench.trace import scopes


def read(run):
    under = scopes.seconds_under(run, "/moe/shared/")
    if under is None or not run["trace"]["busy_s"]:
        return None
    return 100.0 * under / run["trace"]["busy_s"]
