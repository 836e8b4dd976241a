"""Model: the fused head loss's share of the device's busy time. Busy seconds
of the ops whose ``op_name`` lies under the ``lm_head_loss`` scope (the
head's product chunk by chunk, softmax and cross entropy, and in the backward
pass the same again plus both gradients) over all busy seconds. The fewer
layers a cell holds, the larger it reads: at depth 1 it is the largest part
of a step, a deployment's sixteen layers would put it near a tenth."""

from chipbench.trace import scopes


def read(run):
    under = scopes.seconds_under(run, "lm_head_loss")
    if under is None or not run["trace"]["busy_s"]:
        return None
    return 100.0 * under / run["trace"]["busy_s"]
