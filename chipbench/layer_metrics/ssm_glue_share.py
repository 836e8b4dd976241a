"""Model: the share of the device's busy time that a state-space mixer spends
on what is neither a projection nor a scan kernel: busy seconds of the ops
whose ``op_name`` lies under ``ssm/conv`` (the causal convolution's shifted
multiply-adds and SiLU) or ``ssm/norm`` (the gate and the grouped RMSNorm),
and of the ops under ``ssm/scan`` that are not the two kernels themselves
(``rdt_ssd_fwd``, ``rdt_ssd_bwd``: softplus, the decays' cumulative sums,
the packing of ``dt`` for the kernels, the splits; forward, recomputed and
backward), over all busy seconds. What a kernel that took the convolution or
the gated norm in would take away. A program without these scopes says
nothing."""

import re

from chipbench.trace import scopes

KERNELS = re.compile(r"^rdt_ssd_(fwd|bwd)")


def read(run):
    trace = run.get("trace")
    if not trace or not run.get("xplane") or not trace["busy_s"]:
        return None
    glue = {op for op, scope in scopes.op_names(run["xplane"]).items()
            if "/ssm/conv/" in scope or "/ssm/norm/" in scope
            or ("/ssm/scan/" in scope and not KERNELS.search(op))}
    if not glue:
        return None
    return 100.0 * sum(sec for op, sec in trace["op_seconds"].items()
                       if op in glue) / trace["busy_s"]
