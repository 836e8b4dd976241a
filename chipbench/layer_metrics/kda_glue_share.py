"""Model: the share of the device's busy time that the Kimi Delta Attention
operators spend round their scan and between their projections: busy seconds
of the ops whose ``op_name`` lies under ``kda/conv`` (the 4-tap convolution
and its SiLU on q, k and v), ``kda/gate`` (the decay's two low-rank products,
its softplus, ``beta``, the two L2 norms) or ``kda/norm`` (the gated norm a
head and the output gate's two products); forward, recomputed and backward,
over all busy seconds. Bandwidth-bound stages beside the projections'
products and the scan. A program without the scopes says nothing."""

from chipbench.trace import scopes


def read(run):
    parts = [scopes.seconds_under(run, f"/kda/{part}/")
             for part in ("conv", "gate", "norm")]
    found = [p for p in parts if p is not None]
    if not found or not run["trace"]["busy_s"]:
        return None
    return 100.0 * sum(found) / run["trace"]["busy_s"]
