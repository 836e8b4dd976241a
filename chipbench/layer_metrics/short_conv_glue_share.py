"""Model: the share of the device's busy time that the convolution operators
spend between their two projections: busy seconds of the ops whose
``op_name`` lies under ``short_conv/conv`` (the two gates and the three taps
of ``C * conv(B * z)``: the kernels ``rdt_gated_conv_fwd|bwd`` with the few
ops round them, or the ``jax.numpy`` form's passes; forward, recomputed and
backward) over all busy seconds. A bandwidth-bound stage beside the
projections' products. A program without the scope says nothing."""

from chipbench.trace import scopes


def read(run):
    under = scopes.seconds_under(run, "/short_conv/conv/")
    if under is None or not run["trace"]["busy_s"]:
        return None
    return 100.0 * under / run["trace"]["busy_s"]
