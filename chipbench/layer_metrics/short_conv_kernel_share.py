"""Kernels: the share of the gated short convolutions a built program holds
that took the kernels' path, from the program's counter
``short_conv_total{kernel|jnp}`` (one count a built layer call by the path
its shapes take; whole process, a calibration fit included, which builds the
same step). 100 on the chip at the published shape; a quiet fall to the
``jax.numpy`` form there (a shape ``short_conv.kernel_ineligible`` refuses)
reads 0. A program without the counter says nothing."""


def read(run):
    calls = run["counters"].get("short_conv_total", {})
    total = sum(calls.values())
    return 100.0 * calls.get("kernel", 0) / total if total else None
