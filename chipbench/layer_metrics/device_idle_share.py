"""Device: 1 - union of the device-op intervals over the traced span, for each
chip; the worst chip is reported."""


def read(run):
    trace = run["trace"]
    if not trace:
        return None
    return 100.0 * max(c["idle_share"] for c in trace["per_chip"])
