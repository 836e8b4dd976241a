"""Placement: device time inside collective operations (all-reduce,
all-gather, reduce-scatter, all-to-all, collective-permute) over device busy
time, from the trace, averaged over the chips."""


def read(run):
    trace = run["trace"]
    if not trace or not trace["busy_s"]:
        return None
    return 100.0 * trace["collective_s"] / trace["busy_s"]
