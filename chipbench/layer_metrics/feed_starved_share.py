"""Device feed: share of the train loop's pulls from the feed that found its
queue empty, from the program's counter ``feed_pulls_total{ready|empty}`` (one
count a batch at the consumer's ``get()`` of the stage the loop pulls from; a
count, whole process, a calibration fit included). It says how often the loop
had to wait for the feed, not that the chip starved: at each epoch's start the
loop runs tens of steps ahead of the device as fast as the feed delivers, and
those pulls find the queue empty while the device's own queue is deep (3% on
one chip, 26% on four, both at 0.8% device idle; PERF.md). Read it beside
``idle_host_late_share`` and ``feed_restart_share``."""


def read(run):
    pulls = run["counters"].get("feed_pulls_total", {})
    total = sum(pulls.values())
    return 100.0 * pulls.get("empty", 0) / total if total else None
