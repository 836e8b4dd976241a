"""Device feed: share of the untraced window epochs' wall the loop spent in
each epoch's FIRST ``next()`` on the feed (the history's
``first_pull_time_s``): ``DeviceFeed.__iter__`` builds a new chain of stage
threads an epoch and the first batch has to come all the way through it, while
the chip has nothing queued. What a feed kept across epochs could give back;
the rest of ``feed_wait_share`` is pulls in the epoch's course."""

from chipbench.trace import idle_causes


def read(run):
    return idle_causes.history_share(run, "first_pull_time_s")
