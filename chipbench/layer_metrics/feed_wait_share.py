"""Device feed: share of the untraced window epochs' wall the train loop spent
waiting in ``next()`` on the feed (``feed_time_s`` of the fit's own history) -
time in which the loop had no batch to dispatch."""


def read(run):
    epochs = run["epochs"]
    wall = sum(e["epoch_time_s"] for e in epochs)
    return 100.0 * sum(e["feed_time_s"] for e in epochs) / wall if wall else None
