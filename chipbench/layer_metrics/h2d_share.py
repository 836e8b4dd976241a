"""Device feed: share of the untraced window epochs' wall the feed's threads
spent in host-to-device placement (``h2d_time_s`` of the fit's own history).
Overlaps dispatch by design."""


def read(run):
    epochs = run["epochs"]
    wall = sum(e["epoch_time_s"] for e in epochs)
    return 100.0 * sum(e["h2d_time_s"] for e in epochs) / wall if wall else None
