"""Train loop: share of the untraced window epochs' wall inside the jitted
step's dispatch calls (``dispatch_time_s``). On the resident path the one
dispatch of an epoch includes the loss fetch, so it carries the device time."""


def read(run):
    epochs = run["epochs"]
    wall = sum(e["epoch_time_s"] for e in epochs)
    return 100.0 * sum(e["dispatch_time_s"] for e in epochs) / wall if wall else None
