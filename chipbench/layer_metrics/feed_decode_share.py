"""Host staging: share of the untraced window epochs' wall that the feed's
threads spent decoding Arrow blocks into batches (``decode_time_s`` of the
fit's own history). It overlaps dispatch by design: it attributes, it does not
sum with the other shares."""


def read(run):
    epochs = run["epochs"]
    wall = sum(e["epoch_time_s"] for e in epochs)
    return 100.0 * sum(e["decode_time_s"] for e in epochs) / wall if wall else None
