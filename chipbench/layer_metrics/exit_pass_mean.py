"""Model: the pass at which a looped model's exit gate expects to stop,
``sum_t t * mass_t / positions``, from the program's counters
``train_exit_mass_total{<pass>}`` (the sum over a step's positions of the exit
probability ``p_t``, a label a pass) and ``train_exit_positions_total`` (the
positions that carried a loss), both summed on the device inside the train
step and fetched with each epoch's loss (whole process, a calibration fit
included). It is a reading of the objective, not a cost (``better: lower``
only because the manifest wants a direction): 1.875 of 4 at a fresh gate
(``lambda`` = 1/2: ``p`` = 1/2, 1/4, 1/8, 1/8), towards 2.5 as the entropy
term flattens the distribution; a gate whose gradient is lost stays where it
started. A program without the counters says nothing."""


def read(run):
    mass = run["counters"].get("train_exit_mass_total", {})
    positions = sum(run["counters"].get(
        "train_exit_positions_total", {}).values())
    if not mass or not positions:
        return None
    return sum(int(t) * m for t, m in mass.items()) / positions
