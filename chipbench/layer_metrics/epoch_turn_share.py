"""Train loop: share of the untraced window epochs' wall in which the chip had
nothing queued because an epoch ended: the history's ``lead_time_s`` (from the
return of the previous epoch's loss fetch to the moment the epoch's first
program was handed over: report, callbacks, the phase span, the save check,
the feed's restart, the first dispatch) over ``epoch_time_s``. On the host's
clock in every epoch, so it does not depend on how many epochs a trace holds.
It holds ``feed_restart_share``."""

from chipbench.trace import idle_causes


def read(run):
    return idle_causes.history_share(run, "lead_time_s")
