"""Train loop: of the seconds the chips sat idle inside the traced span, the
percentage between two program executions BEFORE the loop had handed the next
one over (the end of its ``train:dispatch``): nothing was queued, the chip
waited for the host. ``python3 -m chipbench.trace.idle_causes <trace>`` lays
these seconds under the loop's finer spans (``train:loss_fetch``,
``train:epoch_turn``, ``feed:start`` ...); the four causes sum to 100."""

from chipbench.trace import idle_causes


def read(run):
    return idle_causes.share(run.get("xplane"), idle_causes.HOST_LATE)
