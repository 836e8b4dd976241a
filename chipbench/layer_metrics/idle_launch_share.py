"""Device: of the seconds the chips sat idle inside the traced span, the
percentage between two program executions AFTER the loop had handed the next
one over (the end of its ``train:dispatch``, paired by order): the program was
queued and had not started. It grows with the number of launches in the span
and says nothing against the host's loop (``chipbench/trace/idle_causes.py``
has the rule; the four causes sum to 100)."""

from chipbench.trace import idle_causes


def read(run):
    return idle_causes.share(run.get("xplane"), idle_causes.LAUNCH)
