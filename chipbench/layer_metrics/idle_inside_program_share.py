"""Device: of the seconds the chips sat idle inside the traced span, the
percentage in gaps that begin and end inside one program execution (one event
of the chip's ``XLA Modules`` line): the chip waited on itself (a copy, a
loop's trip count, a collective's partner) while the host had done its part.

One rule cuts every gap (``chipbench/trace/idle_causes.py``): inside a
program, launch, host late, unmatched; the four sum to 100. Unlike the three
``idle_*_share`` of ``host_spans.py`` it does not ask where the loop's thread
stood: a loop parked in the loss fetch stands there for the whole epoch."""

from chipbench.trace import idle_causes


def read(run):
    return idle_causes.share(run.get("xplane"), idle_causes.INSIDE)
