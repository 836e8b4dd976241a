"""Device: of the seconds the chips sat idle inside the traced span, the
percentage in gaps that begin and end inside one program execution (one event
of the chip's ``XLA Modules`` line): the chip waited on itself (a copy, a
loop's trip count, a collective's partner) while the host had done its part.

One rule cuts every gap (``chipbench/trace/idle_causes.py``): inside a
program, launch, host late, unmatched; the four sum to 100. Unlike the idle
seconds by loop span of ``host_spans.py`` (the result line's
``breakdown.idle_gaps``) it does not ask where the loop's thread stood: a
loop parked in the loss fetch stands there for the whole epoch. A loop's or a
conditional's own control between its body's ops counts here; a kernel that
holds a zero-length event is busy (``reduce.leaf_ops``)."""

from chipbench.trace import idle_causes


def read(run):
    return idle_causes.share(run.get("xplane"), idle_causes.INSIDE)
