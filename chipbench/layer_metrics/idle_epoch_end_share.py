"""Train loop: of the seconds the chips sat idle inside the traced span, the
percentage that fell under the program's step span ``train:epoch_end``:
between the last dispatch of an epoch and the
end of its callbacks (loss fetch, report, eval, callbacks).

Read off the trace itself: the program's annotations and the device's
``XLA Ops`` share its clock (``chipbench/trace/host_spans.py``). The three
``idle_*_share`` and what lies under none of them sum to 100. While the device
is idle under 1% of the time they are shares of ~0.05 s and wander from run to
run; they become the guide once the step is short and the host sets the pace."""

from chipbench.trace import host_spans


def read(run):
    return host_spans.idle_share("train:epoch_end")
