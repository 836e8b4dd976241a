"""Train loop: seconds of epoch 0 of the measured fit - the program's phase
span ``train:epoch`` with ``epoch=0``, loop top to the end of the callbacks,
which is where the window opens. It holds ``train:first_dispatch`` (trace,
lower, compile-cache load); the rest is one epoch of steps."""

from chipbench.trace import fit_spans


def read(run):
    return fit_spans.epoch0_s()
