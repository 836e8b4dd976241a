"""Frame to dataset: seconds of the measured fit's start-up that no phase span
names - from the start of ``fit:run`` to the end of epoch 0's ``train:epoch``,
less the union of ``fit:convert``, ``fit:shuffle``, ``fit:feed``, ``fit:init``,
``train:place`` and that epoch. Mesh and optimizer construction, building the
jitted step, imports. Where this grows, a span is missing."""

from chipbench.trace import fit_spans


def read(run):
    return fit_spans.unattributed_s()
