"""Frame to dataset: seconds the measured fit spent on its feed and its state
before the first step - the program's phase spans ``fit:feed`` (residency
decision, feed construction, first host batch), ``fit:init`` (``model.init``
and the optimizer state, eager, on device 0) and ``train:place`` (placement
under the sharding rules)."""

from chipbench.trace import fit_spans


def read(run):
    return fit_spans.phase_s("fit:feed", "fit:init", "train:place")
