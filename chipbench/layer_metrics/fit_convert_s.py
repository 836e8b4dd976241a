"""Frame to dataset: seconds the measured ``fit_on_frame`` spent turning the
frame into a dataset - the program's phase spans ``fit:convert`` (the ETL
action that materialises the frame into recoverable blocks) and ``fit:shuffle``
(the ``random_shuffle`` pass of a streaming fit). With ``fit_state_s``,
``fit_epoch0_s`` and ``fit_unattributed_s`` it sums to ``fit_startup_s``."""

from chipbench.trace import fit_spans


def read(run):
    return fit_spans.phase_s("fit:convert", "fit:shuffle")
