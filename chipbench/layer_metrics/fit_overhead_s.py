"""Frame to dataset: wall of the measured ``fit_on_frame`` call minus the
window - its start-up (``fit_startup_s``) plus what follows the last epoch
(``final_save_s``), as a session's first fit pays them with the compile cache
and the native libraries already on disk. One reading a run, and on the sealed
machine its spread is that of a lazy ``import orbax.checkpoint`` (PERF.md), so
it is a per-layer number and not an end-to-end metric."""


def read(run):
    clock = run["clock"]
    if "fit_startup_s" not in clock or "final_save_s" not in clock:
        return None
    return clock["fit_startup_s"] + clock["final_save_s"]
