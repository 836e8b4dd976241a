"""Checkpoint: the benchmark's clock from the report of the last epoch to the
return of ``fit_on_frame`` - the final checkpoint save (device to host, then
orbax to disk) and the assembly of the result."""


def read(run):
    return run["clock"].get("final_save_s")
