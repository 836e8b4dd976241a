"""Frame to dataset: the benchmark's clock from the call of the measured
``fit_on_frame`` to the report of epoch 0 - frame to dataset conversion,
shuffle pass, cache or feed construction, state initialisation and placement,
trace and compile-cache load, and epoch 0 itself."""


def read(run):
    return run["clock"].get("fit_startup_s")
