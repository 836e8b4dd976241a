"""Model: share of the embedding tables the model declared that the train step
updates row-wise (differentiates, updates and writes only the rows a batch
looked up) and not dense (sweeps the whole table), from the program's counter
``train_table_updates_total{rowwise|dense}`` (one count a declared table a
built step; a count, whole process, a calibration fit included, which builds
the same step). A table stays dense when it has no more rows than the batch,
when the optimizer's probe fails, or under accumulation or a pipeline; the
fit's log names which. A program without the counter says nothing."""


def read(run):
    tables = run["counters"].get("train_table_updates_total", {})
    total = sum(tables.values())
    return 100.0 * tables.get("rowwise", 0) / total if total else None
