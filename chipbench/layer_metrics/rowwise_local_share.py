"""Model: share of the row-wise embedding tables whose looked-up rows are read
and written shard by shard (the table's rows are split over mesh axes and each
shard walks its own slice of the batch's distinct ids) and not by every chip
that holds a part of the table walking all of them, from the program's counter
``train_table_walk_total{shard_local|global}`` (one count a row-wise table a
built step; a count, whole process, a calibration fit included, which builds
the same step). 0 where no table is sharded (one chip). A program without the
counter says nothing."""


def read(run):
    tables = run["counters"].get("train_table_walk_total", {})
    total = sum(tables.values())
    return 100.0 * tables.get("shard_local", 0) / total if total else None
