"""Session and ETL plane: the benchmark's clock around the configuration's ETL
plan, from reading the Parquet input to the frame materialised in the
executors' caches (``persist()``)."""


def read(run):
    return run["clock"].get("etl_wall_s")
