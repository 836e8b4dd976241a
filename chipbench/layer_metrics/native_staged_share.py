"""Host staging: share of the tables the feed decoded through the native
staging library and not the numpy fallback, from the program's counter
``feed_staged_tables_total{path}`` (a count, whole process)."""


def read(run):
    staged = run["counters"].get("feed_staged_tables_total", {})
    total = sum(staged.values())
    if not total:
        return None
    native = sum(v for k, v in staged.items() if "native" in str(k))
    return 100.0 * native / total
