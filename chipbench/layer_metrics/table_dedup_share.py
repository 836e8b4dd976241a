"""Model: the share of the device's busy time that the de-duplication of the
row-wise embedding tables' ids takes. Busy seconds of the ops whose
``op_name`` lies under the scope ``table_dedup`` (the ids of the tables a
step updates by row stacked ``[T, B]``, the sorts over the last axis and the
running count that number the distinct ids, and on a mesh the gather of the
batch's ids that every chip sorts) over all busy seconds. What is left to
take from the pass: a step that looked its rows up without sorting the ids
would read 0. A fusion counts for the scope of its root, so a lookup fused
with the slice that hands it a table's ``inv`` counts for whichever of the two
is the root. A program without the scope says nothing."""

from chipbench.trace import scopes


def read(run):
    under = scopes.seconds_under(run, "/table_dedup/")
    if under is None or not run["trace"]["busy_s"]:
        return None
    return 100.0 * under / run["trace"]["busy_s"]
