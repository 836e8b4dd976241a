"""Model: what a looped model's loop over its passes costs BESIDE the layers
it runs, as a share of the device's busy time. Busy seconds of the ops whose
``op_name`` lies under the scope ``loop`` (``raydp_tpu/models/transformer.py``,
``_looped``: the one ``lax.scan`` over ``total_ut_steps``, forward and
transposed) and under no ``block_<i>``, ``exit_gate`` or ``lm_head_loss``
scope, over all busy seconds: the stacked residuals' writes and reads (what a
recomputed block keeps, once a pass and a layer), the carry's copies, the
shared weights' gradients summed pass by pass, and the final norm that ends
every pass (``trace/scopes.py`` reads the programs the trace stores). The
layers' own time, which the loop multiplies by the passes, is not in it. Small
is good. A program without the scope says nothing."""

import re

from chipbench.trace import scopes

INSIDE = re.compile(r"/block_\d+/|/exit_gate/|lm_head_loss")


def read(run):
    trace = run.get("trace")
    if not trace or not run.get("xplane") or not trace["busy_s"]:
        return None
    looped = {op: scope for op, scope in scopes.op_names(run["xplane"]).items()
              if "/loop/" in scope}
    if not looped:
        return None
    own = {op for op, scope in looped.items() if not INSIDE.search(scope)}
    return 100.0 * sum(sec for op, sec in trace["op_seconds"].items()
                       if op in own) / trace["busy_s"]
