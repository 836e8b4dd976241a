"""Model: the operations the traced epochs' items need (forward and backward
matrix multiplications from the configuration's shapes, ``chipbench/flops``;
gathers and the table update count zero) over what the chips could have done
at their bf16 peak in the time they were busy."""


def read(run):
    trace = run["trace"]
    if not trace or not trace["busy_s"] or not run["traced_items"]:
        return None
    possible = trace["busy_s"] * run["chips"] * run["peak"]["bf16_flops_per_s"]
    return 100.0 * run["flops_per_item"] * run["traced_items"] / possible
