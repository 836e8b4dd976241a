"""Model: how unevenly the router filled the experts of an ``afmoe`` model:
slots of each layer's fullest expert over the mean slots an expert, over
**all** the experts (128), whatever this chip holds, from the program's
counter ``moe_slots_total{max_expert|all}`` (summed on the device inside the
train step, fetched with each epoch's loss; whole process, a calibration fit
included). 1.0 is a perfectly even router: what the balancing bias steers
to, 0.001 a step. The expert count comes from the configuration whose cell
this metric lists; a program without the counter, or a run of another
configuration, says nothing."""

from chipbench.trace import kernels

CONFIG = "trinity-mini"


def read(run):
    slots = run["counters"].get("moe_slots_total", {})
    sizes = kernels.sizes_of(CONFIG, run) if slots.get("all") else None
    if sizes is None:
        return None
    return slots["max_expert"] / (slots["all"] / sizes[0]["num_experts"])
