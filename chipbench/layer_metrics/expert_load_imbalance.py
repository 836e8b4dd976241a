"""Model: how unevenly the router filled the experts. Slots of each layer's
fullest expert over the mean slots an expert, from the program's counter
``moe_slots_total{max_expert|all}`` (summed on the device inside the train
step, fetched with each epoch's loss; whole process, a calibration fit
included). 1.0 is a perfectly even router; a dropless layer's grouped
products take as long as their fullest groups make them. The experts a layer
holds come from the configuration whose cells this metric lists. A program
without the counter, or a run of another configuration, says nothing."""

from chipbench.trace import kernels

CONFIG = "olmoe-1b-7b"


def read(run):
    slots = run["counters"].get("moe_slots_total", {})
    sizes = kernels.sizes_of(CONFIG, run) if slots.get("all") else None
    if sizes is None:
        return None
    return slots["max_expert"] / (slots["all"] / sizes[0]["num_experts"])
