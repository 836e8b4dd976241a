"""Model: how unevenly the router filled the experts. Slots of each layer's
fullest expert over the mean slots an expert, over **all** the experts the
router chooses among, whatever this chip holds
(``flops/<family>.num_experts`` of the cell's configuration), from the
program's counter ``moe_slots_total{max_expert|all}`` (summed on the device
inside the train step, fetched with each epoch's loss; whole process, a
calibration fit included). 1.0 is a perfectly even router: what a balance
loss or a balancing bias steers to; a dropless layer's grouped products take
as long as their fullest groups make them. A program without the counter, or
a family without experts, says nothing."""


def read(run):
    slots = run["counters"].get("moe_slots_total", {})
    experts = getattr(run["flops"], "num_experts", None)
    if not slots.get("all") or "max_expert" not in slots or experts is None:
        return None
    return slots["max_expert"] / (slots["all"] / experts(run["cfg"]))
