"""Model: the share of the routed slots that fell on an expert this chip
holds, from the program's counter ``moe_slots_total{held|all}`` (summed on
the device inside the train step, fetched with each epoch's loss; whole
process, a calibration fit included). 25% under even routing with 16 of 64
experts held: how near the held experts' load is to a deployment's chip.
The grouped products run over these slots and no other. A program without
the counter says nothing."""


def read(run):
    slots = run["counters"].get("moe_slots_total", {})
    if not slots.get("all") or "held" not in slots:
        return None
    return 100.0 * slots["held"] / slots["all"]
