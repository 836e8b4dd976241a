"""Model: the share of the routed slots that fell on an expert this chip
holds, from the program's counter ``moe_slots_total{held|all}`` (summed on
the device inside the train step, fetched with each epoch's loss; whole
process, a calibration fit included). Under even routing it is the share of
the experts held (25% with 16 of 64, 12.5% with 16 of 128): how near the held
experts' load is to a deployment's chip. The grouped products run over these
slots and no other. It counts with no size of the configuration's; a program
that holds every expert, or has no such counter, says nothing."""


def read(run):
    slots = run["counters"].get("moe_slots_total", {})
    if not slots.get("all") or "held" not in slots:
        return None
    return 100.0 * slots["held"] / slots["all"]
