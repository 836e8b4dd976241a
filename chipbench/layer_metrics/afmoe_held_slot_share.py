"""Model: the share of the routed slots that fell on an expert this chip
holds, in an ``afmoe`` model, from the program's counter
``moe_slots_total{held|all}`` (summed on the device inside the train step,
fetched with each epoch's loss; whole process, a calibration fit included).
12.5% when the bias has balanced the load over 128 experts of which 16 are
held: how near the held experts' load is to a deployment's chip. The grouped
products run over these slots and no other. It counts with no size of the
configuration's, so it reads any run that holds a share; a program without
the counter says nothing."""


def read(run):
    slots = run["counters"].get("moe_slots_total", {})
    if not slots.get("all") or "held" not in slots:
        return None
    return 100.0 * slots["held"] / slots["all"]
