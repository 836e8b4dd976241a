"""Kernels: the forward state-space scan kernel's share of its roofline, over
all the state-space layers held. The seconds of ``rdt_ssd_fwd``
(``raydp_tpu/ops/ssd_scan``'s ``KERNEL_NAMES``) in the traced epochs against
the least a chip could take for the same executions: each kernel instruction
of the step's program runs once over every traced sequence, so the
executions are counted from the trace itself (``trace/executions.py``: a
recomputed layer's second forward scan counts with its seconds), and one
execution's work is the cell's family's (``flops/<family>.ssd_forward``: the
intra-chunk products over the positions a position sees, ``C B^T`` once a
group, the two products with the carried state; ``x``, ``B``, ``C`` and
``dt`` read and ``y`` written once; the chunk states the program also writes
are not counted) at the peaks of ``peaks.json``. A program without the
kernel, or a family that counts none, says nothing. Never clipped."""

from chipbench.trace import executions, roofline

KERNEL = r"^rdt_ssd_fwd"


def read(run):
    found = executions.work_of(run, {"scan": KERNEL}, "ssd_forward", KERNEL)
    return None if found is None else roofline.share(*found, run["peak"])
