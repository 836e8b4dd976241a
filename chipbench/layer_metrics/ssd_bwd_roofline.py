"""Kernels: the backward state-space scan kernel's share of its roofline,
over all the state-space layers held. The seconds of ``rdt_ssd_bwd`` in the
traced epochs against the least a chip could take for the products the
gradient needs (``flops/<family>.ssd_backward``: over the positions a
position sees, ``C B^T`` again, ``dC`` and ``dB`` once a group, ``dy x^T`` and
``dx`` once a head, and five products with a head's state; ``x``, ``dy``,
``B``, ``C`` and ``dt`` read, ``dx``, ``dB``, ``dC`` and ``d dt`` written
once; the chunk states it also reads are not counted), one execution for
each kernel instruction the trace holds (``trace/executions.py``). A program
without the kernel, or a family that counts none, says nothing. Never
clipped."""

from chipbench.trace import executions, roofline

KERNEL = r"^rdt_ssd_bwd"


def read(run):
    found = executions.work_of(run, {"scan": KERNEL}, "ssd_backward", KERNEL)
    return None if found is None else roofline.share(*found, run["peak"])
