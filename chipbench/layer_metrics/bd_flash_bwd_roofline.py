"""Kernels: the backward flash-attention kernels' share of their roofline
under the block-diffusion mask, over all the layers held. The seconds of
``rdt_flash_bd_bwd_dkdv_dq`` (one kernel a layer) or, where a K/V head's
gradients do not fit the kernel's VMEM budget, of ``rdt_flash_bd_bwd_dkdv``
and ``rdt_flash_bd_bwd_dq`` together, against the least a chip could take for
the five products the gradient needs over each layer's ``L^2 + L Bd`` visible
pairs, K and V read and dK and dV written once a group
(``flops/<family>.bd_flash_backward``: one layer's kernel or pair; the layers
are counted from the trace, one for each instruction that writes dK and dV:
``trace/executions.py``). A pair of kernels forms the scores and dP twice,
which is recompute and not counted. 100 would be the MXU busy with the five
products alone at its published bf16 peak; the masked part of edge tiles and
the elementwise passes between the products keep the kernel under it. A
program without these kernels, or a family that counts none, says nothing.
Never clipped."""

from chipbench.trace import executions, roofline

LAYERS = r"^rdt_flash_bd_bwd_dkdv"       # the one kernel, or the pair's first
KERNEL = r"^rdt_flash_bd_bwd_"


def read(run):
    found = executions.work_of(run, {"blockdiff": LAYERS},
                               "bd_flash_backward", KERNEL)
    return None if found is None else roofline.share(*found, run["peak"])
