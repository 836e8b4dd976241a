"""Kernels: the forward flash-attention kernel's share of its roofline under
the block-diffusion mask, over all the layers held. The seconds of
``rdt_flash_bd_fwd`` (``raydp_tpu/ops/flash_attention``'s
``BLOCKDIFF_KERNEL_NAMES``; a name the causal and windowed kernels' readers do
not match) in the traced epochs against the least a chip could take for the
same executions: each kernel instruction of the step's program runs once over
every traced row, so the executions are counted from the trace itself
(``trace/executions.py``; a recomputed block that ran its forward kernel a
second time would count it with its seconds), and one execution's work is the
cell's family's (``flops/<family>.bd_flash_forward``: QK^T and PV over the
``L^2 + L Bd`` pairs the mask leaves visible in a row of ``L`` tokens and
their noised copy, K and V read once a group of query heads) at the peaks of
``peaks.json``. 100 would be the MXU busy with those two products alone at its
published bf16 peak: the kernel also computes the masked part of the tiles an
edge crosses (288 half-block tiles for 256 tiles' worth of visible pairs at
8,192 tokens in blocks of 4) and the softmax between the products, so it
cannot reach it. A program without the kernel, or a family that counts none,
says nothing. Never clipped."""

from chipbench.trace import executions, roofline

KERNEL = r"^rdt_flash_bd_fwd"


def read(run):
    found = executions.work_of(run, {"blockdiff": KERNEL}, "bd_flash_forward",
                               KERNEL)
    return None if found is None else roofline.share(*found, run["peak"])
