"""Model: the block-diffusion noise's share of the device's busy time. Busy
seconds of the ops whose ``op_name`` lies under the ``diffusion`` scope (the
draw of one t a block and one Bernoulli a token, the masked copy of the row,
laying out ``[row ; noised row]``, the position ids and the per-position
weights of the loss; ``raydp_tpu/models/transformer.py``) over all busy
seconds (``trace/scopes.py`` reads the programs the trace stores). It is drawn
on the device inside the train step, so nothing of it rides the feed; small is
good. A program without the scope says nothing."""

from chipbench.trace import scopes


def read(run):
    under = scopes.seconds_under(run, "/diffusion/")
    if under is None or not run["trace"]["busy_s"]:
        return None
    return 100.0 * under / run["trace"]["busy_s"]
