"""Model: a looped model's exit gate's share of the device's busy time. Busy
seconds of the ops whose ``op_name`` lies under the scope ``exit_gate`` (the
gate's product with every pass's hidden states, its sigmoid, the exit
distribution, the entropy and the counts, forward and backward;
``raydp_tpu/models/transformer.py``) over all busy seconds
(``trace/scopes.py`` reads the programs the trace stores). The gate reads
``passes x tokens x hidden`` numbers and computes next to nothing, so it
should be small; the head's four passes are ``head_loss_share``'s. A program
without the scope says nothing."""

from chipbench.trace import scopes


def read(run):
    under = scopes.seconds_under(run, "/exit_gate/")
    if under is None or not run["trace"]["busy_s"]:
        return None
    return 100.0 * under / run["trace"]["busy_s"]
