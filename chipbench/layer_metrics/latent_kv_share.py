"""Model: the latent K/V path's share of the device's busy time. Busy seconds
of the ops whose ``op_name`` lies under the scope ``attn/latent`` (latent
attention's down-projection to the K/V latent and the one rotary key, the
latent's norm, the up-projection to every head's keys and values, RoPE on
the two rotary parts, and the broadcast and concatenation that lay the one
rotary key into every head's key; forward, recomputed and backward) over all
busy seconds. What a kernel that read the shared rotary key once, or an
up-projection folded into the queries' and the output's, would take away.
The flash kernels and the query and output projections lie outside the
scope. A program without the scope says nothing."""

from chipbench.trace import scopes


def read(run):
    under = scopes.seconds_under(run, "/attn/latent/")
    if under is None or not run["trace"]["busy_s"]:
        return None
    return 100.0 * under / run["trace"]["busy_s"]
