"""Share of the device's busy time under the name scope ``exit_gate``: the
exit gate's product over the passes' normed states, the exit distribution
and its entropy, the weights they hand the chunked head and what the loss
takes off for the entropy, forward and backward together, by self time.
The head's own products lie outside it (``head_device_pct.train``).  Read
through ``mla_device_pct.train``'s table of whole scopes.  A program
without the scope, as the parent of the PR that added it, gives ``None``."""
from benchmark import common

mla = common.load_module("metrics", "mla_device_pct.train")


def read(ctx):
    shares = mla.under(ctx, ("exit_gate",))
    if shares is None or not shares[0]["exit_gate"]:
        return None
    by_scope, busy = shares
    common.say(f"exit_gate scope: {by_scope['exit_gate'] / 1e6:.3f} ms of "
               f"{busy / 1e6:.3f} busy")
    return 100.0 * by_scope["exit_gate"] / busy
