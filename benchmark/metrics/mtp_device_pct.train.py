"""Share of the device's busy time under the name scope ``mtp``: the
multi-token-prediction module's merge (the two norms and the projection of
the next token's embedding beside the trunk's hidden state), its one routed
block with latent attention, and its own final norm, forward and backward
together, by self time.  The head and the embedding are the trunk's and lie
outside it.  The module's attention counts under ``mla_device_pct.train``
too, and its routed FFN under ``moe_device_pct.train``: this share cuts the
step another way.  A program without the scope gives ``None``."""
from benchmark import common

mla = common.load_module("metrics", "mla_device_pct.train")


def read(ctx):
    shares = mla.under(ctx, ("mtp",))
    if shares is None or not shares[0]["mtp"]:
        return None
    by_scope, busy = shares
    common.say(f"mtp scope: {by_scope['mtp'] / 1e6:.3f} ms of "
               f"{busy / 1e6:.3f} busy")
    return 100.0 * by_scope["mtp"] / busy
