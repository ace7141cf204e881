"""Share of the device's busy time under ``moe_route``, ``moe_dispatch`` and
``moe_combine``: the part of the routed FFN that is no matrix product of an
expert (the router and its top-k, the sort by expert, the gather into the
experts' order and the weighted sum back), forward and backward together,
by self time."""
from benchmark import common

moe = common.load_module("metrics", "moe_device_pct.train")


def read(ctx):
    return moe.pct(ctx, ("moe_route", "moe_dispatch", "moe_combine"))
