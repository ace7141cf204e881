"""Share of the device's busy time under ``eva_pool``, ``eva_summary`` and
``eva_merge``: the part of EVA attention that no kernel serves (pooling the
chunks, a window's queries against the summaries before it, the merge of
the two softmaxes), forward and backward together, by self time."""
from benchmark import common

attention = common.load_module("metrics", "eva_attention_device_pct.train")


def read(ctx):
    return attention.pct(ctx, ("eva_pool", "eva_summary", "eva_merge"))
