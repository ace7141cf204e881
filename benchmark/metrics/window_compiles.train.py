"""Traces of training programs inside the measured stretch: the registry's
``training_compile_total`` after it minus before it, over all its labels.
Should be 0."""


def read(ctx):
    before, after = ctx["counters_before"], ctx["counters_after"]
    if before is None or after is None:
        return None
    key = "training_compile_total"
    if key not in after:
        return None
    return float(after[key] - before.get(key, 0.0))
