"""Fences the step profiler took inside the traced stretch: the registry's
``stepprof_fences_total`` after it minus before it, over all its labels."""
from benchmark import program_spans


def read(ctx):
    return program_spans.counter_delta(ctx, "stepprof_fences_total")
