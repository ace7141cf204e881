"""Idle share of the traced stretch that falls under the step profiler's
sampled fence (``dl4j.profiler_fence``), the waits for the window inside
it apart."""
from benchmark import program_spans


def read(ctx):
    return program_spans.idle_pct(ctx, "fence")
