"""Idle share of the traced stretch that falls under the dispatch of a
jitted program (``dl4j.call.*``, its trace and compile when it has to) or
under host-to-device placement (``dl4j.h2d``)."""
from benchmark import program_spans


def read(ctx):
    return program_spans.idle_pct(ctx, "dispatch")
