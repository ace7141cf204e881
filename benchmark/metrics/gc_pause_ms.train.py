"""Milliseconds a step that Python's collector held the host inside the
training entry: the registry's ``host_gc_pause_seconds_total`` after the
traced stretch minus before it, over the stretch's steps."""
from benchmark import program_spans


def read(ctx):
    seconds = program_spans.counter_delta(ctx, "host_gc_pause_seconds_total")
    steps = ctx["stretch"]["steps"] if ctx.get("stretch") else 0
    if seconds is None or not steps:
        return None
    return 1e3 * seconds / steps
