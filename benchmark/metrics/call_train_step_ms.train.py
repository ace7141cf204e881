"""Mean length of ``dl4j.call.train_step`` in the traced stretch: what the
host spends inside one call of the jitted step.  Most of it is the
runtime's wait to take the next execution, which is back-pressure from
the device, so the number follows the device's step time; the host's own
dispatch work is what is left of it when the pipeline is empty (the idle
time under the span, ``idle_dispatch_pct.train``)."""
from benchmark import program_spans

SPAN = "dl4j.call.train_step"


def read(ctx):
    t = program_spans.tables(ctx)
    if t is None or SPAN not in t["span_ns"]:
        return None
    count, total = t["span_ns"][SPAN]
    return total / count / 1e6
