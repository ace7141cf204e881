"""Idle share of the traced stretch that falls under the training entry's
own time: the self time of ``dl4j.fit`` / ``dl4j.fit_on_device`` (its
bookkeeping between the spans inside it), ``dl4j.input_wait`` and
``dl4j.sync``.  With the other idle parts it adds up to
``device_idle_pct.train`` of the same run."""
from benchmark import program_spans


def read(ctx):
    return program_spans.idle_pct(ctx, "entry")
