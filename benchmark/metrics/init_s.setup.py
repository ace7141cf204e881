"""Wall seconds of set-up inside the containers' ``init()`` (the span
``dl4j.init``), the programs it traces, loads or compiles included: the
program's counter ``model_init_seconds_total`` when set-up ended."""
from benchmark import setup_parts


def read(ctx):
    return setup_parts.counter(ctx, "model_init_seconds_total")
