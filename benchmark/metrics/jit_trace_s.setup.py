"""Seconds of set-up JAX traced Python into jaxprs, every program
together: the program's counter ``jit_trace_seconds_total`` when set-up
ended, over its labels.  A warm compile cache saves none of them.  Also
says the program's table by jitted program on standard error."""
from benchmark import setup_parts


def read(ctx):
    value = setup_parts.counter(ctx, "jit_trace_seconds_total")
    if value is not None:
        setup_parts.say_table(ctx)
    return value
