"""Seconds of set-up the compiler ran, for programs the persistent cache
did not serve: the program's counter ``jit_compile_seconds_total`` when
set-up ended, over its labels.  0 in a warm run."""
from benchmark import setup_parts


def read(ctx):
    return setup_parts.counter(ctx, "jit_compile_seconds_total")
