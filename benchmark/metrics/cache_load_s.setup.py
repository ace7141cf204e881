"""Seconds of set-up spent loading executables the persistent compile
cache served: the program's counter ``jit_cache_load_seconds_total`` when
set-up ended, over its labels."""
from benchmark import setup_parts


def read(ctx):
    return setup_parts.counter(ctx, "jit_cache_load_seconds_total")
