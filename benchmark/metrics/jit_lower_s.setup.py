"""Seconds of set-up JAX lowered jaxprs to MLIR modules: the program's
counter ``jit_lower_seconds_total`` when set-up ended, over its labels.
A warm compile cache saves none of them."""
from benchmark import setup_parts


def read(ctx):
    return setup_parts.counter(ctx, "jit_lower_seconds_total")
