"""Programs set-up had to compile: the program's counter
``jit_programs_compiled_total`` when set-up ended, over its labels.
0 says the run was warm."""
from benchmark import setup_parts


def read(ctx):
    return setup_parts.counter(ctx, "jit_programs_compiled_total")
