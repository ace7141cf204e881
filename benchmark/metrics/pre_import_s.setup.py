"""The process's age when the program's package began its import: the
interpreter, ``import jax`` and the runtime reaching the chip, which no
change to the program moves.  The program's gauge
``process_age_at_import_seconds``, set once."""
from benchmark import setup_parts


def read(ctx):
    return setup_parts.gauge("process_age_at_import_seconds")
