"""Seconds the program's package took to import itself (``jax`` is
imported before it: ``run.py`` asks for the devices first).  The program's
gauge ``package_import_seconds``, set once."""
from benchmark import setup_parts


def read(ctx):
    return setup_parts.gauge("package_import_seconds")
