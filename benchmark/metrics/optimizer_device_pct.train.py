"""Share of the device's busy time under the scopes ``optimizer`` (the
update, its application and the constraints) and ``grad_post`` (unscaling,
normalisation and the gradient statistics), by self time."""
from benchmark import program_spans


def read(ctx):
    return program_spans.device_pct(ctx, phases=("optimizer", "grad_post"))
