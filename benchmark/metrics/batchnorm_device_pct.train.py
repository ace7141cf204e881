"""Share of the device's busy time under the scope ``BatchNormalization``,
forward and backward together, by self time."""
from benchmark import program_spans


def read(ctx):
    return program_spans.device_pct(ctx, layer="BatchNormalization")
