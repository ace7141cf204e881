"""Share of the device's busy time under ``transpose(jvp(forward))``: the
backward pass of the step program, by self time."""
from benchmark import program_spans


def read(ctx):
    return program_spans.device_pct(ctx, phases=("backward",))
