"""Share of the traced stretch in which no operation ran on the chip's
busiest line of operations."""


def read(ctx):
    t = ctx["trace"]
    if t is None or t["window_ns"] <= 0:
        return None
    return 100.0 * (1.0 - t["busy_ns_busiest"] / t["window_ns"])
