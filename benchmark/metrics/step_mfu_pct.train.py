"""Model FLOPs of the steps in the traced stretch over the stretch's length
and the chips' peak: the whole step's share of the chip."""


def read(ctx):
    t, run = ctx["trace"], ctx["stretch"]
    if t is None or not run["steps"] or ctx["flops_per_step"] is None:
        return None
    seconds = t["window_ns"] / 1e9
    peak = ctx["peaks"]["bf16_flops_per_s"] * ctx["chips"]
    return 100.0 * ctx["flops_per_step"] * run["steps"] / seconds / peak
