"""Share of the device's busy time that a looped range costs beyond its
layers: the operations under the name scope ``loop`` that lie in the scan
over the passes alone (no deeper ``while``: not in the run's scan over its
layers) and under no layer class, forward and backward together, by self
time.  That is the stacking of the passes' outputs and of what each pass
saves, the cast of the weights, the float32 sums of their gradients over
the passes, and the outer ``while`` itself.  What the layers do inside the
loop is theirs (``head_device_pct.train``, ``remat_device_pct.train``, the
kernels' rooflines).  Read through ``mla_device_pct.train``'s table of
whole scopes; the rows that go to standard error name what was counted.  A
program without the scope, as the parent of the PR that added it, gives
``None``."""
from benchmark import common, program_spans

mla = common.load_module("metrics", "mla_device_pct.train")

SCOPE = "loop"
ROWS = 8


def of_the_loop_alone(scope: str) -> bool:
    parts = scope.split("/")
    if SCOPE not in parts:
        return False
    inside = parts[parts.index(SCOPE) + 1:]
    return inside.count("while") <= 1 and not any(
        program_spans.LAYER.match(part) for part in inside)


def read(ctx):
    if ctx.get("trace") is None:
        return None
    path = ctx.get("xplane") or mla.tr.newest_xplane(
        program_spans.trace_dir_of(ctx["cell"]))
    table = mla.scope_self_times(path)
    if table is None or not table[1]:
        return None
    selfs, busy = table
    mine = {scope: ns for scope, ns in selfs.items()
            if of_the_loop_alone(scope)}
    if not mine:
        return None
    for scope, ns in sorted(mine.items(), key=lambda kv: -kv[1])[:ROWS]:
        common.say(f"loop alone: {scope[-80:]:80s} {ns / 1e6:9.3f} ms "
                   f"{100 * ns / busy:6.2f} % of busy")
    return 100.0 * sum(mine.values()) / busy
