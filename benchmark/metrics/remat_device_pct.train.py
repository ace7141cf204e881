"""Share of the device's busy time spent recomputing a forward pass in the
backward: the operations whose scope holds ``rematted_computation``, the
name ``jax.checkpoint`` gives what it replays
(``transpose(jvp(forward))/.../checkpoint/rematted_computation/...``), by
self time.

``backward_device_pct.train`` counts these with the backward.  This reader
takes them apart from it: under ``cache_mode="remat"`` a scanned run replays
of a block whatever it did not save, and a run that saves by name replays
the element-wise rest (norm statistics, an activation).  What is replayed
keeps the layer's scope inside, so the rows that go to standard error name
the layer class and what follows it: a traced run says which products still
run twice.

The file is reduced once more here, as ``scan_saved_device_pct.train``
does: the operations of the traced stretch, clipped to it, by self time.
A step that replays nothing, or a trace without scopes, gives ``None``."""
import functools

from benchmark import common, program_spans
from benchmark import trace_reduce as tr

REMAT = "rematted_computation"
ROWS = 12


def replayed(scoped_ops):
    """``({scope below rematted_computation: self ns}, busy ns)`` of one
    device line's operations, each named by its scope."""
    selfs = tr.self_times(scoped_ops)
    rows = {}
    for scope, ns in selfs.items():
        parts = scope.split("/")
        if REMAT in parts:
            row = "/".join(parts[parts.index(REMAT) + 1:]) or REMAT
            rows[row] = rows.get(row, 0.0) + ns
    return rows, sum(selfs.values())


@functools.lru_cache(maxsize=2)
def stretch_replayed(path: str):
    """``replayed`` of the traced stretch in one ``.xplane.pb``, or ``None``
    where the stretch or the scopes cannot be found."""
    host, scoped, any_scope = program_spans.load(path)
    outer = [e for e in host if e.name == program_spans.OUTER]
    planes = tr.device_planes(scoped)
    if not any_scope or not outer or not planes:
        return None
    lo = min(e.start for e in outer)
    hi = max(e.start + e.dur for e in outer)

    def within(plane):
        return [tr.Event(e.plane, e.line, e.name, max(e.start, lo),
                         min(e.start + e.dur, hi) - max(e.start, lo))
                for e in tr.device_ops(scoped, plane)
                if e.start + e.dur > lo and e.start < hi]
    rows, busy = replayed(max((within(p) for p in planes),
                              key=lambda ops: sum(e.dur for e in ops)))
    top = sorted(rows.items(), key=lambda kv: -kv[1])
    for row, ns in top[:ROWS]:
        common.say(f"replayed: {row[-70:]:70s} {ns / 1e6:9.3f} ms "
                   f"{100 * ns / busy:6.2f} % of busy")
    if len(top) > ROWS:
        rest = sum(ns for _, ns in top[ROWS:])
        common.say(f"replayed: {len(top) - ROWS} more scopes, "
                   f"{rest / 1e6:.3f} ms")
    return rows, busy


def read(ctx):
    if ctx.get("trace") is None:
        return None
    path = ctx.get("xplane") or tr.newest_xplane(
        program_spans.trace_dir_of(ctx["cell"]))
    found = stretch_replayed(path)
    if found is None or not found[1] or not found[0]:
        return None
    rows, busy = found
    return 100.0 * sum(rows.values()) / busy
