"""Share of the device's busy time in operations XLA itself rematerialized:
those whose name holds ``.remat`` (``fusion.416.remat``,
``fusion.1652.remat2``), by self time.

The compiler repeats an operation where keeping its result alive would not
fit: it clones the instruction and names the clone after the original with
``.remat`` and a count.  ``remat_device_pct.train`` does not see these: it
reads the scope ``jax.checkpoint`` gives what the program asked to be
replayed, and a clone keeps its original's scope.  The file is reduced once
more here, as that reader does, but by the compiler's names: the operations
of the traced stretch, clipped to it, by self time; the longest go to
standard error by name.  A run that was not traced, or a trace without the
stretch, gives ``None``; a traced step in which nothing was repeated reads
0."""
import functools

from benchmark import common, program_spans
from benchmark import trace_reduce as tr

MARK = ".remat"
ROWS = 12


def repeated(ops):
    """``({name: self ns} of the operations XLA repeated, busy ns)`` of one
    device line's operations, each named as the compiler named it."""
    selfs = tr.self_times(ops)
    return ({name: ns for name, ns in selfs.items() if MARK in name},
            sum(selfs.values()))


@functools.lru_cache(maxsize=2)
def stretch_repeated(path: str):
    """``repeated`` of the traced stretch in one ``.xplane.pb``, or ``None``
    where the stretch cannot be found."""
    events = tr.load_events(path)
    outer = [e for e in tr.host_spans(events)
             if e.name == program_spans.OUTER]
    planes = tr.device_planes(events)
    if not outer or not planes:
        return None
    lo = min(e.start for e in outer)
    hi = max(e.start + e.dur for e in outer)

    def within(plane):
        return [tr.Event(e.plane, e.line, e.name, max(e.start, lo),
                         min(e.start + e.dur, hi) - max(e.start, lo))
                for e in tr.device_ops(events, plane)
                if e.start + e.dur > lo and e.start < hi]
    rows, busy = repeated(max((within(p) for p in planes),
                              key=lambda ops: sum(e.dur for e in ops)))
    top = sorted(rows.items(), key=lambda kv: -kv[1])
    for name, ns in top[:ROWS]:
        common.say(f"xla remat: {name:40s} {ns / 1e6:9.3f} ms "
                   f"{100 * ns / busy:6.2f} % of busy")
    if len(top) > ROWS:
        common.say(f"xla remat: {len(top) - ROWS} more names, "
                   f"{sum(ns for _, ns in top[ROWS:]) / 1e6:.3f} ms")
    return rows, busy


def read(ctx):
    if ctx.get("trace") is None:
        return None
    path = ctx.get("xplane") or tr.newest_xplane(
        program_spans.trace_dir_of(ctx["cell"]))
    found = stretch_repeated(path)
    if found is None or not found[1]:
        return None
    rows, busy = found
    return 100.0 * sum(rows.values()) / busy
