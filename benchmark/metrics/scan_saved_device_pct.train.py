"""Share of the device's busy time that a scan over layers spends moving
what it saves for the backward: the operations whose scope lies in a
``while`` body of the forward or of the backward and names no layer class
(``program_spans.layer_of`` is ``None``), by self time.

A scanned run writes each value the transposed body will read into a stack
with a leading axis of the run's length (``jvp(forward)/while/body/
dynamic_update_slice``) and reads it back in the backward
(``transpose(jvp(forward))/while/body/dynamic_slice`` and ``squeeze``,
with a layout ``copy`` where the stack's layout is not the reader's); none
of these lies under the layer's own scope, which ``nn/scan_layers`` opens
around ``apply`` alone.  What the layer computes, recomputed or not, keeps
its class in the scope and is not counted.  A matmul that writes its
product into a stack in place carries the layer's scope too: its share of a
gain shows in ``step_mfu_pct.train``, not here.

The file is reduced once more here, as ``eva_attention_device_pct.train``
does: the operations of the traced stretch, clipped to it, by self time.
The rows go to standard error by what follows the body in the scope, so a
traced run says which copies are left.  A step without a scan, or a trace
without scopes, gives ``None``."""
import functools

from benchmark import common, program_spans
from benchmark import trace_reduce as tr

BODY = ["while", "body"]


def copy_row(scope: str):
    """``(phase, what follows the body in the scope)`` of an operation
    whose ``scope`` lies in a ``while`` body of the forward or the backward
    and under no layer class; ``None`` for any other."""
    parts = scope.split("/")
    phase = program_spans.phase_of(scope)
    bodies = [i for i in range(len(parts) - 1) if parts[i:i + 2] == BODY]
    if phase not in ("forward", "backward") or not bodies or \
            program_spans.layer_of(scope) is not None:
        return None
    return phase, "/".join(parts[bodies[-1] + 2:]) or "body"


def scan_copies(scoped_ops):
    """``({(phase, row): self ns}, busy ns)`` of one device line's
    operations, each named by its scope: the scan's copies by
    ``copy_row``."""
    selfs = tr.self_times(scoped_ops)
    rows = {}
    for scope, ns in selfs.items():
        row = copy_row(scope)
        if row is not None:
            rows[row] = rows.get(row, 0.0) + ns
    return rows, sum(selfs.values())


@functools.lru_cache(maxsize=2)
def stretch_copies(path: str):
    """``scan_copies`` of the traced stretch in one ``.xplane.pb``, or
    ``None`` where the stretch or the scopes cannot be found."""
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
    rows, busy = scan_copies(max((within(p) for p in planes),
                                 key=lambda ops: sum(e.dur for e in ops)))
    for (phase, row), ns in sorted(rows.items(), key=lambda kv: -kv[1]):
        common.say(f"scan copies: {phase:8s} {row:40s} {ns / 1e6:9.3f} ms "
                   f"{100 * ns / busy:6.2f} % of busy")
    return rows, busy


def read(ctx):
    if ctx.get("trace") is None:
        return None
    path = ctx.get("xplane") or tr.newest_xplane(
        program_spans.trace_dir_of(ctx["cell"]))
    found = stretch_copies(path)
    if found is None or not found[1] or not found[0]:
        return None
    rows, busy = found
    return 100.0 * sum(rows.values()) / busy
