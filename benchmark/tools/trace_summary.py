#!/usr/bin/env python3
"""Look at one trace by hand: planes, lines, the names that take the time.

    python3 benchmark/tools/trace_summary.py <trace dir or .xplane.pb> [match]

Prints, for every plane and line, the number of events and the union of
their intervals; for each device plane the 25 operations with most self
time; and every distinct name that contains ``match`` (default "flash")
with the statistics the trace attaches to its first event.
"""
from __future__ import annotations

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from benchmark import trace_reduce as tr  # noqa: E402


def main(argv) -> int:
    path = argv[1]
    match = argv[2] if len(argv) > 2 else "flash"
    if os.path.isdir(path):
        path = tr.newest_xplane(path)
    print(path, os.path.getsize(path), "bytes")
    events = tr.load_events(path)
    lines = {}
    for e in events:
        lines.setdefault((e.plane, e.line), []).append(e)
    for (plane, line), evs in sorted(lines.items()):
        busy = tr.busy_union([(e.start, e.start + e.dur) for e in evs])
        lo = min(e.start for e in evs)
        hi = max(e.start + e.dur for e in evs)
        print(f"PLANE {plane!r} LINE {line!r}: {len(evs)} events, union "
              f"{busy / 1e6:.3f} ms, from {lo / 1e6:.3f} to {hi / 1e6:.3f} ms")
    for plane in tr.device_planes(events):
        ops = tr.device_ops(events, plane)
        print(f"-- {plane}: operations on line {tr.ops_line(events, plane)!r}")
        for name, t in sorted(tr.self_times(ops).items(),
                              key=lambda kv: -kv[1])[:25]:
            n = sum(1 for e in ops if e.name == name)
            print(f"   {t / 1e6:10.3f} ms self  x{n:<6d} {name[:110]}")
    from jax.profiler import ProfileData
    seen = set()
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for ev in line.events:
                if match in ev.name and (plane.name, line.name,
                                         ev.name) not in seen:
                    seen.add((plane.name, line.name, ev.name))
                    stats = {k: str(v)[:80] for k, v in ev.stats}
                    print(f"MATCH {plane.name!r} {line.name!r} "
                          f"{ev.name[:100]!r} {ev.duration_ns / 1e3:.1f} us "
                          f"{stats}")
    for e in sorted(tr.host_spans(events), key=lambda e: e.start)[:12]:
        print(f"SPAN {e.plane!r} {e.line!r} {e.name} at {e.start / 1e6:.3f} "
              f"ms for {e.dur / 1e6:.3f} ms")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
