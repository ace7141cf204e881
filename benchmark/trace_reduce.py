"""From a profiler trace (``.xplane.pb``) to events, and from events to
numbers, in pure functions: busy union, sums by name, idle gaps with the
host span open at each.  Times are in nanoseconds as the trace has them.
"""
from __future__ import annotations

import glob
import os
import re
from collections import namedtuple

Event = namedtuple("Event", "plane line name start dur")

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
NOT_OPS = ("XLA Modules", "Steps", "Step", "XLA TraceMe", "Framework Ops",
           "Framework Name Scope", "Source code", "Source")


def newest_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")),
        key=os.path.getmtime)
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def short_name(name: str) -> str:
    """The trace names a device operation by its whole HLO line,
    ``%flash_fwd.6 = (bf16[48,1024,64]{...}) custom-call(...)``: keep the
    operation's own name, ``flash_fwd.6``."""
    return name.split(" = ", 1)[0].lstrip("%")[:120]


def load_events(path: str):
    """Every event of every line of every plane, as ``Event`` tuples, the
    names shortened by ``short_name``."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    out = []
    for plane in data.planes:
        for line in plane.lines:
            for ev in line.events:
                out.append(Event(plane.name, line.name, short_name(ev.name),
                                 float(ev.start_ns), float(ev.duration_ns)))
    return out


def kernel_events(ops, kernel: str):
    """The operations that ARE calls of ``kernel``: named ``kernel`` or
    ``kernel.<n>``, not those that only take its result."""
    return [e for e in ops
            if e.name == kernel or e.name.startswith(kernel + ".")]


# ------------------------------------------------------------ pure functions
def device_planes(events):
    return sorted({e.plane for e in events if DEVICE_PLANE.match(e.plane)})


def busy_union(intervals) -> float:
    """Length of the union of ``(start, end)`` intervals."""
    total, cur_s, cur_e = 0.0, None, None
    for s, e in sorted(intervals):
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        elif e > cur_e:
            cur_e = e
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def ops_line(events, plane: str) -> str:
    """The line of ``plane`` that holds the device's operations: the one
    named ``XLA Ops``, else the busiest line that is not a line of modules,
    steps or scopes."""
    lines = {}
    for e in events:
        if e.plane == plane and e.dur > 0:
            lines.setdefault(e.line, []).append((e.start, e.start + e.dur))
    if OPS_LINE in lines:
        return OPS_LINE
    rest = {k: busy_union(v) for k, v in lines.items() if k not in NOT_OPS}
    if not rest:
        raise ValueError(f"plane {plane} has no line of operations")
    return max(rest, key=rest.get)


def device_ops(events, plane: str):
    line = ops_line(events, plane)
    return [e for e in events
            if e.plane == plane and e.line == line and e.dur > 0]


def clip(intervals, lo: float, hi: float):
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if min(e, hi) > max(s, lo)]


def self_times(ops):
    """Name -> time in which that operation ran and no operation nested in
    it did (a ``while`` holds its body's operations on the same line)."""
    out = {}
    stack = []                       # [end, name, self_time, cursor]
    for e in sorted(ops, key=lambda e: (e.start, -e.dur)):
        end = e.start + e.dur
        while stack and stack[-1][0] <= e.start:
            top = stack.pop()
            top[2] += max(0.0, top[0] - top[3])
            out[top[1]] = out.get(top[1], 0.0) + top[2]
        if stack:
            top = stack[-1]
            top[2] += max(0.0, e.start - top[3])
            top[3] = max(top[3], min(end, top[0]))
        stack.append([end, e.name, 0.0, e.start])
    while stack:
        top = stack.pop()
        top[2] += max(0.0, top[0] - top[3])
        out[top[1]] = out.get(top[1], 0.0) + top[2]
    return out


def sums_by_name(ops, match=None):
    """Name -> (summed duration, count) of the operations whose name
    contains ``match`` (all, where it is None)."""
    out = {}
    for e in ops:
        if match is None or match in e.name:
            d, n = out.get(e.name, (0.0, 0))
            out[e.name] = (d + e.dur, n + 1)
    return out


def idle_gaps(intervals, lo: float, hi: float):
    """The stretches of ``[lo, hi]`` that no interval covers, longest
    first, as ``(start, length)``."""
    gaps, cursor = [], lo
    for s, e in sorted(clip(intervals, lo, hi)):
        if s > cursor:
            gaps.append((cursor, s - cursor))
        cursor = max(cursor, e)
    if hi > cursor:
        gaps.append((cursor, hi - cursor))
    return sorted(gaps, key=lambda g: -g[1])


def span_open_at(spans, t: float):
    """Name of the innermost of ``spans`` (events) open at time ``t``."""
    best = None
    for e in spans:
        if e.start <= t < e.start + e.dur:
            if best is None or e.start >= best.start:
                best = e
    return None if best is None else best.name


def host_spans(events, prefix: str = "bench."):
    return [e for e in events
            if not DEVICE_PLANE.match(e.plane) and e.name.startswith(prefix)]


# ------------------------------------------------------------------- summary
def reduce(events, outer_span: str = "bench.fit", prefix: str = "bench."):
    """What the readers and the result line need from one traced stretch.
    The stretch is the outermost ``outer_span`` on the host; the device's
    operations are clipped to it."""
    spans = host_spans(events, prefix)
    outer = [e for e in spans if e.name == outer_span]
    if not outer:
        raise ValueError(f"the trace holds no host span '{outer_span}'")
    lo = min(e.start for e in outer)
    hi = max(e.start + e.dur for e in outer)
    planes = device_planes(events)
    if not planes:
        raise ValueError("the trace holds no device plane")
    per_plane, busiest, busiest_busy = {}, None, -1.0
    for plane in planes:
        ops = [e for e in device_ops(events, plane)
               if e.start + e.dur > lo and e.start < hi]
        busy = busy_union(clip([(e.start, e.start + e.dur) for e in ops],
                               lo, hi))
        per_plane[plane] = {"ops": ops, "busy_ns": busy}
        if busy > busiest_busy:
            busiest, busiest_busy = plane, busy
    ops = per_plane[busiest]["ops"]
    selfs = sorted(self_times(ops).items(), key=lambda kv: -kv[1])
    gaps = idle_gaps([(e.start, e.start + e.dur) for e in ops], lo, hi)
    named_gaps = [[span_open_at(spans, s) or "no span", length / 1e9]
                  for s, length in gaps[:5]]
    return {
        "window_ns": hi - lo,
        "busy_ns_mean": sum(p["busy_ns"] for p in per_plane.values())
        / len(per_plane),
        "busy_ns_busiest": busiest_busy,
        "ops": ops,
        "device_ops": [[name, t / 1e9] for name, t in selfs[:10]],
        "idle_gaps": named_gaps,
    }
