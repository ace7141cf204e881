"""What the program says about its own time, read from the traced stretch's
``.xplane.pb``: the host spans it writes (``dl4j.*``, always written by
``observability/tracer.py``) and the name scopes of its step program
(``forward``, ``grad_post``, ``optimizer`` and one scope per layer class,
which the chip's trace keeps in each operation's ``tf_op`` statistic).

Three tables, printed to standard error on every traced run and read by
the per-layer metrics under ``metrics/``:

    idle by span     each gap of the device's line of operations, split
                     among the innermost program spans that cover it (a
                     gap is caused by what the host did during it, not by
                     the span open when it began)
    device by phase  self time of the device's operations under
                     ``transpose(jvp(forward))`` (backward), ``optimizer``,
                     ``grad_post``, ``forward``, and the rest
    device by layer  the same self time by the layer class in the scope,
                     forward and backward together

A fusion carries the scope of its root instruction, so a pass the
compiler fused across two scopes counts under the one it ends in.

The readers' context holds only the reduced operations, so the file is
read again here, once (``run.py`` deletes it after every reader has run).
A trace without ``dl4j.*`` spans or without scopes, as the parent of the
PR that added them writes, gives ``None`` for what it lacks.  A trace in
which a training entry wrote its span and the device's operations lack
the step program's scopes is another matter: the persistent compile
cache leaves name scopes out of its key, so a program whose scopes alone
changed is served the old executable with the old scopes, and the shares
would be read from them.  That raises ``StaleScopes`` and the run fails.
"""
from __future__ import annotations

import functools
import os
import re

from benchmark import common
from benchmark import trace_reduce as tr

PREFIX = "dl4j."
OUTER = "bench.fit"
SCOPE_STAT = "tf_op"

# which layer of PERF.md section 3 a span's idle time is put down to
ENTRY = ("dl4j.fit", "dl4j.fit_on_device", "dl4j.input_wait", "dl4j.sync")
GROUPS = ("entry", "dispatch", "fence", "window_wait", "gc", "other",
          "uncovered")

BACKWARD = "transpose(jvp(forward))"
FORWARD = ("jvp(forward)", "forward")
PHASES = ("forward", "backward", "grad_post", "optimizer", "outside")
LAYER = re.compile(r"^[A-Z][A-Za-z0-9]*$")
# a training entry's span, and the phases its step program always names
TRAINING_ENTRIES = ("dl4j.fit", "dl4j.fit_on_device")
STEP_PHASES = ("forward", "backward", "optimizer")


class StaleScopes(RuntimeError):
    """The program wrote a training entry's span, and the operations the
    device ran under it lack the step program's name scopes."""


def group_of(span: str) -> str:
    if span in ENTRY:
        return "entry"
    if span.startswith("dl4j.call.") or span == "dl4j.h2d":
        return "dispatch"
    if span == "dl4j.profiler_fence":
        return "fence"
    if span == "dl4j.window_wait":
        return "window_wait"
    if span == "dl4j.gc":
        return "gc"
    return "other"


# ------------------------------------------------------------ pure functions
def innermost_segments(spans, lo: float, hi: float):
    """``[(start, end, name)]``, sorted and disjoint: the parts of
    ``[lo, hi]`` that some span covers, each under the innermost span
    there (the one that started last; of two that started together, the
    shorter)."""
    clipped = [(max(e.start, lo), min(e.start + e.dur, hi), e.name)
               for e in spans if min(e.start + e.dur, hi) > max(e.start, lo)]
    bounds = sorted({t for s, e, _ in clipped for t in (s, e)})
    starts = sorted(clipped, key=lambda c: c[0])
    out, active, i = [], [], 0
    for left, right in zip(bounds, bounds[1:]):
        while i < len(starts) and starts[i][0] <= left:
            active.append(starts[i])
            i += 1
        active = [c for c in active if c[1] > left]
        if not active:
            continue
        name = max(active, key=lambda c: (c[0], -c[1]))[2]
        if out and out[-1][2] == name and out[-1][1] == left:
            out[-1] = (out[-1][0], right, name)
        else:
            out.append((left, right, name))
    return out


def split_gaps(gaps, segments):
    """``({name: ns}, uncovered ns)``: every gap ``(start, length)`` split
    among the ``segments`` of ``innermost_segments`` that cover it."""
    by_name, uncovered, j = {}, 0.0, 0
    for start, length in sorted(gaps):
        end, covered = start + length, 0.0
        while j < len(segments) and segments[j][1] <= start:
            j += 1
        k = j
        while k < len(segments) and segments[k][0] < end:
            s, e, name = segments[k]
            part = min(e, end) - max(s, start)
            if part > 0:
                by_name[name] = by_name.get(name, 0.0) + part
                covered += part
            k += 1
        uncovered += length - covered
    return by_name, uncovered


def phase_of(scope: str) -> str:
    """Which part of the step an operation's scope puts it in."""
    parts = scope.split("/")
    if BACKWARD in parts:
        return "backward"
    for phase in ("optimizer", "grad_post"):
        if phase in parts:
            return phase
    if any(p in parts for p in FORWARD):
        return "forward"
    return "outside"


def layer_of(scope: str):
    """The layer class in a scope: its first component spelt like a class
    (jax's own components are lower case or ``name(...)``)."""
    for part in scope.split("/"):
        if LAYER.match(part):
            return part
    return None


def device_shares(scoped_ops):
    """``({phase: ns}, {layer: ns}, busy ns)`` from the operations of one
    device line, each named by its scope: self time, so a ``while`` does
    not count its body twice."""
    selfs = tr.self_times(scoped_ops)
    by_phase, by_layer = {}, {}
    for scope, t in selfs.items():
        phase = phase_of(scope)
        by_phase[phase] = by_phase.get(phase, 0.0) + t
        layer = layer_of(scope)
        if layer is not None and phase in ("forward", "backward"):
            by_layer[layer] = by_layer.get(layer, 0.0) + t
    return by_phase, by_layer, sum(selfs.values())


def summarize(host, scoped, any_scope: bool):
    """The tables from ``host`` (the host's ``bench.`` and ``dl4j.``
    events) and ``scoped`` (the device planes' operations, named by
    scope).  ``None`` where the stretch cannot be found."""
    outer = [e for e in host if e.name == OUTER]
    planes = tr.device_planes(scoped)
    if not outer or not planes:
        return None
    lo = min(e.start for e in outer)
    hi = max(e.start + e.dur for e in outer)

    def within(plane):
        return [e for e in tr.device_ops(scoped, plane)
                if e.start + e.dur > lo and e.start < hi]

    def busy(ops):
        return tr.busy_union(tr.clip([(e.start, e.start + e.dur)
                                      for e in ops], lo, hi))

    ops = max((within(p) for p in planes), key=busy)
    spans = [e for e in host if e.name.startswith(PREFIX)]
    out = {"window_ns": hi - lo, "idle_ns": (hi - lo) - busy(ops),
           "idle_by_span": None, "idle_by_group": None, "span_ns": {},
           "device_by_phase": None, "device_by_layer": None,
           "device_self_ns": None}
    if spans:
        gaps = tr.idle_gaps([(e.start, e.start + e.dur) for e in ops],
                            lo, hi)
        segments = innermost_segments(spans, lo, hi)
        by_span, uncovered = split_gaps(gaps, segments)
        groups = dict.fromkeys(GROUPS, 0.0)
        for name, ns in by_span.items():
            groups[group_of(name)] += ns
        groups["uncovered"] = uncovered
        out["idle_by_span"], out["idle_by_group"] = by_span, groups
        out["longest_gaps"] = [
            (length, split_gaps([(start, length)], segments)[0])
            for start, length in gaps[:5]]
        for e in spans:
            if e.start >= lo and e.start + e.dur <= hi:
                n, total = out["span_ns"].get(e.name, (0, 0.0))
                out["span_ns"][e.name] = (n + 1, total + e.dur)
    if any_scope:
        clipped = [tr.Event(e.plane, e.line, e.name, max(e.start, lo),
                            min(e.start + e.dur, hi) - max(e.start, lo))
                   for e in ops]
        (out["device_by_phase"], out["device_by_layer"],
         out["device_self_ns"]) = device_shares(clipped)
    if any(e.name in TRAINING_ENTRIES for e in spans):
        missing = [p for p in STEP_PHASES
                   if not (out["device_by_phase"] or {}).get(p)]
        if missing:
            raise StaleScopes(
                "the trace holds a training entry's span, and no operation "
                f"of the stretch ran under the scope of {missing}: the "
                "program names its scopes but the executable lacks them, "
                "as one served from a compile cache filled before the "
                "scopes changed")
    return out


# ------------------------------------------------------------------ reading
def varint(buf, i: int):
    """``(value, next index)`` of the base-128 number at ``buf[i]``."""
    value, shift = 0, 0
    while True:
        byte = buf[i]
        i += 1
        value |= (byte & 0x7F) << shift
        shift += 7
        if byte < 0x80:
            return value, i


def wire_fields(buf):
    """``(field number, value)`` of one protocol-buffer message: an int
    for a varint, the bytes for a length-delimited field; fixed-width
    fields are passed over.  ``jax.profiler.ProfileData`` shows neither
    the statistics of an event's metadata, where the chip's trace keeps
    an operation's scope, nor the id that joins an event to them, so the
    file is read here by hand."""
    i, n = 0, len(buf)
    while i < n:
        key, i = varint(buf, i)
        number, kind = key >> 3, key & 7
        if kind == 0:
            value, i = varint(buf, i)
            yield number, value
        elif kind == 2:
            length, i = varint(buf, i)
            yield number, buf[i:i + length]
            i += length
        elif kind in (1, 5):
            i += 8 if kind == 1 else 4
        else:
            raise ValueError(f"wire type {kind} in an .xplane.pb")


def decode(buf) -> str:
    return bytes(buf).decode()


def read_planes(data: bytes, stat: str = SCOPE_STAT):
    """``[(plane name, lines, metadata)]`` of a serialized ``XSpace``:
    ``lines`` as ``[(line name, timestamp ns, [(metadata id, offset ps,
    duration ps)])]``, ``metadata`` as ``{metadata id: (event name, its
    string statistic ``stat`` or None)}``.  An event names its metadata
    by an id that holds within its own plane, and the compiler's names
    (``fusion.1``, ``copy.3``) hold only within one program, so the id and
    not the name joins an operation to its scope.  (Of an ``XSpace``
    ``planes`` = 1; of an ``XPlane`` ``name`` = 2, ``lines`` = 3,
    ``event_metadata`` = 4, ``stat_metadata`` = 5; of an ``XLine`` ``name``
    = 2, ``timestamp_ns`` = 3, ``events`` = 4; of an ``XEvent``
    ``metadata_id`` = 1, ``offset_ps`` = 2, ``duration_ps`` = 3; of an
    ``XEventMetadata`` ``name`` = 2, ``stats`` = 5; of an ``XStat``
    ``metadata_id`` = 1, ``str_value`` = 5, ``ref_value`` = 7.)"""
    out = []
    for number, plane in wire_fields(memoryview(data)):
        if number != 1:
            continue
        name, lines, events, stat_names = "", [], [], {}
        for f, value in wire_fields(plane):
            if f == 2:
                name = decode(value)
            elif f == 3:
                lines.append(value)
            elif f in (4, 5):
                entry = dict(wire_fields(value))   # the map's key, value
                if f == 4:
                    events.append((entry.get(1, 0), entry[2]))
                else:
                    meta = dict(wire_fields(entry[2]))
                    stat_names[entry.get(1, 0)] = decode(meta.get(2, b""))
        metadata = {}
        for key, meta in events:
            event_name, scope = "", None
            for f, value in wire_fields(meta):
                if f == 2:
                    event_name = decode(value)
                elif f == 5:
                    st = dict(wire_fields(value))
                    if stat_names.get(st.get(1)) != stat:
                        continue
                    if 5 in st:
                        scope = decode(st[5])
                    elif 7 in st:
                        scope = stat_names.get(st[7])
            metadata[key] = (event_name, scope)
        read_lines = []
        for line in lines:
            line_name, timestamp, line_events = "", 0, []
            for f, value in wire_fields(line):
                if f == 2:
                    line_name = decode(value)
                elif f == 3:
                    timestamp = value
                elif f == 4:
                    e = dict(wire_fields(value))
                    line_events.append((e.get(1, 0), e.get(2, 0),
                                        e.get(3, 0)))
            read_lines.append((line_name, timestamp, line_events))
        out.append((name, read_lines, metadata))
    return out


def load(path: str):
    """``(host events, device operations named by scope, whether any
    operation had a scope)`` of one ``.xplane.pb``, in whole nanoseconds
    as ``jax.profiler.ProfileData`` gives them (``trace_reduce`` reads the
    same file through it).  A scope is the statistic
    less the ``:`` and the operation's type that end it; an operation
    without one is named ``""``."""
    with open(path, "rb") as f:
        data = f.read()
    host, scoped, any_scope = [], [], False
    for plane, lines, metadata in read_planes(data):
        device = bool(tr.DEVICE_PLANE.match(plane))
        if device:
            names = {key: (scope or "").split(":")[0]
                     for key, (_, scope) in metadata.items()}
            any_scope = any_scope or any(names.values())
        else:
            names = {key: name for key, (name, _) in metadata.items()
                     if name.startswith((PREFIX, "bench."))}
        for line, timestamp, events in lines:
            for key, offset_ps, duration_ps in events:
                if key in names:
                    (scoped if device else host).append(tr.Event(
                        plane, line, names[key],
                        float(timestamp + offset_ps // 1000),
                        float(duration_ps // 1000)))
    return host, scoped, any_scope


def ms(ns: float) -> str:
    return f"{ns / 1e6:9.3f} ms"


def say_tables(t: dict) -> None:
    window = t["window_ns"]
    common.say(f"program spans: stretch {ms(window)}, device idle "
               f"{ms(t['idle_ns'])} ({100 * t['idle_ns'] / window:.3f} %)")
    if t["idle_by_span"] is None:
        common.say("program spans: the trace holds no dl4j.* span")
    else:
        for name, ns in sorted(t["idle_by_span"].items(),
                               key=lambda kv: -kv[1]):
            n, total = t["span_ns"].get(name, (0, 0.0))
            common.say(f"  idle under {name:28s} {ms(ns)} "
                       f"{100 * ns / window:6.3f} %  [{group_of(name)}]  "
                       f"{n} spans, {ms(total)} in all")
        ns = t["idle_by_group"]["uncovered"]
        common.say(f"  idle under no dl4j.* span            {ms(ns)} "
                   f"{100 * ns / window:6.3f} %")
        for length, parts in t["longest_gaps"]:
            common.say(f"  gap {ms(length)}: " + ", ".join(
                f"{name} {ns / 1e6:.3f}" for name, ns in
                sorted(parts.items(), key=lambda kv: -kv[1])))
        for name, (n, total) in sorted(t["span_ns"].items()):
            common.say(f"  span {name:30s} x{n:<5d} mean "
                       f"{total / n / 1e6:9.4f} ms")
    if t["device_by_phase"] is None:
        common.say("program spans: the operations carry no name scope")
        return
    busy = t["device_self_ns"]
    for phase in PHASES:
        ns = t["device_by_phase"].get(phase, 0.0)
        common.say(f"  device {phase:10s} {ms(ns)} {100 * ns / busy:6.2f} % "
                   "of busy")
    for layer, ns in sorted(t["device_by_layer"].items(),
                            key=lambda kv: -kv[1]):
        common.say(f"  device layer {layer:24s} {ms(ns)} "
                   f"{100 * ns / busy:6.2f} % of busy")


@functools.lru_cache(maxsize=2)
def tables_of(path: str):
    t = summarize(*load(path))
    if t is not None:
        say_tables(t)
    return t


def trace_dir_of(cell: dict) -> str:
    """Where ``run.py`` puts the traced stretch of ``cell`` (a file under
    ``workloads/``): ``out/trace/<the cell's name in the manifest>``."""
    pair = (cell["config"], cell["traffic"])
    for w in common.load_manifest()["workloads"]:
        if (w["config"], w["traffic"]) == pair:
            return os.path.join(common.OUT, "trace", w["name"])
    raise KeyError(f"no cell {pair} in BENCHMARK.json")


def tables(ctx):
    """The tables of the traced stretch a reader's context belongs to:
    the file ``ctx["xplane"]`` names, else the newest one under this
    cell's own trace directory.  ``None`` for a run that was not traced."""
    if ctx.get("trace") is None:
        return None
    path = ctx.get("xplane")
    if path is None:
        path = tr.newest_xplane(trace_dir_of(ctx["cell"]))
    return tables_of(path)


def idle_pct(ctx, group: str):
    """Idle share of the stretch put down to ``group``, in per cent."""
    t = tables(ctx)
    if t is None or t["idle_by_group"] is None:
        return None
    return 100.0 * t["idle_by_group"][group] / t["window_ns"]


def device_pct(ctx, phases=(), layer=None):
    """Self time under ``phases`` (or under ``layer``) over the device's
    busy time, in per cent; ``None`` where nothing ran under them."""
    t = tables(ctx)
    if t is None or t["device_by_phase"] is None or not t["device_self_ns"]:
        return None
    if layer is not None:
        ns = t["device_by_layer"].get(layer)
    else:
        found = [t["device_by_phase"][p] for p in phases
                 if p in t["device_by_phase"]]
        ns = sum(found) if found else None
    return None if ns is None else 100.0 * ns / t["device_self_ns"]


def counter_delta(ctx, name: str):
    """A registry counter after the traced stretch minus before it;
    ``None`` where the program has no such counter."""
    before, after = ctx.get("counters_before"), ctx.get("counters_after")
    if before is None or after is None or name not in after:
        return None
    return float(after[name] - before.get(name, 0.0))
