"""Span-based tracer: every span is a ``jax.profiler.TraceAnnotation``, and
an enabled tracer also records it (nested spans with monotonic timing,
cross-thread / cross-process context propagation).

One rule: ``get_tracer().span(name)`` always writes into the profiler's
trace.  Whoever records one (``jax.profiler.start_trace``,
``utils/profiling.ProfilerListener``, ``benchmark/run.py --trace 1``) gets
the program's spans on the profiler's clock beside the device's
operations; while nobody records, an annotation is one flag check.  The
training entries' spans (prefix ``dl4j.``, listed in the README's
"Observability" section) sit at the places every fit loop passes through.

The recorded model is deliberately small (a working subset of
OpenTelemetry's):

- a **Span** is a named interval with attributes, a ``trace_id`` shared
  by everything descending from one root, and a ``parent_id``;
- the **active span stack** is thread-local, so ``span()`` nests
  naturally inside one thread;
- a **SpanContext** is the serializable (trace_id, span_id) pair a
  parent hands to another thread (``parallel/master.py`` worker pools)
  or another process (``parallel/master_mp.py`` puts it in the job
  spec); ``attach(ctx)`` re-roots the local stack under the remote
  parent.

Recording is OFF by default (unlike the metrics registry, which stays on
— recorded spans allocate objects and read clocks, counters are plain
float adds).  A disabled tracer's ``span()`` hands back the bare
annotation: no ``Span``, no clock reads, no device syncs ever.
"""
from __future__ import annotations

import contextlib
import functools
import gc
import os
import threading
import uuid
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional

import jax

from .clock import monotonic_s, wall_s
from .registry import MetricsRegistry, default_registry

__all__ = ["Span", "SpanContext", "Tracer", "get_tracer", "init_entry",
           "open_entry", "set_default_tracer", "training_entry"]

# span-duration histogram bounds: phase timings range from sub-ms host
# work to multi-second aggregation rounds
_SPAN_BUCKETS = (0.0005, 0.001, 0.005, 0.01, 0.05, 0.1, 0.5, 1.0, 5.0,
                 10.0, 60.0)


def _new_id() -> str:
    return uuid.uuid4().hex[:16]


@dataclass(frozen=True)
class SpanContext:
    """Serializable propagation handle: everything a child span in
    another thread/process needs to join the trace."""
    trace_id: str
    span_id: str

    def to_dict(self) -> Dict[str, str]:
        return {"trace_id": self.trace_id, "span_id": self.span_id}

    @classmethod
    def from_dict(cls, d: Dict[str, str]) -> "SpanContext":
        return cls(trace_id=str(d["trace_id"]), span_id=str(d["span_id"]))


@dataclass
class Span:
    name: str
    trace_id: str
    span_id: str
    parent_id: Optional[str]
    attributes: Dict[str, Any] = field(default_factory=dict)
    start_wall_s: float = 0.0
    _start_mono: float = 0.0
    duration_s: Optional[float] = None

    def set_attribute(self, key: str, value: Any) -> None:
        self.attributes[key] = value

    @property
    def context(self) -> SpanContext:
        return SpanContext(trace_id=self.trace_id, span_id=self.span_id)

    def to_dict(self) -> Dict[str, Any]:
        return {"name": self.name, "trace_id": self.trace_id,
                "span_id": self.span_id, "parent_id": self.parent_id,
                "start_wall_s": self.start_wall_s,
                "duration_s": self.duration_s,
                "attributes": dict(self.attributes)}


class _RemoteParent:
    """Stack entry representing a span living in another thread/process —
    context-only, never timed or recorded locally."""

    __slots__ = ("trace_id", "span_id")

    def __init__(self, ctx: SpanContext):
        self.trace_id = ctx.trace_id
        self.span_id = ctx.span_id


@contextlib.contextmanager
def _noop_cm():
    yield None


class _RecordedSpan:
    """What an enabled tracer's ``span()`` returns: the profiler's
    annotation and the recorded :class:`Span`, opened and closed
    together."""

    __slots__ = ("_tracer", "_annotation", "_name", "_attributes", "_span")

    def __init__(self, tracer, annotation, name, attributes):
        self._tracer = tracer
        self._annotation = annotation
        self._name = name
        self._attributes = attributes
        self._span = None

    def __enter__(self) -> "Span":
        st = self._tracer._stack()
        parent = st[-1] if st else None
        sp = self._span = Span(
            name=self._name,
            trace_id=parent.trace_id if parent else _new_id(),
            span_id=_new_id(),
            parent_id=parent.span_id if parent else None,
            attributes=dict(self._attributes),
            start_wall_s=wall_s(),
            _start_mono=monotonic_s())
        st.append(sp)
        self._annotation.__enter__()
        return sp

    def __exit__(self, exc_type, exc, tb):
        self._annotation.__exit__(exc_type, exc, tb)
        sp, st = self._span, self._tracer._stack()
        sp.duration_s = monotonic_s() - sp._start_mono
        if st and st[-1] is sp:
            st.pop()
        else:  # tolerate out-of-order exits from generator teardown
            try:
                st.remove(sp)
            except ValueError:
                pass
        self._tracer._record(sp)
        return False


class Tracer:
    """Create with ``enabled=True`` (or call :func:`get_tracer` after
    ``set_default_tracer``) to record spans.

    ``registry``: span durations land in a ``span_seconds{name=...}``
    histogram there (defaults to the process-global registry).
    ``max_finished``: ring buffer of completed spans kept for
    inspection/tests; 0 keeps none.
    """

    def __init__(self, enabled: bool = False,
                 registry: Optional[MetricsRegistry] = None,
                 max_finished: int = 1024,
                 event_log=None):
        self._enabled = enabled
        self._registry = registry
        self._max_finished = max_finished
        self._event_log = event_log
        self._tls = threading.local()
        self._finished: List[Span] = []
        self._finished_lock = threading.Lock()

    # -- state ---------------------------------------------------------------
    @property
    def enabled(self) -> bool:
        return self._enabled

    def enable(self) -> "Tracer":
        self._enabled = True
        return self

    def disable(self) -> "Tracer":
        self._enabled = False
        return self

    def _stack(self) -> list:
        st = getattr(self._tls, "stack", None)
        if st is None:
            st = self._tls.stack = []
        return st

    def current_span(self) -> Optional[Span]:
        st = self._stack()
        for entry in reversed(st):
            if isinstance(entry, Span):
                return entry
        return None

    def current_context(self) -> Optional[SpanContext]:
        """Propagation handle for the innermost active span (remote or
        local); None outside any span or when disabled."""
        st = self._stack()
        if not st:
            return None
        top = st[-1]
        return SpanContext(trace_id=top.trace_id, span_id=top.span_id)

    @property
    def finished_spans(self) -> List[Span]:
        with self._finished_lock:
            return list(self._finished)

    def clear_finished(self) -> None:
        with self._finished_lock:
            self._finished.clear()

    # -- span lifecycle ------------------------------------------------------
    def span(self, name: str, **attributes):
        """A context manager around one named interval.  Always a
        ``jax.profiler.TraceAnnotation`` (the attributes become its
        statistics); an enabled tracer also records a nested
        :class:`Span` and yields it."""
        annotation = jax.profiler.TraceAnnotation(name, **attributes)
        if not self._enabled:
            return annotation
        return _RecordedSpan(self, annotation, name, attributes)

    @contextlib.contextmanager
    def attach(self, ctx: Optional[SpanContext]):
        """Continue a trace started elsewhere: spans opened inside this
        context parent onto ``ctx`` (worker threads get the master's
        context; worker processes get it from the serialized job spec).
        A None ctx (or a disabled tracer) is a no-op, so call sites can
        propagate unconditionally."""
        if not self._enabled or ctx is None:
            with _noop_cm():
                yield self
            return
        st = self._stack()
        entry = _RemoteParent(ctx)
        st.append(entry)
        try:
            yield self
        finally:
            try:
                st.remove(entry)
            except ValueError:
                pass

    # -- sinks ---------------------------------------------------------------
    def _record(self, sp: Span) -> None:
        if self._max_finished:
            with self._finished_lock:
                self._finished.append(sp)
                if len(self._finished) > self._max_finished:
                    del self._finished[:len(self._finished)
                                       - self._max_finished]
        reg = self._registry if self._registry is not None \
            else default_registry()
        if reg.enabled:
            reg.histogram("span_seconds",
                          "Tracer span durations by span name",
                          ("name",), buckets=_SPAN_BUCKETS) \
               .labels(sp.name).observe(sp.duration_s)
        if self._event_log is not None:
            self._event_log.emit("span", **sp.to_dict())
        # finished spans also land in the flight recorder's span ring so
        # a crash dump carries the recent execution timeline
        from .recorder import get_flight_recorder
        rec = get_flight_recorder()
        if rec is not None:
            rec.record_span(sp)


# env opt-in: DL4J_TPU_TRACE=1 has the default tracer record its spans
# from import time (the knob production pods flip without code changes)
_default_tracer = Tracer(enabled=bool(os.environ.get("DL4J_TPU_TRACE", "")))
_default_tracer_lock = threading.Lock()


def get_tracer() -> Tracer:
    """The process-global tracer every built-in instrumentation point
    uses unless handed an explicit instance.  Disabled by default."""
    return _default_tracer


def set_default_tracer(tracer: Tracer) -> Tracer:
    """Swap the process-global tracer; returns the previous one."""
    global _default_tracer
    with _default_tracer_lock:
        prev, _default_tracer = _default_tracer, tracer
    return prev


# ------------------------------------------------------- training entries
class _CollectorWatch:
    """Python's collector as seen from a training entry: while one is
    open, each collection is a ``dl4j.gc`` span and adds its length to
    ``host_gc_pause_seconds_total``.  ``gc.callbacks`` belongs
    to the process, so entries that nest or run side by side (worker
    threads of a master) share the one callback."""

    def __init__(self):
        self._lock = threading.Lock()
        self._depth = 0
        self._open = None          # (annotation, start) of the collection
        self._pause = None

    def acquire(self) -> None:
        with self._lock:
            self._depth += 1
            if self._depth > 1:
                return
            reg = default_registry()
            self._pause = reg.counter(
                "host_gc_pause_seconds_total",
                "Seconds Python's collector held the host inside "
                "training entries") if reg.enabled else None
            gc.callbacks.append(self._on_collection)

    def release(self) -> None:
        with self._lock:
            self._depth -= 1
            if self._depth == 0:
                gc.callbacks.remove(self._on_collection)
                self._open = None

    def _on_collection(self, phase: str, info: dict) -> None:
        if phase == "start":
            annotation = jax.profiler.TraceAnnotation("dl4j.gc")
            annotation.__enter__()
            self._open = (annotation, monotonic_s())
        elif self._open is not None:
            annotation, start = self._open
            self._open = None
            annotation.__exit__(None, None, None)
            if self._pause is not None:
                self._pause.inc(monotonic_s() - start)


_collector_watch = _CollectorWatch()


def training_entry(name: str):
    """Decorator for a training entry (``fit``, ``fit_on_device``): the
    whole call is the span ``name``, and the collector is watched until
    it returns or raises."""
    def decorate(entry):
        @functools.wraps(entry)
        def traced(*args, **kwargs):
            _collector_watch.acquire()
            try:
                with get_tracer().span(name):
                    return entry(*args, **kwargs)
            finally:
                _collector_watch.release()
        return traced
    return decorate


# ---------------------------------------------------------------- start-up
class _OpenEntry(threading.local):
    """The innermost of the program's entries open on this thread that
    make jitted programs: the name of an ``InstrumentedJit`` inside its
    call, ``"init"`` inside a container's ``init()``, else None.  The
    ``fn`` label of the ``jit_*`` counters (``nn/compile_cache``)."""
    fn = None


open_entry = _OpenEntry()


def init_entry(init):
    """Decorator for a container's ``init()``: the call is the span
    ``dl4j.init``, its wall time goes to ``model_init_seconds_total``,
    and the programs JAX makes inside count under ``fn="init"``."""
    @functools.wraps(init)
    def traced(*args, **kwargs):
        began = monotonic_s()
        outer, open_entry.fn = open_entry.fn, "init"
        try:
            with get_tracer().span("dl4j.init"):
                return init(*args, **kwargs)
        finally:
            open_entry.fn = outer
            reg = default_registry()
            if reg.enabled:
                reg.counter("model_init_seconds_total",
                            "Wall seconds inside the containers' init()"
                            ).inc(monotonic_s() - began)
    return traced
