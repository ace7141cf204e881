"""deeplearning4j_tpu.observability — unified metrics + tracing.

One coherent telemetry layer for the training, parallel, and serving
tiers (the role TensorFlow's built-in metrics/tracing runtime plays,
Abadi et al. 2016), replacing the fragmented per-module counters the
reference stack grew (PerformanceListener wall clocks, external
OpProfiler, UI stats storage — SURVEY §5):

- :mod:`registry` — dependency-free Counter/Gauge/Histogram with label
  sets; thread-safe; process-global default + injectable instances;
- :mod:`exposition` — Prometheus text format + JSON snapshot (served on
  ``/metrics`` by both HTTP servers in ``serving/``);
- :mod:`tracer` — every span is a ``jax.profiler.TraceAnnotation``, so
  a profiler trace holds the program's own spans (``dl4j.fit``,
  ``dl4j.input_wait``, ``dl4j.h2d``, ``dl4j.call.<name>``,
  ``dl4j.window_wait``, ``dl4j.profiler_fence``, ``dl4j.sync``,
  ``dl4j.gc``, ``dl4j.init``; the table is in the README) beside the device's
  operations, which the step program's name scopes (``forward``,
  ``grad_post``, ``optimizer``, one per layer class) name; an enabled
  tracer also records nested spans on monotonic clocks with
  cross-thread / cross-process context propagation.  The collector's
  callback of the training entries keeps
  ``host_gc_pause_seconds_total``;
- :mod:`events` — structured JSONL event log for offline analysis;
- :mod:`listener` — ``MetricsListener`` publishing score/throughput/
  grad-norm/device-memory from the ``TrainingListener`` hook points;
- :mod:`clock` — the monotonic/wall helpers everything above sources
  timings from, and ``process_age_s()``;
- :mod:`startup` — ``startup_report()``: where the host's seconds went
  between the start of the process and the first steady step (the
  process's age at the package's import, the import, the containers'
  ``init()`` under the span ``dl4j.init``, and JAX's own seconds of
  tracing, lowering, loading from the persistent cache and compiling, by
  program);
- :mod:`quantiles` — sliding-window exact quantiles (``LatencyWindow``),
  the live p50/p99 read the serving tier's SLO admission control gates
  on (registry histograms answer scrape-interval questions, not
  "what is the p99 right now");
- :mod:`recorder` — the flight recorder: bounded ring buffers of recent
  spans/events/metric snapshots per subsystem channel, dumped as atomic
  checksummed JSON artifacts on crashes, preemptions, evictions, and
  SLO breaches (``/debug/flightrecorder`` on both HTTP servers);
- :mod:`health` — streaming anomaly detection (NaN loss/grads, EWMA
  spike, throughput regression, padding drift, serving
  p99/shed-rate) that flips ``/health`` to ``degraded``, can trigger an
  immediate checkpoint save, and (opt-in) stops training;
- :mod:`profiler` — the step profiler: per-step phase attribution
  (etl/h2d/dispatch/device/listener/forensics/checkpoint) with a
  SAMPLED device fence, dispatch-depth and live-bytes gauges, and
  Chrome-trace export (``/debug/profile`` on both HTTP servers).

Cost model: METRICS are on by default (the registry is plain host
arithmetic — serving ``/metrics`` and the training counters work out of
the box) and ``default_registry().disable()`` short-circuits every
instrument write to one bool check; RECORDING spans is off by default
(enable via ``DL4J_TPU_TRACE=1`` or an injected ``Tracer``), while the
annotation a span writes into a profiler trace is always there and
costs one flag check when nobody records.  ``Tracer(bridge_xprof=True)``
and ``DL4J_TPU_TRACE=xprof`` are gone: bridging is what a span is.
Nothing in this package forces a device sync, the step profiler's
sampled fence apart (default on, every 16th step of a ``fit``).
"""
from __future__ import annotations

from .clock import monotonic_s, process_age_s, wall_s
from .events import EventLog, configure_event_log, emit_event, get_event_log
from .exposition import CONTENT_TYPE, escape_label_value, render_text
from .health import (Detection, HealthConfig, HealthMonitor,
                     HealthTermination, get_health_monitor,
                     set_health_monitor)
from .profiler import (StepProfiler, chrome_trace, dump_chrome_trace,
                       load_chrome_trace, phase_summary, record_slices,
                       step_profiler_for, stepprof_enabled)
from .quantiles import LatencyWindow, bucket_quantile
from .recorder import (FlightRecorder, get_flight_recorder, load_dump,
                       set_flight_recorder)
from .registry import (DEFAULT_BUCKETS, Counter, Gauge, Histogram,
                       MetricsRegistry, default_registry,
                       set_default_registry)
from .startup import startup_report
from .tracer import Span, SpanContext, Tracer, get_tracer, set_default_tracer

__all__ = [
    "CONTENT_TYPE", "Counter", "DEFAULT_BUCKETS", "Detection", "EventLog",
    "FlightRecorder", "Gauge", "HealthConfig", "HealthMonitor",
    "HealthTermination", "Histogram", "LatencyWindow", "MetricsListener",
    "MetricsRegistry", "Span",
    "SpanContext", "StepProfiler", "Tracer", "bucket_quantile",
    "chrome_trace", "configure_event_log",
    "default_registry", "dump_chrome_trace",
    "emit_event", "escape_label_value", "get_event_log",
    "get_flight_recorder", "get_health_monitor", "get_tracer",
    "load_chrome_trace", "load_dump",
    "monotonic_s", "phase_summary", "process_age_s", "record_slices",
    "render_text", "set_default_registry",
    "set_default_tracer", "set_flight_recorder", "set_health_monitor",
    "startup_report", "step_profiler_for", "stepprof_enabled", "wall_s",
]


def __getattr__(name):
    # MetricsListener imports train.listeners, which itself uses the
    # clock helpers here — resolve lazily to keep the import DAG acyclic
    if name == "MetricsListener":
        from .listener import MetricsListener
        return MetricsListener
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")
