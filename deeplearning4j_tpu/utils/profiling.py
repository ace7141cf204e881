"""Profiling hooks: XLA trace capture + device memory reports.

Reference tracing (SURVEY §5): ``PerformanceListener`` wall-clock counters +
external ND4J ``OpProfiler``.  The TPU equivalents are the XLA profiler
(Xprof traces viewable in TensorBoard/Perfetto) and device memory
introspection — surfaced here as a listener that brackets a chosen
iteration window, plus small functional helpers.
"""
from __future__ import annotations

import contextlib
import logging
from typing import Optional

import jax

from ..train.listeners import TrainingListener

log = logging.getLogger("deeplearning4j_tpu.profiling")

__all__ = ["ProfilerListener", "trace_annotation", "device_memory_stats",
           "device_platform"]


def device_platform() -> str:
    """Backend platform of the default device ("cpu"/"gpu"/"tpu"), or
    "unknown" when no backend is reachable — the serving tier's /health
    readiness reports ride this."""
    try:
        return jax.devices()[0].platform
    except Exception:
        return "unknown"


class ProfilerListener(TrainingListener):
    """Capture an XLA trace for iterations [start, start+num) into
    ``log_dir`` (open with TensorBoard's profile plugin or Perfetto).
    The first iterations are compile-heavy, so ``start_iteration``
    defaults past them."""

    def __init__(self, log_dir: str, start_iteration: int = 3,
                 num_iterations: int = 3):
        self.log_dir = log_dir
        self.start_iteration = start_iteration
        self.end_iteration = start_iteration + num_iterations
        self._active = False
        self.captured = False

    def iteration_done(self, model, iteration, epoch):
        if not self._active and not self.captured and \
                iteration >= self.start_iteration:
            jax.profiler.start_trace(self.log_dir)
            self._active = True
            log.info("XLA trace started at iteration %d -> %s",
                     iteration, self.log_dir)
        elif self._active and iteration >= self.end_iteration:
            self.stop()

    def stop(self):
        if self._active:
            jax.profiler.stop_trace()
            self._active = False
            self.captured = True
            log.info("XLA trace written to %s", self.log_dir)

    def on_epoch_end(self, model):
        # never leave a trace running across epochs
        self.stop()


@contextlib.contextmanager
def trace_annotation(name: str):
    """Label a host-side region so it shows up on the Xprof timeline
    (ETL, checkpointing, eval — the reference's StatsCalculationHelper
    phase-timing role).  ``observability.get_tracer().span(name)`` writes
    the same annotation, and an enabled tracer also lands the span in
    the metrics registry / event log."""
    with jax.profiler.TraceAnnotation(name):
        yield


def device_memory_stats(device=None) -> Optional[dict]:
    """Live HBM usage for one device: {bytes_in_use, peak_bytes_in_use,
    bytes_limit} — None when the backend doesn't expose it (CPU)."""
    d = device or jax.devices()[0]
    stats = getattr(d, "memory_stats", None)
    if stats is None:
        return None
    try:
        s = d.memory_stats()
    except Exception:
        return None
    if not s:
        return None
    return {k: s[k] for k in ("bytes_in_use", "peak_bytes_in_use",
                              "bytes_limit") if k in s}
