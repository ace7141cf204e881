"""Start-up accounted for from inside the program: where the host's
seconds went between the start of the process and the first steady step.

The parts, each in the process-global registry:

- gauge ``process_age_at_import_seconds``: the process's age when the
  package's import began — the interpreter, ``import jax`` where the
  caller asked for devices first, the runtime reaching the chip; nothing
  a change to this package moves;
- gauge ``package_import_seconds``: the package's own import
  (``deeplearning4j_tpu/__init__.py`` reads the clock on its first and
  last line);
- counter ``model_init_seconds_total``: wall time inside the containers'
  ``init()`` (``tracer.init_entry``, span ``dl4j.init``);
- counters ``jit_trace_seconds_total``, ``jit_lower_seconds_total``,
  ``jit_cache_load_seconds_total``, ``jit_compile_seconds_total``,
  ``jit_programs_loaded_total``, ``jit_programs_compiled_total``, by
  ``fn``: JAX's own durations, kept by the listeners of
  ``nn/compile_cache``.

``startup_report()`` is the one reading of them all.  There is no switch:
the counters are written only when a program is traced, lowered, loaded
or compiled, so a steady step costs what it cost.
"""
from __future__ import annotations

import logging
import threading
from typing import Any, Dict, Optional

from .clock import monotonic_s, process_age_s
from .registry import default_registry

__all__ = ["JIT_COUNTERS", "log_startup_once", "note_import",
           "startup_report"]

log = logging.getLogger(__name__)

#: the six ``jit_*`` counters (label ``fn``), by their key in the report:
#: the one table of their names, which the listeners write by too
JIT_COUNTERS = {
    "trace_s": ("jit_trace_seconds_total",
                "Seconds JAX traced Python into jaxprs "
                "(jaxpr_trace_duration)"),
    "lower_s": ("jit_lower_seconds_total",
                "Seconds JAX lowered jaxprs to MLIR modules "
                "(jaxpr_to_mlir_module_duration)"),
    "cache_load_s": ("jit_cache_load_seconds_total",
                     "Seconds of backend_compile_duration of programs the "
                     "persistent cache served"),
    "compile_s": ("jit_compile_seconds_total",
                  "Seconds of backend_compile_duration of programs the "
                  "persistent cache did not serve"),
    "programs_loaded": ("jit_programs_loaded_total",
                        "Programs loaded from the persistent compile "
                        "cache"),
    "programs_compiled": ("jit_programs_compiled_total",
                          "Programs compiled (the persistent cache missed, "
                          "or is off)"),
}

_logged = False
_logged_lock = threading.Lock()


def note_import(began: float) -> None:
    """The package's import is over: ``began`` is the monotonic clock's
    reading on the first line of ``deeplearning4j_tpu/__init__.py``."""
    took = monotonic_s() - began
    age = process_age_s()
    reg = default_registry()
    if not reg.enabled:
        return
    reg.gauge("package_import_seconds",
              "Seconds the package's own import took").set(took)
    if age is not None:
        reg.gauge("process_age_at_import_seconds",
                  "The process's age when the package's import began"
                  ).set(max(age - took, 0.0))


def _value(reg, name: str) -> Optional[float]:
    inst = reg.get(name)
    return None if inst is None else inst.value


def startup_report() -> Dict[str, Any]:
    """Where this process's start-up went, in seconds: the two gauges of
    the import, ``model_init_s``, and under ``jit`` the six counters by
    ``fn`` (``{"train_step": {"trace_s": ..., "programs_compiled": ...},
    "init": ..., "eager": ...}``).  A part nothing has written yet is
    None (the gauges, the init) or absent (an ``fn``)."""
    reg = default_registry()
    jit: Dict[str, Dict[str, float]] = {}
    for key, (name, _text) in JIT_COUNTERS.items():
        counter = reg.get(name)
        for (fn,), child in (counter.samples() if counter else ()):
            jit.setdefault(fn, dict.fromkeys(JIT_COUNTERS, 0.0))[key] = \
                child.value
    return {"process_age_at_import_s":
            _value(reg, "process_age_at_import_seconds"),
            "package_import_s": _value(reg, "package_import_seconds"),
            "model_init_s": _value(reg, "model_init_seconds_total"),
            "jit": jit}


def log_startup_once() -> None:
    """Log the report at INFO, the first time a process asks."""
    global _logged
    with _logged_lock:
        if _logged:
            return
        _logged = True
    log.info("start-up by parts: %s", startup_report())
