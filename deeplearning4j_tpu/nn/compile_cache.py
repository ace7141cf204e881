"""Compilation-reuse layer: shared trace cache + persistent XLA cache wiring.

XLA compilation is the dominant fixed cost of the TPU execution model
(PAPERS.md: the Julia-to-TPU paper reports compile times rivaling first-epoch
runtime; the TensorFlow paper's core bet is compile-once/run-everywhere).
Three mechanisms make that the framework default:

1. **Shared trace cache** (`shared_jit`): jitted step functions are keyed by
   a structural *topology signature* of the network configuration in a
   process-global weak-value cache.  `MultiLayerNetwork.clone()` (and the
   replica pools the training masters build from it) then reuse the
   already-compiled executable instead of re-tracing an identical topology
   once per replica.  Entries are weakly held: they live exactly as long as
   some network's instance cache still references them.

2. **Compile observability** (`InstrumentedJit`): every shared jitted
   function counts its (re)traces into ``training_compile_total{fn}`` —
   incremented *at trace time* via a deliberate Python side effect inside
   the traced function, the one moment jit runs the Python body — and
   records trace+compile wall time in ``training_compile_seconds{fn}``,
   so recompile storms show up in /metrics instead of as mystery
   latency.  Every call is the span ``dl4j.call.<name>``: a dispatch,
   and the trace and compile when the call has to.  Which of those a
   call paid for, and for how long, JAX says itself: the listeners that
   ``wire_persistent_cache`` registers keep its seconds of tracing,
   lowering, loading from the persistent cache and compiling in the six
   ``jit_*`` counters (``observability/startup.JIT_COUNTERS``), by the
   ``InstrumentedJit`` whose call is open on the thread.

3. **Persistent compile cache** (`wire_persistent_cache`): JAX's on-disk
   compilation cache, wired at package init, so a restarted process
   reloads executables instead of recompiling the world.  The directory is
   ``$JAX_COMPILATION_CACHE_DIR`` where that is set (then no code sets
   one), else one fixed git-ignored path inside the checkout.
"""
from __future__ import annotations

import dataclasses
import hashlib
import json
import logging
import os
import threading
import weakref
from typing import Any, Callable, Dict, Optional, Tuple

import jax

from . import scan_layers as _scan_layers
from ..observability.clock import monotonic_s
from ..observability.registry import default_registry
from ..observability.startup import JIT_COUNTERS, log_startup_once
from ..observability.tracer import get_tracer, open_entry

log = logging.getLogger(__name__)

__all__ = ["topology_signature", "shared_jit", "InstrumentedJit",
           "wire_persistent_cache", "persistent_cache_status",
           "DEFAULT_CACHE_DIR",
           "trace_cache_size", "clear_trace_cache",
           "iter_trace_cache", "set_audit_capture", "audit_capture_mode"]

# compile wall times: sub-100ms CPU toy nets up to minutes-long TPU programs
_COMPILE_BUCKETS = (0.01, 0.025, 0.05, 0.1, 0.25, 0.5, 1.0, 2.5, 5.0,
                    10.0, 30.0, 60.0, 120.0, 300.0)


# --------------------------------------------------------------- signature
def _encode(obj: Any, seen: set) -> Any:
    """Canonical, value-based encoding of a configuration object tree.

    Two structurally identical configs (e.g. a ``clone()``'s deepcopy)
    must encode identically; anything we cannot encode by value falls back
    to an identity token, which disables sharing for that config rather
    than risking a false cache hit.
    """
    if obj is None or isinstance(obj, (bool, int, float, str)):
        return obj
    oid = id(obj)
    if oid in seen:
        return ["@cycle"]
    if isinstance(obj, (list, tuple)):
        seen = seen | {oid}
        return [_encode(v, seen) for v in obj]
    if isinstance(obj, dict):
        seen = seen | {oid}
        return [["@dict"]] + sorted(
            ([_encode(k, seen), _encode(v, seen)] for k, v in obj.items()),
            key=lambda kv: json.dumps(kv[0], sort_keys=True))
    if isinstance(obj, (set, frozenset)):
        return [["@set"]] + sorted(
            (_encode(v, seen | {oid}) for v in obj),
            key=lambda v: json.dumps(v, sort_keys=True))
    if dataclasses.is_dataclass(obj) and not isinstance(obj, type):
        seen = seen | {oid}
        return [["@dc", type(obj).__module__, type(obj).__qualname__]] + [
            [f.name, _encode(getattr(obj, f.name), seen)]
            for f in dataclasses.fields(obj)]
    # dtypes / numpy scalars / small arrays (e.g. loss unit weights)
    try:
        import numpy as np
        if isinstance(obj, np.dtype):
            return ["@dtype", str(obj)]
        if isinstance(obj, np.ndarray) or isinstance(obj, jax.Array):
            a = np.asarray(obj)
            return ["@arr", str(a.dtype), list(a.shape),
                    hashlib.sha256(a.tobytes()).hexdigest()]
    except Exception:
        pass
    if isinstance(obj, type):
        return ["@type", obj.__module__, obj.__qualname__]
    if callable(obj):
        # named functions deepcopy to themselves, so module+qualname is a
        # stable value key; anonymous callables fall through to identity
        mod = getattr(obj, "__module__", None)
        qn = getattr(obj, "__qualname__", None)
        if mod and qn and "<locals>" not in qn and "<lambda>" not in qn:
            return ["@fn", mod, qn]
    # non-dataclass object with a plain __dict__: encode by value (layer
    # confs that predate @dataclass); otherwise identity token (no sharing)
    d = getattr(obj, "__dict__", None)
    if isinstance(d, dict) and type(obj).__module__ != "builtins":
        seen = seen | {oid}
        return [["@obj", type(obj).__module__, type(obj).__qualname__]] + \
            sorted(([k, _encode(v, seen)] for k, v in d.items()),
                   key=lambda kv: kv[0])
    return ["@id", type(obj).__qualname__, oid]


def topology_signature(conf: Any) -> str:
    """Structural signature of a network configuration: layer/vertex confs,
    dtypes, optimizer spec, preprocessors — everything that determines the
    traced program, by VALUE.  Deepcopied configs (``clone()``) produce the
    same signature; any config edit (transfer-learning fine-tune, fold)
    produces a different one."""
    payload = json.dumps(_encode(conf, set()), sort_keys=True,
                         separators=(",", ":"), default=repr)
    return hashlib.sha256(payload.encode()).hexdigest()


# ----------------------------------------------------------- audit capture
# IR-audit spec capture (tools/graftaudit): every InstrumentedJit records
# the abstract signature — shapes, dtypes, NamedShardings, raw Python
# scalars — of the calls that define its compiled variants, so the
# auditor can re-derive the jaxpr / partitioned HLO of the REAL
# production programs without holding example arrays alive.
#
#   "trace" (default)  record a spec only when the call (re)traced — the
#                      capture rides the already-slow compile path, so the
#                      steady state pays nothing;
#   "all"              record every distinct call signature (the audit
#                      harness arms this while driving multi-mesh
#                      workloads: a dp=4 call after a dp=2 call reuses the
#                      ONE trace, so trace-time capture alone would miss
#                      the second sharding layout);
#   "off"              never record.
_AUDIT_MODE = "trace"
#: distinct specs kept per jitted function (oldest dropped beyond this) —
#: covers a serving bucket ladder without unbounded growth
_AUDIT_SPEC_CAP = 16


def set_audit_capture(mode: str) -> None:
    """Set the audit spec-capture mode: ``"trace"`` | ``"all"`` | ``"off"``."""
    global _AUDIT_MODE
    if mode not in ("trace", "all", "off"):
        raise ValueError(f"unknown audit capture mode {mode!r}")
    _AUDIT_MODE = mode


def audit_capture_mode() -> str:
    return _AUDIT_MODE


def _audit_leaf(x: Any) -> Any:
    """Abstract one call-argument leaf for later replay through ``lower``.

    Arrays become ``ShapeDtypeStruct`` (keeping a ``NamedSharding`` so the
    audit lowering runs the same GSPMD partitioning the production call
    did); Python scalars are kept VERBATIM so the replayed trace sees the
    identical weak-type promotion behaviour."""
    if x is None or isinstance(x, (bool, int, float, complex, str)):
        return x
    shape = getattr(x, "shape", None)
    dtype = getattr(x, "dtype", None)
    if shape is None or dtype is None:
        return x
    sh = getattr(x, "sharding", None)
    if sh is not None and type(sh).__name__ == "NamedSharding":
        return jax.ShapeDtypeStruct(tuple(shape), dtype, sharding=sh)
    return jax.ShapeDtypeStruct(tuple(shape), dtype)


def _leaf_descriptor(leaf: Any) -> Tuple:
    """Hashable identity of one abstracted leaf (spec dedupe key)."""
    if isinstance(leaf, jax.ShapeDtypeStruct):
        sh = getattr(leaf, "sharding", None)
        if sh is not None:
            mesh = sh.mesh
            return ("sds", leaf.shape, str(leaf.dtype), str(sh.spec),
                    tuple(mesh.shape.items()))
        return ("sds", leaf.shape, str(leaf.dtype), None, None)
    return ("py", type(leaf).__name__, repr(leaf))


def _spec_key(spec: Any) -> Tuple:
    leaves, treedef = jax.tree_util.tree_flatten(spec)
    return (treedef, tuple(_leaf_descriptor(l) for l in leaves))


def _liveness_probe(args: Tuple) -> Tuple:
    """Per-positional-arg weakrefs to the call's array leaves.

    The lifetime auditor (tools/graftaudit/lifetime.py) queries these
    LONG after the call: a binding whose every array leaf is gone (its
    weakref died, or the buffer was donated away — ``is_deleted()``) was
    provably dead after the call in this process, i.e. safe to donate.
    Weakrefs only — the probe must never extend any array's lifetime
    (the module contract: audit capture holds no example arrays alive).
    """
    probe = []
    for arg in args:
        refs = []
        for leaf in jax.tree_util.tree_leaves(arg):  # graftlint: disable=JX030  (audit-capture path: runs once per recorded call spec, never in the steady fit loop)
            if getattr(leaf, "shape", None) is None or \
                    getattr(leaf, "dtype", None) is None:
                continue            # python scalar / non-array leaf
            try:
                refs.append(weakref.ref(leaf))
            except TypeError:
                pass                # un-weakref-able array type
        probe.append(tuple(refs))
    return tuple(probe)


def _probe_status(refs: Tuple) -> str:
    """``"dead"`` | ``"live"`` | ``"unknown"`` for one argument's probe."""
    if not refs:
        return "unknown"            # no array leaves were captured
    for r in refs:
        leaf = r()
        if leaf is None:
            continue                # object collected: leaf is dead
        try:
            if leaf.is_deleted():
                continue            # donated away: buffer is dead
        except AttributeError:
            pass                    # numpy leaf: alive object == live
        return "live"
    return "dead"


# ------------------------------------------------------------ shared cache
class InstrumentedJit:
    """A jitted callable that observes its own (re)traces.

    The wrapped Python function body runs exactly once per trace — that is
    the hook: it bumps ``training_compile_total{fn}`` and flags the calling
    thread, so ``__call__`` can attribute the call's wall time to
    ``training_compile_seconds{fn}``.  In JAX, trace+lower+compile are
    synchronous within the triggering call (only execution is async), so
    that wall time is an honest compile cost, and the call's span
    ``dl4j.call.<name>`` covers it (jax's own ``backend_compile``
    annotation nests inside).
    """

    __slots__ = ("name", "fn", "_tls", "_fun", "_donate", "_audit_specs",
                 "_audit_live", "_audit_lock", "_span_name", "__weakref__")

    def __init__(self, fun: Callable, name: str,
                 donate_argnums: Tuple[int, ...] = ()):
        self.name = name
        self._span_name = "dl4j.call." + name
        self._tls = threading.local()
        # audit surface (tools/graftaudit): the raw builder function and
        # its declared donation — re-lowering goes through a FRESH
        # jax.jit of `_fun` so an audit never ticks the compile counters
        # the production tests pin
        self._fun = fun
        self._donate = tuple(donate_argnums)
        self._audit_specs: Dict[Tuple, Tuple] = {}
        self._audit_live: Dict[Tuple, Tuple] = {}
        self._audit_lock = threading.Lock()
        self.fn = self._jitted(fun)

    def _jitted(self, fun: Callable):
        holder_ref = weakref.ref(self)

        def traced(*args, **kwargs):
            holder = holder_ref()
            if holder is not None:
                holder._note_trace()
            return fun(*args, **kwargs)

        # the program carries the wrapper's name to the compiler (module
        # ``jit_<name>``) and into a profile (scopes start ``jit(<name>)/``)
        traced.__name__ = traced.__qualname__ = self.name
        return jax.jit(traced, donate_argnums=self._donate)

    def _inputs_alone(self, error, args, kwargs):
        """The compiler refused, for memory, a program in which a scanned
        run under ``cache_mode="remat"`` kept names beside its input
        (``nn/scan_layers``): trace it again, now and at every later
        shape, with such runs keeping their input alone, which is the
        program remat had before it kept anything."""
        fun = self._fun

        def inputs_alone(*a, **k):
            with _scan_layers.input_alone():
                return fun(*a, **k)
        log.warning("%s does not fit the device with the names its remat "
                    "runs kept; traced again with their inputs alone (%s)",
                    self.name, str(error).splitlines()[0][:200])
        reg = default_registry()
        if reg.enabled:
            reg.counter("scan_fallbacks_total",
                        "Programs the compiler refused for memory and "
                        "that were traced again with remat runs keeping "
                        "their input alone", ("fn",)).labels(self.name).inc()
        self.fn = self._jitted(inputs_alone)
        return self.fn(*args, **kwargs)

    def _note_trace(self) -> None:
        self._tls.traced = True
        reg = default_registry()
        if reg.enabled:
            reg.counter("training_compile_total",
                        "XLA traces (whether one was then compiled or "
                        "loaded from the persistent cache: "
                        "jit_programs_compiled_total, "
                        "jit_programs_loaded_total)", ("fn",)
                        ).labels(self.name).inc()

    def __call__(self, *args, **kwargs):
        self._tls.traced = False
        t0 = monotonic_s()
        kept = _scan_layers.kept_runs()
        # what JAX traces, lowers, loads or compiles on this thread until
        # the call returns is this program's (the ``fn`` of the jit_*
        # counters)
        outer, open_entry.fn = open_entry.fn, self.name
        try:
            with get_tracer().span(self._span_name):
                try:
                    out = self.fn(*args, **kwargs)
                except Exception as e:
                    if _scan_layers.kept_runs() == kept or \
                            not _scan_layers.refused_for_memory(e):
                        raise
                    out = self._inputs_alone(e, args, kwargs)
        finally:
            open_entry.fn = outer
        if _AUDIT_MODE == "all" or (_AUDIT_MODE == "trace"
                                    and self._tls.traced):
            self._record_spec(args, kwargs)
        if self._tls.traced:
            dt = monotonic_s() - t0
            reg = default_registry()
            if reg.enabled:
                reg.histogram(
                    "training_compile_seconds",
                    "Wall time of calls that (re)traced, i.e. trace + "
                    "lowering + cache load or compile + first dispatch "
                    "(by parts: the jit_* counters)", ("fn",),
                    buckets=_COMPILE_BUCKETS).labels(self.name).observe(dt)
            # (a step traced inside an epoch's program is not yet counted)
            if outer is None and self.name.startswith(_TRAINING_PROGRAMS):
                log_startup_once()
        return out

    @property
    def last_call_traced(self) -> bool:
        """Did THIS thread's most recent call trigger a (re)trace?"""
        return bool(getattr(self._tls, "traced", False))

    def lower(self, *args, **kwargs):
        """AOT lowering passthrough (memory analysis, HLO dumps)."""
        return self.fn.lower(*args, **kwargs)

    # ------------------------------------------------------ audit surface
    def _record_spec(self, args, kwargs) -> None:
        try:
            spec = jax.tree_util.tree_map(_audit_leaf,
                                          (args, dict(kwargs)))
            key = _spec_key(spec)
        except Exception:
            return              # unabstractable call: audit sees nothing
        try:
            probe = _liveness_probe(args)
        except Exception:
            probe = ()
        with self._audit_lock:
            if key in self._audit_specs:
                return
            if len(self._audit_specs) >= _AUDIT_SPEC_CAP:
                dropped = next(iter(self._audit_specs))
                self._audit_specs.pop(dropped)
                self._audit_live.pop(dropped, None)
            self._audit_specs[key] = spec
            if probe:
                self._audit_live[key] = probe

    def audit_specs(self) -> "list":
        """Recorded abstract call specs, oldest first: each is an
        ``(args, kwargs)`` pytree of ``ShapeDtypeStruct`` / raw Python
        scalars describing one compiled variant of this function."""
        with self._audit_lock:
            return list(self._audit_specs.values())

    def audit_liveness(self, spec) -> Tuple[str, ...]:
        """Observed caller liveness per POSITIONAL argument of one
        recorded spec: ``"dead"`` (every array leaf of the binding was
        collected or donated since the call — the caller provably never
        re-reads it), ``"live"`` (at least one leaf still alive — e.g. a
        device-resident dataset re-fed every epoch, or ``net.params``
        passed to serve), or ``"unknown"`` (no array leaves captured).
        One observation, not a proof of the general contract — the
        lifetime solver combines it with ``DEAD_AFTER_CALL`` kind
        contracts and jaxpr-side aliasing compatibility."""
        try:
            key = _spec_key(spec)
        except Exception:
            return ()
        with self._audit_lock:
            probe = self._audit_live.get(key)
        if probe is None:
            return ()
        return tuple(_probe_status(refs) for refs in probe)

    @property
    def donate_argnums(self) -> Tuple[int, ...]:
        """Donation the builder declared (platform branches already
        applied) — the auditor's ground truth for AX005."""
        return self._donate

    def audit_jaxpr(self, spec):
        """ClosedJaxpr of one recorded spec — the exact trace the
        production call executed (same builder function, same abstract
        arguments), produced without touching the instrumented jit."""
        args, kwargs = spec
        return jax.make_jaxpr(self._fun)(*args, **kwargs)

    def audit_lower(self, spec):
        """Lower one recorded spec through a FRESH un-instrumented jit of
        the builder function: same jaxpr, same shardings, same donation —
        but no compile-counter tick and no entry in jax's dispatch cache
        for the production wrapper, so audits are invisible to the
        zero-recompile contracts the tests pin."""
        args, kwargs = spec
        return jax.jit(self._fun,
                       donate_argnums=self._donate).lower(*args, **kwargs)


_TRACE_CACHE: "weakref.WeakValueDictionary[Tuple, InstrumentedJit]" = \
    weakref.WeakValueDictionary()
_TRACE_LOCK = threading.RLock()


def shared_jit(key: Tuple, builder: Callable[[], Tuple[Callable, Tuple]],
               *, name: str) -> InstrumentedJit:
    """Get-or-build a shared jitted function.

    ``key`` must be a hashable structural key (network class, topology
    signature, function kind).  ``builder`` returns ``(fun,
    donate_argnums)`` — the builder is the single source of truth for
    donation, so a kind's donation policy cannot drift between the builder
    and its call sites.  ``fun`` must close over *configuration* only —
    never over a network instance — so every equal-signature network can
    safely execute the cached callable with its own params/state/opt_state
    arguments.

    Entries are weakly referenced: a function stays cached exactly while at
    least one network's instance ``_jit_cache`` holds it.
    """
    with _TRACE_LOCK:
        entry = _TRACE_CACHE.get(key)
        if entry is not None:
            reg = default_registry()
            if reg.enabled:
                reg.counter("training_trace_cache_hits_total",
                            "Shared trace-cache hits (a clone/replica "
                            "reused an already-jitted step)", ("fn",)
                            ).labels(name).inc()
            return entry
        fun, donate_argnums = builder()
        entry = InstrumentedJit(fun, name=name,
                                donate_argnums=tuple(donate_argnums))
        _TRACE_CACHE[key] = entry
        return entry


def trace_cache_size() -> int:
    return len(_TRACE_CACHE)


def iter_trace_cache() -> "list":
    """Snapshot of the live shared-trace-cache entries as ``(key, entry)``
    pairs (strong refs — callers should drop the list when done).  This is
    the IR auditor's program enumeration: every jitted kind any live
    network compiled — train steps, serve, prefill, decode — is reachable
    here, so the audit traverses real production programs, not fixtures."""
    with _TRACE_LOCK:
        return [(k, v) for k, v in _TRACE_CACHE.items() if v is not None]


def clear_trace_cache() -> None:
    """Drop every shared entry (tests; live networks keep their own refs)."""
    with _TRACE_LOCK:
        _TRACE_CACHE.clear()


# -------------------------------------------------------- persistent cache
#: Where the on-disk compile cache lives when ``JAX_COMPILATION_CACHE_DIR``
#: does not place it from outside: ONE fixed, git-ignored path inside the
#: checkout, next to the native build.  Never a temp name, a pid or a
#: timestamp — the path is part of the cache key, so a directory that
#: moves never hits.
DEFAULT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    "_compile_cache")

_PERSISTENT_STATUS: Dict[str, Any] = {"enabled": False}
_PERSISTENT_LISTENING = False
_PERSISTENT_LOCK = threading.Lock()

# ------------------------------------------------- JAX's own durations
# Where the host's seconds went whenever JAX made a program: the six
# registry counters of ``observability/startup.JIT_COUNTERS`` by ``fn``, the
# name of the ``InstrumentedJit`` whose call is open on the thread, ``init``
# inside a container's ``init()``, else ``eager`` (the caller's own
# ``jax.jit``s and loose operations).  Written only when a program is traced,
# lowered, loaded or compiled: a steady step touches none.
_CACHE_HIT = "/jax/compilation_cache/cache_hits"
_BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
_JIT_DURATIONS = {
    "/jax/core/compile/jaxpr_trace_duration": "trace_s",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower_s",
    _BACKEND_COMPILE: "compile_s",
}
#: programs whose first call ends a process's start-up (the report is
#: logged once, when one of them has traced and returned)
_TRAINING_PROGRAMS = ("train_step", "epoch")


class _ThreadEvents(threading.local):
    """What the listeners keep for one thread between JAX's events."""

    def __init__(self):
        # the cache has served the program whose backend_compile_duration
        # comes next
        self.served = False
        # one entry an interval open on the thread (JAX says when one
        # begins and when it ends): the seconds of those it has held
        self.open = []
        # (counter, fn) -> what to add once the outermost interval ends
        self.own = {}


_EVENTS = _ThreadEvents()


def _cache_entries(path: str) -> int:
    try:
        return sum(1 for f in os.listdir(path) if not f.startswith("."))
    except OSError:
        return 0


def _on_cache_event(event: str, **_kw) -> None:
    if event == _CACHE_HIT:
        _EVENTS.served = True


def _on_begin(event: str, _started: float, **_kw) -> None:
    """An interval of tracing, lowering or compiling begins on this thread
    (JAX records its start as a scalar under the duration event's name)."""
    if event in _JIT_DURATIONS:
        _EVENTS.open.append(0.0)


def _on_duration(event: str, seconds: float, **_kw) -> None:
    """JAX's duration events into the ``jit_*`` counters, each second
    once.  A trace sends an event for every jitted function traced inside
    it (the step of a scanned stack: thousands), each inside its interval,
    and so does an operation that runs eagerly under a trace, with its own
    lowering and compile: an interval is counted less what it held.  The
    registry is written when the outermost interval ends."""
    which = _JIT_DURATIONS.get(event)
    if which is None:
        return
    events = _EVENTS
    # no entry: the listeners were registered while the interval was open
    held = events.open.pop() if events.open else 0.0
    if events.open:
        events.open[-1] += seconds
    own, fn = events.own, open_entry.fn or "eager"
    if event == _BACKEND_COMPILE:
        served, events.served = events.served, False
        which = "cache_load_s" if served else "compile_s"
        programs = ("programs_loaded" if served else "programs_compiled", fn)
        own[programs] = own.get(programs, 0) + 1
    own[which, fn] = own.get((which, fn), 0.0) + max(seconds - held, 0.0)
    if events.open:
        return
    reg = default_registry()
    if reg.enabled:
        for (counter, fn), amount in own.items():
            name, text = JIT_COUNTERS[counter]
            reg.counter(name, text, ("fn",)).labels(fn).inc(amount)
    own.clear()


def _listen() -> None:
    """Register the three listeners, once a process; the six counters stand
    at nought from then on (a warm run says ``compiled 0``, not nothing)."""
    global _PERSISTENT_LISTENING
    reg = default_registry()
    if reg.enabled:
        for name, text in JIT_COUNTERS.values():
            reg.counter(name, text, ("fn",))
    with _PERSISTENT_LOCK:
        if not _PERSISTENT_LISTENING:
            jax.monitoring.register_event_listener(_on_cache_event)
            jax.monitoring.register_scalar_listener(_on_begin)
            jax.monitoring.register_event_duration_secs_listener(
                _on_duration)
            _PERSISTENT_LISTENING = True


def wire_persistent_cache() -> Dict[str, Any]:
    """Wire JAX's persistent (on-disk) compilation cache; runs at package
    import, so a restarted process reloads executables instead of
    recompiling the world.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set the directory is placed
    from outside: JAX reads the variable itself and this code sets no
    directory at all.  Where it is not, the cache goes to
    ``DEFAULT_CACHE_DIR``.  Thresholds are lowered so every entry persists
    (the min-compile-time default would skip small programs).  The key
    leaves a program's metadata out (JAX's default), so a program that
    differs from a cached one only in its name scopes is served the old
    executable, and a profile then reads the old scopes.  A checkout
    that cannot be written (read-only install) leaves the cache off and
    says so in the returned status; nothing else is caught.  Either way
    the listeners that keep JAX's own seconds of tracing, lowering, loading
    and compiling are registered (``_listen``).  Returns the status dict, including how many entries a previous process left behind
    (``existing_entries``)."""
    global _PERSISTENT_STATUS
    _listen()
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR", "")
    placed_by = "JAX_COMPILATION_CACHE_DIR" if path else "checkout"
    if not path:
        path = DEFAULT_CACHE_DIR
        try:
            os.makedirs(path, exist_ok=True)
        except OSError as e:
            with _PERSISTENT_LOCK:
                _PERSISTENT_STATUS = {"enabled": False, "dir": path,
                                      "error": f"{type(e).__name__}: {e}"}
                return dict(_PERSISTENT_STATUS)
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    existing = _cache_entries(path)
    reg = default_registry()
    if reg.enabled:
        reg.gauge("training_persistent_cache_entries",
                  "Entries found in the persistent XLA compile cache dir "
                  "at wiring time").set(existing)
    with _PERSISTENT_LOCK:
        _PERSISTENT_STATUS = {"enabled": True, "dir": path,
                              "placed_by": placed_by,
                              "existing_entries": existing}
        return dict(_PERSISTENT_STATUS)


def persistent_cache_status() -> Dict[str, Any]:
    """The wiring status plus what has happened since: ``entries`` now in
    the directory, and this process's programs by where they came from:
    ``hits`` loaded from the directory (``jit_programs_loaded_total``),
    ``misses`` compiled (``jit_programs_compiled_total``: every program
    while the cache is off)."""
    with _PERSISTENT_LOCK:
        status = dict(_PERSISTENT_STATUS)
    reg = default_registry()
    for key, which in (("hits", "programs_loaded"),
                       ("misses", "programs_compiled")):
        counter = reg.get(JIT_COUNTERS[which][0])
        status[key] = int(sum(child.value for _fn, child in
                              counter.samples())) if counter else 0
    if status.get("enabled"):
        status["enabled"] = bool(jax.config.jax_enable_compilation_cache)
        status["entries"] = _cache_entries(status["dir"])
    return status
