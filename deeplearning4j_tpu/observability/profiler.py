"""StepProfiler: per-step time attribution with MFU, dispatch-depth,
and memory-watermark telemetry.

``/metrics`` says *how fast* a step was; nothing in the stack said
*where the time went* — host dispatch vs device compute vs ETL wait vs
listener/forensics bookkeeping — so every optimisation PR has had to
re-derive that split ad hoc.  The :class:`StepProfiler` attributes every
training step's wall time into named phases::

    etl_wait | h2d | dispatch | device | listener | forensics | checkpoint

and exports the result through every existing observability surface: a
bounded FlightRecorder ``profile`` channel (Chrome-trace dumpable,
served live at ``GET /debug/profile``), registry gauges
(``training_mfu{program}``, ``training_dispatch_depth``,
``device_live_bytes``), and the HealthMonitor's MFU-regression
detector.

Honesty model — the one thing this module must not lie about:

- The *device* slice can only be measured by materializing the step's
  result (``jax.block_until_ready``), which is exactly the per-step
  host sync the fit loops' async-dispatch design exists to avoid.  So
  the fence is SAMPLED: every ``sample_every``-th step pays one fence
  (counted in ``stepprof_fences_total``), all other steps stay fully
  async — zero extra syncs, the PR 16 host-sync sweep invariant.  On
  unsampled steps the device slice is ``None``, never an estimate.
- The **dispatch-depth gauge** counts async dispatches since the last
  materialization point the profiler can see (its own fences, plus
  materializations the caller reports via :meth:`materialized`): it
  makes pipelining visible — depth pinned at 0 means some hidden sync
  is serializing every step.
- **MFU** derives from the committed graftaudit card ``flops`` field
  (``tools/graftaudit/cards/``) — cards are the single source of truth
  for program FLOPs; no analytic formulas are duplicated here.  The
  peak-FLOP/s denominator comes from ``DL4J_TPU_PEAK_FLOPS`` or a
  per-chip table for known TPU kinds; with neither, achieved FLOP/s is
  still exported and the MFU gauge is withheld rather than faked.
- **Memory watermarks** sum live device bytes (``jax.live_arrays``) at
  fences and compare the observed peak against the AX008
  ``peak_live_bytes`` budget from ``tools/graftaudit/budgets.json``
  (``device_live_bytes_budget_ratio{program}``) — an approaching OOM
  pages before it happens.

Enablement: ``DL4J_TPU_STEPPROF`` (default on; the per-step cost is a
handful of ``perf_counter`` reads plus one buffered tuple append: on the
chip not measured apart from the fence).  The fence is the span
``dl4j.profiler_fence`` in a profiler trace; on a TPU v5e, GPT-2 medium
through ``fit``, each one opens a device gap of 14.8-16.6 ms (3.0-4.5 ms
under the fence itself, the live-array walk after the device has run
dry, and 8.6-11.2 ms under the next step's dispatch into the empty
pipeline; ``PERF.md`` section 5), 12 of them in 200 steps.
``DL4J_TPU_STEPPROF_SAMPLE`` sets the fence cadence (default 16);
``DL4J_TPU_STEPPROF_PROGRAM`` overrides the program label the fit
loops pass, mapping a run onto its canonical card/budget entry.

This module is the ONE place a fence inside a loop is legal — the
graftlint JX029 rule flags ``block_until_ready`` in loops everywhere
else in the package, because an unsampled fence in a hot loop is the
regression class the host-sync sweep removed.
"""
from __future__ import annotations

import hashlib
import json
import os
import re
from typing import Any, Dict, List, Optional

from .clock import monotonic_s, wall_s
from .recorder import get_flight_recorder
from .registry import MetricsRegistry, default_registry
from .tracer import get_tracer

__all__ = ["StepProfiler", "step_profiler_for", "stepprof_enabled",
           "record_slices", "resolve_card_flops", "resolve_budget_bytes",
           "peak_device_flops", "live_device_bytes", "phase_summary",
           "chrome_trace", "dump_chrome_trace", "load_chrome_trace",
           "CHANNEL", "PHASES", "TRACE_FORMAT", "TRACE_PREFIX"]

CHANNEL = "profile"
PHASES = ("etl_wait", "h2d", "dispatch", "device", "listener",
          "forensics", "checkpoint")
TRACE_FORMAT = "dl4j-tpu-stepprof-trace-v1"
TRACE_PREFIX = "stepprof-"

#: serve/decode slice keys in their temporal order (Chrome-trace layout)
SLICE_KEYS = ("queue_wait_s", "batch_form_s", "execute_s")

# repo root when running from a checkout: profiler.py lives at
# <root>/deeplearning4j_tpu/observability/profiler.py
_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

# bf16 peak FLOP/s per chip for known TPU generations (the roofline
# denominator when DL4J_TPU_PEAK_FLOPS is not set); prefix-matched
# against device_kind, most specific first
_PEAK_FLOPS_BY_KIND = (
    ("TPU v6", 918e12),
    ("TPU v5p", 459e12),
    ("TPU v5 lite", 197e12),
    ("TPU v5e", 197e12),
    ("TPU v4", 275e12),
    ("TPU v3", 123e12),
    ("TPU v2", 45e12),
)


def stepprof_enabled() -> bool:
    """Default on; ``DL4J_TPU_STEPPROF=0`` disables every hook."""
    return os.environ.get("DL4J_TPU_STEPPROF", "1") != "0"


def _default_sample_every() -> int:
    try:
        return max(1, int(os.environ.get("DL4J_TPU_STEPPROF_SAMPLE", "16")))
    except ValueError:
        return 16


# ---------------------------------------------------------------- cards
def _card_path(program: str) -> str:
    directory = os.environ.get("DL4J_TPU_CARDS_DIR") or os.path.join(
        _REPO_ROOT, "tools", "graftaudit", "cards")
    # mirrors tools/graftaudit/cards.card_filename (not imported: the
    # audit toolchain must stay optional at runtime)
    fname = re.sub(r"[^A-Za-z0-9_.-]+", "_", program) + ".json"
    return os.path.join(directory, fname)


def resolve_card_flops(program: str) -> Optional[float]:
    """FLOPs of one execution of ``program`` from its committed
    graftaudit card — the single source of truth for program cost; None
    when no card exists (installed package, un-audited program)."""
    try:
        with open(_card_path(program), "r", encoding="utf-8") as fh:
            flops = json.load(fh).get("flops")
        flops = float(flops)
        return flops if flops > 0 else None
    except (OSError, ValueError, TypeError):
        return None


def resolve_budget_bytes(program: str) -> Optional[int]:
    """The AX008 ``peak_live_bytes`` ceiling for ``program`` from
    ``tools/graftaudit/budgets.json`` (or ``DL4J_TPU_BUDGETS``)."""
    path = os.environ.get("DL4J_TPU_BUDGETS") or os.path.join(
        _REPO_ROOT, "tools", "graftaudit", "budgets.json")
    try:
        with open(path, "r", encoding="utf-8") as fh:
            row = json.load(fh)["programs"][program]
        b = int(row["peak_live_bytes"])
        return b if b > 0 else None
    except (OSError, ValueError, KeyError, TypeError):
        return None


def peak_device_flops() -> Optional[float]:
    """Aggregate peak FLOP/s across local devices: ``DL4J_TPU_PEAK_FLOPS``
    (already aggregate) wins; else the per-chip table for known TPU
    kinds x device count; else None — MFU is withheld, never faked."""
    env = os.environ.get("DL4J_TPU_PEAK_FLOPS")
    if env:
        try:
            peak = float(env)
            return peak if peak > 0 else None
        except ValueError:
            return None
    try:
        import jax
        devices = jax.devices()
    except Exception:
        return None
    kind = str(getattr(devices[0], "device_kind", "") or "")
    for prefix, peak in _PEAK_FLOPS_BY_KIND:
        if kind.startswith(prefix):
            return peak * len(devices)
    return None


def live_device_bytes() -> Optional[int]:
    """Sum of live device-array bytes (the observed-watermark sample
    taken at fences); None when the runtime can't say."""
    try:
        import jax
        arrays = jax.live_arrays()
    except Exception:
        return None
    total = 0
    for a in arrays:
        try:
            total += int(a.nbytes)
        except Exception:
            pass   # deleted/donated buffers race the walk; skip them
    return total


class StepProfiler:
    """Per-step phase attribution for one fit/serve loop.

    Hot-path protocol (``nn/_common.fit_batches`` drives it; every call
    is a couple of ``perf_counter`` reads and float math — no allocation,
    no locks, no device access on unsampled steps)::

        prof.begin(t_step, etl_s)      # loop's existing step-start read
        prof.mark("h2d", dt)           # inner slices: placed, finish_step
        prof.mark("listener", dt)
        prof.dispatched(loss)          # async dispatch returned; maybe
                                       #   fence (sampled): device slice,
                                       #   live bytes, MFU
        prof.lap("forensics")          # bookkeeping laps
        prof.lap("checkpoint")
        prof.end(iteration, compile_step)

    Step records buffer as raw tuples and drain into the FlightRecorder
    ``profile`` channel every ``FLUSH_EVERY`` steps (the
    ``_StepForensics`` amortization pattern); ``flush()`` in the loop's
    ``finally`` guarantees no step is lost to an exception."""

    FLUSH_EVERY = 16
    __slots__ = ("program", "enabled", "sample_every", "ring", "fences",
                 "steps", "dispatch_depth", "max_depth",
                 "live_bytes_watermark", "card_flops", "budget_bytes",
                 "peak_flops", "last_mfu", "last_achieved_flops",
                 "_registry", "_monitor", "_wall0", "_buf", "_t0", "_last",
                 "_etl", "_h2d", "_listener", "_dispatch", "_device",
                 "_forensics", "_checkpoint", "_sampled", "_drained_wait",
                 "_live", "_ratio", "_mfu", "_ach")

    def __init__(self, program: str = "train_step", *,
                 sample_every: Optional[int] = None,
                 registry: Optional[MetricsRegistry] = None,
                 recorder=None, monitor=None):
        self.program = program
        self.enabled = True
        self.sample_every = max(1, int(sample_every)) \
            if sample_every is not None else _default_sample_every()
        rec = recorder if recorder is not None else get_flight_recorder()
        self.ring = rec.channel(CHANNEL) \
            if (rec is not None and rec.enabled) else None
        self._registry = registry
        self._monitor = monitor
        # cold, once per fit: committed card/budget lookups + roofline
        self.card_flops = resolve_card_flops(program)
        self.budget_bytes = resolve_budget_bytes(program)
        self.peak_flops = peak_device_flops() if self.card_flops else None
        self.fences = 0
        self.steps = 0
        self.dispatch_depth = 0
        self.max_depth = 0
        self.live_bytes_watermark = 0
        self.last_mfu: Optional[float] = None
        self.last_achieved_flops: Optional[float] = None
        # record timestamps derive from the monotonic reads the loop
        # already takes (the _StepForensics wall0 trick)
        self._wall0 = wall_s() - monotonic_s()
        self._buf: list = []
        self._t0 = self._last = 0.0
        self._etl = self._h2d = self._listener = 0.0
        self._dispatch = self._forensics = self._checkpoint = 0.0
        self._device: Optional[float] = None
        self._sampled = False
        self._drained_wait = 0.0
        self._live: Optional[int] = None
        self._ratio: Optional[float] = None
        self._mfu: Optional[float] = None
        self._ach: Optional[float] = None

    def _reg(self) -> MetricsRegistry:
        return self._registry if self._registry is not None \
            else default_registry()

    # ------------------------------------------------------ step protocol
    def begin(self, t0: float, etl_s: float = 0.0) -> None:
        """Open a step at the loop's own step-start monotonic read;
        ``etl_s`` is the already-measured time blocked on the pipeline
        *before* ``t0`` (the step record's window starts at etl start)."""
        self._t0 = self._last = t0
        self._etl = etl_s if etl_s > 0.0 else 0.0
        self._h2d = self._listener = 0.0
        self._dispatch = self._forensics = self._checkpoint = 0.0
        self._device = None
        self._sampled = False
        self._drained_wait = 0.0

    def mark(self, phase: str, seconds: float) -> None:
        """Credit an inner slice measured by the step body (h2d device
        placement, the listener loop) — subtracted from the enclosing
        dispatch window so nothing is double-counted."""
        if phase == "h2d":
            self._h2d += seconds
        elif phase == "listener":
            self._listener += seconds

    def dispatched(self, handle=None, window=None) -> None:
        """The async step dispatch returned.  Every ``sample_every``-th
        step additionally fences on ``handle`` to measure the device
        slice honestly (the ONLY profiler-added sync; counted).

        ``window``: the fit loop's bounded :class:`~..nn.dispatch.
        DispatchWindow` (or None).  A sampled fence first drains it,
        attributing each drained step's device slice individually by
        completion spacing — without this, the device time of steps still
        in flight would be billed to the fenced step's slice."""
        now = monotonic_s()
        self._dispatch = now - self._last - self._h2d - self._listener
        self._last = now
        self.steps += 1
        depth = self.dispatch_depth + 1
        self.dispatch_depth = depth
        if depth > self.max_depth:
            self.max_depth = depth
        if handle is not None and self.steps % self.sample_every == 0:
            with get_tracer().span("dl4j.profiler_fence"):
                self._fence(handle, now, window)

    def drained(self, k: int = 1) -> None:
        """The dispatch window materialized ``k`` in-flight steps: the
        pipeline shortened — keep the depth gauge tracking real window
        occupancy (steady state: ``max_depth`` == configured depth)."""
        d = self.dispatch_depth - k
        self.dispatch_depth = d if d > 0 else 0

    def lap(self, phase: str) -> None:
        """Close a bookkeeping slice (forensics / checkpoint) at now."""
        now = monotonic_s()
        if phase == "forensics":
            self._forensics = now - self._last
        elif phase == "checkpoint":
            self._checkpoint = now - self._last
        self._last = now

    def end(self, iteration: int, compile_step: bool = False) -> None:
        """Seal the step record (wall = etl + everything since begin).
        A LIST, not a tuple: a later pipeline-aware fence may patch the
        device slice in once the step's in-flight token drains."""
        # the fence's wait on EARLIER steps' in-flight tokens is billed
        # to those steps' records (_patch_device), so it is excluded from
        # this step's wall — the coverage contract (phase sum == wall on
        # sampled steps) holds at every dispatch depth, nothing is
        # counted twice
        wall = self._etl + (monotonic_s() - self._t0) - self._drained_wait
        self._buf.append([
            self._wall0 + self._t0 - self._etl, iteration, wall,
            self._etl, self._h2d, self._dispatch, self._device,
            self._listener, self._forensics, self._checkpoint,
            self._sampled, compile_step, self.dispatch_depth,
            self._live, self._ratio, self._mfu, self._ach])
        if len(self._buf) >= self.FLUSH_EVERY:
            self.flush()

    def materialized(self) -> None:
        """The caller just forced a host sync outside the profiler's own
        fences (epoch-end score float, a monitor's same-step check): the
        dispatch pipeline is drained — reset the depth baseline."""
        self.dispatch_depth = 0

    # ------------------------------------------------- fence (cold, 1/N)
    def _patch_device(self, iteration: int, seconds: float) -> None:
        """Attribute a drained in-flight step's device slice to ITS OWN
        buffered record (found by iteration; the record may already have
        flushed — a miss just leaves that slice unattributed, never
        mis-billed).  A fence-measured device value is never overwritten."""
        for rec in reversed(self._buf):
            if rec[1] == iteration:
                if rec[6] is None:
                    rec[6] = seconds
                return

    def _fence(self, handle, t_disp: float, window=None) -> None:
        import jax
        # pipeline-aware: drain the bounded window FIRST, attributing each
        # drained step's device slice by completion spacing, so the fenced
        # step's slice below is its own marginal device time — not the
        # queued tail of every step still in flight
        t_prev = t_disp
        if window is not None and len(window):
            for iteration, t_done in window.drain_timed():
                self._patch_device(iteration, t_done - t_prev)
                t_prev = t_done
            self._drained_wait = t_prev - t_disp
        jax.block_until_ready(handle)
        now = monotonic_s()
        device = now - t_prev
        self._device = device
        self._last = now
        self._sampled = True
        self.fences += 1
        if window is None:
            # no bounded window feeding drained(): the fence is the only
            # materialization point, so it resets the occupancy itself
            self.dispatch_depth = 0
        # with a window, the books already balance: the drain above
        # retired every EARLIER step's slot via drained(), and the
        # fenced step's own slot — counted by its dispatched() — is
        # retired by its own pop when the loop pushes its token.  A
        # hard reset here would make that pop a double decrement and
        # pin the steady-state gauge at depth-1 instead of the
        # configured depth.
        live = live_device_bytes()
        self._live = live
        if live is not None and live > self.live_bytes_watermark:
            self.live_bytes_watermark = live
        ratio = None
        if self.budget_bytes and self.live_bytes_watermark:
            ratio = self.live_bytes_watermark / self.budget_bytes
        self._ratio = ratio
        achieved = mfu = None
        if self.card_flops and device > 0:
            achieved = self.card_flops / device
            self.last_achieved_flops = achieved
            if self.peak_flops:
                mfu = achieved / self.peak_flops
                self.last_mfu = mfu
        self._ach, self._mfu = achieved, mfu
        if mfu is not None:
            mon = self._monitor
            if mon is None:
                from .health import get_health_monitor
                mon = get_health_monitor()
            if mon is not None:
                mon.observe_mfu(mfu, program=self.program, step=self.steps)
        reg = self._reg()
        if reg.enabled:
            p = self.program
            reg.counter("stepprof_fences_total",
                        "Sampled block_until_ready fences taken by the "
                        "step profiler", ("program",)).labels(p).inc()
            reg.gauge("training_dispatch_depth",
                      "Async dispatches in flight between materialization "
                      "points (max over the last sample window)"
                      ).set(self.max_depth)
            self.max_depth = 0
            if achieved is not None:
                reg.gauge("training_achieved_flops",
                          "Achieved FLOP/s of the sampled device slice "
                          "(card flops / fenced device time)",
                          ("program",)).labels(p).set(achieved)
            if mfu is not None:
                reg.gauge("training_mfu",
                          "Model FLOP/s utilization: achieved over peak "
                          "device FLOP/s", ("program",)).labels(p).set(mfu)
            if live is not None:
                reg.gauge("device_live_bytes",
                          "Live device bytes sampled at the last profiler "
                          "fence").set(live)
            if ratio is not None:
                reg.gauge("device_live_bytes_budget_ratio",
                          "Observed live-bytes watermark over the AX008 "
                          "peak_live_bytes budget",
                          ("program",)).labels(p).set(ratio)

    # ------------------------------------------------------- flush (cold)
    def flush(self) -> None:
        """Drain buffered steps into the recorder's ``profile`` ring."""
        buf = self._buf
        if not buf:
            return
        self._buf = []
        ring = self.ring
        if ring is None:
            return
        prog = self.program
        for (ts, it, wall, etl, h2d, disp, dev, lst, fore, ckpt,
             sampled, comp, depth, live, ratio, mfu, ach) in buf:
            rec = {"ts": ts, "type": "step", "program": prog,
                   "iteration": it, "wall_s": round(wall, 7),
                   "sampled": sampled, "compile": comp, "depth": depth,
                   # a device slice on an UNSAMPLED record came from a
                   # later fence draining this step's in-flight token —
                   # honest timing, but attributed after the fact
                   **({"drained": True}
                      if (not sampled and dev is not None) else {}),
                   "phases": {
                       "etl_wait": round(etl, 7),
                       "h2d": round(h2d, 7),
                       "dispatch": round(disp, 7),
                       "device": None if dev is None else round(dev, 7),
                       "listener": round(lst, 7),
                       "forensics": round(fore, 7),
                       "checkpoint": round(ckpt, 7)}}
            if live is not None:
                rec["live_bytes"] = live
            if ratio is not None:
                rec["budget_ratio"] = round(ratio, 4)
            if mfu is not None:
                rec["mfu"] = mfu
            if ach is not None:
                rec["achieved_flops"] = ach
            ring.append(rec)


def step_profiler_for(program: str, **kwargs) -> Optional[StepProfiler]:
    """The fit loop's entry point: a fresh profiler, or None when
    ``DL4J_TPU_STEPPROF=0`` — and never an exception, because telemetry
    must not break training.  ``DL4J_TPU_STEPPROF_PROGRAM`` overrides
    the label (mapping a run onto its canonical card/budget entry)."""
    if not stepprof_enabled():
        return None
    program = os.environ.get("DL4J_TPU_STEPPROF_PROGRAM", program)
    try:
        return StepProfiler(program, **kwargs)
    except Exception:
        return None


def record_slices(kind: str, *, recorder=None, **fields: Any) -> None:
    """Serve/decode-side contribution to the ``profile`` channel: one
    record per batch/step with its ``*_s`` slices (``queue_wait_s``,
    ``batch_form_s``, ``execute_s``).  A cheap guarded single
    ``record()`` — the serving loops call this once per *batch*, not
    per request."""
    if not stepprof_enabled():
        return
    rec = recorder if recorder is not None else get_flight_recorder()
    if rec is None or not rec.enabled:
        return
    rec.record(CHANNEL, kind, **fields)


# ------------------------------------------------------------- summaries
def phase_summary(records: List[Dict[str, Any]]) -> Dict[str, Any]:
    """Aggregate ``profile``-channel step records into the text-table /
    ``/debug/profile`` summary: mean seconds + share per phase over
    steady (non-compile) steps, and the sampled-step coverage (phase
    sum over measured wall — the honesty check)."""
    steps = [r for r in records if r.get("type") == "step"
             and not r.get("compile")]
    out: Dict[str, Any] = {"steps": len(steps)}
    if not steps:
        return out
    wall = sum(r.get("wall_s", 0.0) for r in steps)
    phases: Dict[str, float] = {}
    for r in steps:
        for name, v in (r.get("phases") or {}).items():
            if v:
                phases[name] = phases.get(name, 0.0) + v
    n = len(steps)
    out["mean_wall_s"] = wall / n
    out["mean_phase_s"] = {k: phases.get(k, 0.0) / n for k in PHASES}
    out["phase_share"] = {k: (phases.get(k, 0.0) / wall if wall else 0.0)
                          for k in PHASES}
    sampled = [r for r in steps if r.get("sampled")]
    out["sampled_steps"] = len(sampled)
    if sampled:
        cov = [sum(v for v in (r.get("phases") or {}).values() if v)
               / r["wall_s"] for r in sampled if r.get("wall_s")]
        if cov:
            out["sampled_coverage"] = sum(cov) / len(cov)
        mfus = [r["mfu"] for r in sampled if r.get("mfu") is not None]
        if mfus:
            out["mean_mfu"] = sum(mfus) / len(mfus)
        ratios = [r["budget_ratio"] for r in sampled
                  if r.get("budget_ratio") is not None]
        if ratios:
            out["max_budget_ratio"] = max(ratios)
    return out


# ----------------------------------------------------------- Chrome trace
_TRACK_HOST, _TRACK_DEVICE = 1, 2


def chrome_trace(records: List[Dict[str, Any]]) -> Dict[str, Any]:
    """Build a Chrome-trace (catapult JSON, ``chrome://tracing`` /
    Perfetto loadable) document from ``profile``-channel records.  Train
    steps lay their host phases sequentially on a host track with the
    sampled device slice on its own track (it genuinely overlaps
    nothing — the fence serialized it); serve/decode records place
    their ``*_s`` slices on per-subsystem tracks."""
    events: List[Dict[str, Any]] = []
    pids: Dict[int, str] = {}
    for r in records:
        kind = r.get("type")
        ts = float(r.get("ts", 0.0)) * 1e6   # catapult wants microseconds
        if kind == "step":
            pid = 1
            pids[pid] = f"train [{r.get('program', 'train_step')}]"
            args = {"iteration": r.get("iteration"),
                    "depth": r.get("depth"),
                    "sampled": bool(r.get("sampled"))}
            for opt in ("mfu", "live_bytes", "budget_ratio"):
                if r.get(opt) is not None:
                    args[opt] = r[opt]
            cursor = ts
            ph = r.get("phases") or {}
            for name in ("etl_wait", "h2d", "dispatch"):
                d = ph.get(name) or 0.0
                if d > 0:
                    events.append({"name": name, "cat": "train", "ph": "X",
                                   "pid": pid, "tid": _TRACK_HOST,
                                   "ts": cursor, "dur": d * 1e6,
                                   "args": args})
                cursor += d * 1e6
            dev = ph.get("device")
            if dev:
                events.append({"name": "device", "cat": "train", "ph": "X",
                               "pid": pid, "tid": _TRACK_DEVICE,
                               "ts": cursor, "dur": dev * 1e6,
                               "args": args})
                cursor += dev * 1e6
            for name in ("listener", "forensics", "checkpoint"):
                d = ph.get(name) or 0.0
                if d > 0:
                    events.append({"name": name, "cat": "train", "ph": "X",
                                   "pid": pid, "tid": _TRACK_HOST,
                                   "ts": cursor, "dur": d * 1e6,
                                   "args": args})
                cursor += d * 1e6
        elif kind in ("serve", "decode", "prefill"):
            pid = 2 if kind == "serve" else 3
            pids[pid] = "serving" if kind == "serve" else "generation"
            cursor = ts
            for key in SLICE_KEYS:
                d = r.get(key) or 0.0
                if d > 0:
                    events.append({"name": f"{kind}:{key[:-2]}",
                                   "cat": kind, "ph": "X", "pid": pid,
                                   "tid": _TRACK_HOST, "ts": cursor,
                                   "dur": d * 1e6})
                cursor += d * 1e6
    meta = [{"name": "process_name", "ph": "M", "pid": pid, "tid": 0,
             "args": {"name": name}} for pid, name in sorted(pids.items())]
    return {"traceEvents": meta + events, "displayTimeUnit": "ms",
            "otherData": {"format": TRACE_FORMAT, "records": len(records)}}


def _seal_trace(doc: Dict[str, Any]) -> bytes:
    """Stamp a sha256 over the canonical traceEvents into the document
    (extra top-level keys are legal catapult metadata, so the artifact
    stays chrome://tracing-loadable AND checksum-verifiable)."""
    canonical = json.dumps(doc["traceEvents"], sort_keys=True,
                           separators=(",", ":")).encode("utf-8")
    doc = dict(doc)
    doc["sha256"] = hashlib.sha256(canonical).hexdigest()
    return json.dumps(doc).encode("utf-8")


def dump_chrome_trace(directory: Optional[str] = None,
                      records: Optional[List[Dict[str, Any]]] = None,
                      recorder=None) -> str:
    """Commit the current ``profile`` window as an atomic checksummed
    Chrome-trace artifact; returns the path written."""
    rec = recorder if recorder is not None else get_flight_recorder()
    if records is None:
        records = rec.channel(CHANNEL).items() if rec is not None else []
    if directory is None and rec is not None:
        directory = rec._resolve_directory(None)
    directory = directory or os.getcwd()
    blob = _seal_trace(chrome_trace(records))
    path = os.path.join(
        directory, f"{TRACE_PREFIX}{os.getpid()}-{int(wall_s())}.json")
    from ..faulttolerance.atomic import atomic_write_bytes
    os.makedirs(directory, exist_ok=True)
    atomic_write_bytes(path, blob)
    return path


def load_chrome_trace(path: str, verify: bool = True) -> Dict[str, Any]:
    """Read a stepprof Chrome-trace artifact; with ``verify`` (default)
    the embedded checksum is recomputed over the canonical traceEvents —
    truncation or bit rot raises ``ValueError``, never loads quietly."""
    with open(path, "r", encoding="utf-8") as fh:
        doc = json.load(fh)
    if "traceEvents" not in doc or "sha256" not in doc:
        raise ValueError(f"{path}: not a stepprof trace artifact")
    if verify:
        canonical = json.dumps(doc["traceEvents"], sort_keys=True,
                               separators=(",", ":")).encode("utf-8")
        want, got = doc["sha256"], hashlib.sha256(canonical).hexdigest()
        if want != got:
            raise ValueError(
                f"{path}: checksum mismatch (artifact corrupt): recorded "
                f"{want[:12]}…, recomputed {got[:12]}…")
    return doc
