"""GenerationEngine: iteration-level continuous batching over the slot
ring.

One decode thread owns the cache and runs a boundary loop; every loop
iteration is one *step boundary*, where all scheduling happens:

1. **Weight sync** — if the serving slot was hot-swapped since the last
   step, every active sequence *migrates*: its full history (prompt +
   tokens so far) re-prefills under the new weights into the same slot,
   so no sequence ever mixes two weight versions inside one KV cache —
   and because migration is just "prefill with a longer prompt", it
   costs zero extra programs.  Reported versions never move backwards.
2. **Joins** — queued requests prefill into free slots (one bucketed
   prefill program call each, first token sampled inside the program)
   and are part of the very next decode batch.  A late request joins a
   RUNNING batch; nothing restarts.
3. **Decode** — one fixed-shape program call advances every active slot
   by one token (inactive slots compute mask-dead garbage — the price of
   a single compiled shape).  Finished sequences (EOS / token budget /
   client gone) vacate their slot at this boundary; the freed slot is
   eligible for a join on the next iteration.

Determinism: sampling keys are ``(request seed, token index)`` — a
request's token stream is bit-identical whether it runs alone or joins a
busy batch (row-independent stacks only; the engine refuses MoE).

Observability: ``generation_active_slots`` / ``generation_tokens_total``
/ ``decode_step_seconds`` / ``generation_prefill_seconds`` metrics,
time-to-first-token and inter-token latency fed to the
:class:`~..observability.health.HealthMonitor` (p99 targets in
``HealthConfig``), a ``decode`` flight-recorder channel, and a
forensic dump with the slot occupancy trail on any decode-step
exception.  Admission: a full join queue sheds with
``serving_shed_total{reason="no_slots"}`` (429 + Retry-After);
readiness = model installed AND join queue below its limit AND the
decode inter-token p99 inside its SLO.
"""
from __future__ import annotations

import logging
import queue
import threading
from concurrent.futures import Future
from concurrent.futures import TimeoutError as FuturesTimeout
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional, Sequence

import jax
import numpy as np

from ..data.shapes import suffix_prefill_buckets
from ..observability import clock
from ..observability.health import get_health_monitor
from ..observability.quantiles import LatencyWindow
from ..observability.recorder import get_flight_recorder
from ..observability.registry import default_registry
from ..parallel.inference import InvalidInputError
from .cache import PagedKV

__all__ = ["GenerationConfig", "GenerationEngine", "GenerationResult",
           "StaticSlotSource"]

log = logging.getLogger("deeplearning4j_tpu.generation")

# decode-step latencies: sub-ms CPU toy steps to multi-second TPU
# dispatch tails
_STEP_BUCKETS = (0.0005, 0.001, 0.0025, 0.005, 0.01, 0.025, 0.05, 0.1,
                 0.25, 0.5, 1.0, 2.5, 10.0)

_UNSET = object()


@dataclass(frozen=True)
class GenerationConfig:
    """Engine shape + policy.  ``max_slots`` and ``max_seq`` are the two
    compiled-shape axes (slot batch, cache capacity); everything else is
    data or host policy and never costs a compile."""

    max_slots: int = 8
    max_seq: int = 256                 # per-slot KV capacity (prompt+gen)
    prefill_ladder: Optional[Sequence[int]] = None
    queue_limit: int = 64              # join-queue bound (shed past it)
    default_max_new_tokens: int = 64
    eos_id: Optional[int] = None       # default per-request EOS
    retry_after_s: float = 1.0
    itl_slo_ms: Optional[float] = None  # decode SLO for readiness
    slo_window: int = 256
    slo_min_samples: int = 16
    # paged-KV knobs (cache.PagedKV): tokens per physical block, pool
    # size (None = full provision: max_slots * ceil(max_seq/block_size)
    # + trash — size it DOWN to the expected actual-length workload to
    # realize the memory win), and the prefix-sharing registry toggle.
    block_size: int = 16
    n_blocks: Optional[int] = None
    prefix_sharing: bool = True


@dataclass
class GenerationResult:
    """One finished request: the generated tokens, the slot version that
    produced each token (hot-swap observability), and why it stopped."""

    tokens: List[int]
    versions: List[int]
    finish: str                        # eos | length | cancelled
    request_id: str
    prompt_len: int = 0


class _GenRequest:
    """Internal per-request state; the public faces are the Future
    (blocking ``generate``) and the bounded event queue (streaming)."""

    __slots__ = ("id", "prompt", "max_new_tokens", "temperature", "top_k",
                 "top_p", "seed", "eos_id", "out_tokens", "versions",
                 "future", "events", "cancelled", "slot",
                 "t_submit", "t_first", "t_last")

    def __init__(self, rid: str, prompt: List[int], max_new_tokens: int,
                 temperature: float, top_k: int, top_p: float, seed: int,
                 eos_id: Optional[int]):
        self.id = rid
        self.prompt = prompt
        self.max_new_tokens = max_new_tokens
        self.temperature = float(temperature)
        self.top_k = int(top_k)
        self.top_p = float(top_p)
        self.seed = int(seed) & 0xFFFFFFFF
        self.eos_id = eos_id
        self.out_tokens: List[int] = []
        self.versions: List[int] = []
        self.future: Future = Future()
        # one event per token + done/error sentinels; bounded so a wedged
        # stream consumer can never grow host memory (the producer drops,
        # the blocking future still completes)
        self.events: "queue.Queue[dict]" = queue.Queue(
            maxsize=max_new_tokens + 2)
        self.cancelled = threading.Event()
        self.slot: Optional[int] = None
        self.t_submit = clock.monotonic_s()
        self.t_first: Optional[float] = None
        self.t_last: Optional[float] = None

    def history(self) -> List[int]:
        """Prompt + everything generated so far — what a weight migration
        re-prefills."""
        return self.prompt + self.out_tokens

    def export_state(self) -> dict:
        """Host-only session snapshot a peer engine can
        :meth:`GenerationEngine.import_session`: because sampling keys
        are ``(seed, token_index)``, history + sampling knobs ARE the
        complete decode state — no device KV ever crosses replicas."""
        return {"request_id": self.id, "prompt": list(self.prompt),
                "tokens": list(self.out_tokens),
                "versions": list(self.versions),
                "max_new_tokens": self.max_new_tokens,
                "temperature": self.temperature, "top_k": self.top_k,
                "top_p": self.top_p, "seed": self.seed,
                "eos_id": self.eos_id}

    def push_event(self, ev: dict) -> None:
        try:
            self.events.put_nowait(ev)
        except queue.Full:      # slow stream consumer: drop, never block
            pass

    def debug_id(self) -> str:
        return (f"{self.id}[prompt={len(self.prompt)},"
                f"out={len(self.out_tokens)}/{self.max_new_tokens}]")


class StaticSlotSource:
    """Slot provider for standalone engines (no ServingEngine): wraps a
    model as an immutable versioned slot; :meth:`swap` installs a new
    model under the next version — the same monotonic-version contract
    ``ServingEngine.hot_swap`` gives."""

    class _Slot:
        __slots__ = ("model", "version")

        def __init__(self, model, version: int):
            self.model = model
            self.version = version

    def __init__(self, model):
        self._lock = threading.Lock()
        self._slot = self._Slot(model, 1)

    def __call__(self):
        with self._lock:
            return self._slot

    def swap(self, model) -> int:
        with self._lock:
            self._slot = self._Slot(model, self._slot.version + 1)
            return self._slot.version


class GenerationEngine:
    """Continuous-batching autoregressive decode over one served model.

    ``slot_source`` is a zero-argument callable returning the current
    serving slot (an object with ``.model`` and ``.version``) or None —
    ``ServingEngine`` passes ``lambda: self.slot`` so generation follows
    its hot-swap/promotion lifecycle; standalone use wraps a model in
    :class:`StaticSlotSource` (or :meth:`for_model`).
    """

    def __init__(self, slot_source: Callable[[], Any],
                 config: Optional[GenerationConfig] = None, *,
                 registry=None, health=None, start: bool = True):
        self.config = config or GenerationConfig()
        if self.config.max_slots < 1:
            raise ValueError("max_slots must be >= 1")
        if self.config.default_max_new_tokens < 1:
            raise ValueError("default_max_new_tokens must be >= 1")
        self._slot_source = slot_source
        self._registry = registry
        self._health = health
        # suffix ladder: shared-prefix admissions prefill only their
        # unshared tail, so short suffixes need small buckets (floor
        # min(8, block_size)); the top bucket stays max_seq so
        # migration re-prefill of a full history always fits
        self.buckets = suffix_prefill_buckets(
            self.config.max_seq, self.config.block_size,
            self.config.prefill_ladder)
        self.ring: Optional[PagedKV] = None
        self._ring_sig: Optional[str] = None
        self._pending: "queue.Queue[_GenRequest]" = queue.Queue(
            maxsize=self.config.queue_limit)
        self._serving_version: Optional[int] = None
        self._warm = False
        self._stats_lock = threading.Lock()
        self._steady_recompiles = 0
        self._tokens_generated = 0
        self._decode_steps = 0
        self._decode_errors = 0
        self._tick_failures = 0
        self._req_counter = 0
        self._ttft_w = LatencyWindow(self.config.slo_window)
        self._itl_w = LatencyWindow(self.config.slo_window)
        self._submit_lock = threading.Lock()
        self._step_lock = threading.Lock()
        self._shutdown = threading.Event()
        self._wake = threading.Event()
        self._thread = threading.Thread(
            target=self._loop, daemon=True, name="dl4j-generate-decode")
        if start:
            self._thread.start()

    @classmethod
    def for_model(cls, model, config: Optional[GenerationConfig] = None,
                  **kw) -> "GenerationEngine":
        return cls(StaticSlotSource(model), config, **kw)

    # ------------------------------------------------------------- plumbing
    def _reg(self):
        return self._registry if self._registry is not None \
            else default_registry()

    def _mon(self):
        return self._health if self._health is not None \
            else get_health_monitor()

    @property
    def queue_depth(self) -> int:
        """Join-queue depth — the fleet router's cheap decode-load
        signal (``status()`` is the full payload; routing needs one
        integer)."""
        return self._pending.qsize()

    @property
    def steady_recompiles(self) -> int:
        with self._stats_lock:
            return self._steady_recompiles

    @property
    def tokens_generated(self) -> int:
        with self._stats_lock:
            return self._tokens_generated

    @property
    def decode_steps(self) -> int:
        with self._stats_lock:
            return self._decode_steps

    def _note_trace(self, fn) -> None:
        """Post-warmup traces are steady-state recompiles — the alarm the
        two-program design must keep at zero."""
        if not (self._warm and bool(getattr(fn, "last_call_traced",
                                            False))):
            return
        with self._stats_lock:
            self._steady_recompiles += 1
        reg = self._reg()
        if reg.enabled:
            reg.counter("serving_steady_recompiles_total",
                        "XLA traces observed after warmup — should stay 0 "
                        "(a novel shape escaped the bucket ladder)").inc()

    def _shed(self, reason: str, tenant: str = "-") -> None:
        reg = self._reg()
        if reg.enabled:
            reg.counter("serving_shed_total",
                        "Requests shed by admission control",
                        ("reason", "tenant")).labels(reason, tenant).inc()
        mon = self._mon()
        if mon is not None:
            mon.observe_request(shed=True)

    # ----------------------------------------------------------- model/ring
    def _model_of(self, slot_obj):
        model = getattr(slot_obj, "model", None)
        if model is None or not hasattr(model, "_get_jitted"):
            raise TypeError(
                f"{type(slot_obj).__name__}.model is not generatable: the "
                "decode engine needs a framework network (_get_jitted)")
        return model

    def _ensure_ring(self, model):
        """(Re)build the slot cache for the served topology.  A
        same-topology hot-swap keeps the ring (weights changed, shapes
        did not); a different topology rebuilds it — active sequences
        were already migrated or failed by then."""
        sig = model._topology_sig()
        if self.ring is None or self._ring_sig != sig:
            if getattr(model.conf, "looped", lambda: None)():
                raise ValueError(
                    "generation cannot run through a looped range: a K/V "
                    "cache a pass, and the exit by threshold while "
                    "decoding, are not written")
            for lc in model.conf.layers:
                if getattr(lc, "AUX_LOSS", False):
                    raise ValueError(
                        "generation requires a row-independent stack: an "
                        "AUX_LOSS (MoE) layer couples rows through expert "
                        "capacity, breaking per-slot determinism")
            if not any(getattr(lc, "HAS_CARRY", False)
                       for lc in model.conf.layers):
                raise ValueError(
                    "generation needs at least one carry-capable layer "
                    "(attention/transformer/RNN) — a pure feed-forward "
                    "stack has nothing to cache")
            self.ring = self._new_ring(model.conf)
            self._ring_sig = sig
        return self.ring

    def _new_ring(self, conf):
        return PagedKV(conf, self.config.max_slots,
                       self.config.max_seq,
                       block_size=self.config.block_size,
                       n_blocks=self.config.n_blocks,
                       prefix_sharing=self.config.prefix_sharing)

    # -------------------------------------------------------------- warmup
    def warmup(self) -> int:
        """Compile the whole steady-state program set — one prefill per
        prompt bucket plus the single decode step — so no request ever
        pays a compile; afterwards any further trace increments
        ``steady_recompiles`` (and the shared
        ``serving_steady_recompiles_total``).  Returns the number of
        programs warmed."""
        slot_obj = self._slot_source()
        if slot_obj is None:
            raise RuntimeError("no model installed to warm")
        model = self._model_of(slot_obj)
        with self._step_lock:
            ring = self._ensure_ring(model)
            # a re-warm while sequences are decoding must not write into
            # the LIVE cache (the warm prefill would overwrite slot 0's
            # KV/pos) — trace against a scratch ring instead: identical
            # shapes, so the compiles land in the same trace cache
            live = ring.active_slots > 0
            caches = self._new_ring(model.conf).caches if live \
                else ring.caches
            warmed = 0
            S = self.config.max_slots
            # warm every suffix bucket against an all-trash table
            # (writes land in block 0, mask-dead) + the one decode
            pf = model._get_jitted("paged_prefill")
            nb = ring.blocks_per_slot
            trow = np.zeros((nb,), np.int32)
            for b in self.buckets:
                toks = np.zeros((1, b), np.int32)
                mask = np.ones((1, b), np.float32)
                _, caches = pf(
                    model.params, model.state, toks, mask, caches,
                    trow, np.int32(0), np.int32(0), np.int32(b),
                    np.int32(0), np.int32(0),
                    np.zeros((2,), np.uint32), np.float32(0.0),
                    np.int32(0), np.float32(1.0))
                warmed += 1
            dec = model._get_jitted("paged_decode")
            out, caches = dec(
                model.params, model.state, np.zeros((S,), np.int32),
                caches, np.zeros((S, nb), np.int32),
                np.zeros((S,), np.int32), np.zeros((S, 2), np.uint32),
                np.zeros((S,), np.float32), np.zeros((S,), np.int32),
                np.ones((S,), np.float32))
            np.asarray(out)      # block until the compile fully lands
            warmed += 1
            if not live:
                # donation consumed the originals: re-home the warmed
                # buffers; a live ring keeps its own (untouched) caches
                ring.caches = caches
            if self._serving_version is None:
                # first warm only: a later version change must go
                # through the tick's migration pass, never be absorbed
                self._serving_version = slot_obj.version
            self._warm = True
        return warmed

    # ----------------------------------------------------------- public API
    def submit(self, tokens, *, max_new_tokens: Optional[int] = None,
               temperature: float = 0.0, top_k: int = 0,
               top_p: float = 1.0, seed: Optional[int] = None,
               eos_id=_UNSET) -> _GenRequest:
        """Admit one generation request; returns the live request handle
        (``.future`` for the blocking result, ``.events`` for the
        per-token stream).  Raises :class:`~..serving.engine.ShedError`
        when admission refuses, :class:`InvalidInputError` on a bad
        prompt/budget."""
        from ..serving.engine import ShedError
        slot_obj = self._slot_source()
        if slot_obj is None:
            self._shed("unready")
            raise ShedError("no model installed", status=503,
                            retry_after_s=self.config.retry_after_s)
        try:
            prompt = [int(t) for t in np.asarray(tokens).reshape(-1)]
        except (TypeError, ValueError) as e:
            # client-shaped garbage is a 400-class error, never a 500
            # that charges the server's failure circuit
            raise InvalidInputError(
                f"prompt must be integer token ids: {e}")
        if not prompt:
            raise InvalidInputError("empty prompt")
        mnt = self.config.default_max_new_tokens \
            if max_new_tokens is None else int(max_new_tokens)
        if mnt < 1:
            raise InvalidInputError(
                f"max_new_tokens must be >= 1, got {mnt}")
        if len(prompt) + mnt > self.config.max_seq:
            raise InvalidInputError(
                f"prompt ({len(prompt)}) + max_new_tokens ({mnt}) exceeds "
                f"the cache capacity max_seq={self.config.max_seq}")
        eos = self.config.eos_id if eos_id is _UNSET else eos_id
        with self._submit_lock:
            if self._shutdown.is_set():
                raise RuntimeError("GenerationEngine shut down")
            self._req_counter += 1
            rid = f"gen-{self._req_counter}"
            if seed is None:
                seed = self._req_counter
            req = _GenRequest(rid, prompt, mnt, temperature, top_k, top_p,
                              seed, eos)
            try:
                self._pending.put_nowait(req)
            except queue.Full:
                # every slot busy AND the join backlog full: shed before
                # the request can queue into a timeout storm
                self._shed("no_slots")
                raise ShedError(
                    f"no free generation slots (queue at "
                    f"{self.config.queue_limit})", status=429,
                    retry_after_s=self.config.retry_after_s)
        self._wake.set()
        return req

    def import_session(self, state: dict) -> _GenRequest:
        """Re-home a session exported from (or mirrored off) another
        engine: builds a request with its generated-so-far tokens
        pre-seeded and enqueues it for ordinary admission — which
        re-prefills the FULL history (the hot-swap migration path,
        cross-replica) and continues the ``(seed, token_index)`` RNG
        schedule at the next index, so the continued stream is
        bit-identical to the one the original replica would have
        produced."""
        from ..serving.engine import ShedError
        try:
            prompt = [int(t) for t in state["prompt"]]
            tokens = [int(t) for t in state.get("tokens", ())]
            mnt = int(state["max_new_tokens"])
            seed = int(state["seed"])
        except (KeyError, TypeError, ValueError) as e:
            raise InvalidInputError(f"malformed session state: {e}")
        if not prompt:
            raise InvalidInputError("empty prompt in imported session")
        if len(tokens) >= mnt:
            raise InvalidInputError(
                f"imported session already finished "
                f"({len(tokens)}/{mnt} tokens)")
        if len(prompt) + mnt > self.config.max_seq:
            raise InvalidInputError(
                f"imported session needs {len(prompt) + mnt} cache rows, "
                f"exceeds max_seq={self.config.max_seq}")
        with self._submit_lock:
            if self._shutdown.is_set():
                raise RuntimeError("GenerationEngine shut down")
            self._req_counter += 1
            rid = state.get("request_id") or f"gen-{self._req_counter}"
            req = _GenRequest(rid, prompt, mnt,
                              state.get("temperature", 0.0),
                              state.get("top_k", 0),
                              state.get("top_p", 1.0), seed,
                              state.get("eos_id"))
            req.out_tokens = tokens
            vers = [int(v) for v in state.get("versions", ())]
            # one version per already-emitted token: a mirror that lost
            # them pads with 0 ("unknown origin version"), never guesses
            req.versions = (vers + [0] * len(tokens))[:len(tokens)]
            try:
                self._pending.put_nowait(req)
            except queue.Full:
                self._shed("no_slots")
                raise ShedError(
                    f"no free generation slots for imported session "
                    f"(queue at {self.config.queue_limit})", status=429,
                    retry_after_s=self.config.retry_after_s)
        self._wake.set()
        return req

    def export_sessions(self) -> List[dict]:
        """Detach every live session (active slots AND the join queue)
        as importable host-only state — the drain/eject half of
        cross-replica migration.  Local handles fail with a marker
        error (no client may silently hang on a drained replica); the
        caller re-homes the states via a peer's
        :meth:`import_session`."""
        states: List[dict] = []
        err = RuntimeError("session exported for cross-replica migration")
        with self._step_lock:
            ring = self.ring
            if ring is not None:
                for slot, req in sorted(ring.occupants().items()):
                    ring.release(slot)
                    ring.note("vacate", slot, req.id, reason="exported")
                    states.append(req.export_state())
                    self._fail(req, err)
            while True:
                try:
                    req = self._pending.get_nowait()
                except queue.Empty:
                    break
                if req.cancelled.is_set():
                    self._finish(req, None, "cancelled")
                    continue
                states.append(req.export_state())
                self._fail(req, err)
        self._set_active_gauge()
        return states

    def generate(self, tokens, timeout: Optional[float] = 60.0,
                 **kw) -> GenerationResult:
        """Submit and block for the finished sequence.  A timeout
        CANCELS the request — the caller is gone, so the slot must not
        keep decoding to the token budget for nobody."""
        req = self.submit(tokens, **kw)
        try:
            return req.future.result(timeout=timeout)
        except FuturesTimeout:
            req.cancelled.set()
            self._wake.set()
            raise

    def stream(self, tokens, timeout: Optional[float] = 60.0, **kw):
        """Submit and yield per-token events as the decode loop emits
        them: ``{"token", "index", "model_version"}`` per step, then one
        ``{"done": True, "finish", "tokens", "model_versions"}`` (or
        ``{"error": ...}``).  Closing the generator early cancels the
        request — its slot vacates at the next step boundary."""
        req = self.submit(tokens, **kw)
        try:
            while True:
                ev = req.events.get(timeout=timeout)
                yield ev
                if ev.get("done") or "error" in ev:
                    return
        finally:
            req.cancelled.set()     # no-op after normal completion
            self._wake.set()

    # --------------------------------------------------------------- status
    def decode_slo_ok(self) -> bool:
        target = self.config.itl_slo_ms
        if target is None:
            return True
        if len(self._itl_w) < self.config.slo_min_samples:
            return True
        p99 = self._itl_w.quantile(0.99)
        return p99 is None or p99 * 1e3 <= target

    def ready(self) -> bool:
        """Generation readiness: model installed AND the join queue below
        its shed limit AND the decode inter-token p99 inside its SLO AND
        the scheduling tick not persistently failing (a wedged slot must
        look red to an orchestrator, not hang clients quietly)."""
        with self._stats_lock:
            wedged = self._tick_failures >= self._TICK_FAILURE_LIMIT
        return (self._slot_source() is not None
                and not wedged
                and self._pending.qsize() < self.config.queue_limit
                and self.decode_slo_ok())

    def status(self) -> dict:
        ring = self.ring
        ttft = self._ttft_w.snapshot()
        itl = self._itl_w.snapshot()
        with self._stats_lock:
            steady = self._steady_recompiles
            tokens = self._tokens_generated
            steps = self._decode_steps
            errors = self._decode_errors
            tick_failures = self._tick_failures
        return {
            "ready": self.ready(),
            "active_slots": 0 if ring is None else ring.active_slots,
            "free_slots": self.config.max_slots if ring is None
            else ring.free_slots,
            "max_slots": self.config.max_slots,
            "max_seq": self.config.max_seq,
            "prefill_buckets": list(self.buckets),
            "queued": self._pending.qsize(),
            "queue_limit": self.config.queue_limit,
            "decode_slo_ok": self.decode_slo_ok(),
            "itl_slo_ms": self.config.itl_slo_ms,
            "ttft_p99_ms": None if ttft["p99"] is None
            else round(ttft["p99"] * 1e3, 3),
            "itl_p99_ms": None if itl["p99"] is None
            else round(itl["p99"] * 1e3, 3),
            "tokens_generated": tokens,
            "decode_steps": steps,
            "decode_errors": errors,
            "tick_failures": tick_failures,
            "steady_recompiles": steady,
            "warm": self._warm,
            "kv_paged": True,
            "kv": (None if ring is None else ring.stats()),
            "cache_bytes": None if ring is None else ring.cache_bytes,
        }

    # ---------------------------------------------------------- decode loop
    # consecutive scheduling-tick failures before the engine declares
    # itself unready and stops hanging the join queue (a decode-step
    # fault is handled INSIDE the tick and never counts here)
    _TICK_FAILURE_LIMIT = 4

    def _loop(self) -> None:
        err_backoff = 0.0
        while not self._shutdown.is_set():
            try:
                worked = self._tick()
            except Exception as e:
                # the loop itself must survive with a growing breather
                # so a persistent fault can't spin the thread hot — but
                # it must not HIDE either: repeated failures flip
                # ready() and fail the queued requests with the cause
                # instead of letting clients hang into timeouts
                log.exception("generation tick failed")
                with self._stats_lock:
                    self._tick_failures += 1
                    failures = self._tick_failures
                if failures >= self._TICK_FAILURE_LIMIT:
                    self._drain_pending(e)
                err_backoff = min(0.25, err_backoff * 2 or 0.01)
                self._shutdown.wait(err_backoff)
                continue
            with self._stats_lock:
                self._tick_failures = 0
            err_backoff = 0.0
            if not worked:
                # fully idle (no occupants, nothing queued): block on
                # the wake event — submit/cancel/shutdown all set it —
                # instead of polling 200x/s for the life of the process
                idle = self._pending.empty() and (
                    self.ring is None or self.ring.active_slots == 0)
                self._wake.wait(None if idle else 0.005)
                self._wake.clear()

    def _tick(self) -> bool:
        slot_obj = self._slot_source()
        if slot_obj is None:
            return False
        with self._step_lock:
            worked = False
            if slot_obj.version != self._serving_version:
                if self._serving_version is None or self.ring is None \
                        or self.ring.active_slots == 0:
                    # nothing to migrate: adopt the version; admission
                    # resolves/validates the model per request, so a
                    # bad slot fails requests instead of wedging ticks
                    if self.ring is not None:
                        # registered prefix blocks hold OLD-version K/V:
                        # a new-version request must never adopt them
                        self.ring.invalidate_shared()
                    self._serving_version = slot_obj.version
                else:
                    # commit the version only AFTER the migration
                    # succeeds: a failure anywhere in the sync leaves it
                    # un-synced, so the next tick retries instead of
                    # decoding the old cache under new weights
                    model = self._model_of(slot_obj)
                    prev = self._serving_version
                    worked = self._migrate(model, slot_obj, prev)
                    self._serving_version = slot_obj.version
            worked = self._admit(slot_obj) or worked
            worked = self._decode_guarded(slot_obj) or worked
        return worked

    def _drain_pending(self, e: Exception) -> None:
        """Fail everything queued with the underlying fault (active
        occupants keep their slots — a later successful tick may still
        migrate them)."""
        while True:
            try:
                req = self._pending.get_nowait()
            except queue.Empty:
                return
            self._fail(req, e)

    def _migrate(self, model, slot_obj, prev: Optional[int]) -> bool:
        """Hot-swap handling at a step boundary: migrate every active
        sequence onto the new weights by re-prefilling its full history
        (the sampled token IS the sequence's next emission — the RNG key
        schedule continues at the same token index), so no sequence ever
        mixes weight versions within its KV cache and reported versions
        never move backwards."""
        old_ring = self.ring
        occupants = {} if old_ring is None else old_ring.occupants()
        if prev is None or not occupants:
            # nothing to migrate — leave the ring (re)build to admission,
            # where a stack-validation failure is attributed to the
            # request it affects instead of wedging the whole tick
            return False
        # the prefix registry holds prev-version K/V — flush it before
        # any re-prefill can publish/adopt under the new one
        old_ring.invalidate_shared()
        ring = self._ensure_ring(model)
        rec = get_flight_recorder()
        for slot, req in sorted(occupants.items()):
            if ring is not old_ring:
                # topology changed: the cache was rebuilt — re-home the
                # sequence into the new ring (same engine config, so a
                # slot is always available for every old occupant)
                old_ring.release(slot)
                slot = ring.acquire(req)
                req.slot = slot
            else:
                # same pool, new weights: drop the slot's stale blocks
                # (occupant stays) — the re-prefill below allocates and
                # writes fresh ones through the ordinary paged path
                ring.reset_slot(slot)
            ring.note("migrate", slot, req.id, pos=len(req.history()),
                      from_version=prev, to_version=slot_obj.version)
            if rec is not None:
                rec.record("decode", "migrate", slot=slot, request=req.id,
                           from_version=prev, to_version=slot_obj.version)
            try:
                tok = self._prefill_into(model, req, slot, req.history())
            except Exception as e:
                ring.release(slot)
                ring.note("migrate_error", slot, req.id, error=str(e))
                self._fail(req, e)
                if self._prefill_failure(e):
                    # donation poisoned the cache mid-migration: the
                    # helper failed everything homed in the ring; fail
                    # the not-yet-migrated stragglers too and rebuild
                    # from scratch at the next admission
                    for _, r2 in sorted(occupants.items()):
                        if not r2.future.done():
                            self._fail(r2, e)
                    return True
                continue
            self._emit(req, tok, slot_obj.version, slot)
        return True

    def _admit(self, slot_obj) -> bool:
        """Joins: drain queued requests into free slots; each becomes
        part of the very next decode batch."""
        model = None
        ring = self.ring
        worked = False
        while ring is None or ring.free_slots > 0:
            try:
                req = self._pending.get_nowait()
            except queue.Empty:
                break
            if req.cancelled.is_set():
                self._finish(req, None, "cancelled")
                worked = True
                continue
            if model is None:
                try:
                    model = self._model_of(slot_obj)
                    ring = self._ensure_ring(model)
                except Exception as e:
                    # the POPPED request must not vanish: fail it with
                    # the real reason (un-generatable stack, bad slot);
                    # the loop keeps draining so every queued request
                    # gets the same informative error, not a timeout
                    self._fail(req, e)
                    model = None
                    worked = True
                    continue
                if ring.free_slots == 0:
                    # raced: topology rebuild freed nothing — requeue
                    self._requeue_or_fail(req)
                    break
            slot = ring.acquire(req)
            if slot is None:
                self._requeue_or_fail(req)
                break
            try:
                # history(), not prompt: a fresh request's history IS its
                # prompt, while an imported session re-prefills its
                # already-generated tokens too and continues mid-stream
                tok = self._prefill_into(model, req, slot, req.history())
            except Exception as e:
                ring.release(slot)
                ring.note("prefill_error", slot, req.id, error=str(e))
                self._fail(req, e)
                worked = True
                if self._prefill_failure(e):
                    break      # ring dropped: re-admit onto a fresh one
                continue
            req.slot = slot
            ring.note("install", slot, req.id, pos=len(req.history()),
                      version=slot_obj.version)
            self._emit(req, tok, slot_obj.version, slot)
            worked = True
        self._set_active_gauge()
        return worked

    def _requeue_or_fail(self, req: _GenRequest) -> None:
        try:
            self._pending.put_nowait(req)
        except queue.Full:
            self._fail(req, RuntimeError("generation queue overflow"))

    def _prefill_into(self, model, req: _GenRequest, slot: int,
                      history: List[int]) -> int:
        """Paged admission: match the longest registered prompt prefix,
        adopt its blocks by reference (COW for a partial tail), allocate
        private blocks for the rest, and run ONE suffix-bucketed
        paged-prefill program call that writes only the unshared tail.
        Cold prompts and migration re-prefills are the same call with
        ``start = 0``."""
        kv: PagedKV = self.ring
        L = len(history)
        t_form = clock.monotonic_s()
        full, partial = kv.match_prefix(history)
        # largest shareable start whose padded suffix still fits the
        # virtual axis (suffix writes run [start, start + bucket))
        plans = ([(len(full), partial)] if partial else []) + \
            [(nf, None) for nf in range(len(full), -1, -1)]
        for nf, pt in plans:
            start = nf * kv.block_size + (pt[1] if pt else 0)
            suffix = L - start
            bucket = next(b for b in self.buckets if suffix <= b)
            if start + bucket <= kv.virtual_seq:
                break
        kv.adopt(slot, req.id, full[:nf])
        cow_src = cow_dst = 0
        if pt is not None:
            dst = kv.cow_begin(slot, req.id, pt[0])
            if dst is None:
                raise RuntimeError(
                    f"KV block pool exhausted admitting {req.id} (COW): "
                    f"{kv.n_blocks} blocks, 0 free/evictable")
            cow_src, cow_dst = pt[0], dst
        try:
            if not kv.ensure_blocks(slot, req.id, L):
                raise RuntimeError(
                    f"KV block pool exhausted admitting {req.id}: needs "
                    f"{-(-L // kv.block_size)} blocks, pool of "
                    f"{kv.n_blocks} has {kv.blocks_free} free")
            toks = np.zeros((1, bucket), np.int32)
            toks[0, :suffix] = history[start:]
            mask = np.zeros((1, bucket), np.float32)
            mask[0, :suffix] = 1.0
            key = np.array([req.seed, len(req.out_tokens)], np.uint32)
            fn = model._get_jitted("paged_prefill")
            t0 = clock.monotonic_s()
            tok_dev, kv.caches = fn(
                model.params, model.state, toks, mask, kv.caches,
                kv.tables[slot].copy(), np.int32(slot), np.int32(start),
                np.int32(suffix), np.int32(cow_src), np.int32(cow_dst),
                key, np.float32(req.temperature), np.int32(req.top_k),
                np.float32(req.top_p))
            self._note_trace(fn)
            tok = int(tok_dev)
        finally:
            if cow_dst:
                kv.cow_end(cow_src)
        kv.pos[slot] = L
        reg = self._reg()
        if start > 0:
            kv.note_shared_hit(slot, req.id, start)
            if reg.enabled:
                reg.counter("generation_prefix_hits_total",
                            "Admissions that adopted registered shared-"
                            "prefix KV blocks").inc()
                reg.counter("generation_prefix_tokens_saved_total",
                            "Prompt tokens NOT prefilled thanks to "
                            "shared-prefix adoption").inc(start)
        kv.register_prefix(slot, req.prompt)
        dt = clock.monotonic_s() - t0
        if reg.enabled:
            reg.histogram("generation_prefill_seconds",
                          "Prefill program wall time per request",
                          buckets=_STEP_BUCKETS).observe(dt)
            reg.gauge("generation_blocks_free",
                      "Free physical KV blocks in the paged pool"
                      ).set(kv.blocks_free)
        from ..observability.profiler import record_slices
        record_slices("prefill", batch_form_s=round(t0 - t_form, 7),
                      execute_s=round(dt, 7), bucket=bucket,
                      shared_tokens=start)
        return tok

    def _decode_guarded(self, slot_obj) -> bool:
        try:
            return self._decode_step(slot_obj)
        except Exception as e:
            self._decode_failure(e)
            return True

    def _decode_step(self, slot_obj) -> bool:
        ring = self.ring
        if ring is None:
            return False
        occupants = ring.occupants()
        for slot, req in sorted(occupants.items()):
            if req.cancelled.is_set():
                self._finish(req, slot, "cancelled")
                del occupants[slot]
        if not occupants:
            self._set_active_gauge()
            return False
        # grow each slot's table across its next block boundary (an
        # aggregated host-side allocation, no device work) and
        # enforce the COW invariant before any write can alias a
        # shared block; a slot the pool cannot grow fails alone
        starved = [(slot, req) for slot, req in
                   sorted(occupants.items())
                   if not ring.ensure_blocks(slot, req.id,
                                             int(ring.pos[slot]) + 1)]
        for slot, req in starved:
            del occupants[slot]
            pos = int(ring.pos[slot])
            ring.release(slot)
            ring.note("vacate", slot, req.id,
                      reason="blocks_exhausted")
            self._fail(req, RuntimeError(
                f"KV block pool exhausted mid-decode for {req.id} at "
                f"pos {pos}: raise n_blocks (pool={ring.n_blocks})"))
        if not occupants:
            self._set_active_gauge()
            return bool(starved)
        for slot in occupants:
            ring.check_writable(slot)
        model = self._model_of(slot_obj)
        S = self.config.max_slots
        t_form = clock.monotonic_s()
        toks = np.zeros((S,), np.int32)
        keys = np.zeros((S, 2), np.uint32)
        temp = np.zeros((S,), np.float32)
        top_k = np.zeros((S,), np.int32)
        top_p = np.ones((S,), np.float32)
        for slot, req in occupants.items():
            toks[slot] = req.out_tokens[-1]
            keys[slot, 0] = req.seed
            keys[slot, 1] = len(req.out_tokens)
            temp[slot] = req.temperature
            top_k[slot] = req.top_k
            top_p[slot] = req.top_p
        t0 = clock.monotonic_s()
        fn = model._get_jitted("paged_decode")
        out_dev, ring.caches = fn(model.params, model.state, toks,
                                  ring.caches, ring.tables.copy(),
                                  ring.pos.copy(), keys, temp, top_k,
                                  top_p)
        self._note_trace(fn)
        # ONE materialization per STEP for the whole slot batch — the
        # per-token host syncs JX023 exists to kill live here, batched
        out = np.asarray(out_dev)
        dt = clock.monotonic_s() - t0
        with self._stats_lock:
            self._decode_steps += 1
        reg = self._reg()
        if reg.enabled:
            reg.histogram("decode_step_seconds",
                          "One fixed-shape decode step over the full "
                          "slot batch", buckets=_STEP_BUCKETS).observe(dt)
        rec = get_flight_recorder()
        if rec is not None:
            rec.record("decode", "step", active=len(occupants),
                       step_s=round(dt, 6), version=slot_obj.version,
                       free=ring.free_slots)
        # stepprof slices: slot-batch formation (the host-side gather of
        # last tokens/keys/sampler params) vs the fenced decode execute
        # (the batched np.asarray above is the ONE step sync)
        from ..observability.profiler import record_slices
        record_slices("decode", batch_form_s=round(t0 - t_form, 7),
                      execute_s=round(dt, 7), active=len(occupants))
        # the step wrote one token per active slot — advance the host
        # position mirrors BEFORE emission (a finishing request releases
        # its slot inside _emit, which resets its mirror)
        for slot in occupants:
            ring.pos[slot] += 1
        for slot, req in sorted(occupants.items()):
            self._emit(req, int(out[slot]), slot_obj.version, slot)
        self._set_active_gauge()
        return True

    def _prefill_failure(self, e: Exception) -> bool:
        """A failed prefill EXECUTION may have consumed the donated
        cache buffers on an accelerator backend (donate_argnums) — the
        pytree can no longer be trusted there, so fail every occupant
        and drop the ring for a fresh rebuild at the next admission.
        CPU skips donation: the ring and its other occupants safely
        survive a single bad prefill.  Returns True when the ring was
        dropped (callers must stop using their local reference)."""
        if jax.default_backend() == "cpu" or self.ring is None:
            return False
        ring = self.ring
        for slot, req in sorted(ring.occupants().items()):
            ring.release(slot)
            ring.note("vacate", slot, req.id, reason="prefill_error")
            self._fail(req, e)
        self._set_active_gauge()
        self.ring = None
        self._ring_sig = None
        return True

    def _decode_failure(self, e: Exception) -> None:
        """A failed decode step: commit forensics WITH the slot occupancy
        trail, then fail every active request (the batch died together —
        their caches may be inconsistent with their histories) and DROP
        the ring: on donating backends the failed call consumed the
        cache buffers (donate_argnums), so reusing the pytree would turn
        one fault into a permanent 'buffer donated' wedge — admission
        rebuilds a fresh ring for the next request."""
        with self._stats_lock:
            self._decode_errors += 1
        ring = self.ring
        snapshot = None if ring is None else ring.occupancy_snapshot()
        rec = get_flight_recorder()
        if rec is not None:
            rec.record("decode", "decode_error",
                       error=f"{type(e).__name__}: {e}",
                       occupancy=snapshot)
            rec.maybe_dump("decode_exception")
        log.exception("decode step failed (%s active slots)",
                      0 if snapshot is None else snapshot["active"])
        if ring is None:
            return
        for slot, req in sorted(ring.occupants().items()):
            ring.release(slot)
            ring.note("vacate", slot, req.id, reason="decode_error")
            self._fail(req, e)
        self._set_active_gauge()
        self.ring = None
        self._ring_sig = None

    # ------------------------------------------------------------- emission
    def _emit(self, req: _GenRequest, tok: int, version: int,
              slot: Optional[int]) -> bool:
        now = clock.monotonic_s()
        mon = self._mon()
        if req.t_first is None:
            req.t_first = now
            ttft = now - req.t_submit
            self._ttft_w.observe(ttft)
            if mon is not None:
                mon.observe_generation(ttft_s=ttft)
        else:
            itl = now - req.t_last
            self._itl_w.observe(itl)
            if mon is not None:
                mon.observe_generation(itl_s=itl)
        req.t_last = now
        req.out_tokens.append(tok)
        req.versions.append(version)
        with self._stats_lock:
            self._tokens_generated += 1
        reg = self._reg()
        if reg.enabled:
            reg.counter("generation_tokens_total",
                        "Tokens emitted by the decode engine").inc()
        req.push_event({"token": tok, "index": len(req.out_tokens) - 1,
                        "model_version": version})
        finish = None
        if req.eos_id is not None and tok == req.eos_id:
            finish = "eos"
        elif len(req.out_tokens) >= req.max_new_tokens:
            finish = "length"
        elif req.cancelled.is_set():
            finish = "cancelled"
        if finish is not None:
            self._finish(req, slot, finish)
            return True
        return False

    def _finish(self, req: _GenRequest, slot: Optional[int],
                finish: str) -> None:
        ring = self.ring
        if slot is not None and ring is not None:
            ring.release(slot)
            ring.note("vacate", slot, req.id,
                      pos=len(req.history()), reason=finish)
        result = GenerationResult(tokens=list(req.out_tokens),
                                  versions=list(req.versions),
                                  finish=finish, request_id=req.id,
                                  prompt_len=len(req.prompt))
        req.push_event({"done": True, "finish": finish,
                        "tokens": result.tokens,
                        "model_versions": result.versions})
        if not req.future.done():
            req.future.set_result(result)

    def _fail(self, req: _GenRequest, e: Exception) -> None:
        req.push_event({"error": f"{type(e).__name__}: {e}"})
        if not req.future.done():
            req.future.set_exception(e)

    def _set_active_gauge(self) -> None:
        reg = self._reg()
        if reg.enabled and self.ring is not None:
            reg.gauge("generation_active_slots",
                      "Generation slots currently occupied by live "
                      "sequences").set(self.ring.active_slots)
            reg.gauge("generation_blocks_free",
                      "Free physical KV blocks in the paged pool"
                      ).set(self.ring.blocks_free)

    # ------------------------------------------------------------ lifecycle
    def shutdown(self) -> None:
        with self._submit_lock:
            self._shutdown.set()
        self._wake.set()
        if self._thread.is_alive():
            self._thread.join(timeout=5)
        err = RuntimeError("GenerationEngine shut down")
        while True:
            try:
                req = self._pending.get_nowait()
            except queue.Empty:
                break
            self._fail(req, err)
        if self.ring is not None:
            for slot, req in sorted(self.ring.occupants().items()):
                self.ring.release(slot)
                self._fail(req, err)
