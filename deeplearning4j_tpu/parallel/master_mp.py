"""TrainingMaster orchestration over real OS processes.

The in-process masters (``parallel/master.py``) prove the averaging /
shared-gradients *semantics* with thread replicas; this module runs the same
contracts with workers as separate processes — the reference's driver +
executor-JVM topology (``ParameterAveragingTrainingMaster.java:62``,
``SharedTrainingWrapper.java:48``).  Coordination rides the
``TcpMessageBroker`` hub (the Aeron/Spark-transport role):

- **averaging**: each worker fits its shard ``averaging_frequency`` batches
  per round, publishes its raveled params (+ optionally updater state) as a
  dense frame, then waits for the master's averaged frame — a synchronous
  parameter-averaging barrier across processes.
- **shared**: workers exchange threshold-quantized param-updates peer-to-peer
  through ``RemoteGradientSharing`` (the SilentUpdatesMessage wire format).
  Arrival is explicit, never timed (the ``SharedTrainingWrapper.java:48``
  registration posture): every subscription is hub-acked, a ready/go
  barrier gates the first publish, and completion is a drain barrier —
  each worker declares its sent-count on a flush topic — together with a
  dense end-of-job residual frame (the quantizer's undelivered remainder)
  — and peers drain until per-sender applied counts reach the declared
  counts and all residuals are in.  Every final table then equals
  init + Σ(all workers' exact deltas); the master asserts inter-worker
  agreement within a float-noise tolerance and installs the mean.

**Task retry** mirrors Spark's RDD-lineage re-execution
(``ParameterAveragingTrainingMaster.java:62``: a lost partition is simply
recomputed from the broadcast parameters): when a worker process exits
without delivering its contribution — any exit code; rc==0 without a
result is just as dead — the master respawns it with a resume spec:

- averaging: restart at the current round from the last averaged frame
  (exactly the broadcast-params re-execution contract);
- shared: re-execute the full shard via a RESYNC handshake — the
  replacement subscribes (hub-acked) first, then asks the master for a
  seed built from its mirror (init + every quantized update seen, plus
  folded residuals and per-sender sequence counts).  Per-sender FIFO +
  sequence numbers make the seed/subscription overlap dedup exactly: no
  update is lost or double-applied.  Semantically the retry is still
  *at-least-once* over BATCHES (the dead incarnation's transmitted
  updates stay in everyone's tables and the replacement re-trains the
  whole shard), so the final-table agreement assertion is waived for the
  run and recorded in ``last_table_spread = None``.
- evaluate/score: stateless — the shard is simply re-executed.

``evaluate`` / ``score`` fan the dataset out over worker processes which
return partial ``Evaluation`` JSON / loss sums for the master to merge
(the ``SparkDl4jMultiLayer.evaluate``/``calculateScore`` map-reduce).

Workers are spawned as ``python -m deeplearning4j_tpu.parallel.master_mp``
with a job directory holding the serialized model, the shard, and a spec;
the test rig (tests/test_masters_mp.py) pins workers to CPU devices so the
whole topology is provable without TPU hardware — the reference's
``local[N]`` posture (``BaseSparkTest.java:46``).
"""
from __future__ import annotations

import contextlib
import io
import json
import os
import struct
import subprocess
import sys
import threading
import time
from typing import Any, Callable, Dict, List, Optional, Tuple

import numpy as np

from ..observability.clock import monotonic_s
from ..observability.recorder import get_flight_recorder
from ..observability.registry import default_registry
from ..observability.tracer import SpanContext, get_tracer

__all__ = ["MultiprocessMaster"]

_UP = "mp.up"          # worker -> master dense frames (averaging rounds)
_DOWN = "mp.down"      # master -> workers averaged frame
_FINAL = "mp.final"    # shared mode: final tables
_DONE = "mp.done"      # per-worker result json
_GRADS = "mp.grads"    # shared mode: quantized updates (RemoteGradientSharing)
_READY = "mp.ready"    # shared mode: worker subscriptions are hub-acked
_GO = "mp.go"          # shared mode: master saw N readies — publishing may start
_FLUSH = "mp.flush"    # shared mode: per-worker declared sent-counts
_RESID = "mp.resid"    # shared mode: dense end-of-job residual flush
_SEED = "mp.seed"      # shared mode: master -> respawned worker resync seed
_HB = "mp.hb"          # worker -> master heartbeat {wid, steps}
_DEAD = "mp.dead"      # master -> workers: eviction notice {wid}

_HB_INTERVAL_S = 0.5   # worker heartbeat period (lease renewal analogue)


def _encode_frame(wid: int, rnd: int, vec: np.ndarray) -> bytes:
    buf = io.BytesIO()
    np.save(buf, np.ascontiguousarray(vec))
    return struct.pack("<ii", wid, rnd) + buf.getvalue()


def _decode_frame(data: bytes):
    wid, rnd = struct.unpack_from("<ii", data)
    vec = np.load(io.BytesIO(data[8:]), allow_pickle=False)
    return wid, rnd, vec


def _ravel(model, with_opt: bool):
    from jax.flatten_util import ravel_pytree
    flat_p, unravel_p = ravel_pytree(model.params)
    if not with_opt:
        return np.asarray(flat_p), (unravel_p, None, flat_p.size)
    flat_o, unravel_o = ravel_pytree(model.opt_state)
    vec = np.concatenate([np.asarray(flat_p), np.asarray(flat_o)])
    return vec, (unravel_p, unravel_o, flat_p.size)


def _unravel_into(model, vec, meta) -> None:
    import jax.numpy as jnp
    unravel_p, unravel_o, n_p = meta
    vec = jnp.asarray(vec)
    model.params = unravel_p(vec[:n_p])
    if unravel_o is not None:
        model.opt_state = unravel_o(vec[n_p:])


def _save_batches(path: str, batches: List[Any]) -> None:
    arrs = {}
    for i, (x, y) in enumerate(batches):
        arrs[f"x{i}"] = np.asarray(x)
        arrs[f"y{i}"] = np.asarray(y)
    np.savez(path, n=np.int64(len(batches)), **arrs)


def _load_batches(path: str):
    z = np.load(path)
    return [(z[f"x{i}"], z[f"y{i}"]) for i in range(int(z["n"]))]


class MultiprocessMaster:
    """Orchestrates N worker processes training one model.

    ``mode``: "averaging" (ParameterAveraging contract) or "shared"
    (SharedGradients / quantized peer-to-peer contract).
    ``worker_env``: extra env vars for workers (the test rig passes
    ``JAX_PLATFORMS=cpu``; production hosts would pass their chip topology).
    ``max_task_retries``: per-worker respawn budget before the job fails
    (the Spark task-retry knob; re-execution semantics in the module doc).
    ``fault_injection``: test-only hook — keys ``die_before_publish``
    (averaging, {wid: round}), ``die_after_batches`` (shared, {wid: k}),
    ``die_at_start`` (evaluate/score, [wid]), ``die_before_done`` /
    ``exit_nonzero_after_done`` ([wid]), ``slow_start`` ({wid: seconds}),
    ``hang_after_batches`` ({wid: k}: the training loop wedges after k
    batches while the heartbeat thread keeps beating — the stall
    watchdog's test case) — applied only to a worker's first incarnation.
    ``straggler_timeout_s``: heartbeat-stall watchdog (see attribute doc).
    """

    _DEAD_GRACE = 2.0   # seconds a dead worker's in-flight message may lag
    # subclasses repoint these to reuse the spawn/retry/collect machinery
    # for other job types (nlp/distributed_vectors rides it for Word2Vec)
    _WORKER_MODULE = "deeplearning4j_tpu.parallel.master_mp"
    _STATELESS_TASKS = ("evaluate", "score")   # _DONE is the contribution

    def __init__(self, num_workers: int = 2, mode: str = "averaging",
                 averaging_frequency: int = 5, average_updaters: bool = True,
                 threshold: float = 1e-3, timeout: float = 300.0,
                 worker_env: Optional[Dict[str, str]] = None,
                 max_task_retries: int = 2,
                 agreement_tol: float = 1e-3,
                 workdir: Optional[str] = None,
                 fault_injection: Optional[Dict[str, Any]] = None,
                 retry_backoff_s: float = 0.1, retry_seed: int = 0,
                 straggler_timeout_s: Optional[float] = None):
        from ..faulttolerance.faults import RetryPolicy
        if mode not in ("averaging", "shared"):
            raise ValueError(f"unknown mode {mode!r}")
        self.retry_policy = RetryPolicy(max_retries=max_task_retries,
                                        backoff_s=retry_backoff_s,
                                        seed=retry_seed)
        self.num_workers = num_workers
        self.mode = mode
        self.averaging_frequency = max(1, averaging_frequency)
        self.average_updaters = average_updaters
        self.threshold = threshold
        self.timeout = timeout
        self.worker_env = dict(worker_env or {})
        self.max_task_retries = max_task_retries
        self.agreement_tol = agreement_tol
        self.workdir = workdir   # parent for auto-created job directories
        self.fault_injection = dict(fault_injection or {})
        # heartbeat-stall watchdog (the thread masters' straggler timeout
        # promoted across the process boundary): a worker whose process is
        # alive but whose heartbeats stop carrying progress for longer
        # than this is killed and respawned.  None = off.  Must be sized
        # well past a normal round (training + barrier waits make no
        # "steps" progress while a worker legitimately blocks).
        self.straggler_timeout_s = straggler_timeout_s
        self.last_results: List[Dict[str, Any]] = []
        self.retried_workers: set = set()
        self.last_table_spread: Optional[float] = None
        self.evicted_workers: set = set()

    # -- plumbing ------------------------------------------------------------
    def _spawn(self, jobdir: str, wid: int, port: int,
               resume_file: Optional[str] = None) -> subprocess.Popen:
        env = dict(os.environ)
        pkg_root = os.path.dirname(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))))
        # prepend, never replace, so user-supplied PYTHONPATH dependencies
        # stay importable; worker_env may still override wholesale
        prev = [p for p in env.get("PYTHONPATH", "").split(os.pathsep) if p]
        env["PYTHONPATH"] = os.pathsep.join([pkg_root] + prev)
        env.update(self.worker_env)
        log = open(os.path.join(jobdir, f"worker_{wid}.log"), "a")
        argv = [sys.executable, "-m", self._WORKER_MODULE,
                jobdir, str(wid), str(port)]
        if resume_file:
            argv.append(resume_file)
        p = subprocess.Popen(argv, env=env, stdout=log,
                             stderr=subprocess.STDOUT)
        p._logfile = log
        if hasattr(self, "_hb"):
            # (re)arm the stall watchdog for this incarnation: progress
            # clock starts at spawn, steps at -1 (= no beat seen yet)
            self._hb[wid] = [monotonic_s(), -1]
        return p

    def _run_job(self, model, jobdir: str, spec: Dict[str, Any],
                 setup, run,
                 resume_payload: Optional[
                     Callable[[int], Tuple[Dict[str, Any],
                                           Optional[np.ndarray]]]] = None):
        """Write the job, serve the broker, create master-side subscriptions
        (``setup`` — BEFORE any worker can publish, the broker retains
        nothing), spawn workers, run the master protocol (``run``), join
        workers, return its result.  ``resume_payload(wid)`` builds the
        (resume-spec, frame) a respawned worker restarts from."""
        from ..streaming.broker import TcpMessageBroker

        self._write_job(model, jobdir)
        # max_queue=0: the master protocol is a reliable transport (the
        # Aeron role) — exact-count drain barriers need lossless delivery;
        # memory is bounded by job size
        broker = TcpMessageBroker(max_queue=0).serve()
        # span-context propagation to worker PROCESSES: the context rides
        # the job spec; each worker re-roots its local spans under it
        # (inert when tracing is off — ctx is None)
        ctx = get_tracer().current_context()
        spec = dict(spec, port=broker.port, num_workers=self.num_workers,
                    averaging_frequency=self.averaging_frequency,
                    average_updaters=self.average_updaters,
                    threshold=self.threshold, timeout=self.timeout,
                    fault=self.fault_injection,
                    trace=None if ctx is None else ctx.to_dict())
        with open(os.path.join(jobdir, "spec.json"), "w") as f:
            json.dump(spec, f)
        done_sub = broker.subscribe(_DONE)
        # heartbeat intake: registered before any worker can beat
        self._hb_sub = broker.subscribe(_HB)
        # wid -> [last_progress_monotonic_s, steps]; seeded at spawn so a
        # worker that wedges before its first beat still trips the watchdog
        self._hb: Dict[int, List[float]] = {}
        subs = setup(broker)
        self._broker = broker
        self._port = broker.port
        self._resume_payload = resume_payload
        self._retries: Dict[int, int] = {}
        self._dead_since: Dict[int, float] = {}
        self.retried_workers = set()
        self.evicted_workers = set()
        self._procs: Dict[int, subprocess.Popen] = {
            w: self._spawn(jobdir, w, broker.port)
            for w in range(self.num_workers)}
        try:
            out = run(broker, subs)
            if spec["task"] not in self._STATELESS_TASKS:
                # every fit contribution is in; a worker respawned from
                # here on only needs to report (for stateless tasks the
                # _DONE message IS the contribution — full re-execution)
                self._resume_payload = \
                    lambda wid: ({"skip_to_done": True}, None)
            results: Dict[int, Dict[str, Any]] = {}
            deadline = time.time() + self.timeout
            while len(results) < self.num_workers:
                payload = done_sub.poll(timeout=0.25)
                if payload is not None:
                    r = json.loads(payload.decode())
                    results[int(r["wid"])] = r
                    continue
                if self._check_liveness(jobdir, satisfied=results.keys()):
                    deadline = time.time() + self.timeout
                if time.time() > deadline:
                    raise RuntimeError(
                        "workers did not report: "
                        + self._logs_tail(jobdir))
            for w, p in self._procs.items():
                rc = p.wait(timeout=30)
                if rc != 0:
                    # its contribution was already received (the results
                    # loop completed), so a teardown crash doesn't fail
                    # the job — record it for the caller instead
                    results[w]["exit_code"] = rc
            self.last_results = [results[w] for w in range(self.num_workers)]
            return out
        finally:
            for p in self._procs.values():
                if p.poll() is None:
                    p.kill()
                p._logfile.close()
            broker.shutdown()

    def _write_job(self, model, jobdir: str) -> None:
        """Serialize the trainee into the job directory (subclasses swap
        the serialization format for their model family)."""
        from ..utils import model_serializer
        model_serializer.write_model(model, os.path.join(jobdir, "model.zip"))

    def _logs_tail(self, jobdir: str) -> str:
        outs = []
        for w in range(self.num_workers):
            path = os.path.join(jobdir, f"worker_{w}.log")
            if os.path.exists(path):
                with open(path) as f:
                    outs.append(f"[worker {w}] " + f.read()[-2000:])
        return "\n".join(outs)

    def _drain_heartbeats(self) -> None:
        """Fold pending worker heartbeats into the watchdog state and the
        ``cluster_heartbeat_age_seconds`` gauge.  The progress clock only
        advances when ``steps`` moves: a wedged worker whose heartbeat
        thread still beats (but whose training loop is stuck) ages out
        exactly like a silent one."""
        sub = getattr(self, "_hb_sub", None)
        if sub is None:
            return
        now = monotonic_s()
        rec = get_flight_recorder()
        while True:
            payload = sub.poll(timeout=0.001)
            if payload is None:
                break
            try:
                d = json.loads(payload.decode())
                wid, steps = int(d["wid"]), int(d.get("steps", 0))
            except (ValueError, KeyError):
                wid = None    # malformed beat (foreign payload): ignore
            if wid is None:
                continue
            cur = self._hb.get(wid)
            if cur is None or steps > cur[1]:
                self._hb[wid] = [now, steps]
                if rec is not None:
                    # the heartbeat trail is what an eviction dump replays
                    rec.record("cluster", "heartbeat", worker=wid,
                               steps=steps)
        reg = default_registry()
        if reg.enabled and self._hb:
            age = reg.gauge("cluster_heartbeat_age_seconds",
                            "Seconds since a worker last made heartbeat "
                            "progress", ("worker",))
            for wid, (t, _) in self._hb.items():
                age.labels(str(wid)).set(max(0.0, now - t))

    def _check_liveness(self, jobdir: str, satisfied=()) -> bool:
        """Respawn workers that exited — ANY exit code — without delivering
        the contribution the current phase is collecting (``satisfied``).
        A short grace window lets a just-published in-flight message land
        before the respawn triggers.  With ``straggler_timeout_s`` set, a
        worker whose process is ALIVE but whose heartbeats stopped
        carrying progress for longer than the timeout is killed and
        respawned too (the thread masters' straggler watchdog, fed by
        process heartbeats).  Returns True when someone was respawned
        (callers extend their deadline: the replacement redoes work)."""
        self._drain_heartbeats()
        respawned = False
        now = monotonic_s()
        reg = default_registry()
        # registry child resolved BEFORE the per-worker loop (JX022: the
        # cached-child idiom — name/label lookups don't belong in loops)
        evict_c = reg.counter(
            "cluster_evictions_total",
            "Workers evicted from the membership view",
            ("reason",)).labels("heartbeat_stall") if reg.enabled else None
        for wid, p in list(self._procs.items()):
            if p.poll() is None or wid in satisfied:
                self._dead_since.pop(wid, None)
                if p.poll() is None and wid not in satisfied and \
                        self.straggler_timeout_s is not None:
                    hb = self._hb.get(wid)
                    if hb is not None and \
                            now - hb[0] > self.straggler_timeout_s:
                        if evict_c is not None:
                            evict_c.inc()
                        self.evicted_workers.add(wid)
                        self._record_eviction(wid, hb, now, jobdir)
                        p.kill()
                        p.wait(timeout=30)
                        self._respawn(wid, jobdir)
                        respawned = True
                continue
            first = self._dead_since.setdefault(wid, now)
            if now - first < self._DEAD_GRACE:
                continue
            self._dead_since.pop(wid, None)
            self._respawn(wid, jobdir)
            respawned = True
        return respawned

    def _record_eviction(self, wid: int, hb, now: float,
                         jobdir: str) -> None:
        """Watchdog eviction forensics: the coordinator commits the
        flight-recorder window (incl. the evicted worker's heartbeat
        trail on the cluster channel) into the job directory — the
        artifact that says WHY worker ``wid`` was killed, written by the
        surviving side before the respawn even starts."""
        rec = get_flight_recorder()
        if rec is None or not rec.enabled:
            return
        rec.record("cluster", "watchdog_eviction", worker=wid,
                   stalled_s=round(now - hb[0], 3), steps=hb[1],
                   timeout_s=self.straggler_timeout_s)
        rec.maybe_dump("watchdog_eviction", directory=jobdir)

    def _respawn(self, wid: int, jobdir: str) -> None:
        n = self._retries.get(wid, 0) + 1
        reg = default_registry()
        if n > self.max_task_retries:
            # the mp topology has no surviving-replica pool to re-chunk a
            # shard onto mid-protocol (the averaging barrier counts all N
            # workers), so an exhausted budget fails the job — recorded as
            # a lost worker for the shared fleet dashboards
            if reg.enabled:
                reg.counter("training_worker_lost_total",
                            "Workers permanently lost (retries/straggler "
                            "budget exhausted)", ("mode",)
                            ).labels("mp").inc()
            if self.mode == "shared":
                # eviction notice: surviving peers drop this sender from
                # their drain barriers IMMEDIATELY instead of spinning
                # until their own deadline — an evicted peer never blocks
                # the drain longer than the master's liveness verdict
                try:
                    self._broker.publish(
                        _DEAD, json.dumps({"wid": wid}).encode())
                except (ConnectionError, OSError):
                    pass   # hub teardown is already in flight
            raise RuntimeError(
                f"worker {wid} failed after {n - 1} retries: "
                + self._logs_tail(jobdir))
        self._retries[wid] = n
        self.retried_workers.add(wid)
        if reg.enabled:
            reg.counter("mp_worker_respawns_total",
                        "Dead worker processes respawned by task retry",
                        ("mode",)).labels(self.mode).inc()
            reg.counter("training_worker_retries_total",
                        "Worker round retries in the training masters",
                        ("mode",)).labels("mp").inc()
        # seeded exponential backoff + jitter: a crash-looping host must
        # not be respawned at full tilt (and N masters sharing a node
        # shouldn't stampede in lockstep)
        self.retry_policy.sleep(n, worker=wid)
        old = self._procs[wid]
        if old.poll() is None:
            old.kill()
        old._logfile.close()
        resume, frame = (self._resume_payload(wid)
                         if self._resume_payload else ({}, None))
        resume = dict(resume)
        if frame is not None:
            fnpy = os.path.join(jobdir, f"resume_{wid}_{n}.npy")
            np.save(fnpy, np.asarray(frame))
            resume["frame"] = fnpy
        rf = os.path.join(jobdir, f"resume_{wid}_{n}.json")
        with open(rf, "w") as f:
            json.dump(resume, f)
        self._procs[wid] = self._spawn(jobdir, wid, self._port,
                                       resume_file=rf)

    def _collect_loop(self, sub, want: int, what: str, jobdir: str,
                      decode_fn,
                      on_idle: Optional[Callable[[], None]] = None):
        """One collection loop for every phase: poll, decode (``decode_fn``
        returns ``(wid, value)`` or ``(None, None)`` to skip stale
        payloads), run ``on_idle`` between polls, respawn dead workers
        (extending the deadline — the replacement redoes work)."""
        got: Dict[int, Any] = {}
        deadline = time.time() + self.timeout
        while len(got) < want:
            payload = sub.poll(timeout=0.25)
            if payload is not None:
                wid, value = decode_fn(payload)
                if wid is not None:
                    got[wid] = value
                continue
            if on_idle is not None:
                on_idle()
            if self._check_liveness(jobdir, satisfied=got.keys()):
                deadline = time.time() + self.timeout
            if time.time() > deadline:
                raise RuntimeError(f"timed out collecting {what}: "
                                   + self._logs_tail(jobdir))
        return got

    def _collect(self, sub, want: int, what: str, jobdir: str,
                 rnd: Optional[int] = None,
                 on_idle: Optional[Callable[[], None]] = None):
        """Collect one dense frame per worker; ``rnd`` filters stale frames
        from pre-respawn incarnations."""
        def decode_fn(payload):
            wid, got_rnd, vec = _decode_frame(payload)
            if rnd is not None and got_rnd != rnd:
                return None, None
            return wid, vec
        return self._collect_loop(sub, want, what, jobdir, decode_fn,
                                  on_idle)

    def _collect_json(self, sub, what: str, jobdir: str,
                      on_idle: Optional[Callable[[], None]] = None,
                      sink: Optional[Callable[[int, Dict[str, Any]],
                                              None]] = None
                      ) -> Dict[int, Dict[str, Any]]:
        """Collect one small JSON message per worker (ready / flush);
        ``sink`` observes each message as it lands (the shared master
        mirrors flush declarations for resync seeds)."""
        def decode_fn(payload):
            d = json.loads(payload.decode())
            wid = int(d["wid"])
            if sink is not None:
                sink(wid, d)
            return wid, d
        return self._collect_loop(sub, self.num_workers, what, jobdir,
                                  decode_fn, on_idle)

    def _prepare_jobdir(self, iterator, jobdir: Optional[str]):
        """Materialize the job directory + per-worker shards (shared by the
        fit and evaluate/score fan-outs so sharding can't diverge)."""
        import tempfile

        from .master import _chunk_batches

        if jobdir is None:
            if self.workdir:
                os.makedirs(self.workdir, exist_ok=True)
            jobdir = tempfile.mkdtemp(prefix="dl4j_mp_", dir=self.workdir)
        os.makedirs(jobdir, exist_ok=True)
        parts = _chunk_batches(iterator, self.num_workers)
        for w, part in enumerate(parts):
            _save_batches(os.path.join(jobdir, f"shard_{w}.npz"), part)
        return jobdir, parts

    # -- training ------------------------------------------------------------
    def fit(self, model, iterator, jobdir: Optional[str] = None) -> None:
        with get_tracer().span("mp.fit", mode=self.mode,
                               workers=self.num_workers):
            jobdir, parts = self._prepare_jobdir(iterator, jobdir)
            n_rounds = (max((len(p) for p in parts), default=0)
                        + self.averaging_frequency - 1
                        ) // self.averaging_frequency
            with_opt = self.average_updaters and self.mode == "averaging"
            vec0, meta = _ravel(model, with_opt)

            if self.mode == "averaging":
                vec = self._fit_averaging(model, jobdir, n_rounds,
                                          np.asarray(vec0))
            else:
                vec = self._fit_shared(model, jobdir, np.asarray(vec0))
            if vec is not None:
                _unravel_into(model, vec, meta)

    def _fit_averaging(self, model, jobdir: str, n_rounds: int,
                       vec0: np.ndarray):
        state = {"rnd": 0, "last": vec0}

        def resume_payload(wid):
            # re-execution from the broadcast params: the respawned worker
            # restarts at the round being collected, seeded with the last
            # averaged frame (round 0: the initial model)
            return {"start_round": state["rnd"]}, state["last"]

        def run(broker, sub):
            last = None
            for rnd in range(n_rounds):
                state["rnd"] = rnd
                frames = self._collect(sub, self.num_workers,
                                       f"round {rnd}", jobdir, rnd=rnd)
                last = np.mean([frames[w] for w in sorted(frames)], axis=0)
                state["last"] = last
                broker.publish(_DOWN, _encode_frame(-1, rnd, last))
            # a crash between the last barrier and the _DONE report is
            # handled by _run_job's skip_to_done resume swap
            return last

        spec = {"task": "fit", "mode": "averaging", "n_rounds": n_rounds}
        return self._run_job(model, jobdir, spec,
                             lambda broker: broker.subscribe(_UP),
                             run, resume_payload)

    def _fit_shared(self, model, jobdir: str, vec0: np.ndarray):
        from .accumulation import decode as _decode_update
        from .remote import decode_message_bytes

        state: Dict[str, Any] = {
            "go": False, "broker": None,
            "mirror": vec0.copy(),      # init + every quantized update seen
            "mirror_counts": {},        # per-sender updates in the mirror
            "resid_sum": np.zeros_like(vec0),
            "resid_wids": set(),        # whose residuals resid_sum holds
            "declared": {},             # flush declarations seen so far
            "grads_sub": None, "resid_sub": None, "ready_sub": None,
            "seed_n": 0,
        }

        def drain_mirror(settle: float = 0.001):
            """``settle``: how long a poll gap ends the drain — resync
            seeds use a longer window so an in-flight frame (mid-transfer
            on the subscription socket) lands in the seed rather than
            falling between seed and the replacement's subscription."""
            while True:
                payload = state["grads_sub"].poll(timeout=settle)
                if payload is None:
                    break
                sender, seq, msg = decode_message_bytes(payload)
                state["mirror"] += np.asarray(_decode_update(msg))
                # per-sender FIFO (one publisher connection) makes seqs
                # arrive dense and in order: the highest seen == the count
                # folded into the mirror, which seeds exact dedup
                state["mirror_counts"][sender] = max(
                    state["mirror_counts"].get(sender, 0), seq)
            while True:
                payload = state["resid_sub"].poll(timeout=settle)
                if payload is None:
                    break
                r_wid, _, vec = _decode_frame(payload)
                if r_wid not in state["resid_wids"]:
                    state["resid_wids"].add(r_wid)
                    state["resid_sum"] += vec

        def serve_resyncs():
            """Answer a respawned worker's resync request with a seed:
            mirror + folded residuals, plus the per-sender bookkeeping the
            replacement needs to run an exact drain barrier (module doc).
            The replacement subscribed (hub-acked) BEFORE requesting, so
            everything published after the seed snapshot reaches it
            directly; sequence numbers dedup the overlap exactly."""
            while True:
                payload = state["ready_sub"].poll(timeout=0.001)
                if payload is None:
                    return
                d = json.loads(payload.decode())
                if not d.get("resync"):
                    continue     # stale pre-go READY from a dead worker
                # settle-drain: a frame mid-transfer on the mirror socket
                # must land in the seed (the replacement can't receive it
                # — it was fanned out before its subscription); 50 ms of
                # silence on loopback means nothing is in flight.  If an
                # extreme straggler still slips through, the replacement's
                # drain barrier times out, and the NEXT resync sees it —
                # self-healing at the cost of one retry.
                drain_mirror(settle=0.05)
                w = int(d["wid"])
                state["seed_n"] += 1
                seed_file = os.path.join(
                    jobdir, f"seed_{w}_{state['seed_n']}.npy")
                np.save(seed_file, state["mirror"] + state["resid_sum"])
                meta = {"wid": w, "file": seed_file,
                        "resid_wids": sorted(state["resid_wids"]),
                        "prior_sent": state["mirror_counts"].get(w, 0),
                        "declared": {str(k): v for k, v
                                     in state["declared"].items()},
                        "mirror_counts": {str(k): v for k, v
                                          in state["mirror_counts"].items()}}
                state["broker"].publish(_SEED, json.dumps(meta).encode())

        def on_idle():
            drain_mirror()
            serve_resyncs()

        def resume_payload(wid):
            # pre-go death: nothing was published — a clean restart.
            # post-go death: the replacement bootstraps via resync, so no
            # frame is shipped at spawn time (it would already be stale).
            return ({"restart": True, "go_done": state["go"]}, None)

        def setup(broker):
            state["broker"] = broker
            state["grads_sub"] = broker.subscribe(_GRADS, ack=True)
            state["resid_sub"] = broker.subscribe(_RESID, ack=True)
            state["ready_sub"] = broker.subscribe(_READY)
            return (broker.subscribe(_FLUSH), broker.subscribe(_FINAL))

        def run(broker, subs):
            flush_sub, final_sub = subs
            self._collect_json(state["ready_sub"], "ready barrier", jobdir)
            broker.publish(_GO, b"go")
            state["go"] = True

            def flush_sink(wid, d):
                state["declared"][wid] = int(d["sent"])
            declared = self._collect_json(flush_sub, "flush counts", jobdir,
                                          on_idle=on_idle, sink=flush_sink)
            finals = self._collect(final_sub, self.num_workers,
                                   "final tables", jobdir,
                                   on_idle=on_idle)
            tables = np.stack([finals[w] for w in sorted(finals)])
            if not self.retried_workers:
                # after a clean drain + dense residual flush every table is
                # init + Σ(all exact deltas); remaining spread is float32
                # summation-order noise, so the bound is tight
                del declared  # counts were the barrier, not the check
                spread = float(np.max(tables.max(axis=0) - tables.min(axis=0))
                               ) if len(tables) > 1 else 0.0
                if spread > self.agreement_tol:
                    raise RuntimeError(
                        f"shared-mode final tables diverge: spread "
                        f"{spread:.3e} > agreement_tol "
                        f"{self.agreement_tol:.3e}")
                self.last_table_spread = spread
            else:
                # at-least-once re-execution re-applied updates; agreement
                # is waived for the run (module doc)
                self.last_table_spread = None
            return tables.mean(axis=0)

        spec = {"task": "fit", "mode": "shared"}
        return self._run_job(model, jobdir, spec, setup, run, resume_payload)

    # -- evaluation / scoring fan-out ---------------------------------------
    def _fan_out_task(self, model, iterator, task: str,
                      jobdir: Optional[str]):
        with get_tracer().span(f"mp.{task}", mode=self.mode,
                               workers=self.num_workers):
            jobdir, _ = self._prepare_jobdir(iterator, jobdir)
            # stateless shards: a respawned worker simply re-executes
            self._run_job(model, jobdir, {"task": task, "mode": self.mode},
                          lambda broker: None, lambda broker, subs: None,
                          resume_payload=lambda wid: ({}, None))
            return self.last_results

    def evaluate(self, model, iterator, jobdir: Optional[str] = None):
        """Distributed classification evaluation: per-process partial
        ``Evaluation`` objects merged on the master."""
        from ..evaluation.classification import Evaluation
        results = self._fan_out_task(model, iterator, "evaluate", jobdir)
        merged = Evaluation()
        for r in results:
            if r.get("evaluation"):
                merged.merge(Evaluation.from_json(r["evaluation"]))
        return merged

    def score(self, model, iterator, average: bool = True,
              jobdir: Optional[str] = None) -> float:
        results = self._fan_out_task(model, iterator, "score", jobdir)
        total = sum(r["loss_sum"] for r in results)
        n = sum(r["n_examples"] for r in results)
        return total / max(n, 1) if average else total


# --------------------------------------------------------------------- worker
def _maybe_hang(fault: Dict[str, Any], wid: int, steps: int) -> None:
    """Fault-injection hook (NOT protocol timing): ``hang_after_batches``
    wedges the training loop after ``steps`` batches while the heartbeat
    thread keeps beating with a frozen count — the stall watchdog's
    prey."""
    if fault.get("hang_after_batches", {}).get(str(wid)) == steps:
        time.sleep(3600)


def _start_heartbeat(broker, wid: int,
                     result: Dict[str, Any]) -> threading.Event:
    """Worker-side lease analogue: publish ``{wid, steps}`` on the
    heartbeat topic every ``_HB_INTERVAL_S`` until the returned event is
    set.  ``steps`` rides along so the master's watchdog can tell a
    wedged-but-alive worker (beats arrive, progress doesn't) from a
    healthy one."""
    stop = threading.Event()

    def beat():
        while True:
            try:
                broker.publish(_HB, json.dumps(
                    {"wid": wid,
                     "steps": int(result.get("steps", 0))}).encode())
            except (ConnectionError, OSError):
                return    # hub gone: the master died or is tearing down
            if stop.wait(_HB_INTERVAL_S):
                return

    threading.Thread(target=beat, daemon=True,
                     name=f"mp-heartbeat-{wid}").start()
    return stop


def _worker_main(jobdir: str, wid: int, port: int,
                 resume_file: Optional[str] = None) -> None:
    with open(os.path.join(jobdir, "spec.json")) as f:
        spec = json.load(f)
    # re-root this process's spans under the master's context (from the
    # job spec); a no-op unless the worker enables its tracer (e.g. via
    # DL4J_TPU_TRACE=1 in worker_env)
    tracer = get_tracer()
    with contextlib.ExitStack() as stack:
        ctx = spec.get("trace")
        if ctx:
            stack.enter_context(tracer.attach(SpanContext.from_dict(ctx)))
        stack.enter_context(tracer.span("mp.worker", worker=wid,
                                        task=spec.get("task")))
        _worker_task(jobdir, wid, port, spec, resume_file)


def _worker_task(jobdir: str, wid: int, port: int, spec: Dict[str, Any],
                 resume_file: Optional[str] = None) -> None:
    resumed = resume_file is not None
    resume: Dict[str, Any] = {}
    if resumed:
        with open(resume_file) as f:
            resume = json.load(f)
    fault = {} if resumed else spec.get("fault", {})
    if fault.get("slow_start", {}).get(str(wid)):
        time.sleep(float(fault["slow_start"][str(wid)]))

    from ..streaming.broker import TcpMessageBroker
    from ..utils import model_serializer

    broker = TcpMessageBroker(port=port)    # client endpoints only
    result: Dict[str, Any] = {"wid": wid, "steps": 0, "resumed": resumed}
    hb_stop = _start_heartbeat(broker, wid, result)
    try:
        if resume.get("skip_to_done"):
            # predecessor crashed after its last fit contribution was
            # collected; nothing to redo — just report
            result.update({"skipped": True, "score": None})
            broker.publish(_DONE, json.dumps(result).encode())
            return
        _worker_run(broker, jobdir, wid, spec, resume, fault, result)
    finally:
        hb_stop.set()


def _worker_run(broker, jobdir: str, wid: int, spec: Dict[str, Any],
                resume: Dict[str, Any], fault: Dict[str, Any],
                result: Dict[str, Any]) -> None:
    from ..utils import model_serializer

    model = model_serializer.restore_multi_layer_network(
        os.path.join(jobdir, "model.zip"))
    batches = _load_batches(os.path.join(jobdir, f"shard_{wid}.npz"))

    task = spec["task"]
    if task == "fit" and spec["mode"] == "averaging":
        # hub-acked: registered before the first _UP publish, so the
        # averaged reply cannot race past this subscription
        down = broker.subscribe(_DOWN, ack=True)
        _, meta = _ravel(model, spec["average_updaters"])
        if resume.get("frame"):
            _unravel_into(model, np.load(resume["frame"]), meta)
        freq = spec["averaging_frequency"]
        for rnd in range(int(resume.get("start_round", 0)), spec["n_rounds"]):
            for batch in batches[rnd * freq:(rnd + 1) * freq]:
                model.fit_batch(batch)
                result["steps"] += 1
                _maybe_hang(fault, wid, result["steps"])
            if fault.get("die_before_publish", {}).get(str(wid)) == rnd:
                os._exit(3)
            vec, _ = _ravel(model, spec["average_updaters"])
            broker.publish(_UP, _encode_frame(wid, rnd, vec))
            # barrier timeout rides the master's configured deadline so a
            # fast worker can't abort a round the master would still accept
            payload = down.poll(timeout=float(spec["timeout"]))
            if payload is None:
                raise RuntimeError(f"worker {wid}: no averaged frame")
            _, got_rnd, avg = _decode_frame(payload)
            assert got_rnd == rnd, (got_rnd, rnd)
            _unravel_into(model, avg, meta)
    elif task == "fit":                     # shared gradients
        _worker_shared_fit(broker, model, batches, spec, resume, fault,
                           wid, result)
    elif task == "evaluate":
        if wid in fault.get("die_at_start", []):
            os._exit(3)
        from ..evaluation.classification import Evaluation
        ev = Evaluation()
        for x, y in batches:
            ev.eval(np.asarray(y), np.asarray(model.output(x)))
        result["evaluation"] = ev.to_json()
        result["n_examples"] = int(sum(np.asarray(x).shape[0]
                                       for x, _ in batches))
    elif task == "score":
        if wid in fault.get("die_at_start", []):
            os._exit(3)
        total, n = 0.0, 0
        for x, y in batches:
            bs = int(np.asarray(x).shape[0])
            total += model.score(x=x, y=y) * bs
            n += bs
        result["loss_sum"] = total
        result["n_examples"] = n
    else:
        raise ValueError(f"unknown task {task!r}")

    result["score"] = model.get_score() if task == "fit" else None
    if wid in fault.get("die_before_done", []):
        os._exit(3)
    broker.publish(_DONE, json.dumps(result).encode())
    if wid in fault.get("exit_nonzero_after_done", []):
        os._exit(5)


def _worker_shared_fit(broker, model, batches, spec, resume, fault,
                       wid: int, result: Dict[str, Any]) -> None:
    """Shared-gradients worker protocol — every arrival explicit:

    1. hub-acked subscriptions (gradients, flush, residual, go/seed);
    2. publish READY, wait for the master's GO.  A replacement respawned
       after GO instead performs a RESYNC handshake: having subscribed
       first (hub-acked), it asks the master for a seed — mirror table +
       folded residuals + per-sender sequence counts — so nothing
       published after the seed snapshot can be missed, and the
       seed/subscription overlap is deduped exactly by sequence number;
    3. train, publishing quantized updates and applying peers';
    4. publish FLUSH declaring the TOTAL sent-count (prior incarnations
       included, so peers' count barriers stay exact) and the handler's
       residual as one dense frame (quantization keeps the clipped excess
       at the sender — "delayed, never lost"; job end is where the delay
       runs out, so the remainder ships dense exactly once);
    5. drain until every peer's applied count (plus what the seed already
       contained) reaches its declared count and every peer's residual is
       accounted for, then add the residuals: each table becomes
       init + Σ(all workers' exact deltas), so the master's agreement
       check is a float-noise bound;
    6. publish the final table for the master's agreement check + mean.
    """
    import jax.numpy as jnp
    from jax.flatten_util import ravel_pytree

    from .accumulation import EncodingHandler
    from .remote import RemoteGradientSharing

    handler = EncodingHandler(initial_threshold=spec["threshold"])
    flush_sub = broker.subscribe(_FLUSH, ack=True)
    resid_sub = broker.subscribe(_RESID, ack=True)
    dead_sub = broker.subscribe(_DEAD, ack=True)
    timeout = float(spec["timeout"])
    post_go_resume = bool(resume.get("go_done"))
    prior_sent = 0
    declared: Dict[int, int] = {}
    mirror_counts: Dict[int, int] = {}
    resids_done: set = set()
    if not post_go_resume:
        sharing = RemoteGradientSharing(broker, wid, topic=_GRADS,
                                        handler=handler, ack=True)
        go_sub = broker.subscribe(_GO, ack=True)
        broker.publish(_READY, json.dumps({"wid": wid}).encode())
        if go_sub.poll(timeout=timeout) is None:
            raise RuntimeError(f"worker {wid}: no GO from master")
    else:
        # resync handshake: subscribe FIRST (hub-acked), then request the
        # seed — updates published after the seed snapshot arrive on the
        # subscription, updates before it are in the seed, and the seed's
        # per-sender counts dedup the overlap exactly (skip_seqs)
        grads_sub_first = broker.subscribe(_GRADS, ack=True)
        seed_sub = broker.subscribe(_SEED, ack=True)
        broker.publish(_READY, json.dumps(
            {"wid": wid, "resync": True}).encode())
        deadline = time.time() + timeout
        meta = None
        while meta is None:
            payload = seed_sub.poll(timeout=1.0)
            if payload is not None:
                d = json.loads(payload.decode())
                if int(d["wid"]) == wid:
                    meta = d
            elif time.time() > deadline:
                raise RuntimeError(f"worker {wid}: no resync seed")
        _, pmeta = _ravel(model, False)
        _unravel_into(model, np.load(meta["file"]), pmeta)
        prior_sent = int(meta["prior_sent"])
        declared = {int(k): int(v) for k, v in meta["declared"].items()}
        mirror_counts = {int(k): int(v)
                         for k, v in meta["mirror_counts"].items()}
        resids_done = set(int(w) for w in meta["resid_wids"])
        sharing = RemoteGradientSharing(
            broker, wid, topic=_GRADS, handler=handler,
            seq_base=prior_sent, skip_seqs=mirror_counts,
            sub=grads_sub_first)
    die_after = fault.get("die_after_batches", {}).get(str(wid))
    for i, batch in enumerate(batches):
        if die_after == i:
            os._exit(3)
        flat_before, unravel = ravel_pytree(model.params)
        flat_before = jnp.array(flat_before)
        model.fit_batch(batch)
        result["steps"] += 1
        _maybe_hang(fault, wid, result["steps"])
        flat_after, _ = ravel_pytree(model.params)
        sharing.publish_update(flat_after - flat_before)
        merged = sharing.apply_updates(flat_after, timeout=0.05)
        model.params = unravel(merged)
    broker.publish(_FLUSH, json.dumps(
        {"wid": wid, "sent": prior_sent + sharing.messages_sent}).encode())
    flat, unravel = ravel_pytree(model.params)
    flat = jnp.asarray(flat)
    resid = sharing.handler.residual
    resid = (np.zeros(int(flat.size), np.float32) if resid is None
             else np.asarray(resid, np.float32))
    broker.publish(_RESID, _encode_frame(wid, 0, resid))
    # drain barrier: applied[p] (+ the seed's mirror_counts[p]) must reach
    # p's declared count and p's residual must be in (directly or folded
    # into the seed) — a respawned peer's re-flush overwrites its declared
    # count (its earlier messages only push applied past it: >= holds).
    # A master eviction notice (_DEAD) marks a peer dead: it drops out of
    # the barrier immediately, so an evicted peer can never hold the
    # survivors hostage until their own deadline.
    resids: Dict[int, np.ndarray] = {}
    deadline = time.time() + timeout
    while True:
        missing = sharing.unresolved_peers(
            declared, spec["num_workers"], mirror_counts=mirror_counts,
            resids_seen=resids, resids_folded=resids_done)
        if not missing:
            break
        payload = flush_sub.poll(timeout=0.05)
        if payload is not None:
            d = json.loads(payload.decode())
            declared[int(d["wid"])] = int(d["sent"])
        payload = resid_sub.poll(timeout=0.05)
        if payload is not None:
            r_wid, _, r_vec = _decode_frame(payload)
            if r_wid != wid and r_wid not in resids_done:
                resids[r_wid] = r_vec
        payload = dead_sub.poll(timeout=0.001)
        if payload is not None:
            sharing.mark_dead(int(json.loads(payload.decode())["wid"]))
        # unbounded drain here: the barrier loop carries its own deadline
        flat = sharing.apply_updates(flat, timeout=0.05, max_messages=0)
        if time.time() > deadline:
            raise RuntimeError(
                f"worker {wid}: drain barrier incomplete, "
                f"missing peers {missing}")
    for p in sorted(resids):
        flat = flat + jnp.asarray(resids[p])
    model.params = unravel(flat)
    vec, _ = _ravel(model, False)
    broker.publish(_FINAL, _encode_frame(wid, 0, vec))
    result["messages_sent"] = sharing.messages_sent
    result["messages_applied"] = sharing.messages_applied
    result["applied_per_peer"] = {
        str(k): v for k, v in sorted(sharing.applied_per_peer.items())}


if __name__ == "__main__":
    _worker_main(sys.argv[1], int(sys.argv[2]), int(sys.argv[3]),
                 sys.argv[4] if len(sys.argv) > 4 else None)
