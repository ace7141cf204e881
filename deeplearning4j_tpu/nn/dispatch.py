"""Bounded asynchronous dispatch window of the fit loop,
``nn/_common.fit_batches`` (ISSUE 18).

JAX dispatch is asynchronous: a jitted step call returns device futures
immediately and the host is free to run step N+1's work (ETL wait,
ShapePolicy padding, h2d placement, listener/forensics bookkeeping)
while step N executes.  Left unbounded, that pipeline can run the host
arbitrarily far ahead of the device — deferred failures surface many
steps late, checkpoint saves capture a state the host believes exists
but the device hasn't produced, and runtime-queue memory grows with the
lead.  The whole-program-compilation argument (arxiv 1810.09868) says
keep work on-device and treat host round-trips as the tax; this module
bounds the tax's dual: how far the host may lead.

:class:`DispatchWindow` holds the loss tokens of in-flight steps.  Depth
semantics: at most ``depth`` steps are un-materialized at the moment a
new step is dispatched — :meth:`push` appends the fresh token then
blocks on the oldest until at most ``depth - 1`` remain, so ``depth=1``
reproduces the fully serial per-step-sync loop and the default
``depth=2`` overlaps one step of host work with device execution.

Contract-preserving drains (the fit loop owns these):

- epoch ends and checkpoint-due boundaries call :meth:`drain` so
  exact-resume parity and the one-sync-per-epoch listener cadence hold;
- a monitor-armed fit already materializes per step (PR 10's same-step
  NaN contract), which empties the window as a side effect;
- exception paths call :meth:`abandon` — never block in a ``finally``.

Every drained token is NaN-checked host-side (``v != v``) with the
token's own iteration, so a deferred device failure at step N surfaces
within the window bound attributed to N, not to the step the host
happened to be dispatching.
"""
from __future__ import annotations

import os
from collections import deque
from typing import Any, Callable, List, Optional, Tuple

from ..observability.clock import monotonic_s
from ..observability.tracer import get_tracer

DEFAULT_DEPTH = 2
ENV_VAR = "DL4J_TPU_DISPATCH_DEPTH"


def configured_depth(default: int = DEFAULT_DEPTH) -> int:
    """The in-flight window depth: ``DL4J_TPU_DISPATCH_DEPTH`` (min 1),
    else ``default``.  Read per fit, not per process — tests and the
    pipeline bench flip it between runs."""
    raw = os.environ.get(ENV_VAR)
    if not raw:
        return default
    try:
        depth = int(raw)
    except ValueError:
        return default
    return max(1, depth)


class DispatchWindow:
    """Bounded in-flight step window (see module docstring).

    owner: the network/model whose fit loop pushes here; drained tokens
    write ``owner.last_drained_score`` / ``owner.last_drained_iteration``
    so listeners can read steady-state rates at the drain boundary
    without forcing their own host sync.

    profiler: an armed :class:`~..observability.profiler.StepProfiler`
    (or None); each drained token calls ``profiler.drained(1)`` so the
    ``training_dispatch_depth`` gauge tracks real window occupancy.

    on_nan: callback ``(iteration, value)`` fired when a drained token
    materializes non-finite — the deferred-failure attribution hook.
    """

    __slots__ = ("depth", "owner", "profiler", "on_nan", "_window")

    def __init__(self, depth: Optional[int] = None, owner: Any = None,
                 profiler: Any = None,
                 on_nan: Optional[Callable[[int, float], None]] = None):
        self.depth = configured_depth() if depth is None \
            else max(1, int(depth))
        self.owner = owner
        self.profiler = profiler
        self.on_nan = on_nan
        self._window: deque = deque()

    def __len__(self) -> int:
        return len(self._window)

    def push(self, token: Any, iteration: int) -> None:
        """Admit one dispatched step's loss token; blocks on the oldest
        in-flight tokens until at most ``depth - 1`` remain (so the NEXT
        dispatch sees at most ``depth`` un-materialized steps)."""
        self._window.append((token, iteration))
        while len(self._window) > self.depth - 1:
            self._pop_block()

    def drain(self) -> None:
        """Materialize every in-flight token (epoch end, checkpoint-due
        boundary, explicit sync point)."""
        while self._window:
            self._pop_block()

    def drain_timed(self) -> List[Tuple[int, float]]:
        """Drain like :meth:`drain` but return ``(iteration,
        t_completed)`` per token — the profiler's pipeline-aware fence
        uses the completion spacing to attribute each drained step's
        device slice individually instead of billing the whole wait to
        the fenced step."""
        out = []
        while self._window:
            iteration = self._window[0][1]
            self._pop_block()
            out.append((iteration, monotonic_s()))
        return out

    def abandon(self) -> None:
        """Drop in-flight tokens WITHOUT blocking (exception paths: the
        loop's final un-guarded ``float(_score)`` still surfaces deferred
        failures through the param dependency chain)."""
        self._window.clear()

    def _pop_block(self) -> float:
        token, iteration = self._window.popleft()
        # float() alone is the sync: the loss is one output of the step's
        # single program, so its materialization implies the whole step
        # finished.  Deliberately NOT jax.block_until_ready — the stepprof
        # host-sync sweep counts those to pin the profiler's fence cadence,
        # and the window's bounded backpressure is loop-owned, not
        # profiler-owned.  The span is the host waiting for the device:
        # the healthy state.
        with get_tracer().span("dl4j.window_wait"):
            value = float(token)
        if self.owner is not None:
            self.owner.last_drained_score = value
            self.owner.last_drained_iteration = iteration
        if self.profiler is not None:
            self.profiler.drained(1)
        if value != value and self.on_nan is not None:
            self.on_nan(iteration, value)
        return value
