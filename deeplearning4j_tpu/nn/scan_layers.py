"""Scan-over-layers: trace one layer body instead of N identical ones.

A 24-block transformer traced layer by layer produces 24 copies of the
same subgraph — trace time, XLA compile time, and compiled-program size
all scale linearly with depth for zero runtime benefit.  When a stack
contains a run of layers with IDENTICAL configuration (same conf values,
names aside, repeated >= ``DL4J_TPU_SCAN_MIN`` times), the forward walk
stacks their params/state on a new leading axis and runs the one layer
body under ``jax.lax.scan`` — the Julia-to-TPU full-compilation paper's
point that structured control flow must reach XLA as control flow, not
as unrolled tape (arxiv 1810.09868).

Exact parity with the unrolled walk is preserved by construction:

  - per-layer RNG keys are precomputed as ``fold_in(key, i)`` — the same
    fold the unrolled loop performs — and scanned over as inputs;
  - the layer body is the layer's own ``apply`` on its own params/state
    slice, so the math per iteration is the unrolled math;
  - the body's mathematics does not depend on what is saved: whatever the
    backward recomputes is the same operations on the same inputs.

What a scanned run saves for the backward pass, stacked over the run
(each saved value is written into a ``[n, ...]`` stack in the forward
and read back in the backward: a copy each way, every step).  One rule:
the run saves each layer's input and a set ``kept`` of the values the
layer names, under ``jax.checkpoint`` with ``save_only_these_names``;
the backward recomputes the rest from them.  A layer declares
``SAVED_NAMES``, the values of its ``apply`` that are dear to recompute,
each tagged with ``jax.ad_checkpoint.checkpoint_name`` where it is
computed, in order of what the backward would repeat per byte stacked
(``TransformerBlock``: EVA attention's pooled keys, values and weights,
the MLP's pre-activation and its gate's, q, k, v after the head split,
the attention's log-sum-exp and output, the stream after the first add).

  without ``cache_mode='remat'``  ``kept`` is every name.  For a GPT-2
             block the backward recomputes the element-wise rest (norms,
             GELU, head merge): no matmul and no kernel runs twice.
             (What is neither named nor cheap is recomputed all the same:
             the scores of ``sdpa_reference``, which the flash kernels
             recompute by design.)  A GPT-2 medium block at 3 x 1024
             tokens saves 8 arrays, 63 MB, where a plain scan stacks 19,
             245 MB: on a TPU v5e the step is 5.5 ms of 89.0 shorter
             (``PERF.md`` sections 5 and 6, PR 30).
  under ``cache_mode='remat'``  ``kept`` is **what fits**, derived and
             never set.  The bytes of each name come from an abstract
             trace of the body as the backward differentiates it
             (:func:`body_census`; the names inside a kernel's forward
             rule are there), times the run's length.  The room is the
             device's ``memory_stats()["bytes_limit"]`` less what the
             step being traced has declared it holds (:func:`holding`:
             ``nn/_common.build_train_step`` declares its arguments, an
             epoch scan its dataset), less the run's own stacks (the
             weights as the walk hands them, their gradients, the layers'
             inputs), less a reserve for one layer's backward:
             ``RESERVE`` times the bytes of the forward's values that the
             transposed body reads, from the same trace.  The names are
             taken greedily in the layer's order, one that does not fit
             passed over (:func:`fitting`).  Where the device reports no
             limit (the CPU) or no step declared anything, the room is
             nought, ``kept`` is empty and the program is a bare
             ``jax.checkpoint`` around the body: the layer's input alone,
             the run's forward FLOPs once more a step.  On a TPU v5e the
             benchmark's ``evabyte-4l.train-fit-long`` (four blocks 4096
             wide on 8192 positions beside 9.86 GB of state) keeps the
             pooled three, the MLP's pre-activation and q, 1.03 GB, of
             eleven names that would stack 3.1 GB (``PERF.md`` sections 5
             to 7, PR 32).
  a layer that names nothing  (``Dense``, ``LSTM``, conv stacks, a block
             of ring or all-to-all attention) keeps ``lax.scan``'s own
             program, whose partial evaluation stacks every intermediate
             the transposed body reads (an inner time scan or a loop of
             collectives is never replayed), and under remat the bare
             ``jax.checkpoint``.

A run inside a looped range (``ListBuilder.loop``: a stretch of the list
walked ``passes`` times on one set of weights, ``nn/multilayer._Walk.loop``)
is this scan under an outer ``lax.scan`` over the passes, unrolled whole,
so the compiled step holds one ``while`` over the layers a pass and
direction and no loop around them.  The body and what it may keep are the
same; what the run's stacks take is counted for what the step then holds:
every name and every layer's input ``passes`` times (each pass's run
stacks its own residuals), each pass's output, the weights as the pass
casts them and one pass's gradients once, and the loop's float32 sums of
the weights' gradients, which unlike a plain run's gradients are live all
through the backward pass.  The compiler's buffer assignment also needs
more than is live at once: ``LOOP_FRAGMENTATION`` of the run's own
stacks, its reserve and each name's bytes is taken off the room (measured
compile-only, as ``RESERVE`` was).  Such a run counts into
``loop_runs_traced_total{layers, passes, saved}`` and sets
``loop_saved_stack_bytes`` beside the two below.

Each scanned run traced into a training step counts into
``scan_runs_traced_total{layer, saved}``: ``all`` (nothing named, no
remat), ``named`` (every name), ``some`` (remat, a part of them),
``input`` (remat, none); ``scan_saved_stack_bytes{layer}`` holds the
bytes the last such run stacks beyond its inputs, and a remat run logs
what it kept, its bytes and the room.

Eligibility (anything else falls back to the unrolled walk, which stays
bit-identical): dataclass confs equal ignoring ``name``; no preprocessor
strictly inside the run; no recurrent carry in flight (tBPTT /
rnn_time_step walk unrolled); no AUX_LOSS (MoE) layers; no per-layer
``PrecisionPolicy`` override inside the run; mask propagation must be
the identity (a layer overriding ``feed_forward_mask`` breaks the run
only when a mask is actually present); not an activation-collecting walk
(``feed_forward`` needs every layer's output); a run never crosses the
bounds of a looped range (the walk asks for the runs of each stretch:
before the range, inside it, after it).

Opt out with ``DL4J_TPU_SCAN_LAYERS=0`` or per-conf via the builder's
``.scan_layers(False)`` (a looped range's passes are then a Python loop
too); ``.scan_layers(k)`` overrides the minimum run
length.
"""
from __future__ import annotations

import contextlib
import contextvars
import copy
import json
import logging
import os
from typing import Dict, List, Optional, Tuple

import numpy as np

__all__ = ["scan_runs", "run_scan", "holding", "DEFAULT_MIN_RUN"]

log = logging.getLogger(__name__)

DEFAULT_MIN_RUN = 4


def _min_run(conf) -> int:
    """Configured minimum homogeneous-run length, or 0 when scanning is
    disabled for this conf/process."""
    mode = conf.defaults.get("scan_layers")
    if mode is False or mode == 0:     # 0 mirrors DL4J_TPU_SCAN_LAYERS=0
        return 0
    if os.environ.get("DL4J_TPU_SCAN_LAYERS", "1").lower() in \
            ("0", "off", "false") and mode is None:
        return 0
    if isinstance(mode, bool) or mode is None:
        return int(os.environ.get("DL4J_TPU_SCAN_MIN",
                                  str(DEFAULT_MIN_RUN)))
    return max(2, int(mode))


def _layer_sig(lc, mask_present: bool, carries_present: bool,
               policy) -> Optional[str]:
    """Value signature of one layer for run grouping, or None when the
    layer cannot participate in a scan run."""
    import dataclasses

    from .compile_cache import _encode
    from .layers.base import LayerConf

    if not dataclasses.is_dataclass(lc):
        return None
    if carries_present and getattr(lc, "HAS_CARRY", False):
        return None
    if getattr(lc, "AUX_LOSS", False):
        return None
    if mask_present and type(lc).feed_forward_mask \
            is not LayerConf.feed_forward_mask:
        return None
    if policy is not None and policy.overrides and \
            getattr(lc, "name", None) in policy.overrides:
        return None
    neutral = copy.copy(lc)
    neutral.name = None
    try:
        payload = json.dumps(_encode(neutral, set()), sort_keys=True,
                             separators=(",", ":"), default=repr)
    except Exception:
        return None
    if "@id" in payload:
        # an identity token means the conf has unencodable values — two
        # layers could never compare equal by value, so no run forms
        return None
    return payload


def scanning(conf) -> bool:
    """Does this conf's walk scan at all?  (Off: runs of layers, and a
    looped range's passes, are walked unrolled.)"""
    return _min_run(conf) > 0


def scan_runs(conf, n: int, *, mask_present: bool, carries_present: bool,
              collect: bool, policy=None, lo: int = 0
              ) -> List[Tuple[int, int]]:
    """Eligible homogeneous runs ``[(start, stop), ...]`` (half-open)
    within ``conf.layers[lo:n]``.  Pure trace-time work — called once per
    stretch traced, never per step."""
    min_run = _min_run(conf)
    if collect or min_run <= 0 or n - lo < min_run:
        return []
    sigs = {i: _layer_sig(conf.layers[i], mask_present, carries_present,
                          policy) for i in range(lo, n)}
    runs: List[Tuple[int, int]] = []
    i = lo
    while i < n:
        if sigs[i] is None:
            i += 1
            continue
        j = i + 1
        # a preprocessor BEFORE layer j would run mid-scan: break the run
        # (one before layer i is fine — it applies ahead of the run)
        while j < n and sigs[j] == sigs[i] and \
                conf.preprocessor(j) is None:
            j += 1
        if j - i >= min_run:
            runs.append((i, j))
        i = j
    return runs


def _count_run(layer: str, saved: str, stack_bytes: int) -> None:
    """One scanned training run traced: trace-time work, like
    ``training_compile_total``."""
    from ..observability.registry import default_registry
    reg = default_registry()
    if reg.enabled:
        reg.counter("scan_runs_traced_total",
                    "Scanned layer runs traced into a training step, by "
                    "what the run saves for the backward pass",
                    ("layer", "saved")).labels(layer, saved).inc()
        reg.gauge("scan_saved_stack_bytes",
                  "Bytes the last scanned run traced stacks for the "
                  "backward pass beyond its layers' inputs",
                  ("layer",)).labels(layer).set(stack_bytes)


def _count_loop(n_run: int, passes: int, saved: str, all_bytes: int) -> None:
    """The same for a run inside a looped range, counted as a loop too."""
    from ..observability.registry import default_registry
    reg = default_registry()
    if reg.enabled:
        reg.counter("loop_runs_traced_total",
                    "Scanned runs of a looped range traced into a training "
                    "step, by the run's layers, the passes over it and what "
                    "a layer-pass saves for the backward pass",
                    ("layers", "passes", "saved")).labels(
                        str(n_run), str(passes), saved).inc()
        reg.gauge("loop_saved_stack_bytes",
                  "Bytes the last looped run traced stacks for the "
                  "backward pass over all its passes: its layers' inputs "
                  "and what it keeps beside them").set(all_bytes)


# ---- what a training program holds while a run is traced -----------------

_held = contextvars.ContextVar("dl4j_tpu_step_held_bytes", default=None)


def tree_bytes(tree) -> int:
    """Bytes of a pytree's array leaves, from their shapes."""
    import jax
    return sum(int(np.prod(a.shape)) * np.dtype(a.dtype).itemsize
               for a in jax.tree_util.tree_leaves(tree)
               if hasattr(a, "shape") and hasattr(a, "dtype"))


@contextlib.contextmanager
def holding(*trees):
    """While a training program is traced: its arguments (parameters,
    optimizer state, batch; a device-resident dataset around an epoch
    scan) lie in the device's memory beside whatever a scanned run
    stacks.  Nested programs add up."""
    token = _held.set((_held.get() or 0) + tree_bytes(trees))
    try:
        yield
    finally:
        _held.reset(token)


def _device_limit() -> Optional[int]:
    """``bytes_limit`` of the device a step runs on by default; ``None``
    where the backend reports none (the CPU)."""
    import jax
    dev = jax.config.jax_default_device
    if not hasattr(dev, "memory_stats"):
        dev = jax.local_devices()[0]
    return (dev.memory_stats() or {}).get("bytes_limit")


def free_bytes() -> int:
    """What the device has left beside what the program being traced has
    declared it holds: nought where no step declared anything, the device
    reports no limit or the compiler has refused this program once, so
    that a remat run keeps its input alone."""
    held = _held.get()
    if held is None or _input_alone.get():
        return 0
    limit = _device_limit()
    return max(0, int(limit) - held) if limit else 0


def _claim(n_bytes: int) -> None:
    """A traced run's stacks join what the step holds: a later run of the
    same step sees the room that is left."""
    if _held.get() is not None:
        _held.set(_held.get() + n_bytes)


# ---- when the compiler refuses what the arithmetic allowed ----------------
# The room is arithmetic on shapes; whether a program fits is the
# compiler's buffer assignment, which fragments by a third to a half of
# the step's temporaries and not smoothly in the shapes (PERF.md section
# 7).  So the first call of a jitted program that the compiler refuses for
# memory after a remat run kept names is traced once more with every such
# run keeping its input alone (``compile_cache.InstrumentedJit``): a job
# that compiled before remat kept anything still compiles.

_input_alone = contextvars.ContextVar("dl4j_tpu_scan_input_alone",
                                      default=False)
_kept_runs = contextvars.ContextVar("dl4j_tpu_scan_kept_runs", default=0)


@contextlib.contextmanager
def input_alone():
    """While a program is traced: remat runs keep their input alone."""
    token = _input_alone.set(True)
    try:
        yield
    finally:
        _input_alone.reset(token)


def kept_runs() -> int:
    """How many remat runs that kept a name this thread has traced."""
    return _kept_runs.get()


def refused_for_memory(error: BaseException) -> bool:
    """Is ``error`` the compiler's refusal of a program that does not fit
    the device's memory?  (Its words, not a failed allocation at run
    time, after which donated arguments are gone.)"""
    text = str(error)
    return "RESOURCE_EXHAUSTED" in text and \
        "Ran out of memory in memory space" in text


# ---- the set of names a run saves ----------------------------------------

# The reserve for one body's backward, in multiples of the bytes its
# transposed form reads from its forward (PERF.md section 7: the TPU's
# compiler was asked, compile-only, at four shapes).
RESERVE = 0.77


# What a run inside a looped range (the scan over the layers in each of
# the unrolled passes) needs beyond what is live at once, as a share of
# it: the TPU's compiler was asked, compile-only, for eight blocks 2048
# wide walked four times at 8192 tokens beside 7.35 GB of state.  Under a
# loop over the passes (PR 40) its buffer assignment read 4.81 G of
# fragmentation on 4.84 G live keeping q, 5.90 on 5.13 keeping q and k,
# 6.22 on 6.01 keeping q, k and v; all three refused, the inputs alone fit
# with 0.5 GiB to spare (PERF.md section 7).  With the passes unrolled
# the constant stays: at 0 the rule keeps q, k and v and the compiler
# refuses the step at 16.70 G of 15.75 (PR 41).
LOOP_FRAGMENTATION = 0.5


def _named_bytes(jaxpr, into: Dict[str, int]) -> None:
    """Bytes by ``checkpoint_name`` over a jaxpr and the jaxprs inside its
    equations (a jitted helper, the forward rule of a kernel)."""
    from jax.extend import core
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "name":
            name = eqn.params["name"]
            into[name] = into.get(name, 0) + tree_bytes(eqn.outvars[0].aval)
        for v in eqn.params.values():
            for sub in (v if isinstance(v, (tuple, list)) else (v,)):
                if isinstance(sub, core.ClosedJaxpr):
                    sub = sub.jaxpr
                if isinstance(sub, core.Jaxpr):
                    _named_bytes(sub, into)


def body_census(body, carry, per_layer) -> Tuple[Dict[str, int], int]:
    """``({name: bytes}, residual bytes)`` of one layer of a run, from an
    abstract trace of ``body`` as the backward differentiates it (so the
    names inside a kernel's forward rule are there): what each
    ``checkpoint_name`` would add to the run's stacks a layer, and the
    bytes of the forward's values that the transposed body reads, its
    arguments aside: what one body's backward holds live."""
    import jax
    from jax.extend import core

    def abstract(tree):
        return jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), tree)
    p, s, k = per_layer

    def differentiated(c, p_, s_, k_):
        return jax.vjp(lambda c_, pp: body(c_, (pp, s_, k_)), c, p_)
    closed, shape = jax.make_jaxpr(differentiated, return_shape=True)(
        abstract(carry), abstract(p), abstract(s), abstract(k))
    names: Dict[str, int] = {}
    _named_bytes(closed.jaxpr, names)
    n_out = len(jax.tree_util.tree_leaves(shape[0]))
    given = set(closed.jaxpr.invars) | set(closed.jaxpr.constvars)
    read = {v for v in closed.jaxpr.outvars[n_out:]
            if isinstance(v, core.Var) and v not in given}
    return names, tree_bytes([v.aval for v in read])


def fitting(sizes: Dict[str, int], room: int) -> Tuple[str, ...]:
    """Greedy in the order given: each name that still fits is kept, one
    that does not is passed over."""
    kept = []
    for name, n_bytes in sizes.items():
        if n_bytes <= room:
            kept.append(name)
            room -= n_bytes
    return tuple(kept)


def run_scan(lc, params_slices, state_slices, h, key, start: int,
             *, train: bool, mask, remat: bool, room: Optional[int] = None,
             passes: int = 1):
    """Execute one homogeneous run under ``jax.lax.scan``.

    ``params_slices``/``state_slices``: the per-layer pytrees in stack
    order.  Returns ``(h, new_state_slices)`` with the same per-layer
    structure the unrolled walk would have produced.  ``room``: the bytes
    a remat run may spend on stacks of its own (the step's arguments
    already taken off); read from the device and the step being traced
    where not given.  ``passes``: how often a step walks this run (a run
    inside a looped range, one of the unrolled passes): what the run
    saves a layer is stacked that many times.
    """
    import jax
    import jax.numpy as jnp

    n_run = len(params_slices)
    stacked_p = jax.tree_util.tree_map(lambda *a: jnp.stack(a),
                                       *params_slices)
    stacked_s = jax.tree_util.tree_map(lambda *a: jnp.stack(a),
                                       *state_slices)
    keys = None
    if key is not None:
        # EXACTLY the unrolled loop's per-layer fold, precomputed and
        # scanned over — parity with the unrolled path is bit-exact
        keys = jnp.stack([jax.random.fold_in(key, start + i)
                          for i in range(n_run)])

    def body(carry, per_layer):
        p, s, k = per_layer
        # the layer's scope, as the unrolled walk names it
        with jax.named_scope(type(lc).__name__):
            y, ns = lc.apply({"params": p, "state": s}, carry, train=train,
                             key=k, mask=mask)
        return y, ns

    names = tuple(getattr(lc, "SAVED_NAMES", ())) if train else ()
    kept, stack_bytes, saved = names, 0, "input" if remat else "all"
    if names:
        per_layer, reads = body_census(body, h, (
            params_slices[0], state_slices[0], None if keys is None
            else jax.ShapeDtypeStruct(keys.shape[1:], keys.dtype)))
        sizes = {n: per_layer[n] * n_run * passes
                 for n in names if n in per_layer}
        if remat:
            # what the run stacks whatever it keeps: the weights as the
            # walk hands them, their gradients, each layer's input; in a
            # looped range the inputs once a pass, each pass's output, and
            # the loop's float32 sums of the gradients
            own = 2 * tree_bytes(stacked_p) + passes * n_run * tree_bytes(h)
            if passes > 1:
                own += passes * tree_bytes(h) + 4 * sum(
                    int(np.prod(a.shape))
                    for a in jax.tree_util.tree_leaves(stacked_p))
            reserve = int(RESERVE * reads)
            room = (free_bytes() if room is None else room) - own - reserve
            costs = sizes
            if passes > 1:
                # a looped run: the compiler's buffer assignment wants
                # half as much again as is live at once
                room -= int(LOOP_FRAGMENTATION * (own + reserve))
                costs = {n: int(v * (1 + LOOP_FRAGMENTATION))
                         for n, v in sizes.items()}
            kept = fitting(costs, room)
            log.info("scan of %d %s under remat keeps %s: %d bytes of %d "
                     "free (beside %d of its own stacks and %d reserved "
                     "for one layer's backward); passed over %s",
                     n_run, type(lc).__name__, list(kept),
                     sum(sizes[n] for n in kept), max(room, 0), own,
                     reserve, [n for n in sizes if n not in kept])
            _claim(own + sum(sizes[n] for n in kept))
            _kept_runs.set(kept_runs() + bool(kept))
        stack_bytes = sum(sizes.get(n, 0) for n in kept)
        saved = "named" if len(kept) >= len(sizes) else \
            "some" if kept else "input"
    if kept:
        # the run saves each layer's input and the values named in
        # ``kept``; the backward recomputes the rest from them.  In a scan
        # nothing can be shared with the recomputation, so no barrier
        # against common-subexpression elimination is needed; under remat
        # it stays, as the bare checkpoint has it: a step that fills the
        # device is scheduled by memory, and without the barrier the same
        # kept set reads 15-18 ms a step slower (PERF.md section 6, PR 32)
        body = jax.checkpoint(
            body, prevent_cse=remat,
            policy=jax.checkpoint_policies.save_only_these_names(*kept))
    elif remat:
        body = jax.checkpoint(body)
    if train:
        _count_run(type(lc).__name__, saved, stack_bytes)
        if passes > 1:
            _count_loop(n_run, passes, saved,
                        passes * n_run * tree_bytes(h) + stack_bytes)
    # explicit length: a paramless/stateless run at inference (no keys)
    # has no xs leaves for scan to infer it from
    h, stacked_ns = jax.lax.scan(body, h, (stacked_p, stacked_s, keys),
                                 length=n_run)
    new_states = [jax.tree_util.tree_map(lambda a, _i=i: a[_i], stacked_ns)
                  for i in range(n_run)]
    return h, new_states
