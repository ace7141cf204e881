"""Shared training machinery for MultiLayerNetwork, ComputationGraph and
the mesh wrappers (``parallel/wrapper.py``): the one train-step builder
(:func:`build_train_step`) and the two places a training loop lives,
:func:`fit_batches` (host-fed batches, one dispatch a step) and
:func:`fit_on_device_epochs` (the dataset in HBM, one dispatch an epoch).

Also one copy of the updater-block construction (reference
``nn/updater/BaseMultiLayerUpdater.java:64-138`` builds per-block updaters for
MLN and ``nn/updater/graph/ComputationGraphUpdater.java`` for graphs — same
logic there too), gradient-normalization pre-apply (:318) and constraint
application, keyed by a ``name -> layer-conf`` map that both network types
produce.
"""
from __future__ import annotations

import time
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import optax

from . import precision as _precision
from . import scan_layers as _scan_layers
from . import sparse as _sparse
from .dispatch import DispatchWindow
from .layers.base import BaseLayerConf, LayerConf
from .layers.moe import publish_expert_tokens
from .layers.recurrent import publish_exit_mass
from ..data.pipeline import ETL_BUCKETS as _ETL_BUCKETS
from ..observability.clock import monotonic_s, wall_s
from ..observability.registry import default_registry
from ..observability.tracer import get_tracer

# training-step histogram bounds: sub-ms CPU steps up to multi-second
# XLA compiles in the "compile" phase series
_STEP_BUCKETS = (0.001, 0.0025, 0.005, 0.01, 0.025, 0.05, 0.1, 0.25,
                 0.5, 1.0, 2.5, 5.0, 10.0, 30.0)


def hyperparam_conf(lc: Optional[LayerConf]) -> Optional[BaseLayerConf]:
    """The conf that carries hyperparams (updater/constraints/normalization):
    wrappers (Bidirectional, LastTimeStep, FrozenLayer) delegate to the layer
    they wrap."""
    seen = set()
    while lc is not None and id(lc) not in seen:
        seen.add(id(lc))
        if isinstance(lc, BaseLayerConf):
            return lc
        inner = getattr(lc, "underlying", None) or getattr(lc, "fwd", None) \
            or getattr(lc, "layer", None)
        lc = inner
    return None


def float_grad_leaves(tree):
    """FLOAT gradient leaves only — the one predicate every norm/stat/
    unscale stage shares: a ``SparseRows`` carrier (``nn/sparse``)
    contributes its int32 row indices as pytree leaves, and reductions
    or scaling over row ids would silently corrupt which rows the
    update lands on."""
    return [g for g in jax.tree_util.tree_leaves(tree)
            if jnp.issubdtype(g.dtype, jnp.floating)]


def map_float_grads(fn, grads):
    """tree_map ``fn`` over float gradient leaves only; non-float
    leaves (``SparseRows`` indices) pass through untouched — see
    :func:`float_grad_leaves`."""
    return jax.tree_util.tree_map(
        lambda g: fn(g) if jnp.issubdtype(g.dtype, jnp.floating) else g,
        grads)


def apply_gradient_normalization(mode: Optional[str], threshold: float, grads):
    """Reference BaseMultiLayerUpdater.preApply :318.

    Norms reduce over float leaves only (see :func:`float_grad_leaves`);
    for a densified-sparse gradient the coalesced values carry exactly
    the dense gradient's nonzero entries, so every norm here equals its
    dense counterpart."""
    if not mode or mode == "none":
        return grads
    mode = mode.lower()
    leaves = float_grad_leaves(grads)
    if mode == "renormalizel2perlayer":
        norm = jnp.sqrt(sum(jnp.sum(g * g) for g in leaves))
        return map_float_grads(lambda g: g / (norm + 1e-8), grads)
    if mode == "renormalizel2perparamtype":
        return map_float_grads(
            lambda g: g / (jnp.linalg.norm(g.reshape(-1)) + 1e-8), grads)
    if mode == "clipelementwiseabsolutevalue":
        return map_float_grads(
            lambda g: jnp.clip(g, -threshold, threshold), grads)
    if mode == "clipl2perlayer":
        norm = jnp.sqrt(sum(jnp.sum(g * g) for g in leaves))
        scale = jnp.minimum(1.0, threshold / (norm + 1e-8))
        return map_float_grads(lambda g: g * scale, grads)
    if mode == "clipl2perparamtype":
        def clip(g):
            n = jnp.linalg.norm(g.reshape(-1))
            return g * jnp.minimum(1.0, threshold / (n + 1e-8))
        return map_float_grads(clip, grads)
    raise ValueError(f"unknown gradient normalization '{mode}'")


def is_frozen(lc: Optional[LayerConf]) -> bool:
    return bool(getattr(lc, "FROZEN", False))


def build_tx(default_u, confs: Dict[str, Optional[LayerConf]],
             params: Dict[str, Any]) -> optax.GradientTransformation:
    """One optax transform; per-layer/bias overrides via multi_transform.
    Frozen groups get ``optax.set_to_zero`` (no update, no updater state)."""
    resolved = {name: hyperparam_conf(lc) for name, lc in confs.items()}
    frozen = {name for name, lc in confs.items() if is_frozen(lc)}
    has_override = any(
        lc is not None and (lc.updater is not None or lc.bias_updater is not None)
        for name, lc in resolved.items() if name not in frozen)
    if not has_override and not frozen:
        return default_u.to_optax()
    transforms = {"default": default_u.to_optax(),
                  "frozen": optax.set_to_zero()}
    labels = {}
    for name, pgroup in params.items():
        lc = resolved.get(name)
        if name in frozen:
            labels[name] = {p: "frozen" for p in pgroup}
            continue
        if lc is None or (lc.updater is None and lc.bias_updater is None):
            labels[name] = {p: "default" for p in pgroup}
            continue
        lu = lc.updater or default_u
        bu = lc.bias_updater
        wl = f"{name}/w"
        transforms[wl] = lu.to_optax()
        lab = {}
        for pname in pgroup:
            if bu is not None and pname in lc._BIAS_PARAMS:
                bl = f"{name}/b"
                transforms[bl] = bu.to_optax()
                lab[pname] = bl
            else:
                lab[pname] = wl
        labels[name] = lab
    return optax.multi_transform(transforms, labels)


def apply_gradient_norm_all(grads, confs: Dict[str, Optional[LayerConf]],
                            gn_mode, gn_thr):
    """Per-group preApply; a layer's own setting REPLACES the global one."""
    for name, lc in confs.items():
        hc = hyperparam_conf(lc)
        own = getattr(hc, "gradient_normalization", None)
        m = own or gn_mode
        if m and grads.get(name):
            t = getattr(hc, "gradient_normalization_threshold", None)
            t = float(t) if t is not None and own else gn_thr
            grads[name] = apply_gradient_normalization(m, t, grads[name])
    return grads


def apply_constraints_all(params, confs: Dict[str, Optional[LayerConf]]):
    """Reference applyConstraints after each step."""
    for name, lc in confs.items():
        hc = hyperparam_conf(lc)
        cs = getattr(hc, "constraints", None)
        if cs and params.get(name):
            pgroup = dict(params[name])
            for c in cs:
                for pname in pgroup:
                    is_bias = pname in hc._BIAS_PARAMS
                    if (is_bias and c.apply_to_biases) or \
                       (not is_bias and c.apply_to_weights):
                        pgroup[pname] = c.apply(pgroup[pname])
            params[name] = pgroup
    return params


def _cast_floats(tree, dtype, only=None):
    """Cast floating leaves to ``dtype`` (mixed-precision helper).  With
    ``only`` set, cast just the leaves currently of that dtype (used to pin
    state back to f32 after a bf16 forward)."""
    dtype = jnp.dtype(dtype)
    src = None if only is None else jnp.dtype(only)

    def cast(a):
        if not hasattr(a, "dtype") or not jnp.issubdtype(a.dtype,
                                                         jnp.floating):
            return a
        if src is not None and a.dtype != src:
            return a
        if src is None and a.dtype != jnp.float32:
            return a
        return a.astype(dtype)

    return jax.tree_util.tree_map(cast, tree)


def _cast_act(h, dtype: Optional[str]):
    """Cast a floating activation to a policy dtype (ints — token ids —
    pass through untouched)."""
    if dtype is None or not hasattr(h, "dtype") or \
            not jnp.issubdtype(h.dtype, jnp.floating) or \
            str(h.dtype) == dtype:
        return h
    return h.astype(dtype)


def compute_dtypes(defaults: Dict[str, Any],
                   confs: Dict[str, Any]) -> Dict[str, str]:
    """Per-layer compute dtypes under the conf's precision policy,
    resolved once at build time (keep_f32 classes and per-name overrides
    stay f32 — their params are never downcast, and the forward walk casts
    activations to match)."""
    pol = _precision.resolve(defaults)
    if pol is None:
        return {}
    dtypes = {name: pol.layer_dtype(lc) for name, lc in confs.items()}
    return {name: dt for name, dt in dtypes.items()
            if dt not in (None, "float32")}


def build_train_step(loss, defaults: Dict[str, Any],
                     confs: Dict[str, Optional[LayerConf]],
                     cast_map: Dict[str, str],
                     tx: optax.GradientTransformation, *,
                     sparse=None, with_carry: bool = False):
    """The train step of both containers: forward + loss + backward +
    update as ONE function to jit, ``step(params, state, opt_state, key,
    x, y, mask, label_mask[, carries]) -> (params, state, opt_state, key,
    loss, gstats[, carries])``.

    ``loss(params, state, x, y, mask, label_mask, *, key, precision[,
    carries]) -> (loss, new_state)`` is the container's own walk
    (``_stack_loss`` / ``_graph_loss`` over its conf; ``x``/``y`` are what
    the container's ``fit`` hands the step: arrays for the stack, lists
    for the graph).  ``confs`` maps each parameter group to its layer
    conf, ``cast_map`` to its compute dtype (:func:`compute_dtypes`).
    ``sparse=(group, conf)`` names a sparse-gradient embedding whose ids
    ARE the batch input (``nn/sparse``): its rows are coalesced outside
    the differentiated function and the update runs in row space.
    ``with_carry`` threads recurrent carries through the step (tBPTT)."""
    gn_mode = defaults.get("gradient_normalization")
    gn_thr = float(defaults.get("gradient_normalization_threshold", 1.0))
    pol = _precision.resolve(defaults)

    def step(params, state, opt_state, key, x, y, mask, label_mask,
             carries=None):
        # fused RNG succession: the split that used to run host-side
        # (``self._rng, key = jax.random.split(self._rng)``) happens
        # inside the program — bit-identical key sequence, one less
        # device dispatch per step, and the key argument gains an
        # alias-matched output (``new_rng``) so it can be donated
        new_rng, key = jax.random.split(key)
        if pol is not None:
            # floating inputs only: integer token ids must reach the
            # embedding gather exact (a bf16 cast quantizes ids > 256)
            x = jax.tree_util.tree_map(
                lambda a: _cast_act(a, pol.compute_dtype), x)
        # sparse-embedding pre-pass (nn/sparse): coalesce the batch's
        # touched table rows OUTSIDE the differentiated function and
        # substitute (table -> gathered rows, ids -> row slots), so the
        # table's cotangent is [capacity, dim] — the dense [vocab, dim]
        # cotangent never exists in this program.  All decisions here
        # are trace-time static (dtype/shape/conf), so the compiled
        # program is fixed per batch signature: zero steady recompiles.
        ctx = None
        if sparse is not None:
            group, emb = sparse
            W0 = params[group]["W"]
            ids = emb.decode_ids(x)
            if ids is None:
                # never a silent dense fallback: falling through here
                # would quietly restore the O(vocab·dim) exchange the
                # flag exists to remove
                raise ValueError(
                    f"layer '{emb.name}': sparse_grad=True needs "
                    "an integer id batch for the densified pre-pass, but "
                    f"this input (shape {tuple(x.shape)}, dtype "
                    f"{x.dtype}) rides the one-hot path — feed ids "
                    "(argmax the one-hots upstream), or drop sparse_grad")
            if not _sparse.table_is_unambiguous(params, W0.shape):
                raise ValueError(
                    f"layer '{emb.name}': another parameter leaf "
                    f"shares the table's exact shape {tuple(W0.shape)} — "
                    "the row-space mirror walk is shape-keyed and cannot "
                    "disambiguate the updater mirrors; resize/split the "
                    "twin parameter or drop sparse_grad")
            ctx = _sparse.RowContext(W0, ids, emb.sparse_grad_capacity)
            params_in = {**params, group: dict(params[group],
                                               W=ctx.rows_ext)}
            x_in = ctx.x_sub
        else:
            params_in, x_in = params, x
        ls = state.get(_precision.SCALE_STATE_KEY) \
            if pol is not None and pol.scaled else None
        scale = ls["scale"] if ls is not None else None

        # scopes are metadata: under value_and_grad the forward's
        # operations are named jvp(forward)/<layer>/..., the backward's
        # transpose(jvp(forward))/<layer>/...
        @jax.named_scope("forward")
        def loss_fn(p):
            if cast_map:
                # mixed precision: cast params per layer for the traced
                # walk; grads w.r.t. the f32 masters accumulate in f32
                # (the cast is part of the differentiated program)
                p = {k: (_cast_floats(v, cast_map[k]) if k in cast_map
                         else v) for k, v in p.items()}
            if with_carry:
                # carry state flows INTO the chunk; gradients do not flow
                # back across the chunk boundary (tBPTT truncation).
                cs = dict(jax.tree_util.tree_map(jax.lax.stop_gradient,
                                                 carries))
                loss_value, new_state = loss(
                    p, state, x_in, y, mask, label_mask, key=key,
                    carries=cs, precision=pol)
            else:
                cs = None
                loss_value, new_state = loss(
                    p, state, x_in, y, mask, label_mask, key=key,
                    precision=pol)
            # loss scaling happens on the objective so the whole backward
            # pass sees scaled gradients (fp16 underflow protection); the
            # reported loss stays unscaled
            obj = loss_value * scale if scale is not None else loss_value
            return obj, (loss_value, new_state, cs)
        # a scanned run under remat keeps what fits beside these
        with _scan_layers.holding(params, state, opt_state, key, x, y, mask,
                                  label_mask, carries):
            (_obj, (loss_value, new_state, new_carries)), grads = \
                jax.value_and_grad(loss_fn, has_aux=True)(params_in)
        if ctx is not None:
            # the densified carrier: coalesced row indices + values (the
            # custom-vjp lookup's segment-summed cotangent), in place of
            # a dense table gradient
            grads = dict(grads)
            grads[group] = dict(grads[group],
                                W=ctx.wrap_grad(grads[group]["W"]))
        finite = None
        with jax.named_scope("grad_post"):
            if scale is not None:
                grads, finite = _precision.unscale_and_check(grads, scale)
            grads = apply_gradient_norm_all(grads, confs, gn_mode, gn_thr)
            # per-iteration gradient stats for listeners (reference
            # ParamAndGradientIterationListener / StatsListener): computed
            # inside the same program so they fuse with the update.  Float
            # leaves only (float_grad_leaves): SparseRows carries int32
            # indices, and coalesced values give the SAME norm the dense
            # gradient would.
            gleaves = float_grad_leaves(grads)
            gnorm = jnp.sqrt(sum(jnp.sum(g * g) for g in gleaves)) \
                if gleaves else jnp.zeros((), jnp.float32)
            glayer = {k: jnp.sqrt(sum(jnp.sum(g * g)
                                      for g in float_grad_leaves(v)))
                      for k, v in grads.items() if v}
        with jax.named_scope("optimizer"):
            if ctx is not None:
                # lazy row-space update: the SAME optax transform runs on
                # [capacity, dim] views — touched rows of the table and of
                # every param-shaped mirror leaf (mu/nu/trace) — then only
                # those rows scatter back.  Untouched rows and mirrors keep
                # their pre-step bytes.
                g_upd = dict(grads)
                g_upd[group] = dict(g_upd[group],
                                    W=g_upd[group]["W"].values)
                p_upd = {**params, group: dict(params[group], W=ctx.rows)}
                opt_upd = _sparse.gather_rows_tree(opt_state, ctx)
            else:
                g_upd, p_upd, opt_upd = grads, params, opt_state
            updates, new_opt = tx.update(g_upd, opt_upd, p_upd)
            new_params = optax.apply_updates(p_upd, updates)
            if ctx is not None:
                new_params = {**new_params, group: dict(
                    new_params[group],
                    W=ctx.scatter_rows(params[group]["W"],
                                       new_params[group]["W"]))}
                new_opt = _sparse.scatter_rows_tree(opt_state, new_opt, ctx)
            new_params = apply_constraints_all(new_params, confs)
        if pol is not None:
            # keep running state (BN statistics) in f32 so the step's
            # input/output treedefs+dtypes stay fixed across iterations
            new_state = _cast_floats(new_state, jnp.float32,
                                     only=pol.compute_dtype)
        gstats = {"global_norm": gnorm, "layer_norms": glayer}
        if ctx is not None:
            # observability: how many real table rows this step exchanged
            # (vs the static capacity) — the densification win, visible
            # to listeners without a host sync
            gstats["embedding_rows_touched"] = ctx.touched()
        if ls is not None:
            # overflow: skip the step wholesale (nn/precision)
            new_params, new_opt, new_state, sel = _precision.overflow_skip(
                pol, ls, finite, params, new_params, opt_state, new_opt,
                state, new_state, gstats)
            if with_carry:
                # the overflowed forward also poisoned the recurrent
                # carries — a skipped chunk must hand the NEXT chunk its
                # pre-step carries, or one overflow taints the rest of
                # the sequence
                new_carries = sel(new_carries, carries)
        if with_carry:
            return (new_params, new_state, new_opt, new_rng, loss_value,
                    gstats, new_carries)
        return new_params, new_state, new_opt, new_rng, loss_value, gstats

    return step


class _StepForensics:
    """Per-step flight-recorder + health-monitor feed of
    :func:`fit_batches`, amortized.

    Processing a step — a recorder dict build plus the monitor's EWMA
    updates — is only a few microseconds warm, but the train loop runs
    that Python cache-cold right after each multi-ms XLA dispatch, which
    inflates every call ~4x and blows the <2% overhead budget on small
    steps.  So :meth:`step` only captures a raw tuple (and, every
    ``grad_check_every``-th step, a *reference* to the still-on-device
    grad stats — the host fetch is deferred too) and :meth:`flush`
    drains the buffer through ``record()``/``observe_step()`` in a tight
    warm loop every ``FLUSH_EVERY`` steps.

    The loss is only materialized per step (``float`` = host sync) when
    a health MONITOR is armed: its NaN/stop/checkpoint reaction is
    contractually same-step, so that configuration pays the sync it
    always paid, and a non-finite loss still flushes IMMEDIATELY.
    Recorder-only forensics buffer the still-async device scalar and
    materialize at flush time — by then the value has long computed, so
    the D2H copy no longer stalls the dispatch pipeline (the lifetime
    audit's host-sync sweep; see tools/graftaudit).  Every dump path
    flushes first: the fit loop flushes on exception and in its
    ``finally``, and the checkpointer's preemption dump calls the
    ``pre_dump`` hook this helper installs — buffered steps can never
    miss an artifact."""

    FLUSH_EVERY = 16
    __slots__ = ("net", "rec", "ring", "mon", "ckpt", "pol", "_buf",
                 "_grad_every", "_wall0", "_saved_kinds")

    def __init__(self, net, rec, mon, ckpt):
        self.net = net
        self.rec = rec if (rec is not None and rec.enabled) else None
        self.ring = self.rec.channel("train") \
            if self.rec is not None else None
        self.mon = mon
        self.ckpt = ckpt
        pol = getattr(net, "shape_policy", None)
        self.pol = pol if hasattr(pol, "last_pad_ratio") else None
        self._grad_every = mon.config.grad_check_every \
            if mon is not None else 0
        # wall = mono + _wall0: record timestamps derive from the step
        # end the loop already clocked, saving a wall read per step
        self._wall0 = wall_s() - monotonic_s()
        self._buf: list = []
        self._saved_kinds: set = set()
        if ckpt is not None:
            ckpt.pre_dump = self.flush

    def step(self, ep: int, seq: int, compile_step: bool,
             dt: float, t_end: float) -> bool:
        """Capture one fitted step (``t_end`` = the loop's monotonic
        step-end read); returns True when the monitor's opt-in
        ``stop_training`` policy says to halt the fit."""
        net = self.net
        loss = net._score
        mon = self.mon
        if mon is not None:
            # the monitor's same-step NaN reaction needs the value NOW;
            # recorder-only runs keep the device scalar async
            loss = float(loss)
        every = self._grad_every
        pol = self.pol
        buf = self._buf
        buf.append(
            (t_end, net.iteration, ep, seq, net.last_batch_size,
             loss, dt, compile_step,
             net._last_grad_stats
             if every > 0 and net.iteration % every == 0 else None,
             pol.last_pad_ratio if pol is not None else None))
        # loss - loss is 0.0 for finite loss, NaN for nan/±inf: the
        # non-finite check without a function call (monitor-armed only —
        # on the async path the check itself would be the host sync)
        if len(buf) >= self.FLUSH_EVERY or \
                (mon is not None and loss - loss != 0.0):
            return self.flush()
        return False

    def flush(self) -> bool:
        """Drain buffered steps into the recorder ring and the monitor;
        returns the monitor's stop verdict."""
        buf = self._buf
        mon = self.mon
        if not buf:
            return mon.should_stop() if mon is not None else False
        self._buf = []
        rec, ckpt, ring = self.rec, self.ckpt, self.ring
        wall0 = self._wall0
        for t_end, it, ep, seq, bs, loss, dt, comp, gref, pad in buf:
            # recorder-only steps buffered the async device scalar; one
            # cheap D2H each at drain time (the value computed steps ago).
            # NOT exception-guarded: this float() is where deferred
            # device-side failures first surface, and they must propagate
            loss = float(loss)
            if ring is not None:
                # literal-dict append onto the hoisted ring: same record
                # shape record() builds, minus the wrapper overhead
                ring.append({"ts": wall0 + t_end, "type": "step",
                             "iteration": it, "epoch": ep, "score": loss,
                             "batch": bs, "step_s": round(dt, 6),
                             "compile": comp})
            if mon is None:
                continue
            grad_norm = None
            if gref is not None:
                try:
                    grad_norm = float(gref["global_norm"])
                except (KeyError, TypeError, ValueError):
                    grad_norm = None
            eps = bs / dt if dt > 0 and not comp else None
            detections = mon.observe_step(
                loss=loss, grad_norm=grad_norm, examples_per_sec=eps,
                padding_ratio=pad, step=it)
            if detections and ckpt is not None and \
                    mon.config.checkpoint_on_detection and \
                    ckpt.manager is not None and \
                    any(d.kind not in self._saved_kinds
                        for d in detections):
                self._saved_kinds.update(d.kind for d in detections)
                try:
                    # ONE immediate save per detection kind marks the
                    # incident step durably without letting a sticky NaN
                    # (re-detected every dedupe_s) rotate the manager's
                    # keep_last window past every pre-incident checkpoint
                    ckpt._save(ep, seq)
                    mon.checkpoint_saves += 1
                except Exception:
                    pass   # a failed emergency save must not kill the fit
        if rec is not None:
            rec.snapshot_metrics()   # internally time-throttled
        return mon.should_stop() if mon is not None else False


def _on_device(a):
    """Device placement for one batch leaf; a leaf the input pipeline
    already placed (``DevicePrefetchIterator``) passes through untouched —
    no second H2D copy, no resharding."""
    if a is None or isinstance(a, jax.Array):
        return a
    return jnp.asarray(a)


def placed(model, put, batch):
    """Place a batch's four parts on the device with ``put``, under the
    span ``dl4j.h2d``; a fit's step profiler is credited the slice."""
    x, y, mask, label_mask = batch
    prof = model._stepprof
    if prof is not None:
        t0 = monotonic_s()
    with get_tracer().span("dl4j.h2d"):
        out = put(x), put(y), put(mask), put(label_mask)
    if prof is not None:
        prof.mark("h2d", monotonic_s() - t0)
    return out


def finish_step(model, step, out) -> None:
    """What every train step leaves on its model, then the listeners'
    turn.  ``_score`` stays the still-ASYNC device loss scalar: a
    ``float()`` here would stall the dispatch pipeline once per step for
    a value nothing reads until a listener or a forensics flush asks;
    ``fit_batch``/``get_score`` materialize on demand, a fit loop once at
    its end."""
    (model.params, model.state, model.opt_state, model._rng, model._score,
     model._last_grad_stats) = out
    model._last_step_traced = bool(getattr(step, "last_call_traced", False))
    model.iteration += 1
    prof = model._stepprof
    if prof is None:
        for lst in model.listeners:
            lst.iteration_done(model, model.iteration, model.epoch)
    else:
        t0 = monotonic_s()
        for lst in model.listeners:
            lst.iteration_done(model, model.iteration, model.epoch)
        prof.mark("listener", monotonic_s() - t0)


def fit_batches(model, batches_factory, epochs: int, prepare, step, *,
                ckpt=None, walk=None):
    """The fit loop behind ``MultiLayerNetwork.fit``,
    ``ComputationGraph.fit`` and ``ParallelWrapper.fit``: epochs of
    host-fed batches, one dispatch of the jitted ``step`` a batch, the
    host up to ``DL4J_TPU_DISPATCH_DEPTH`` steps ahead of the device.

    ``batches_factory()`` gives an epoch's batches in the caller's own
    form; ``prepare(batch)`` validates, pads and places one (through
    :func:`placed`), sets ``model.last_batch_size`` and returns the
    step's four batch arguments.  ``walk(batch)``, where given, may train
    a batch in steps of its own (tBPTT chunks) and say so by returning
    true.  ``ckpt`` (a ``faulttolerance`` ``FitCheckpointer``) adds
    periodic saves, the SIGTERM save and the resume cursor; the loop
    closes it.
    """
    from ..observability.health import get_health_monitor
    from ..observability.profiler import step_profiler_for
    from ..observability.recorder import get_flight_recorder
    span = get_tracer().span
    # observability (cheap by default: plain host float math per step,
    # instruments resolved once per fit, no device sync forced here; a
    # disabled registry reduces all of it to one bool check)
    reg = default_registry()
    obs = reg.enabled
    # runtime forensics: the flight recorder keeps the recent-step
    # window for crash dumps; the health monitor (when installed)
    # watches the step signals for NaNs/spikes/throughput collapse
    rec = get_flight_recorder()
    rec_on = rec is not None and rec.enabled
    mon = get_health_monitor()
    forensics = _StepForensics(model, rec, mon, ckpt) \
        if (rec_on or mon is not None) else None
    # per-step phase attribution (etl/h2d/dispatch/device/listener/
    # forensics/checkpoint) with a SAMPLED device fence — steady
    # unsampled steps stay fully async (the host-sync sweep holds)
    prof = step_profiler_for("train_step")
    model._stepprof = prof

    # bounded async dispatch (ISSUE 18): the host may run up to
    # DL4J_TPU_DISPATCH_DEPTH (default 2) steps ahead of the device,
    # overlapping step N+1's ETL/padding/h2d/bookkeeping with step
    # N's execution.  Drains at epoch ends and checkpoint boundaries
    # keep exact-resume parity; every drained token is NaN-checked
    # with ITS OWN iteration so deferred device failures surface
    # within the window bound, correctly attributed.
    def _nan_at_drain(iteration, value):
        if rec_on:
            rec.record("train", "nan_at_drain", score=value,
                       iteration=int(iteration))
    win = DispatchWindow(owner=model, profiler=prof, on_nan=_nan_at_drain)
    if obs:
        steps_c = reg.counter("training_steps_total",
                              "Optimizer steps taken")
        examples_c = reg.counter("training_examples_total",
                                 "Training examples consumed")
        step_h = reg.histogram(
            "training_step_seconds",
            "Train step wall time, split compile vs steady",
            ("phase",), buckets=_STEP_BUCKETS)
        etl_fetch_h = reg.histogram(
            "training_etl_seconds",
            "Time blocked on the data pipeline per batch, by stage",
            ("stage",), buckets=_ETL_BUCKETS).labels("fetch")
        step_compile_h = step_h.labels("compile")
        step_steady_h = step_h.labels("steady")
    steady_examples, steady_s = 0, 0.0
    start_epoch = ckpt.start_epoch if ckpt is not None else 0
    stop = False
    try:
        for ep in range(start_epoch, epochs):
            for lst in model.listeners:
                lst.on_epoch_start(model)
            batches = iter(batches_factory())
            # resume cursor: the first resumed epoch skips the batches
            # the checkpointed run already consumed (the data-pipeline
            # seq cursor) WITHOUT fitting or touching the RNG, so the
            # resumed stream lines up with the uninterrupted run's
            skip = ckpt.skip_batches \
                if (ckpt is not None and ep == ckpt.start_epoch) else 0
            seq = 0
            while True:
                t_etl = time.perf_counter()
                with span("dl4j.input_wait"):
                    batch = next(batches, None)
                # ETL/compute boundary timing (reference lastEtlTime,
                # MultiLayerNetwork.java:1203-1209): time blocked on the
                # data pipeline, visible to PerformanceListener
                model.last_etl_ms = (time.perf_counter() - t_etl) * 1e3
                if batch is None:
                    break
                if seq < skip:
                    seq += 1
                    continue
                t_step = monotonic_s()
                if prof is not None:
                    prof.begin(t_step, model.last_etl_ms * 1e-3)
                if walk is None or not walk(batch):
                    # the jitted step is called from this frame: the depth
                    # of the Python stack under which it is first traced
                    # picks how long that trace takes (PERF.md section 6,
                    # PR 27), so no helper sits between
                    args = prepare(batch)
                    finish_step(model, step, step(
                        model.params, model.state, model.opt_state,
                        model._rng, *args))
                if prof is not None:
                    prof.dispatched(model._score, window=win)
                compile_step = model._last_step_traced
                t_end = monotonic_s()
                dt = t_end - t_step
                if obs:
                    (step_compile_h if compile_step
                     else step_steady_h).observe(dt)
                    etl_fetch_h.observe(model.last_etl_ms / 1e3)
                    steps_c.inc()
                    examples_c.inc(model.last_batch_size)
                    if not compile_step:
                        steady_examples += model.last_batch_size
                        steady_s += dt
                seq += 1
                if forensics is not None and \
                        forensics.step(ep, seq, compile_step, dt, t_end):
                    stop = True   # opt-in health stop: clean return
                if prof is not None:
                    prof.lap("forensics")
                if not stop and ckpt is not None:
                    if ckpt.due():
                        # checkpoint boundary: materialize the whole
                        # window so the save captures finished steps
                        # and mid-window resume stays digest-exact
                        win.drain()
                    if ckpt.after_batch(ep, seq):
                        stop = True   # SIGTERM: final save — return
                if prof is not None:
                    if ckpt is not None:
                        prof.lap("checkpoint")
                    prof.end(model.iteration, compile_step)
                if stop:
                    break
                # admit this step into the in-flight window (blocks on
                # the oldest step once the window is full — the
                # bounded-pipeline backpressure point)
                win.push(model._score, model.iteration)
            if stop:
                break
            # ONE materialization per epoch (fit_on_device's sync
            # convention): steps pipelined async all epoch; epoch-end
            # listeners (MetricsListener score/grad-norm) see a host
            # float without forcing their own sync
            win.drain()
            with span("dl4j.sync"):
                model._score = float(model._score)
                publish_expert_tokens(model)
                publish_exit_mass(model)
            if prof is not None:
                prof.materialized()
            for lst in model.listeners:
                lst.on_epoch_end(model)
            model.epoch += 1
            if ckpt is not None and ckpt.after_epoch(ep):
                stop = True
                break
        # stop-path exits (health stop, SIGTERM) break before the
        # epoch-end drain; materialize what's still in flight so the
        # drained-score bookkeeping is consistent on clean returns
        win.drain()
    except Exception as e:
        # never block on in-flight work while unwinding — the final
        # un-guarded float(_score) convention still surfaces deferred
        # device failures for callers that catch and continue
        win.abandon()
        # unhandled fit exception: commit the flight-recorder window
        # BEFORE propagating — the artifact that explains the crash
        # must exist even if the process dies on the way up
        if rec_on:
            if forensics is not None:
                try:
                    forensics.flush()
                except Exception:
                    pass   # forensics must not mask the real error
            rec.record("train", "fit_exception",
                       error=f"{type(e).__name__}: {e}",
                       iteration=int(model.iteration))
            rec.maybe_dump(
                "fit_exception",
                directory=(ckpt.manager.directory
                           if ckpt is not None and ckpt.manager
                           is not None else None))
        raise
    finally:
        if forensics is not None:
            try:
                forensics.flush()
            except Exception:
                pass
        if prof is not None:
            model._stepprof = None
            try:
                prof.flush()
            except Exception:
                pass   # profile telemetry must not mask the real error
        if ckpt is not None:
            ckpt.close()
    # ONE materialization for the whole fit: the steps keep _score the
    # async device scalar so they pipeline.  NOT exception-guarded: this
    # float() is where deferred device-side failures first surface, and
    # they must propagate
    with span("dl4j.sync"):
        model._score = float(model._score)
        publish_expert_tokens(model)
        publish_exit_mass(model)
    if obs and steady_s > 0:
        # steady-state throughput: the compile-dominated first step
        # is excluded
        reg.gauge("training_examples_per_sec",
                  "Training examples/sec over the last fit() "
                  "(compile excluded where the path can tell)"
                  ).set(steady_examples / steady_s)
    return model


def fit_on_device_epochs(model, xs, ys, batch_size: int, epochs: int,
                         shuffle: bool, call_step, fit_tail, ckpt=None):
    """Shared device-resident epoch trainer behind
    ``MultiLayerNetwork.fit_on_device`` / ``ComputationGraph.fit_on_device``.

    One jitted program scans the train step over all minibatches, gathering
    each minibatch from the single HBM-resident dataset copy inside the scan
    body (a whole-dataset permuted copy would double the footprint of an
    HBM-bound feature).  ``xs``/``ys``: lists of device arrays.
    ``call_step(p, s, o, key, bx, by)`` adapts the model's jitted train step
    to list-shaped batches; ``fit_tail(xt, yt)`` trains the ragged tail via
    the normal per-batch path.  ``ckpt`` (a ``faulttolerance``
    ``FitCheckpointer``) adds epoch-boundary checkpoint saves + resume —
    it pins the per-epoch path (the fused program has no epoch
    boundaries) and offsets the epoch loop by the restored cursor.
    """
    try:
        return _fit_on_device_epochs(model, xs, ys, batch_size, epochs,
                                     shuffle, call_step, fit_tail, ckpt)
    finally:
        # every exit — validation raises included — must uninstall the
        # checkpointer's SIGTERM hook and join its in-flight write
        if ckpt is not None:
            ckpt.close()


def _fit_on_device_epochs(model, xs, ys, batch_size, epochs, shuffle,
                          call_step, fit_tail, ckpt):
    n = int(xs[0].shape[0])
    for a in list(xs) + list(ys):
        if int(a.shape[0]) != n:
            # jnp gather clamps out-of-range indices, so a mismatch would
            # silently train on duplicated rows rather than erroring
            raise ValueError(
                f"all inputs/labels need the same leading dimension; got "
                f"{[int(b.shape[0]) for b in list(xs) + list(ys)]}")
    nb = n // batch_size
    if nb == 0:
        raise ValueError(f"batch_size {batch_size} exceeds dataset ({n})")
    used = nb * batch_size
    pol = getattr(model, "shape_policy", None)
    if pol is not None and pol.enabled:
        # let the per-batch path know the scan's steady batch size, so the
        # ragged tail (fit_tail -> _fit_one) pads onto it instead of
        # compiling a dedicated tail-sized train step
        pol.observe("train", batch_size)
    from .compile_cache import shared_jit
    sig = model._topology_sig()
    cache_key = ("epoch_scan", nb, batch_size,
                 tuple(a.shape[1:] for a in xs),
                 tuple(a.shape[1:] for a in ys))
    fn = model._jit_cache.get(cache_key)
    if fn is None:
        def build_epoch_fn():
            def epoch_fn(params, state, opt_state, key, xd, yd, perm_steps):
                def body(carry, idx):
                    p, s, o, k = carry
                    bx = [a[idx] for a in xd]  # one minibatch gather per step
                    by = [a[idx] for a in yd]
                    # the fused-RNG step splits its key internally and
                    # returns the successor — the split that used to live
                    # here, so the key sequence is bit-identical
                    p, s, o, k, loss, gstats = call_step(p, s, o, k, bx, by)
                    return (p, s, o, k), (loss, gstats)

                # the dataset lies on the device beside every step
                with _scan_layers.holding(xd, yd):
                    (p, s, o, k), (losses, gstats) = jax.lax.scan(
                        body, (params, state, opt_state, key), perm_steps)
                # listeners see the final step's gradient norms
                gstats = jax.tree_util.tree_map(lambda a: a[-1], gstats)
                # the final key is returned (and discarded by the caller)
                # so the key ARGUMENT has an alias-matched output and can
                # be donated like the rest of the training carry
                return p, s, o, k, losses, gstats
            return epoch_fn, (0, 1, 2, 3)

        # shared across equal-topology networks (replicas): call_step only
        # closes over the model's shared jitted step, never the model
        fn = shared_jit((type(model).__name__, sig) + cache_key,
                        build_epoch_fn, name="epoch_scan")
        model._jit_cache[cache_key] = fn
    # Fused multi-epoch program (VERDICT r4 item 2): when nothing needs a
    # per-epoch Python hook — no listeners, no ragged tail — ALL epochs run
    # as ONE dispatch: an outer scan draws each epoch's permutation on
    # device and inner-scans the train step, so the inter-epoch dispatch
    # and its host work vanish entirely.  Per-epoch listeners or a tail
    # keep the per-epoch loop below (async dispatch still pipelines it).
    fuse = epochs > 1 and used == n and not model.listeners \
        and (ckpt is None or ckpt.manager is None)
    if ckpt is not None and ckpt.start_epoch:
        # resumed run: the restored cursor says this many epochs already
        # landed in the checkpoint — run only the remainder
        epochs = max(epochs - ckpt.start_epoch, 0)
        fuse = False
    if fuse:
        fused_key = ("epochs_scan", nb, batch_size, epochs, shuffle,
                     tuple(a.shape[1:] for a in xs),
                     tuple(a.shape[1:] for a in ys))
        fused = model._jit_cache.get(fused_key)
        if fused is None:
            def epochs_fn(params, state, opt_state, key, xd, yd):
                def epoch_body(carry, _):
                    p, s, o, k = carry
                    k, pk, ek = jax.random.split(k, 3)
                    perm = (jax.random.permutation(pk, n) if shuffle
                            else jnp.arange(n)).reshape(nb, batch_size)

                    def body(c, idx):
                        p_, s_, o_, k_ = c
                        bx = [a[idx] for a in xd]
                        by = [a[idx] for a in yd]
                        # gstats are DISCARDED inside the traced program:
                        # nothing in the fused (listener-free) path reads
                        # them, and dropping them from the outputs lets XLA
                        # dead-code-eliminate the per-step gradient-norm
                        # reductions (~2 full passes over every gradient
                        # leaf per step on a large model).  The fused-RNG
                        # step splits k_ internally (bit-identical to the
                        # split that used to live here).
                        p_, s_, o_, k_, loss, _g = call_step(
                            p_, s_, o_, k_, bx, by)
                        return (p_, s_, o_, k_), loss

                    (p, s, o, _), losses = jax.lax.scan(
                        body, (p, s, o, ek), perm)
                    return (p, s, o, k), losses[-1]

                with _scan_layers.holding(xd, yd):
                    (p, s, o, k), last_losses = jax.lax.scan(
                        epoch_body, (params, state, opt_state, key), None,
                        length=epochs)
                return p, s, o, k, last_losses

            fused = shared_jit((type(model).__name__, sig) + fused_key,
                               lambda: (epochs_fn, (0, 1, 2, 3)),
                               name="epochs_scan")
            model._jit_cache[fused_key] = fused
    try:
        if fuse:
            model._rng, key = jax.random.split(model._rng)
            (model.params, model.state, model.opt_state, _k,
             last_losses) = fused(model.params, model.state,
                                  model.opt_state, key, xs, ys)
            model.iteration += nb * epochs
            model.last_batch_size = batch_size
            model._score = last_losses[-1]
            # the fused program discards gradient stats (XLA DCE, see
            # above): consumers must see "absent", not a previous
            # non-fused fit's stale norms
            model._last_grad_stats = None
            model.epoch += epochs
        else:
            _fit_epochs(model, xs, ys, epochs, n, nb, used, batch_size,
                        shuffle, fn, fit_tail, ckpt)
    except BaseException:
        # aborted fit: best-effort coercion so _score can't stay a device
        # scalar, but the original error keeps propagating
        try:
            model._score = float(model._score)
        except Exception:
            model._score = float("nan")
        raise
    # one final sync so "fit returned" still means "training finished" (the
    # last epoch's loss transitively waits on every queued epoch).  NOT
    # exception-guarded: with async dispatch this float() is where deferred
    # device-side failures (OOM, runtime faults) first surface, and they
    # must raise out of fit, not become a silent nan.
    with get_tracer().span("dl4j.sync"):
        model._score = float(model._score)
    return model


def _fit_epochs(model, xs, ys, epochs, n, nb, used, batch_size, shuffle,
                fn, fit_tail, ckpt=None):
    epoch0 = ckpt.start_epoch if ckpt is not None else 0
    for ep in range(epochs):
        for lst in model.listeners:
            lst.on_epoch_start(model)
        model._rng, key, pk = jax.random.split(model._rng, 3)
        perm = (jax.random.permutation(pk, n) if shuffle
                else jnp.arange(n))
        perm_steps = perm[:used].reshape(nb, batch_size)
        (model.params, model.state, model.opt_state, _k, losses,
         gstats) = fn(model.params, model.state, model.opt_state, key,
                      xs, ys, perm_steps)
        model.iteration += nb
        model.last_batch_size = batch_size
        # keep the score a DEVICE scalar inside the loop: a float() here
        # would host-sync every epoch, serializing epochs against the
        # dispatch round-trip instead of letting JAX's async dispatch
        # pipeline them back to back.  Listeners that
        # read get_score() materialize it on demand.
        model._score = losses[-1]
        model._last_grad_stats = gstats
        for lst in model.listeners:
            lst.iteration_done(model, model.iteration, model.epoch)
        if used < n:
            tail = perm[used:]
            fit_tail([a[tail] for a in xs], [a[tail] for a in ys])
        for lst in model.listeners:
            lst.on_epoch_end(model)
        model.epoch += 1
        if ckpt is not None and ckpt.after_epoch(epoch0 + ep):
            break   # SIGTERM: final save taken — return cleanly
