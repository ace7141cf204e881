"""Shared training-step machinery for MultiLayerNetwork and ComputationGraph.

One copy of the updater-block construction (reference
``nn/updater/BaseMultiLayerUpdater.java:64-138`` builds per-block updaters for
MLN and ``nn/updater/graph/ComputationGraphUpdater.java`` for graphs — same
logic there too), gradient-normalization pre-apply (:318) and constraint
application, keyed by a ``name -> layer-conf`` map that both network types
produce.
"""
from __future__ import annotations

from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import optax

from .layers.base import BaseLayerConf, LayerConf
from ..observability.tracer import get_tracer


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
