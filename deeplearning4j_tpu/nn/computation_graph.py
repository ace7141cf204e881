"""ComputationGraph — DAG network runtime.

TPU-native re-design of ``nn/graph/ComputationGraph.java:87``: the reference
walks the topological order per call, managing workspaces and hand-accumulated
fan-in epsilons; here the whole DAG (forward + loss + backward + update) is
traced once into a single jitted XLA program.  Fan-in gradient accumulation is
what jax.grad does by construction; workspace reuse is XLA's buffer allocator
plus argument donation.

Multi-input / multi-output: ``fit`` takes a MultiDataSet-shaped batch
(features list, labels list, optional masks); the loss is the sum over output
layers (reference computeGradientAndScore, ComputationGraph.java:1310-1320).
"""
from __future__ import annotations

from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
import optax

from . import precision as _precision
from ._common import (_cast_floats, apply_constraints_all,
                      apply_gradient_norm_all, build_tx,
                      fit_on_device_epochs, hyperparam_conf)
from .compile_cache import shared_jit, topology_signature
from .multilayer import _cast_act
from .conf.computation_graph import (ComputationGraphConfiguration,
                                     GraphVertexConf, LayerVertex)
from .conf.updaters import Sgd, UpdaterConf
from .layers.base import BaseLayerConf
from ..data.shapes import default_shape_policy
from ..observability.clock import monotonic_s
from ..observability.tracer import get_tracer, training_entry
from ..train.listeners import TrainingListener

Array = jax.Array


def _as_list(x) -> List:
    if x is None:
        return []
    if isinstance(x, (list, tuple)):
        return list(x)
    return [x]


def _vertex_confs(conf) -> Dict[str, Any]:
    return {name: getattr(v, "layer", None)
            for name, v in conf.vertices.items()}


def _graph_forward(conf, params, state, inputs: List[Array], *, train: bool,
                   key, masks: Optional[List[Optional[Array]]] = None,
                   exclude_outputs: bool = False, precision=None):
    """Walk the static topological order; returns (acts, new_state, masks).

    acts: dict vertex-name -> activation (plus network inputs).  A free
    function over the configuration — never touches a graph instance — so
    the jitted programs built from it live in the process-global trace
    cache and serve every equal-topology graph (clones, master replicas).
    """
    acts: Dict[str, Array] = {}
    mask_of: Dict[str, Optional[Array]] = {}
    for i, n in enumerate(conf.network_inputs):
        acts[n] = inputs[i]
        mask_of[n] = masks[i] if masks else None
    new_state = dict(state)
    # output vertices whose activation nothing consumes can be skipped
    # when the caller only needs pre-output activations for the loss
    consumed = {src for ins in conf.vertex_inputs.values() for src in ins}
    for vi, name in enumerate(conf.topological_order):
        v = conf.vertices[name]
        if exclude_outputs and name in conf.network_outputs and \
                name not in consumed and isinstance(v, LayerVertex) and \
                hasattr(v.layer, "compute_loss"):
            continue
        ins = conf.vertex_inputs[name]
        xs = [acts[s] for s in ins]
        ms = [mask_of.get(s) for s in ins]
        # LastTimeStepVertex keys sequence length off a *named* input mask
        mi = getattr(v, "mask_input", None)
        if mi:
            ms = [mask_of.get(mi)] + ms[1:]
        lkey = jax.random.fold_in(key, vi) if key is not None else None
        if precision is not None:
            vdt = precision.input_dtype(getattr(v, "layer", None) or v)
            xs = [_cast_act(x, vdt) for x in xs]
        variables = {"params": params.get(name, {}),
                     "state": state.get(name, {})}
        # one scope per vertex, named by its layer conf's class (see
        # nn/multilayer._stack_forward)
        with jax.named_scope(type(getattr(v, "layer", None) or v).__name__):
            if train and conf.defaults.get("cache_mode") == "remat" and \
                    isinstance(v, LayerVertex):
                # rematerialize per-vertex activations on the backward pass
                # (the WorkspaceMode/CacheMode role: trade FLOPs for HBM —
                # SURVEY §7 "Workspaces → jax.checkpoint")
                def _apply(vv, xx, kk, mm, _v=v):
                    return _v.apply(vv, xx, train=True, key=kk, masks=mm)
                y, lstate = jax.checkpoint(_apply)(variables, xs, lkey, ms)
            else:
                y, lstate = v.apply(variables, xs, train=train, key=lkey,
                                    masks=ms)
        acts[name] = y
        new_state[name] = lstate
        mask_of[name] = v.feed_forward_mask(ms, xs)
    return acts, new_state, mask_of


def _graph_loss(conf, params, state, inputs, labels, *, train: bool, key,
                masks=None, label_masks=None, precision=None):
    acts, new_state, mask_of = _graph_forward(
        conf, params, state, inputs, train=train, key=key, masks=masks,
        exclude_outputs=True, precision=precision)
    # accumulate in the loss dtype (a dtype-defaulted zeros(()) start is
    # f64 under x64 and would promote every head's loss — graftaudit AX001)
    total = None
    for oi, name in enumerate(conf.network_outputs):
        v = conf.vertices[name]
        if not (isinstance(v, LayerVertex) and
                hasattr(v.layer, "compute_loss")):
            raise ValueError(
                f"network output '{name}' is not an output layer vertex")
        src = conf.vertex_inputs[name][0]
        h = acts[src]
        if precision is not None:
            # head matmul in the compute dtype; the loss reductions
            # upcast to f32 inside nn/losses
            h = _cast_act(h, precision.layer_dtype(v.layer))
        lm = None
        if label_masks is not None and oi < len(label_masks):
            lm = label_masks[oi]
        if lm is None:
            lm = mask_of.get(src)
        lkey = (jax.random.fold_in(key, 10_000 + oi)
                if key is not None else None)
        variables = {"params": params.get(name, {}),
                     "state": state.get(name, {})}
        with jax.named_scope(type(v.layer).__name__):
            l = v.compute_loss(variables, h, labels[oi], train=train,
                               key=lkey, mask=lm)
        total = l if total is None else total + l
    if total is None:
        total = jnp.zeros((), jnp.float32)
    reg = jnp.zeros((), dtype=total.dtype)
    for name, v in conf.vertices.items():
        lp = params.get(name, {})
        if lp:
            reg = reg + v.regularization_score(lp)
        if getattr(getattr(v, "layer", None), "AUX_LOSS", False):
            aux = new_state.get(name, {}).get("aux_loss")
            if aux is not None:
                reg = reg + aux
    return total + reg, new_state


def _build_graph_fn(conf, tx, kind: str):
    """Build the Python function behind one jitted graph entry point;
    returns ``(fun, donate_argnums)``.  Closures capture only conf/tx
    (shared-cache safe; the per-instance closure is the JX013 hazard)."""
    outs = conf.network_outputs
    if kind == "output":
        def fn(params, state, xs):
            acts, _, _ = _graph_forward(conf, params, state, xs,
                                        train=False, key=None)
            return [acts[o] for o in outs]
        return fn, ()
    if kind == "output_train":
        def fn(params, state, xs, key):
            acts, _, _ = _graph_forward(conf, params, state, xs,
                                        train=True, key=key)
            return [acts[o] for o in outs]
        return fn, ()
    if kind == "score":
        def fn(params, state, xs, ys, label_masks):
            return _graph_loss(conf, params, state, xs, ys, train=False,
                               key=None, label_masks=label_masks)
        return fn, ()
    if kind == "train_step":
        # maximal donation (graftaudit AX007): the fused-RNG step returns
        # the successor key as an alias-matched output, so the key buffer
        # donates and recycles in place with the training carry
        return _build_graph_train_step(conf, tx), (0, 1, 2, 3)
    raise KeyError(kind)


def _build_graph_train_step(conf, tx):
    gn_mode = conf.defaults.get("gradient_normalization")
    gn_thr = float(conf.defaults.get(
        "gradient_normalization_threshold", 1.0))
    pol = _precision.resolve(conf.defaults)
    confs = _vertex_confs(conf)
    for name, lc in confs.items():
        if getattr(lc, "sparse_grad", False) or \
                getattr(getattr(lc, "layer", None), "sparse_grad", False):
            # surfaced at build time, never a silent dense fallback: the
            # densified pre-pass (nn/sparse) is wired into the
            # MultiLayerNetwork train step only — a graph vertex here
            # would quietly train with the dense [vocab, dim] cotangent
            # the flag promises to eliminate
            raise ValueError(
                f"vertex '{name}': sparse_grad=True is supported on "
                "MultiLayerNetwork (first-layer embedding) only; the "
                "ComputationGraph train step has no densified sparse-"
                "gradient pre-pass — drop the flag, or move the "
                "embedding model to a MultiLayerNetwork stack")
    cast_map = {}
    if pol is not None:
        for name, v in conf.vertices.items():
            dt = pol.layer_dtype(getattr(v, "layer", None) or v)
            if dt not in (None, "float32"):
                cast_map[name] = dt

    def step(params, state, opt_state, key, xs, ys, masks, label_masks):
        # fused RNG succession (see nn/multilayer._build_train_step): the
        # host-side split moves into the program — bit-identical key
        # sequence, one less dispatch, and the key becomes donatable
        new_rng, key = jax.random.split(key)
        if pol is not None:
            xs = [_cast_act(x, pol.compute_dtype) for x in xs]
        ls = state.get(_precision.SCALE_STATE_KEY) \
            if pol is not None and pol.scaled else None
        scale = ls["scale"] if ls is not None else None

        # the same scopes as nn/multilayer._build_train_step
        @jax.named_scope("forward")
        def loss_fn(p):
            if cast_map:
                p = {k: (_cast_floats(v, cast_map[k]) if k in cast_map
                         else v) for k, v in p.items()}
            loss, new_state = _graph_loss(conf, p, state, xs, ys,
                                          train=True, key=key, masks=masks,
                                          label_masks=label_masks,
                                          precision=pol)
            obj = loss * scale if scale is not None else loss
            return obj, (loss, new_state)
        (_obj, (loss, new_state)), grads = \
            jax.value_and_grad(loss_fn, has_aux=True)(params)
        finite = None
        with jax.named_scope("grad_post"):
            if scale is not None:
                grads, finite = _precision.unscale_and_check(grads, scale)
            grads = apply_gradient_norm_all(grads, confs, gn_mode, gn_thr)
            gleaves = jax.tree_util.tree_leaves(grads)
            gnorm = jnp.sqrt(sum(jnp.sum(g * g) for g in gleaves)) \
                if gleaves else jnp.zeros((), jnp.float32)
            glayer = {k: jnp.sqrt(sum(jnp.sum(g * g)
                                      for g in jax.tree_util.tree_leaves(v)))
                      for k, v in grads.items() if v}
        with jax.named_scope("optimizer"):
            updates, new_opt = tx.update(grads, opt_state, params)
            new_params = optax.apply_updates(params, updates)
            new_params = apply_constraints_all(new_params, confs)
        if pol is not None:
            new_state = _cast_floats(new_state, jnp.float32,
                                     only=pol.compute_dtype)
        gstats = {"global_norm": gnorm, "layer_norms": glayer}
        if ls is not None:
            # overflow: skip the step wholesale (nn/precision)
            new_params, new_opt, new_state, _sel = \
                _precision.overflow_skip(
                    pol, ls, finite, params, new_params, opt_state,
                    new_opt, state, new_state, gstats)
        return new_params, new_state, new_opt, new_rng, loss, gstats

    return step


class ComputationGraph:
    """DAG network: init → fit/output/score/evaluate."""

    def __init__(self, conf: ComputationGraphConfiguration):
        conf.resolve()
        self.conf = conf
        self.params: Dict[str, Any] = {}
        self.state: Dict[str, Any] = {}
        self.opt_state = None
        self.iteration = 0
        self.epoch = 0
        self.last_batch_size = 0
        self.listeners: List[TrainingListener] = []
        self._score = float("nan")
        # drain-boundary telemetry (nn/dispatch.DispatchWindow): see
        # MultiLayerNetwork.__init__
        self.last_drained_score = float("nan")
        self.last_drained_iteration = -1
        self._last_grad_stats = None
        self._last_step_traced = False
        # per-fit StepProfiler (see MultiLayerNetwork): _fit_one credits
        # its h2d/listener slices through it when a fit attaches one
        self._stepprof = None
        self._tx = None
        self._rng = jax.random.PRNGKey(conf.seed)
        # instance view over the process-global trace cache (compile_cache)
        self._jit_cache: Dict[Any, Any] = {}
        self._topo_sig: Optional[str] = None
        self._pad_safe: Optional[bool] = None
        self.shape_policy = default_shape_policy()

    # ------------------------------------------------------------------ init
    def init(self) -> "ComputationGraph":
        key = jax.random.PRNGKey(self.conf.seed)
        self.params, self.state = {}, {}
        for name in self.conf.topological_order:
            v = self.conf.vertices[name]
            key, sub = jax.random.split(key)
            itypes = self.conf.vertex_input_types.get(name, [None])
            out = v.init(sub, itypes)
            self.params[name] = out.get("params", {})
            self.state[name] = out.get("state", {})
        ls = _precision.init_scale_state(
            _precision.resolve(self.conf.defaults))
        if ls is not None:
            self.state[_precision.SCALE_STATE_KEY] = ls
        self._tx = self._build_tx()
        self.opt_state = self._tx.init(self.params)
        return self

    def _default_updater(self) -> UpdaterConf:
        u = self.conf.defaults.get("updater")
        return u if u is not None else Sgd(learning_rate=0.1)

    def _layer_conf_map(self):
        return {name: getattr(v, "layer", None)
                for name, v in self.conf.vertices.items()}

    def _build_tx(self) -> optax.GradientTransformation:
        return build_tx(self._default_updater(), self._layer_conf_map(),
                        self.params)

    # -------------------------------------------------------------- forward
    def _forward(self, params, state, inputs: List[Array], *, train: bool,
                 key, masks: Optional[List[Optional[Array]]] = None,
                 exclude_outputs: bool = False):
        """Delegate to the conf-parameterized ``_graph_forward`` (kept as a
        method for external callers)."""
        return _graph_forward(self.conf, params, state, inputs, train=train,
                              key=key, masks=masks,
                              exclude_outputs=exclude_outputs)

    def _loss(self, params, state, inputs, labels, *, train: bool, key,
              masks=None, label_masks=None):
        """Delegate to the conf-parameterized ``_graph_loss``."""
        return _graph_loss(self.conf, params, state, inputs, labels,
                           train=train, key=key, masks=masks,
                           label_masks=label_masks)

    # ---------------------------------------------------------- public API
    def output(self, *inputs, train: bool = False):
        """Activations of the network outputs (reference ``output(...)``).
        Returns a single array if one output, else a list.  Ragged eval
        batches pad onto a compiled bucket and the padded rows are sliced
        off every head (row-wise inference is value-preserving)."""
        xs = [jnp.asarray(x) for x in inputs]
        n = -1
        pol = self.shape_policy
        if not train and pol is not None and pol.enabled and xs and \
                all(getattr(x, "ndim", 1) >= 2 for x in xs) and \
                self._pad_output_safe():
            padded, b = pol.pad_eval_rows_multi(xs)
            if padded is not xs:   # same list object back == nothing padded
                xs, n = padded, b
        if train:
            self._rng, key = jax.random.split(self._rng)
            fn = self._get_jitted("output_train")
            ys = fn(self.params, self.state, xs, key)
        else:
            fn = self._get_jitted("output")
            ys = fn(self.params, self.state, xs)
        if n >= 0:
            ys = [y[:n] if getattr(y, "shape", (0,))[0] > n else y
                  for y in ys]
        return ys[0] if len(ys) == 1 else list(ys)

    def output_single(self, *inputs, train: bool = False) -> Array:
        y = self.output(*inputs, train=train)
        if isinstance(y, list):
            raise ValueError("output_single on a multi-output graph")
        return y

    def feed_forward(self, *inputs, train: bool = False) -> Dict[str, Array]:
        """All vertex activations keyed by vertex name."""
        xs = [jnp.asarray(x) for x in inputs]
        key = None
        if train:
            self._rng, key = jax.random.split(self._rng)
        acts, _, _ = self._forward(self.params, self.state, xs, train=train,
                                   key=key)
        return acts

    def score(self, dataset=None, inputs=None, labels=None) -> float:
        """Loss on a dataset; with no arguments, the score of the most
        recent training minibatch (reference ``score()`` / ``score(DataSet)``
        — same contract as MultiLayerNetwork)."""
        if dataset is None and inputs is None:
            return float(self._score)   # device scalar mid-fit_on_device
        if dataset is not None:
            inputs, labels, _, _ = self._normalize_batch(dataset)
        inputs = [jnp.asarray(x) for x in _as_list(inputs)]
        labels = [jnp.asarray(y) for y in _as_list(labels)]
        lms = None
        pol = self.shape_policy
        if pol is not None and pol.enabled and self._pad_eval_safe():
            # ragged scoring batch rides a compiled bucket; padded rows
            # are masked out of every output's loss
            inputs, labels, lms = pol.pad_multi_batch(inputs, labels, None,
                                                      path="score")
        fn = self._get_jitted("score")
        loss, _ = fn(self.params, self.state, inputs, labels, lms)
        return float(loss)

    def _topology_sig(self) -> str:
        if self._topo_sig is None:
            self._topo_sig = topology_signature(self.conf)
        return self._topo_sig

    def invalidate_compile_cache(self) -> "ComputationGraph":
        """Drop compiled-function views after IN-PLACE conf edits (see
        ``MultiLayerNetwork.invalidate_compile_cache``)."""
        self._jit_cache = {}
        self._topo_sig = None
        self._pad_safe = None
        return self

    def _get_jitted(self, kind: str):
        fn = self._jit_cache.get(kind)
        if fn is None:
            if self._tx is None and kind == "train_step":
                self._tx = self._build_tx()
            fn = shared_jit(
                (type(self).__name__, self._topology_sig(), kind),
                lambda: _build_graph_fn(self.conf, self._tx, kind),
                name=kind)
            self._jit_cache[kind] = fn
        return fn

    def _pad_flags(self):
        """See ``MultiLayerNetwork._pad_flags``: (row-independent
        inference, loss-path eval safe, train safe)."""
        if self._pad_safe is None:
            from .layers.normalization import BatchNormalization
            row_indep = eval_safe = train_safe = True
            for name, v in self.conf.vertices.items():
                lc = getattr(v, "layer", None)
                if getattr(lc, "AUX_LOSS", False):
                    # MoE: padded rows compete for expert capacity AND the
                    # whole-batch aux term defeats the label mask
                    row_indep = False
                if name in self.conf.network_outputs and lc is not None \
                        and not getattr(lc, "SUPPORTS_LOSS_MASK", True):
                    eval_safe = False
                if isinstance(hyperparam_conf(lc) or lc,
                              BatchNormalization):
                    train_safe = False
            eval_safe = eval_safe and row_indep
            train_safe = train_safe and eval_safe
            self._pad_safe = (row_indep, eval_safe, train_safe)
        return self._pad_safe

    def _pad_output_safe(self) -> bool:
        return self._pad_flags()[0]

    def _pad_eval_safe(self) -> bool:
        return self._pad_flags()[1]

    def _pad_train_safe(self) -> bool:
        return self._pad_flags()[2]

    def _fit_one(self, xs, ys, ms, lms):
        """One train step (shared by fit's inner loop and fit_batch).
        Leaves ``_score`` as the ASYNC device loss scalar — see
        ``MultiLayerNetwork._fit_one`` (the host-sync sweep); the fit
        loop materializes once at the end, ``fit_batch`` on return."""
        prof = self._stepprof
        if prof is not None:
            _t = monotonic_s()
        with get_tracer().span("dl4j.h2d"):
            xs = [jnp.asarray(x) for x in xs]
            ys = [jnp.asarray(y) for y in ys]
            ms = None if ms is None else [
                None if m is None else jnp.asarray(m) for m in _as_list(ms)]
            lms = None if lms is None else [
                None if m is None else jnp.asarray(m)
                for m in _as_list(lms)]
        if prof is not None:
            prof.mark("h2d", monotonic_s() - _t)
        self.last_batch_size = int(xs[0].shape[0])
        pol = self.shape_policy
        if pol is not None and pol.enabled and ms is None and \
                self._pad_train_safe():
            # ragged batches pad onto an already-compiled bucket; padded
            # rows carry a zero label mask on EVERY output head
            xs, ys, lms = pol.pad_multi_batch(xs, ys, lms, path="train")
        step_fn = self._get_jitted("train_step")
        # fused-RNG step: splits the key inside the program (bit-identical
        # to the host split it replaces) and returns the successor
        (self.params, self.state, self.opt_state, self._rng, loss,
         gstats) = step_fn(
            self.params, self.state, self.opt_state, self._rng, xs, ys,
            ms, lms)
        self._score = loss
        self._last_grad_stats = gstats
        self._last_step_traced = bool(getattr(step_fn, "last_call_traced",
                                              False))
        self.iteration += 1
        if prof is None:
            for lst in self.listeners:
                lst.iteration_done(self, self.iteration, self.epoch)
        else:
            _t = monotonic_s()
            for lst in self.listeners:
                lst.iteration_done(self, self.iteration, self.epoch)
            prof.mark("listener", monotonic_s() - _t)
        return self._score

    def fit_batch(self, batch) -> float:
        """One train step on one batch WITHOUT epoch bookkeeping (used by
        EarlyStoppingTrainer, which owns the epoch loop)."""
        if self.params == {}:
            self.init()
        return float(self._fit_one(*self._normalize_batch(batch)))

    @training_entry("dl4j.fit")
    def fit(self, data=None, labels=None, *, epochs: int = 1,
            masks=None, label_masks=None, checkpoint=None,
            resume_from=None) -> "ComputationGraph":
        """Train.  ``data`` may be (inputs, labels) (each an array or list of
        arrays) or an iterable of MultiDataSet-shaped batches.

        ``checkpoint``/``resume_from``: crash-consistent periodic saves and
        exact mid-epoch resume (``faulttolerance.CheckpointConfig``; see
        ``MultiLayerNetwork.fit``)."""
        if self.params == {}:
            self.init()
        if labels is not None:
            one = (_as_list(data), _as_list(labels), masks, label_masks)
            batches_factory = lambda: [one]
        elif isinstance(data, tuple) and len(data) in (2, 4):
            # fit((inputs, labels)) single-batch form — a tuple is NOT an
            # iterator of batches
            batches_factory = lambda: [self._normalize_batch(data)]
        elif hasattr(data, "features"):
            # a single DataSet/MultiDataSet IS one batch, not a batch iterator
            batches_factory = lambda: [self._normalize_batch(data)]
        elif hasattr(data, "reset") or hasattr(data, "__iter__"):
            if not hasattr(data, "reset") and epochs > 1 and iter(data) is data:
                data = [self._normalize_batch(b) for b in data]
                batches_factory = lambda: data
            else:
                src = data

                def batches_factory():
                    if hasattr(src, "reset"):
                        src.reset()
                    for b in src:
                        yield self._normalize_batch(b)
        else:
            raise ValueError("fit() needs (inputs, labels) or an iterator")

        # constructed only after every validation raise above: the SIGTERM
        # hook it installs must always reach the loop's finally/close()
        ckpt = None
        if checkpoint is not None or resume_from is not None:
            from ..faulttolerance.checkpoint import FitCheckpointer
            ckpt = FitCheckpointer(self, checkpoint, resume_from)
        from ..observability.health import get_health_monitor
        from ..observability.profiler import step_profiler_for
        from ..observability.recorder import get_flight_recorder
        from .multilayer import _StepForensics
        rec = get_flight_recorder()
        rec_on = rec is not None and rec.enabled
        mon = get_health_monitor()
        forensics = _StepForensics(self, rec, mon, ckpt) \
            if (rec_on or mon is not None) else None
        # per-step phase attribution with a sampled device fence (see
        # MultiLayerNetwork.fit / observability/profiler.py)
        prof = step_profiler_for("train_step")
        self._stepprof = prof

        # bounded async dispatch (ISSUE 18; see MultiLayerNetwork.fit):
        # up to DL4J_TPU_DISPATCH_DEPTH steps in flight, drained at epoch
        # ends and checkpoint boundaries, NaN-checked per drained token
        from .dispatch import DispatchWindow

        def _nan_at_drain(iteration, value):
            if rec_on:
                rec.record("train", "nan_at_drain", score=value,
                           iteration=int(iteration))
        win = DispatchWindow(owner=self, profiler=prof,
                             on_nan=_nan_at_drain)
        start_epoch = ckpt.start_epoch if ckpt is not None else 0
        stop = False
        span = get_tracer().span
        try:
            for ep in range(start_epoch, epochs):
                for lst in self.listeners:
                    lst.on_epoch_start(self)
                batches = iter(batches_factory())
                # resume cursor: skip already-consumed batches of the first
                # resumed epoch without fitting (see MultiLayerNetwork.fit)
                skip = ckpt.skip_batches \
                    if (ckpt is not None and ep == ckpt.start_epoch) else 0
                seq = 0
                while True:
                    with span("dl4j.input_wait"):
                        batch = next(batches, None)
                    if batch is None:
                        break
                    if seq < skip:
                        seq += 1
                        continue
                    t_step = monotonic_s()
                    if prof is not None:
                        prof.begin(t_step)
                    self._fit_one(*batch)
                    if prof is not None:
                        prof.dispatched(self._score, window=win)
                    seq += 1
                    t_end = monotonic_s()
                    if forensics is not None and forensics.step(
                            ep, seq, self._last_step_traced,
                            t_end - t_step, t_end):
                        stop = True   # opt-in health stop: clean return
                    if prof is not None:
                        prof.lap("forensics")
                    if not stop and ckpt is not None:
                        if ckpt.due():
                            # checkpoint boundary drains the window first
                            # (mid-window resume stays digest-exact)
                            win.drain()
                        if ckpt.after_batch(ep, seq):
                            stop = True   # SIGTERM: final save taken
                    if prof is not None:
                        if ckpt is not None:
                            prof.lap("checkpoint")
                        prof.end(self.iteration, self._last_step_traced)
                    if stop:
                        break
                    # admit this step into the in-flight window (bounded-
                    # pipeline backpressure point)
                    win.push(self._score, self.iteration)
                if stop:
                    break
                # ONE materialization per epoch (fit_on_device's sync
                # convention): steps pipelined async all epoch; epoch-end
                # listeners (MetricsListener score/grad-norm) see a host
                # float without forcing their own sync
                win.drain()
                with span("dl4j.sync"):
                    self._score = float(self._score)
                if prof is not None:
                    prof.materialized()
                for lst in self.listeners:
                    lst.on_epoch_end(self)
                self.epoch += 1
                if ckpt is not None and ckpt.after_epoch(ep):
                    stop = True
                    break
            # stop-path exits break before the epoch-end drain
            win.drain()
        except Exception as e:
            # never block on in-flight work while unwinding (the final
            # un-guarded float(_score) still surfaces deferred failures)
            win.abandon()
            if rec_on:   # crash forensics before the exception propagates
                if forensics is not None:
                    try:
                        forensics.flush()
                    except Exception:
                        pass   # forensics must not mask the real error
                rec.record("train", "fit_exception",
                           error=f"{type(e).__name__}: {e}",
                           iteration=int(self.iteration))
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
                self._stepprof = None
                try:
                    prof.flush()
                except Exception:
                    pass   # profile telemetry must not mask the real error
            if ckpt is not None:
                ckpt.close()
        # ONE materialization for the whole fit (async steps pipeline).
        # NOT exception-guarded: deferred device failures surface here
        with span("dl4j.sync"):
            self._score = float(self._score)
        return self

    @training_entry("dl4j.fit_on_device")
    def fit_on_device(self, inputs, labels, *, batch_size: int,
                      epochs: int = 1, shuffle: bool = True,
                      checkpoint=None, resume_from=None
                      ) -> "ComputationGraph":
        """Device-resident epoch training for graphs: the dataset stays in
        HBM and one jitted program scans the train step over all minibatches
        (one dispatch per epoch; see ``MultiLayerNetwork.fit_on_device``).
        ``inputs``/``labels``: array or list of arrays (multi-input/output).
        """
        if self.params == {}:
            self.init()
        ckpt = None
        if checkpoint is not None or resume_from is not None:
            from ..faulttolerance.checkpoint import FitCheckpointer
            ckpt = FitCheckpointer(self, checkpoint, resume_from)
        step = self._get_jitted("train_step")
        return fit_on_device_epochs(
            self, [jnp.asarray(a) for a in _as_list(inputs)],
            [jnp.asarray(a) for a in _as_list(labels)], batch_size, epochs,
            shuffle,
            call_step=lambda p, s, o, k, bx, by: step(p, s, o, k, bx, by,
                                                      None, None),
            fit_tail=lambda xt, yt: self._fit_one(xt, yt, None, None),
            ckpt=ckpt)

    @staticmethod
    def _normalize_batch(b):
        if isinstance(b, (tuple, list)):
            if len(b) == 2:
                return _as_list(b[0]), _as_list(b[1]), None, None
            if len(b) == 4:
                return (_as_list(b[0]), _as_list(b[1]),
                        None if b[2] is None else _as_list(b[2]),
                        None if b[3] is None else _as_list(b[3]))
        if hasattr(b, "features"):
            fm = getattr(b, "features_mask", None)
            lm = getattr(b, "labels_mask", None)
            return (_as_list(b.features), _as_list(b.labels),
                    None if fm is None else _as_list(fm),
                    None if lm is None else _as_list(lm))
        raise ValueError(f"cannot interpret batch of type {type(b)}")

    # ------------------------------------------------------------- queries
    def get_score(self) -> float:
        # may be a device scalar mid-fit_on_device (kept async so epochs
        # pipeline); materialize on demand
        return float(self._score)

    def num_params(self) -> int:
        return sum(int(np.prod(p.shape))
                   for p in jax.tree_util.tree_leaves(self.params))

    def param_bytes(self, per_device: bool = False) -> int:
        """Parameter memory: global bytes, or with ``per_device=True`` the
        bytes ONE device holds — a ZeRO-3 sharded graph (``parallel/
        sharded.py`` NamedSharding layout) reports ~1/dp of global."""
        from ..parallel.sharded import param_bytes, per_device_param_bytes
        return per_device_param_bytes(self.params) if per_device \
            else param_bytes(self.params)

    def evaluate(self, iterator_or_x, y=None):
        from ..evaluation.classification import Evaluation
        return self._evaluate_with(Evaluation(), iterator_or_x, y)

    def evaluate_regression(self, iterator_or_x, y=None):
        from ..evaluation.regression import RegressionEvaluation
        return self._evaluate_with(RegressionEvaluation(), iterator_or_x, y)

    def evaluate_roc(self, iterator_or_x, y=None, threshold_steps: int = 0):
        from ..evaluation.roc import ROC
        return self._evaluate_with(ROC(threshold_steps), iterator_or_x, y)

    def _evaluate_with(self, ev, iterator_or_x, y=None):
        """First network output vs labels (reference ComputationGraph
        evaluate/evaluateROC/evaluateRegression)."""
        for xs, yy in self._eval_batches(iterator_or_x, y):
            out = self.output(*xs)
            if isinstance(out, list):
                out = out[0]
            ev.eval(np.asarray(yy), np.asarray(out))
        return ev

    def _eval_batches(self, it, y):
        if y is not None:
            yield _as_list(it), _as_list(y)[0]
            return
        if hasattr(it, "reset"):
            it.reset()
        for b in it:
            xs, ys, _, _ = self._normalize_batch(b)
            yield xs, ys[0]

    def set_listeners(self, *listeners):
        self.listeners = list(listeners)
        return self

    def add_listeners(self, *listeners):
        self.listeners.extend(listeners)
        return self

    def clone(self) -> "ComputationGraph":
        import copy
        other = ComputationGraph(copy.deepcopy(self.conf))
        copy_tree = lambda t: jax.tree_util.tree_map(lambda a: jnp.array(a), t)
        other.params = copy_tree(self.params)
        other.state = copy_tree(self.state)
        other._tx = other._build_tx()
        if self.opt_state is not None:
            other.opt_state = copy_tree(self.opt_state)
        else:
            other.init()
        # split the parent stream per clone (identical dropout masks across
        # data-parallel replicas would correlate their gradient noise);
        # the deepcopied conf signs identically, so compiled steps are
        # reused from the shared trace cache
        self._rng, other._rng = jax.random.split(self._rng)
        other.shape_policy = self.shape_policy
        other.iteration = self.iteration
        other.epoch = self.epoch
        return other


def check_graph_gradients(net: ComputationGraph, inputs, labels, *,
                          epsilon: float = 1e-6, max_rel_error: float = 1e-3,
                          min_abs_error: float = 1e-8, masks=None,
                          label_masks=None, print_results: bool = False,
                          subset: Optional[int] = None, seed: int = 12345,
                          exclude: tuple = ("centers",)) -> bool:
    """GradientCheckUtil for graphs (reference checkGradients CG variant)."""
    from ..utils.gradient_check import _check_gradients_impl
    if not net.params:
        net.init()
    params = jax.tree_util.tree_map(
        lambda a: jnp.asarray(a, jnp.float64), net.params)
    state = jax.tree_util.tree_map(
        lambda a: jnp.asarray(a, jnp.float64)
        if jnp.issubdtype(a.dtype, jnp.floating) else a, net.state)
    xs = [jnp.asarray(x, jnp.float64) for x in _as_list(inputs)]
    ys = [jnp.asarray(y, jnp.float64) for y in _as_list(labels)]

    @jax.jit  # graftlint: disable=JX028  (f64 gradient-check probe; cold diagnostic path, never steady-state)
    def loss_fn(p):
        loss, _ = net._loss(p, state, xs, ys, train=False, key=None,
                            masks=masks, label_masks=label_masks)
        return loss

    analytic = jax.grad(loss_fn)(params)
    return _check_gradients_impl(loss_fn, params, analytic, epsilon,
                                 max_rel_error, min_abs_error, print_results,
                                 subset, seed, exclude)
