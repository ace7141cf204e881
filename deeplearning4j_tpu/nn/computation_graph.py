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

import contextlib
import functools
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
import optax

from . import precision as _precision
from ._common import (_cast_act, _on_device, build_train_step, build_tx,
                      compute_dtypes, finish_step, fit_batches,
                      fit_on_device_epochs, hyperparam_conf, placed)
from .compile_cache import shared_jit, topology_signature
from .conf.computation_graph import (ComputationGraphConfiguration,
                                     GraphVertexConf, LayerVertex)
from .conf.updaters import Sgd, UpdaterConf
from .layers.base import BaseLayerConf
from ..data.shapes import default_shape_policy
from ..observability.tracer import init_entry, training_entry
from ..train.listeners import TrainingListener

Array = jax.Array


def _as_list(x) -> List:
    if x is None:
        return []
    if isinstance(x, (list, tuple)):
        return list(x)
    return [x]


def _on_device_list(a):
    """Device placement for one part of a graph's batch: a list of leaves
    (one an input, output or mask), or None."""
    return None if a is None else [_on_device(e) for e in _as_list(a)]


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
        # nn/multilayer._stack_forward); a vertex the builder gave a scope
        # of its own (a module of several vertices) runs under that first
        scope = conf.vertex_scopes.get(name)
        with jax.named_scope(scope) if scope else contextlib.nullcontext(), \
                jax.named_scope(type(getattr(v, "layer", None) or v).__name__):
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


def _graph_loss(conf, params, state, inputs, labels, masks=None,
                label_masks=None, *, train: bool, key, precision=None):
    """Sum of the output layers' losses + regularization.  Free function
    over the configuration; with ``conf`` and ``train`` bound it is the
    ``loss`` of ``_common.build_train_step``."""
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
    """The graph's train step: ``_common.build_train_step`` over
    ``_graph_loss``."""
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
    cast_map = compute_dtypes(
        conf.defaults, {name: getattr(v, "layer", None) or v
                        for name, v in conf.vertices.items()})
    return build_train_step(
        functools.partial(_graph_loss, conf, train=True), conf.defaults,
        confs, cast_map, tx)


class ComputationGraph:
    """DAG network: init → fit/output/score/evaluate."""

    def __init__(self, conf: ComputationGraphConfiguration):
        if getattr(conf, "loop", None):
            raise ValueError(
                "ComputationGraph cannot walk a looped range: a vertex "
                "fed by one that comes after it is not written; a looped "
                "list runs as a MultiLayerNetwork")
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
        # the running fit's StepProfiler (``_common.fit_batches`` attaches
        # it; ``placed`` and ``finish_step`` credit it their slices)
        self._stepprof = None
        self._tx = None
        self._rng = jax.random.PRNGKey(conf.seed)
        # instance view over the process-global trace cache (compile_cache)
        self._jit_cache: Dict[Any, Any] = {}
        self._topo_sig: Optional[str] = None
        self._pad_safe: Optional[bool] = None
        self.shape_policy = default_shape_policy()

    # ------------------------------------------------------------------ init
    @init_entry
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

    def _prepare(self, batch):
        """Place and pad one batch: the train step's four batch arguments
        (the fit loop's ``prepare``)."""
        xs, ys, ms, lms = placed(self, _on_device_list, batch)
        self.last_batch_size = int(xs[0].shape[0])
        pol = self.shape_policy
        if pol is not None and pol.enabled and ms is None and \
                self._pad_train_safe():
            # ragged batches pad onto an already-compiled bucket; padded
            # rows carry a zero label mask on EVERY output head
            xs, ys, lms = pol.pad_multi_batch(xs, ys, lms, path="train")
        return xs, ys, ms, lms

    def _fit_one(self, xs, ys, ms, lms):
        """One train step outside a fit loop (``fit_batch``,
        ``fit_on_device``'s ragged tail), from the loop's two pieces;
        returns the still-async loss (``_common.finish_step``)."""
        step = self._get_jitted("train_step")
        args = self._prepare((xs, ys, ms, lms))
        finish_step(self, step, step(
            self.params, self.state, self.opt_state, self._rng, *args))
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
        exact mid-epoch resume (``faulttolerance.CheckpointConfig``; the
        loop is ``nn/_common.fit_batches``)."""
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
        return fit_batches(self, batches_factory, epochs, self._prepare,
                           self._get_jitted("train_step"), ckpt=ckpt)

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
