"""MultiLayerNetwork — sequential-stack network runtime.

TPU-native re-design of ``nn/multilayer/MultiLayerNetwork.java:90``.  Where the
reference drives per-layer Java loops (``feedForwardToLayer`` :903,
``calcBackpropGradients`` :1282) with params as views into one flat array, the
TPU design traces the whole forward+backward+update into ONE jitted XLA
program:

  - forward:   python loop over layer confs, unrolled at trace time (static)
  - backward:  ``jax.value_and_grad`` over the whole stack (replaces the
               hand-written backpropGradient chain)
  - update:    optax transforms fused into the same program; buffer donation
               gives in-place semantics (the flat param view's job)
  - gradient normalization (``BaseMultiLayerUpdater.preApply`` :318) and
    constraints run inside the same program.

Param pytree layout: ``{"layer_0": {...}, "layer_1": {...}}`` keyed by position,
so checkpoints are stable under layer renames (the reference's flat
``coefficients.bin`` role is played by the serialized pytree; see
utils/model_serializer.py).
"""
from __future__ import annotations

import functools
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
import optax

from . import precision as _precision
from . import scan_layers as _scan_layers
from ._common import (_cast_act, _cast_floats, _on_device,
                      build_train_step, build_tx,
                      compute_dtypes, finish_step, fit_batches,
                      fit_on_device_epochs, hyperparam_conf, placed)
from .compile_cache import shared_jit, topology_signature
from .conf.multi_layer import MultiLayerConfiguration
from .conf.schedules import resolve as resolve_schedule
from .conf.updaters import Sgd, UpdaterConf
from .layers.base import BaseLayerConf
from ..data.shapes import _pad_time, default_shape_policy
from ..observability.tracer import init_entry, training_entry
from ..train.listeners import TrainingListener


Array = jax.Array


def _layer_confs(conf) -> Dict[str, Any]:
    return {f"layer_{i}": lc for i, lc in enumerate(conf.layers)}


def _stack_forward(conf, params, state, x, *, train: bool, key, mask=None,
                   to_layer: Optional[int] = None, collect: bool = False,
                   carries: Optional[Dict[str, Any]] = None,
                   return_mask: bool = False, precision=None):
    """Trace the layer stack; returns (final_activation_or_list, new_state).

    A free function over the *configuration* — it must never touch a
    network instance, so the jitted programs built from it can live in the
    process-global trace cache and serve every equal-topology network
    (clones, master replicas).

    carries: optional dict of recurrent-layer carries keyed ``layer_i``
    (tBPTT chunk state / rnnTimeStep streaming state). When given, a dict
    of the same shape is written back into ``carries`` (callers pass a
    mutable dict and read the updated entries).

    precision: resolved ``PrecisionPolicy`` for mixed-precision walks
    (the train step passes it; inference/score paths keep their
    full-precision numerics and pass None).

    Homogeneous layer runs (identical confs repeated — a deep transformer
    stack) execute under ``jax.lax.scan`` so the program traces ONE layer
    body instead of N (``nn/scan_layers``); everything else walks
    unrolled, bit-identically to the pre-scan code.
    """
    n = len(conf.layers) if to_layer is None else to_layer
    walk = _Walk(conf, train=train, collect=collect, carries=carries,
                 precision=precision)
    new_state = dict(state)
    loop = conf.looped()
    if loop is None or loop[0] >= n:
        h, mask = walk.layers(params, state, new_state, x, mask, key, 0, n)
    else:
        first, stop, _ = loop
        refused = (
            "collecting every layer's activations (feed_forward)"
            if collect else
            "with a recurrent carry (tBPTT, rnn_time_step, generation: a "
            "K/V cache a pass is not written)" if carries is not None else
            f"up to layer {n}, inside it" if n < stop else None)
        if refused:
            raise ValueError(f"a looped range cannot be walked {refused}: "
                             "each layer of the range has an output a pass")
        h, mask = walk.layers(params, state, new_state, x, mask, key, 0,
                              first)
        h, mask = walk.loop(params, state, new_state, h, mask, key)
        h, mask = walk.layers(params, state, new_state, h, mask, key, stop,
                              n)
    out = walk.acts if collect else h
    if return_mask:
        return out, new_state, mask
    return out, new_state


class _Walk:
    """One trace of the layer stack: what every stretch of it shares (the
    configuration and the mode), the walk of a stretch (``layers``) and
    of the looped range (``loop``)."""

    def __init__(self, conf, *, train, collect, carries, precision):
        self.conf, self.train, self.collect = conf, train, collect
        self.carries, self.precision = carries, precision
        self.remat = bool(train
                          and conf.defaults.get("cache_mode") == "remat")
        self.acts = []

    def layers(self, params, state, new_state, h, mask, key, lo: int,
               hi: int, passes: int = 1):
        """Walk ``conf.layers[lo:hi]`` from ``h``, reading ``state`` and
        writing ``new_state``; returns ``(h, mask)``.  ``passes``: how
        often the stretch is walked a step (the looped range's count, for
        what a scanned run's stacks take)."""
        conf, train = self.conf, self.train
        layers, precision, carries = conf.layers, self.precision, self.carries
        runs = dict(_scan_layers.scan_runs(
            conf, hi, mask_present=mask is not None,
            carries_present=carries is not None, collect=self.collect,
            policy=precision, lo=lo))
        i = lo
        while i < hi:
            lc = layers[i]
            pp = conf.preprocessor(i)
            if pp is not None:
                h = pp.pre_process(h, mask)
                if mask is not None:
                    itype = conf.layer_input_types[i] \
                        if conf.layer_input_types else None
                    mask = pp.feed_forward_mask(mask, itype)
            if precision is not None:
                h = _cast_act(h, precision.input_dtype(lc))
            stop = runs.get(i)
            if stop is not None:
                # homogeneous run [i, stop): ONE traced body under lax.scan
                h, run_states = _scan_layers.run_scan(
                    lc, [params.get(f"layer_{j}", {})
                         for j in range(i, stop)],
                    [state.get(f"layer_{j}", {}) for j in range(i, stop)],
                    h, key, i, train=train, mask=mask, remat=self.remat,
                    passes=passes)
                for off, ls in enumerate(run_states):
                    new_state[f"layer_{i + off}"] = ls
                i = stop
                continue
            lkey = jax.random.fold_in(key, i) if key is not None else None
            variables = {"params": params.get(f"layer_{i}", {}),
                         "state": state.get(f"layer_{i}", {})}
            lname = f"layer_{i}"
            # one scope per layer, named by its conf's class: the profile
            # of a step splits by layer kind (metadata only; the program
            # is the same)
            with jax.named_scope(type(lc).__name__):
                if carries is not None and getattr(lc, "HAS_CARRY", False):
                    h, new_carry = lc.apply_with_carry(
                        variables, h, carries.get(lname), train=train,
                        key=lkey, mask=mask)
                    carries[lname] = new_carry
                    lstate = variables.get("state", {})
                elif self.remat:
                    # rematerialize per-layer activations on the backward
                    # pass (the WorkspaceMode/CacheMode role: trade FLOPs
                    # for HBM)
                    def _apply(vv, hh, kk, mm, _lc=lc):
                        return _lc.apply(vv, hh, train=True, key=kk, mask=mm)
                    h, lstate = jax.checkpoint(_apply)(variables, h, lkey,
                                                       mask)
                else:
                    h, lstate = lc.apply(variables, h, train=train, key=lkey,
                                         mask=mask)
            new_state[lname] = lstate
            if mask is not None:
                mask = lc.feed_forward_mask(mask, None)
            if self.collect:
                self.acts.append(h)
            i += 1
        return h, mask

    def loop(self, params, state, new_state, h, mask, key):
        """The looped range ``[first, stop)``, ``passes`` times on its one
        set of parameters: an outer ``lax.scan`` over the passes whose
        body is the walk of the range (its scanned run of identical
        blocks, then the layers that close a pass); each pass reads the
        last one's output, and the outputs leave joined in time,
        pass-major, ``[b, passes * t, d]`` (a mask is repeated to match).

        The outer scan is unrolled whole (``unroll=True``): the body is
        traced once and the jaxpr keeps its ``scan``, but the compiled
        step has one level of loops, each pass's run one ``while`` over
        its layers.  Under a ``while`` over the passes around that one
        the TPU's compiler, short of room in its own buffer assignment,
        repeated the blocks' products in the backward (22 ``.remat``
        instructions in Ouro's step where now 1, peak 15.24 -> 14.78 GiB;
        compile-only, PR 41).

        The range's parameters come in as the step holds them (under a
        lower-precision policy: the float32 masters, which
        ``_build_train_step`` leaves uncast for this range) and are cast
        inside the pass, so the loop's sum of a weight's gradient over the
        passes is a float32 sum.  The range's state is carried from pass
        to pass; the key of pass ``r`` is ``fold_in(key, n_layers + r)``.
        With scanning off (``DL4J_TPU_SCAN_LAYERS=0``,
        ``.scan_layers(False)``) the passes are a Python loop."""
        conf, precision = self.conf, self.precision
        first, stop, passes = conf.looped()
        names = [f"layer_{i}" for i in range(first, stop)]
        masters = {k: params[k] for k in names if k in params}
        # as the step's own cast of the other layers (``build_train_step``)
        casts = {} if precision is None else {
            k: dt for k in masters if (dt := precision.layer_dtype(
                conf.layers[int(k[6:])])) not in (None, "float32")}

        def one_pass(h_in, range_state, pass_key):
            p = {k: _cast_floats(v, casts[k]) if k in casts else v
                 for k, v in masters.items()}
            written = dict(range_state)
            h_out, _ = self.layers(p, range_state, written, h_in, mask,
                                   pass_key, first, stop, passes=passes)
            return h_out.astype(h_in.dtype), written

        n_all = len(conf.layers)
        keys = None if key is None else [
            jax.random.fold_in(key, n_all + r) for r in range(passes)]
        range_state = {k: state.get(k, {}) for k in names}
        with jax.named_scope("loop"):
            if _scan_layers.scanning(conf):
                def body(carry, pass_key):
                    h_out, written = one_pass(*carry, pass_key)
                    return (h_out, written), h_out
                (_, range_state), hs = jax.lax.scan(
                    body, (h, range_state),
                    None if keys is None else jnp.stack(keys),
                    length=passes, unroll=True)
                out = jnp.moveaxis(hs, 0, 1).reshape(
                    h.shape[0], passes * h.shape[1], *h.shape[2:])
            else:
                outs = []
                for r in range(passes):
                    h, range_state = one_pass(
                        h, range_state, None if keys is None else keys[r])
                    outs.append(h)
                out = jnp.concatenate(outs, axis=1)
        new_state.update(range_state)
        if mask is not None:
            mask = jnp.tile(mask, (1, passes))
        return out, mask


def _stack_loss(conf, params, state, x, y, mask=None, label_mask=None, *,
                train: bool, key, carries=None, precision=None):
    """Forward to last layer's loss + regularization (reference
    computeGradientAndScore, MultiLayerNetwork.java:2206).  Free function
    over the configuration — see ``_stack_forward``; with ``conf`` and
    ``train`` bound it is the ``loss`` of ``_common.build_train_step``."""
    layers = conf.layers
    n = len(layers)
    h, new_state, pmask = _stack_forward(
        conf, params, state, x, train=train, key=key, mask=mask,
        to_layer=n - 1, carries=carries, return_mask=True,
        precision=precision)
    out_conf = layers[-1]
    if not hasattr(out_conf, "compute_loss"):
        raise ValueError(
            f"last layer '{out_conf.name}' is not an output layer")
    pp = conf.preprocessor(n - 1)
    if pp is not None:
        h = pp.pre_process(h, mask)
    if precision is not None:
        # the head's matmul runs in the compute dtype; the fused
        # softmax/loss reductions upcast to f32 inside nn/losses
        h = _cast_act(h, precision.layer_dtype(out_conf))
    lkey = jax.random.fold_in(key, n - 1) if key is not None else None
    variables = {"params": params.get(f"layer_{n-1}", {}),
                 "state": state.get(f"layer_{n-1}", {})}
    # label mask defaults to the PROPAGATED feature mask (reference
    # per-timestep masking when labelsMask is absent; a LastTimeStep/
    # global-pooling layer consumes the time axis and nulls the mask)
    lm = label_mask if label_mask is not None else pmask
    with jax.named_scope(type(out_conf).__name__):
        if hasattr(out_conf, "loss_and_state"):
            # a head that threads state of its own (the exits' shares)
            loss, new_state[f"layer_{n-1}"] = out_conf.loss_and_state(
                variables, h, y, train=train, key=lkey, mask=lm)
        else:
            loss = out_conf.compute_loss(variables, h, y, train=train,
                                         key=lkey, mask=lm)
    # accumulator follows the LOSS dtype: a dtype-defaulted zeros(())
    # is f64 under x64 and silently promotes the whole loss output
    # (graftaudit AX001); f64 gradient-check runs still get f64 here
    # because their loss is already f64
    reg = jnp.zeros((), dtype=loss.dtype)
    for i, lc in enumerate(layers):
        lp = params.get(f"layer_{i}", {})
        if lp:
            reg = reg + lc.regularization_score(lp)
        if getattr(lc, "AUX_LOSS", False):
            aux = new_state.get(f"layer_{i}", {}).get("aux_loss")
            if aux is not None:
                reg = reg + aux
    return loss + reg, new_state


def _build_stack_fn(conf, tx, kind: str):
    """Build the Python function behind one jitted entry point.

    Returns ``(fun, donate_argnums)``.  Every closure here captures only
    ``conf``/``tx`` — structural configuration shared by all equal-signature
    networks — never a network instance, which is what makes the functions
    safe to place in the process-global trace cache (and is exactly the
    hazard graftlint JX013 flags).
    """
    if conf.looped() and kind in ("rnn_time_step", "train_step_carry",
                                  "paged_prefill", "paged_decode"):
        raise ValueError(
            f"'{kind}' cannot run through a looped range: a recurrent "
            "carry or a K/V cache a pass is not written")
    if kind == "output":
        def fn(params, state, x):
            return _stack_forward(conf, params, state, x, train=False,
                                  key=None)
        return fn, ()
    if kind == "serve":
        # the serving engine's forward: identical program to "output" but
        # with the input batch donated — the engine builds a fresh padded
        # device batch per dispatch and never rereads it, so XLA may alias
        # the buffer into activations (one less live HBM copy per batch).
        # CPU doesn't implement donation and warns per compile; skip there
        # (graftaudit AX005 audits this contract; the CPU skip is a
        # justified manifest suppression in tools/graftaudit/canonical.py).
        def fn(params, state, x):
            return _stack_forward(conf, params, state, x, train=False,
                                  key=None)
        return fn, (() if jax.default_backend() == "cpu" else (2,))
    if kind == "output_train":
        def fn(params, state, x, key):
            return _stack_forward(conf, params, state, x, train=True,
                                  key=key)
        return fn, ()
    if kind == "score":
        def fn(params, state, x, y, label_mask):
            return _stack_loss(conf, params, state, x, y, train=False,
                               key=None, label_mask=label_mask)
        return fn, ()
    if kind == "rnn_time_step":
        def fn(params, state, x, carries):
            carries = dict(carries)
            y, _ = _stack_forward(conf, params, state, x, train=False,
                                  key=None, carries=carries)
            return y, carries
        return fn, ()
    if kind == "train_step":
        # maximal donation (graftaudit AX007): params/state/opt-state AND
        # the RNG key are dead after the call — the fused-RNG step returns
        # the successor key as an alias-matched output, so the 8-byte key
        # buffer recycles in place like the training carry does
        return _build_train_step(conf, tx, False), (0, 1, 2, 3)
    if kind == "train_step_carry":
        # tBPTT additionally donates the recurrent carries (argnum 8):
        # each chunk's carries are consumed by exactly one step
        return _build_train_step(conf, tx, True), (0, 1, 2, 3, 8)
    if kind in ("paged_prefill", "paged_decode"):
        # autoregressive generation programs (bucketed prompt-suffix
        # prefill + fixed-shape slot-batch decode through the paged
        # block pool): built in generation/programs.py, registered here
        # so they ride the same process-global trace cache, instance
        # _jit_cache lifetime, and compile counters as every other entry
        # point
        from ..generation.programs import build_generation_fn
        return build_generation_fn(conf, kind)
    raise KeyError(kind)


def _sparse_embedding_conf(conf):
    """The stack's sparse-gradient embedding layer, or None.

    Only the FIRST layer is eligible: the sparse pre-pass coalesces the
    raw batch ids before the traced stack runs, and only layer_0's ids
    ARE the batch input.  A ``sparse_grad=True`` anywhere else is a
    config error surfaced at build time, not a silent dense fallback.
    """
    from .layers.feedforward import EmbeddingLayer, EmbeddingSequenceLayer
    found = None
    # scan the WHOLE stack before returning: a flag on a later layer
    # must fail even when layer_0 is itself valid
    for i, lc in enumerate(conf.layers):
        if not getattr(lc, "sparse_grad", False):
            continue
        if i != 0:
            raise ValueError(
                f"layer '{lc.name}': sparse_grad=True requires the "
                "embedding to be the first layer (its ids must be the "
                "batch input for the densified pre-pass); position "
                f"{i} gets dense gradients — drop the flag there")
        if not isinstance(lc, (EmbeddingLayer, EmbeddingSequenceLayer)):
            raise ValueError(
                f"layer '{lc.name}': sparse_grad is an embedding-layer "
                "contract")
        if float(lc.resolved("l1", 0.0) or 0.0) or \
                float(lc.resolved("l2", 0.0) or 0.0):
            raise ValueError(
                f"layer '{lc.name}': sparse_grad=True with l1/l2 on the "
                "table is unsupported — dense weight decay touches every "
                "row, defeating the touched-rows-only exchange; drop the "
                "regularization or the flag")
        found = lc
    return found


def _build_train_step(conf, tx, with_carry: bool):
    """The stack's train step: ``_common.build_train_step`` over
    ``_stack_loss``, with the sparse-embedding pre-pass where layer_0 asks
    for it and the recurrent carries for tBPTT."""
    confs = _layer_confs(conf)
    sparse_emb = _sparse_embedding_conf(conf)
    cast_map = compute_dtypes(conf.defaults, confs)
    loop = conf.looped()
    if loop:
        # a looped range's masters reach its walk uncast: it casts them
        # inside each pass, so that their gradient sums over the passes in
        # float32 (``_Walk.loop``)
        first, stop, _ = loop
        cast_map = {k: v for k, v in cast_map.items()
                    if not first <= int(k[6:]) < stop}
    return build_train_step(
        functools.partial(_stack_loss, conf, train=True), conf.defaults,
        confs, cast_map, tx,
        sparse=None if sparse_emb is None else ("layer_0", sparse_emb),
        with_carry=with_carry)


def _build_pretrain_step(conf, tx, i: int):
    """Pretrain step for layer ``i``: the frozen prefix and running state
    ride in as ARGUMENTS (the old per-call closure baked them in as trace
    constants — stale after any host-side update, and re-jitted per call)."""
    lc = conf.layers[i]

    def step(p_i, opt_state, key, x, frozen, state):
        # fused RNG succession (see _common.build_train_step): the split
        # happens inside the program; the successor key is returned
        new_rng, key = jax.random.split(key)

        def loss_fn(pp):
            feats = x
            if i > 0:
                all_p = dict(frozen)
                all_p[f"layer_{i}"] = pp
                feats, _ = _stack_forward(conf, all_p, state, x,
                                          train=False, key=None, to_layer=i)
            variables = {"params": pp,
                         "state": state.get(f"layer_{i}", {})}
            return lc.pretrain_loss(variables, feats, key=key, train=True)
        loss, grads = jax.value_and_grad(loss_fn)(p_i)
        updates, new_opt = tx.update(grads, opt_state, p_i)
        return optax.apply_updates(p_i, updates), new_opt, new_rng, loss

    return step


class MultiLayerNetwork:
    """Sequential network: init → fit/output/score/evaluate."""

    def __init__(self, conf: MultiLayerConfiguration):
        conf.resolve()
        self.conf = conf
        self.layers = conf.layers
        self.params: Dict[str, Any] = {}
        self.state: Dict[str, Any] = {}
        self.opt_state = None
        self.iteration = 0
        self.epoch = 0
        self.last_batch_size = 0
        self.listeners: List[TrainingListener] = []
        self._score = float("nan")
        # drain-boundary telemetry (nn/dispatch.DispatchWindow): the last
        # materialized step's score/iteration — what rate/score listeners
        # read mid-fit without forcing their own host sync
        self.last_drained_score = float("nan")
        self.last_drained_iteration = -1
        self._tx = None
        self._rng = jax.random.PRNGKey(conf.seed)
        # instance view over the process-global trace cache: holds strong
        # refs to the shared jitted entries this network uses (the global
        # cache is weak-valued, so these refs ARE the entries' lifetime)
        self._jit_cache: Dict[Any, Any] = {}
        self._topo_sig: Optional[str] = None
        self._pad_safe: Optional[bool] = None
        self.shape_policy = default_shape_policy()
        self._rnn_carries = None
        self._rnn_carry_batch = -1
        # embedding-first boundary validation cache: None = undecided,
        # False = no id layer, else the layer conf
        self._id_layer = None
        # did the most recent train step (re)trace?  Read from the shared
        # InstrumentedJit after each step: the metrics split
        # (training_step_seconds{phase=compile|steady}) keys off the REAL
        # trace events, so a clone's cache-hit first step reads steady and
        # a mid-fit retrace (new shape/treedef) reads compile
        self._last_step_traced = False
        # the running fit's StepProfiler (``_common.fit_batches`` attaches
        # it; ``placed`` and ``finish_step`` credit it their slices); None
        # outside a profiled fit
        self._stepprof = None

    # ------------------------------------------------------------------ init
    @init_entry
    def init(self) -> "MultiLayerNetwork":
        key = jax.random.PRNGKey(self.conf.seed)
        self.params, self.state = {}, {}
        for i, lc in enumerate(self.layers):
            key, sub = jax.random.split(key)
            itype = self.conf.layer_input_types[i] if self.conf.layer_input_types else None
            v = lc.init(sub, itype)
            self.params[f"layer_{i}"] = v.get("params", {})
            self.state[f"layer_{i}"] = v.get("state", {})
        ls = _precision.init_scale_state(
            _precision.resolve(self.conf.defaults))
        if ls is not None:
            # loss-scale carry rides the state pytree: donated through the
            # step, checkpointed, and averaged like any training state
            self.state[_precision.SCALE_STATE_KEY] = ls
        self._tx = self._build_tx()
        self.opt_state = self._tx.init(self.params)
        return self

    def _default_updater(self) -> UpdaterConf:
        u = self.conf.defaults.get("updater")
        return u if u is not None else Sgd(learning_rate=0.1)

    def _layer_conf_map(self):
        return {f"layer_{i}": lc for i, lc in enumerate(self.layers)}

    def _build_tx(self) -> optax.GradientTransformation:
        """One optax transform; per-layer overrides via multi_transform
        (the reference's per-UpdaterBlock machinery,
        ``nn/updater/BaseMultiLayerUpdater.java:64-138``)."""
        return build_tx(self._default_updater(), self._layer_conf_map(),
                        self.params)

    # -------------------------------------------------------------- forward
    def _forward(self, params, state, x, *, train: bool, key, mask=None,
                 to_layer: Optional[int] = None, collect: bool = False,
                 carries: Optional[Dict[str, Any]] = None,
                 return_mask: bool = False):
        """Delegate to the conf-parameterized ``_stack_forward`` (kept as a
        method for external callers: solvers, gradient checks,
        TransferLearningHelper)."""
        return _stack_forward(self.conf, params, state, x, train=train,
                              key=key, mask=mask, to_layer=to_layer,
                              collect=collect, carries=carries,
                              return_mask=return_mask)

    def _loss(self, params, state, x, y, *, train: bool, key, mask=None,
              label_mask=None, carries=None):
        """Delegate to the conf-parameterized ``_stack_loss``."""
        return _stack_loss(self.conf, params, state, x, y, train=train,
                           key=key, mask=mask, label_mask=label_mask,
                           carries=carries)

    # ---------------------------------------------------------- public API
    def output(self, x, train: bool = False) -> Array:
        """Forward pass (reference ``output(INDArray, train)``). train=True
        keeps stochastic regularization (dropout) active — MC-dropout style.

        Inference batches route through the shape policy: a ragged eval
        batch pads up to an already-compiled bucket and the padded rows are
        sliced off the result (row-wise inference programs make this
        value-preserving; ``train=True`` skips padding — stochastic draws
        and BN batch statistics are shape-dependent)."""
        self._validate_input_ids(x)
        x = jnp.asarray(x)
        pol = self.shape_policy
        n = -1
        if not train and pol is not None and pol.enabled and \
                getattr(x, "ndim", 1) >= 2 and self._pad_output_safe():
            x, n = pol.pad_eval_rows(x)
        if train:
            fn = self._get_jitted("output_train")
            self._rng, key = jax.random.split(self._rng)
            y, _ = fn(self.params, self.state, x, key)
        else:
            fn = self._get_jitted("output")
            y, _ = fn(self.params, self.state, x)
        if n >= 0 and getattr(y, "shape", (0,))[0] > n:
            y = y[:n]
        return y

    def feed_forward(self, x, train: bool = False) -> List[Array]:
        """All layer activations (reference ``feedForward``). train=True keeps
        stochastic regularization active (fresh RNG draw per call)."""
        key = None
        if train:
            self._rng, key = jax.random.split(self._rng)
        acts, _ = self._forward(self.params, self.state, jnp.asarray(x),
                                train=train, key=key, collect=True)
        return acts

    def score(self, dataset=None, x=None, y=None) -> float:
        """Loss on a dataset; with no arguments, the score of the most recent
        training minibatch (reference ``score()`` / ``score(DataSet)``)."""
        if dataset is None and x is None:
            return float(self._score)   # device scalar mid-fit_on_device
        if dataset is not None:
            x, y, _, _ = self._normalize_batch(dataset)
        self._validate_input_ids(x)
        x, y = jnp.asarray(x), jnp.asarray(y)
        lm = None
        pol = self.shape_policy
        if pol is not None and pol.enabled and self._pad_eval_safe():
            # ragged scoring batches ride an already-compiled bucket with
            # the padded rows masked out of the loss (exact: the masked
            # mean's denominator counts only rows with mask weight)
            x, y, lm = pol.pad_score_batch(x, y)
        fn = self._get_jitted("score")
        loss, _ = fn(self.params, self.state, x, y, lm)
        return float(loss)

    def _topology_sig(self) -> str:
        if self._topo_sig is None:
            self._topo_sig = topology_signature(self.conf)
        return self._topo_sig

    def invalidate_compile_cache(self) -> "MultiLayerNetwork":
        """Drop this network's compiled-function views and re-derive its
        topology signature.  Call after mutating ``conf``/layer confs IN
        PLACE (transfer-learning fine-tune on a live net, BN folding);
        builder-style APIs that construct a fresh network need nothing —
        the edited conf signs differently and lands in its own cache slot.
        """
        self._jit_cache = {}
        self._topo_sig = None
        self._pad_safe = None
        self._id_layer = None
        return self

    def _get_jitted(self, kind: str):
        fn = self._jit_cache.get(kind)
        if fn is None:
            if self._tx is None and kind in ("train_step",
                                             "train_step_carry"):
                self._tx = self._build_tx()
            fn = shared_jit(
                (type(self).__name__, self._topology_sig(), kind),
                lambda: _build_stack_fn(self.conf, self._tx, kind),
                name=kind)
            self._jit_cache[kind] = fn
        return fn

    def _validate_input_ids(self, x):
        """Host-side id-range validation for embedding-first networks
        at the fit/output/score boundary (the traced gather clamps
        out-of-range ids silently; see ``feedforward.validate_host_ids``
        — device-resident and float/one-hot batches pass through)."""
        lc0 = self._id_layer
        if lc0 is None:
            from .layers.feedforward import (EmbeddingLayer,
                                             EmbeddingSequenceLayer)
            lc = self.layers[0] if self.layers else None
            lc0 = lc if isinstance(
                lc, (EmbeddingLayer, EmbeddingSequenceLayer)) else False
            self._id_layer = lc0
        if lc0:
            from .layers.feedforward import validate_host_ids
            validate_host_ids(lc0, x)

    def _pad_flags(self):
        if self._pad_safe is None:
            from .layers.normalization import BatchNormalization
            # an AUX_LOSS layer (MoE) couples rows even at inference:
            # padded rows compete for expert CAPACITY, shifting real rows'
            # routing, and its load-balancing loss term is computed from
            # the whole batch (the label mask cannot silence padded rows)
            row_indep = all(not getattr(lc, "AUX_LOSS", False)
                            for lc in self.layers)
            eval_safe = row_indep and (
                not self.layers or getattr(self.layers[-1],
                                           "SUPPORTS_LOSS_MASK", True))
            # BatchNorm additionally trains on batch statistics, which
            # padded rows would perturb (eval uses running stats: safe)
            train_safe = eval_safe and all(
                not isinstance(hyperparam_conf(lc) or lc,
                               BatchNormalization) for lc in self.layers)
            self._pad_safe = (row_indep, eval_safe, train_safe)
        return self._pad_safe

    def _pad_output_safe(self) -> bool:
        """output() padding only needs row-independent inference."""
        return self._pad_flags()[0]

    def _pad_eval_safe(self) -> bool:
        """Loss-path (score) padding additionally needs a mask-honoring
        head — see data/shapes.py."""
        return self._pad_flags()[1]

    def _pad_train_safe(self) -> bool:
        """Training padding additionally requires no cross-batch layers
        (BatchNorm batch statistics)."""
        return self._pad_flags()[2]

    @training_entry("dl4j.fit")
    def fit(self, data=None, labels=None, *, epochs: int = 1,
            mask=None, label_mask=None, checkpoint=None,
            resume_from=None) -> "MultiLayerNetwork":
        """Train. ``data`` may be (x, y) arrays or an iterable of batches
        (the DataSetIterator role).

        ``checkpoint``: a ``faulttolerance.CheckpointConfig`` — periodic
        crash-consistent saves (params + updater + RNG + data cursor +
        shape-policy buckets), optionally with a SIGTERM save-on-preempt
        hook.  ``resume_from``: a checkpoint directory / store /
        ``CheckpointManager`` — restores full training state and resumes
        mid-epoch at the exact saved batch cursor, reproducing the
        uninterrupted run's params (checkpointing is RNG-neutral, so runs
        with and without it are byte-identical).  The loop itself is
        ``nn/_common.fit_batches``."""
        from ..data.dataset import DataSet
        if self.params == {}:
            self.init()
        if labels is not None:
            batches_factory = lambda: [(data, labels, mask, label_mask)]
        elif isinstance(data, DataSet):
            batches_factory = lambda: [self._normalize_batch(data)]
        elif isinstance(data, tuple) and len(data) in (2, 4):
            # fit((x, y)) single-batch form — must not be iterated as batches
            batches_factory = lambda: [self._normalize_batch(data)]
        elif hasattr(data, "reset") or hasattr(data, "__iter__"):
            if not hasattr(data, "reset") and epochs > 1 and iter(data) is data:
                # bare generator: can't be re-iterated per epoch; materialize
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
            raise ValueError("fit() needs (x, y) or an iterator")

        algo = self.conf.defaults.get("optimization_algo", "sgd")
        if algo not in ("sgd", "stochastic_gradient_descent"):
            if checkpoint is not None or resume_from is not None:
                raise ValueError(
                    "checkpoint=/resume_from= are only supported on the SGD "
                    f"path; optimization_algo='{algo}' routes through the "
                    "legacy solvers")
            # legacy full-batch solvers (reference Solver → LBFGS/CG/line
            # search, StochasticGradientDescent.java:58 being the default)
            from ..train.solvers import Solver
            solver = Solver(self, algo, max_iterations=int(
                self.conf.defaults.get("max_iterations", 100)))
            for _ in range(epochs):
                for lst in self.listeners:
                    lst.on_epoch_start(self)
                for batch in batches_factory():
                    x, y, m, lm = batch
                    self.last_batch_size = int(getattr(x, "shape", (0,))[0])
                    solver.optimize(x, y, mask=m, label_mask=lm)
                for lst in self.listeners:
                    lst.on_epoch_end(self)
                self.epoch += 1
            return self

        # constructed only after every validation raise above: the SIGTERM
        # hook it installs must always reach the loop's finally/close()
        ckpt = None
        if checkpoint is not None or resume_from is not None:
            from ..faulttolerance.checkpoint import FitCheckpointer
            ckpt = FitCheckpointer(self, checkpoint, resume_from)
        return fit_batches(
            self, batches_factory, epochs, self._prepare,
            self._get_jitted("train_step"), ckpt=ckpt,
            walk=self._fit_tbptt if self.conf.backprop_type == "tbptt"
            else None)

    @training_entry("dl4j.fit_on_device")
    def fit_on_device(self, x, y, *, batch_size: int, epochs: int = 1,
                      shuffle: bool = True, checkpoint=None,
                      resume_from=None) -> "MultiLayerNetwork":
        """Device-resident epoch training: the whole dataset lives in HBM and
        ONE jitted program scans the train step across all minibatches, so an
        epoch costs a single dispatch.

        TPU-first counterpart of the reference's prefetching iterator stack
        (``AsyncDataSetIterator`` hides host ETL latency behind compute;
        here nothing crosses the host boundary at all, which also removes
        per-step dispatch latency — decisive on remote-attached devices).
        Use plain ``fit`` when data exceeds HBM or per-iteration listener
        granularity matters: listeners here fire once per epoch with the
        recorded final-batch score (per-step hooks would force host syncs).

        ``checkpoint``/``resume_from`` (``faulttolerance``): epoch-boundary
        crash-consistent saves and exact epoch-granular resume.  A
        checkpoint config pins the per-epoch dispatch path (the fused
        multi-epoch program has no epoch boundaries to save at).
        """
        if self.params == {}:
            self.init()
        if self.conf.backprop_type == "tbptt":
            raise ValueError(
                "fit_on_device does not support tBPTT (the scanned step has "
                "no carry truncation); use fit()")
        algo = self.conf.defaults.get("optimization_algo", "sgd")
        if algo not in (None, "sgd", "stochastic_gradient_descent"):
            raise ValueError(
                f"fit_on_device requires the SGD path; optimization_algo="
                f"'{algo}' routes through the legacy solvers — use fit()")
        # constructed only after the validation raises above (its SIGTERM
        # hook must always reach fit_on_device_epochs' finally/close())
        ckpt = None
        if checkpoint is not None or resume_from is not None:
            from ..faulttolerance.checkpoint import FitCheckpointer
            ckpt = FitCheckpointer(self, checkpoint, resume_from)
        step = self._get_jitted("train_step")
        return fit_on_device_epochs(
            self, [jnp.asarray(x)], [jnp.asarray(y)], batch_size, epochs,
            shuffle,
            call_step=lambda p, s, o, k, bx, by: step(p, s, o, k, bx[0],
                                                      by[0], None, None),
            fit_tail=lambda xt, yt: self._fit_one(xt[0], yt[0], None, None),
            ckpt=ckpt)

    def _fit_tbptt(self, batch) -> bool:
        """Truncated BPTT (reference ``doTruncatedBPTT``,
        MultiLayerNetwork.java:1393): split the time axis into
        tbptt_fwd_length chunks; recurrent state (h, c) carries across chunk
        boundaries with gradients stopped at each boundary — so the backward
        window equals the forward chunk, the reference's default fwd==back
        configuration.  ``tbptt_back_length`` is accepted for config parity.

        The fit loop's ``walk``: false, and nothing done, for a batch no
        longer than one chunk (the plain step trains it).
        """
        x, y, mask, label_mask = batch
        if getattr(x, "ndim", 2) != 3 or \
                x.shape[1] <= self.conf.tbptt_fwd_length:
            return False
        self.last_batch_size = int(x.shape[0])
        self._validate_input_ids(x)
        step = self._get_jitted("train_step_carry")
        pol = self.shape_policy
        pad_on = pol is not None and pol.enabled and self._pad_train_safe()
        if pad_on:
            # batch-axis bucketing (ragged epoch tails) before chunking;
            # time-axis chunk padding happens per-chunk below
            x, y, mask, label_mask = pol.pad_train_batch(
                x, y, mask, label_mask, path="tbptt")
        L = self.conf.tbptt_fwd_length
        T = x.shape[1]
        rows = x.shape[0]
        carries = self._init_carries(rows)
        # one device placement per BATCH, not per chunk (JX012: the
        # transfer belongs outside the loop); chunk slices below are
        # device-side views of these arrays
        x = _on_device(x)
        y = _on_device(y)
        mask = _on_device(mask)
        label_mask = _on_device(label_mask)
        from .layers.recurrent import Bidirectional
        # a backward-direction RNN would consume the padded timesteps FIRST,
        # polluting state that reaches every real timestep — never pad
        # bidirectional chunks
        pad_tail = pad_on and T % L != 0 and not any(
            isinstance(lc, Bidirectional) for lc in self.layers)
        traced = False
        for t0 in range(0, T, L):
            sl = slice(t0, min(t0 + L, T))
            xm = None if mask is None else mask[:, sl]
            ym = None if label_mask is None else label_mask[:, sl]
            yc = y[:, sl] if getattr(y, "ndim", 2) == 3 else y
            if pad_tail and sl.stop - sl.start < L:
                # final short chunk pads to the chunk length L so every
                # T hits the ONE compiled chunk program: padded timesteps
                # are zero in data AND feature mask, so the propagated
                # mask excludes them from the loss; this is the last
                # chunk, so the polluted carry is never consumed
                pad = L - (sl.stop - sl.start)
                xc_len = sl.stop - sl.start
                xm = xm if xm is not None else jnp.ones(
                    (rows, xc_len), jnp.float32)
                xm = _pad_time(xm, pad)
                if ym is not None and getattr(ym, "ndim", 1) == 2:
                    ym = _pad_time(ym, pad)
                xc = _pad_time(x[:, sl], pad)
                if getattr(yc, "ndim", 2) == 3:
                    yc = _pad_time(yc, pad)
                x_chunk = xc
            else:
                x_chunk = x[:, sl]
            *out, carries = step(
                self.params, self.state, self.opt_state, self._rng,
                x_chunk, yc, xm, ym, carries)
            # the score stays a device scalar inside the chunk loop
            finish_step(self, step, out)
            traced = traced or self._last_step_traced
        # one sync per batch, so deferred device failures surface in fit
        self._score = float(self._score)
        self._last_step_traced = traced
        return True

    def _init_carries(self, batch: int):
        """Zero carries for every recurrent layer (keyed ``layer_i``)."""
        dtype = jnp.float64 if jax.config.jax_enable_x64 else jnp.float32
        carries = {}
        for i, lc in enumerate(self.layers):
            if getattr(lc, "HAS_CARRY", False):
                carries[f"layer_{i}"] = lc.init_carry(batch, dtype)
        return carries

    # ------------------------------------------------------------- pretrain
    def pretrain(self, data, epochs: int = 1) -> "MultiLayerNetwork":
        """Greedy layerwise unsupervised pretraining (reference
        ``MultiLayerNetwork.pretrain(DataSetIterator)`` :1173): every
        PRETRAINABLE layer (AutoEncoder/RBM/VAE) trains on the features
        produced by the (already-pretrained) layers below it."""
        if self.params == {}:
            self.init()
        for i, lc in enumerate(self.layers):
            if getattr(lc, "PRETRAINABLE", False):
                self.pretrain_layer(i, data, epochs=epochs)
        return self

    def pretrain_layer(self, i: int, data, epochs: int = 1) -> None:
        """Pretrain one layer (reference ``pretrainLayer``).  The prefix
        0..i-1 runs inference-mode under the same jit; only layer i's params
        receive gradients/updates."""
        lc = self.layers[i]
        if not getattr(lc, "PRETRAINABLE", False):
            return
        if self.params == {}:
            self.init()
        from ._common import hyperparam_conf
        hc = hyperparam_conf(lc)
        updater = (hc.updater if hc is not None and hc.updater is not None
                   else self._default_updater())
        tx = updater.to_optax()
        lname = f"layer_{i}"
        opt = tx.init(self.params[lname])
        frozen = {k: v for k, v in self.params.items() if k != lname}
        # shared-cache entry: the step closes over conf/tx only; the frozen
        # prefix and running state ride as ARGUMENTS (the old closure baked
        # them in as trace constants AND re-jitted per pretrain_layer call)
        step = self._jit_cache.get(f"pretrain_{i}")
        if step is None:
            step = shared_jit(
                (type(self).__name__, self._topology_sig(), "pretrain", i),
                lambda: (_build_pretrain_step(self.conf, tx, i), (0, 1, 2)),
                name=f"pretrain_{i}")
            self._jit_cache[f"pretrain_{i}"] = step
        p_i = self.params[lname]
        if epochs > 1 and not hasattr(data, "shape") and \
                not isinstance(data, (tuple, list)) and \
                not hasattr(data, "features") and \
                not hasattr(data, "reset") and \
                hasattr(data, "__iter__") and iter(data) is data:
            # bare generator: materialize for re-iteration.  A list is always
            # a sequence of batches — only a TUPLE is a single (x, y) pair —
            # so a 2-element generator doesn't collapse into a pair below.
            data = list(data)
        for _ in range(epochs):
            for batch in self._pretrain_batches(data):
                # fused-RNG step: splits the key inside the program
                # (bit-identical to the host split it replaces) and
                # returns the successor; key + p_i + opt donate in place
                p_i, opt, self._rng, loss = step(
                    p_i, opt, self._rng, jnp.asarray(batch), frozen,
                    self.state)
                # device scalar in-loop (steps pipeline); one sync below
                self._score = loss
                self.iteration += 1
                for lst in self.listeners:
                    lst.iteration_done(self, self.iteration, self.epoch)
        # NOT exception-guarded: deferred device failures surface here
        self._score = float(self._score)
        self.params[lname] = p_i
        # rebuild optimizer state so supervised fine-tuning starts clean
        self.opt_state = self._tx.init(self.params)

    def _pretrain_batches(self, data):
        if hasattr(data, "shape"):                      # bare feature array
            yield data
            return
        if isinstance(data, tuple) and len(data) in (2, 4):
            yield self._normalize_batch(data)[0]        # (x, y): features only
            return
        if hasattr(data, "features"):                   # single DataSet
            yield self._normalize_batch(data)[0]
            return
        if hasattr(data, "reset"):
            data.reset()
        for b in data:
            yield b if hasattr(b, "shape") else self._normalize_batch(b)[0]

    def _prepare(self, batch):
        """Validate, pad and place one batch: the train step's four batch
        arguments (the fit loop's ``prepare``)."""
        x, y, m, lm = batch
        self.last_batch_size = int(getattr(x, "shape", (0,))[0])
        self._validate_input_ids(x)
        pol = self.shape_policy
        if pol is not None and pol.enabled and self._pad_train_safe():
            # ragged batches (partial epoch tails) pad onto an
            # already-compiled bucket; padded rows are loss-masked so the
            # step is numerically the unpadded one (data/shapes.py)
            x, y, m, lm = pol.pad_train_batch(x, y, m, lm)
        return placed(self, _on_device, (x, y, m, lm))

    def _fit_one(self, x, y, m, lm):
        """One train step outside a fit loop (``fit_batch``,
        ``fit_on_device``'s ragged tail), from the loop's two pieces;
        returns the still-async loss (``_common.finish_step``)."""
        step = self._get_jitted("train_step")
        args = self._prepare((x, y, m, lm))
        finish_step(self, step, step(
            self.params, self.state, self.opt_state, self._rng, *args))
        return self._score

    def fit_batch(self, batch) -> float:
        """One train step on one batch WITHOUT epoch bookkeeping (used by
        EarlyStoppingTrainer, which owns the epoch loop)."""
        if self.params == {}:
            self.init()
        return float(self._fit_one(*self._normalize_batch(batch)))

    # ------------------------------------------------------ stateful RNN API
    def rnn_time_step(self, x) -> Array:
        """Streaming inference with persistent recurrent state (reference
        ``rnnTimeStep``, MultiLayerNetwork.java:2690).  x: [b, t, f] or
        [b, f] (single step).  State persists across calls until
        ``rnn_clear_previous_state``."""
        from .layers.recurrent import Bidirectional
        if any(isinstance(lc, Bidirectional) for lc in self.layers):
            raise ValueError(
                "rnn_time_step does not support bidirectional layers — the "
                "backward pass needs the full sequence (reference throws "
                "likewise)")
        x = jnp.asarray(x)
        # [b, f] = one feature step, squeezed to [b,1,f] — EXCEPT for
        # embedding-sequence models, whose 2-D input is token ids [b, t]
        from .layers.feedforward import EmbeddingSequenceLayer
        ids_model = bool(self.layers) and isinstance(
            self.layers[0], EmbeddingSequenceLayer)
        squeeze = x.ndim == 2 and not ids_model
        if squeeze:
            x = x[:, None, :]
        if getattr(self, "_rnn_carries", None) is None or \
                self._rnn_carry_batch != x.shape[0]:
            self._rnn_carries = self._init_carries(x.shape[0])
            self._rnn_carry_batch = x.shape[0]
        fn = self._get_jitted("rnn_time_step")
        y, self._rnn_carries = fn(self.params, self.state, x, self._rnn_carries)
        return y[:, 0] if squeeze and y.ndim == 3 else y

    def rnn_clear_previous_state(self):
        self._rnn_carries = None
        self._rnn_carry_batch = -1

    def rnn_get_previous_state(self, layer: int):
        c = getattr(self, "_rnn_carries", None)
        return None if c is None else c.get(f"layer_{layer}")

    def rnn_set_previous_state(self, layer: int, state) -> None:
        if getattr(self, "_rnn_carries", None) is None:
            raise ValueError("no rnn state yet — call rnn_time_step first")
        self._rnn_carries[f"layer_{layer}"] = state

    @staticmethod
    def _normalize_batch(b):
        if isinstance(b, (tuple, list)):
            if len(b) == 2:
                return b[0], b[1], None, None
            if len(b) == 4:
                return tuple(b)
        if hasattr(b, "features"):
            return (b.features, b.labels,
                    getattr(b, "features_mask", None),
                    getattr(b, "labels_mask", None))
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
        bytes ONE device holds — a ZeRO-3 sharded net (``parallel/
        sharded.py`` NamedSharding layout) reports ~1/dp of global."""
        from ..parallel.sharded import param_bytes, per_device_param_bytes
        return per_device_param_bytes(self.params) if per_device \
            else param_bytes(self.params)

    def params_flat(self) -> np.ndarray:
        """Flat param vector — serialization/compat view, NOT a runtime
        invariant (see SURVEY §7 'hardest parts')."""
        leaves = []
        for i in range(len(self.layers)):
            lp = self.params.get(f"layer_{i}", {})
            for name in sorted(lp):
                leaves.append(np.asarray(lp[name]).reshape(-1))
        return np.concatenate(leaves) if leaves else np.zeros(0, np.float32)

    def evaluate(self, iterator_or_x, y=None):
        from ..evaluation.classification import Evaluation
        ev = Evaluation()
        for x, yy in self._eval_batches(iterator_or_x, y):
            ev.eval(np.asarray(yy), np.asarray(self.output(x)))
        return ev

    def evaluate_regression(self, iterator_or_x, y=None):
        from ..evaluation.regression import RegressionEvaluation
        ev = RegressionEvaluation()
        for x, yy in self._eval_batches(iterator_or_x, y):
            ev.eval(np.asarray(yy), np.asarray(self.output(x)))
        return ev

    def evaluate_roc(self, iterator_or_x, y=None, threshold_steps: int = 0):
        from ..evaluation.roc import ROC
        ev = ROC(threshold_steps)
        for x, yy in self._eval_batches(iterator_or_x, y):
            ev.eval(np.asarray(yy), np.asarray(self.output(x)))
        return ev

    def _eval_batches(self, it, y):
        if y is not None:
            yield it, y
            return
        if hasattr(it, "reset"):
            it.reset()
        for b in it:
            x, yy, _, _ = self._normalize_batch(b)
            yield x, yy

    def set_listeners(self, *listeners):
        self.listeners = list(listeners)
        return self

    def add_listeners(self, *listeners):
        self.listeners.extend(listeners)
        return self

    def clone(self) -> "MultiLayerNetwork":
        import copy
        other = MultiLayerNetwork(copy.deepcopy(self.conf))
        # REAL copies: the jitted train step donates the original's buffers
        # (donate_argnums), so aliasing them would leave the clone holding
        # deleted arrays after the original trains.
        copy_tree = lambda t: jax.tree_util.tree_map(lambda a: jnp.array(a), t)
        other.params = copy_tree(self.params)
        other.state = copy_tree(self.state)
        other._tx = other._build_tx()
        if self.opt_state is not None:
            other.opt_state = copy_tree(self.opt_state)
        else:
            other.init()
        # split the parent stream per clone: giving every replica the
        # conf-seed key would make data-parallel workers draw IDENTICAL
        # dropout masks/shuffles (correlated noise defeats the averaging)
        self._rng, other._rng = jax.random.split(self._rng)
        # deepcopied conf signs identically, so the clone's first step
        # reuses the parent's compiled executables from the shared cache
        other.shape_policy = self.shape_policy
        other.iteration = self.iteration
        other.epoch = self.epoch
        return other
