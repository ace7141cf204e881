"""ParallelWrapper — mesh-sharded training of a MultiLayerNetwork.

Reference semantics (``parallelism/ParallelWrapper.java:58``): N workers, one
model replica each, params synchronized by averaging or shared quantized
gradients.  TPU-native semantics: ONE jitted SPMD program over a device mesh;
gradients are reduced by XLA-inserted psum over ICI every step (mathematically
the reference's averagingFrequency=1 with exact sync — stronger guarantees at
higher speed, because ICI all-reduce is bandwidth-optimal).

Tensor parallelism (absent in the reference) comes free from the same
machinery: give parameter leaves a PartitionSpec over the 'model' axis and
GSPMD partitions the matmuls Megatron-style.
"""
from __future__ import annotations

from typing import Any, Callable, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from .mesh import (DATA_AXIS, MODEL_AXIS, batch_spec, make_mesh,
                   place_sharded, shard_batch, zero3_spec)
from ..nn._common import finish_step, fit_batches, placed
from ..observability.tracer import training_entry


def _param_specs(params, rule: Optional[Callable[[str, str, Any], P]]):
    """Build a PartitionSpec pytree for params. rule(layer, name, leaf)->P."""
    if rule is None:
        return jax.tree_util.tree_map(lambda _: P(), params)
    out = {}
    for lname, lp in params.items():
        out[lname] = {pname: rule(lname, pname, leaf)
                      for pname, leaf in lp.items()}
    return out


def place_opt_state(opt_state, param_treedef, place_param_tree: Callable,
                    place_other: Callable):
    """Walk an optax state pytree: subtrees shaped exactly like the params
    (mu/nu/trace...) are placed by ``place_param_tree``; every other leaf
    (step counts, scalars) by ``place_other``.  Container structure
    (NamedTuples, tuples, lists, dicts) is preserved.  Shared by the
    replicated wrapper and the ZeRO-3 sharded trainer."""
    def walk(o):
        if jax.tree_util.tree_structure(o) == param_treedef:
            return place_param_tree(o)
        if isinstance(o, tuple) and hasattr(o, "_fields"):  # NamedTuple
            return type(o)(*[walk(c) for c in o])
        if isinstance(o, tuple):
            return tuple(walk(c) for c in o)
        if isinstance(o, list):
            return [walk(c) for c in o]
        if isinstance(o, dict):
            return {k: walk(v) for k, v in o.items()}
        return place_other(o)

    return walk(opt_state)


def megatron_dense_rule(params) -> Callable[[str, str, Any], P]:
    """Alternate column/row parallel sharding for stacked dense layers:
    even layers split n_out over 'model', odd layers split n_in — activations
    stay sharded between the pair and XLA inserts one all-reduce per pair."""
    def _pos(name):
        tail = name.rsplit("_", 1)[-1]
        return int(tail) if tail.isdigit() else None

    order = sorted((n for n in params.keys() if _pos(n) is not None),
                   key=_pos)
    idx = {n: i for i, n in enumerate(order)}  # non-layer_N names replicate

    def rule(lname, pname, leaf):
        if pname == "W" and getattr(leaf, "ndim", 0) == 2:
            col = idx.get(lname, 0) % 2 == 0
            return P(None, MODEL_AXIS) if col else P(MODEL_AXIS, None)
        if pname == "b" and idx.get(lname, 0) % 2 == 0 and getattr(leaf, "ndim", 0) == 1:
            return P(MODEL_AXIS)
        return P()

    return rule


class ParallelWrapper:
    """Train a model over a mesh. Drop-in for single-device ``model.fit``."""

    def __init__(self, model, mesh: Optional[Mesh] = None, *,
                 param_rule: Optional[Callable] = None,
                 shard_optimizer_state: bool = False):
        if model.params == {}:
            model.init()
        self.model = model
        self.mesh = mesh if mesh is not None else make_mesh()
        self.param_rule = param_rule
        # ZeRO-1 / "Automatic Cross-Replica Sharding of Weight Update in
        # Data-Parallel Training" (arXiv:2004.13336, PAPERS.md): shard the
        # optimizer state over the data axis; GSPMD then compiles the
        # update as reduce-scatter(grads) -> sharded optimizer math ->
        # all-gather(params), cutting optimizer memory by 1/dp with the
        # same numerics.
        if shard_optimizer_state and param_rule is not None:
            raise ValueError(
                "shard_optimizer_state=True is only supported with "
                "replicated params (param_rule=None): a TP param_rule "
                "already shards the optimizer state with the params")
        self.shard_optimizer_state = shard_optimizer_state
        self._place()
        self._step = None

    # ------------------------------------------------------------------
    def _place(self):
        m, mesh = self.model, self.mesh
        pspecs = _param_specs(m.params, self.param_rule)
        to_sh = lambda spec: NamedSharding(mesh, spec)
        self.param_shardings = jax.tree_util.tree_map(
            to_sh, pspecs, is_leaf=lambda x: isinstance(x, P))
        # place_sharded: direct device_put with the per-shard assembly
        # fallback for backends where a multi-process NamedSharding put
        # is unimplemented (the CPU rig limitation PR 7 recorded)
        m.params = jax.tree_util.tree_map(place_sharded, m.params,
                                          self.param_shardings)
        repl = NamedSharding(mesh, P())
        m.state = jax.tree_util.tree_map(
            lambda a: place_sharded(a, repl), m.state)
        # the RNG key rides the fused-RNG step (in and out), so it must
        # start mesh-replicated: the step returns the successor key with
        # this sharding, and a first-call mismatch would cost one extra
        # executable lowering
        m._rng = place_sharded(m._rng, repl)
        # optimizer state: subtrees shaped like params (optax mu/nu/trace...)
        # get the param sharding; everything else (counts) is replicated
        param_treedef = jax.tree_util.tree_structure(m.params)

        def zero1_sharding(leaf):
            """The shared ZeRO layout rule, threshold 0 (ZeRO-1 shards
            every divisible optimizer leaf; biases/scalars replicate
            because no axis divides)."""
            d = self.mesh.shape.get(DATA_AXIS, 1)
            return NamedSharding(
                mesh, zero3_spec(getattr(leaf, "shape", ()), d, 0))

        if self.shard_optimizer_state and self.param_rule is None:
            place_param_tree = lambda o: jax.tree_util.tree_map(
                lambda a: place_sharded(a, zero1_sharding(a)), o)
        else:
            place_param_tree = lambda o: jax.tree_util.tree_map(
                place_sharded, o, self.param_shardings)
        m.opt_state = place_opt_state(
            m.opt_state, param_treedef, place_param_tree,
            lambda o: place_sharded(o, repl))

    def remesh(self, mesh: Mesh) -> "ParallelWrapper":
        """Re-target the wrapper onto a different mesh and re-place all
        device state under its layout (the elastic shrink/grow path: the
        survivor mesh becomes the new topology).  The jitted train step
        is untouched — sharding lives in the step's ARGUMENTS, so the
        process-global trace serves the new mesh without retracing."""
        self.mesh = mesh
        self._place()
        return self

    # ---- model duck-typing (EarlyStoppingTrainer & friends) ----------
    @property
    def params(self):
        return self.model.params

    def init(self):
        self.model.init()
        self._place()
        return self

    def get_score(self) -> float:
        return self.model.get_score()

    def score(self, *a, **kw) -> float:
        return self.model.score(*a, **kw)

    def _normalize_batch(self, b):
        return self.model._normalize_batch(b)

    def clone(self):
        """Snapshot of the UNDERLYING model (savers keep plain models)."""
        return self.model.clone()

    def evaluate(self, *a, **kw):
        return self.model.evaluate(*a, **kw)

    def fit_batch(self, batch) -> float:
        """One sharded train step on one batch, no epoch bookkeeping
        (the EarlyStoppingTrainer inner-loop contract)."""
        m = self.model
        trimmed = self._trim(m._normalize_batch(batch))
        if trimmed is None:    # sub-shard batch: nothing to step on
            return m._score
        step = self._get_step()
        args = self._prepare(trimmed)
        finish_step(m, step, step(m.params, m.state, m.opt_state, m._rng,
                                  *args))
        m._score = float(m._score)
        return m._score

    def _prepare(self, batch):
        """Validate one trimmed batch and shard it over the mesh: the
        train step's four batch arguments (the fit loop's ``prepare``)."""
        m = self.model
        x = batch[0]
        if hasattr(m, "_validate_input_ids"):
            # embedding-first boundary validation (the traced gather
            # clamps out-of-range ids silently)
            m._validate_input_ids(x)
        first = x[0] if isinstance(x, (list, tuple)) else x
        m.last_batch_size = int(getattr(first, "shape", (0,))[0])
        return placed(m, self._put, batch)

    def _data_axis_size(self) -> int:
        return int(np.prod([self.mesh.shape[a] for a in (DATA_AXIS,)
                            if a in self.mesh.shape]))

    def _trim(self, batch):
        """Drop the remainder rows of a partial batch so the leading dim
        shards evenly over the data axis (standard DP practice; the
        reference round-robins whole batches to workers instead)."""
        d = self._data_axis_size()
        x = batch[0][0] if isinstance(batch[0], (list, tuple)) else batch[0]
        n = int(x.shape[0])
        keep = (n // d) * d
        if keep == n:
            return batch
        if keep == 0:
            return None   # batch smaller than the data axis: skip it

        def cut(a):
            if a is None:
                return None
            if isinstance(a, (list, tuple)):
                return [None if e is None else e[:keep] for e in a]
            return a[:keep]

        return tuple(cut(p_) for p_ in batch)

    def _put(self, a):
        if a is None:
            return None
        if isinstance(a, (list, tuple)):
            return [self._put_one(e) for e in a]
        return self._put_one(a)

    def _put_one(self, a):
        """Shard one batch leaf; a leaf already placed on THIS mesh (a
        ``DevicePrefetchIterator(mesh=...)`` upstream) passes through with
        no second H2D copy or reshard.  Device arrays on a different mesh
        or uncommitted still go through ``device_put`` (it reshards)."""
        if a is None:
            return None
        if isinstance(a, jax.Array):
            sh = getattr(a, "sharding", None)
            if (isinstance(sh, NamedSharding) and sh.mesh == self.mesh
                    and sh.spec == batch_spec(a.ndim)):
                return a
            return shard_batch(self.mesh, a)
        return shard_batch(self.mesh, jnp.asarray(a))

    def _get_step(self):
        if self._step is None:
            self._step = self.model._get_jitted("train_step")
        return self._step

    # ------------------------------------------------------------------
    @training_entry("dl4j.fit")
    def fit(self, data=None, labels=None, *, epochs: int = 1,
            mask=None, label_mask=None):
        """Shard each batch over the mesh then run the jitted SPMD step.
        Same contract as ``MultiLayerNetwork.fit``: (x, y) arrays or an
        iterable/iterator of batches, optional masks, multiple epochs;
        the loop is the model's own (``nn/_common.fit_batches``), so on a
        ZeRO-3 layout the dispatch window is what lets the NEXT step's
        host work overlap the in-flight step's all-gather + compute."""
        m = self.model
        if labels is not None:
            src = [(data, labels, mask, label_mask)]
        elif hasattr(data, "reset") or hasattr(data, "__iter__"):
            src = data
            if not hasattr(src, "reset") and epochs > 1 and iter(src) is src:
                src = [m._normalize_batch(b) for b in src]
        else:
            raise ValueError("fit() needs (x, y) or an iterator")

        def batches_factory():
            if hasattr(src, "reset"):
                src.reset()
            for b in src:
                trimmed = self._trim(m._normalize_batch(b))
                if trimmed is not None:
                    yield trimmed
        fit_batches(m, batches_factory, epochs, self._prepare,
                    self._get_step())
        return self

    def average_params(self):
        """No-op: SPMD keeps replicas exact (reference averageModelsParams
        exists because its replicas drift; ours cannot)."""
        return self.model.params
