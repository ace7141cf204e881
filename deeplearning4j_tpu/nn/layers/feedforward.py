"""Feed-forward layers: Dense, Output, Loss, Activation, Dropout, Embedding.

Reference: ``nn/layers/feedforward/dense/DenseLayer.java``,
``nn/conf/layers/{DenseLayer,OutputLayer,LossLayer,ActivationLayer,
DropoutLayer,EmbeddingLayer}``.  The matmul runs in the layer's dtype
(bfloat16-ready) and XLA fuses bias+activation into it — the MXU path.
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Optional, Sequence

import jax
import jax.numpy as jnp

from ...utils.serde import register_serde
from .. import losses as _losses
from ..conf.input_type import InputType
from .base import BaseLayerConf, LayerConf


@register_serde
@dataclass
class DenseLayer(BaseLayerConf):
    INPUT_KIND = "ff"

    n_in: int = 0
    n_out: int = 0
    has_bias: bool = True

    # ---- shape inference ----------------------------------------------------
    def set_n_in(self, itype: InputType, override: bool = False) -> None:
        if self.n_in == 0 or override:
            if itype.kind not in ("ff", "cnnflat"):
                raise ValueError(
                    f"layer '{self.name}': dense layer expects FF input, got {itype}")
            self.n_in = itype.flat_size() if itype.kind == "cnnflat" else itype.size

    def output_type(self, itype: InputType) -> InputType:
        return InputType.feed_forward(self.n_out)

    # ---- runtime ------------------------------------------------------------
    def init(self, key, itype):
        if self.n_in <= 0 or self.n_out <= 0:
            raise ValueError(
                f"layer '{self.name}': n_in={self.n_in}, n_out={self.n_out} — "
                "set n_in explicitly or declare the network input type "
                "(set_input_type) so it can be inferred")
        params = {"W": self.make_weight(key, (self.n_in, self.n_out))}
        if self.has_bias:
            params["b"] = self.make_bias((self.n_out,))
        return {"params": params, "state": {}}

    def _operands(self, variables, x, train, key):
        """``(x, params)`` as the product takes them: dropout on the
        input, noise on the weights."""
        params = self.maybe_noise_weights(key, variables["params"], train)
        return self.maybe_dropout_input(key, x, train), params

    def pre_output(self, variables, x, *, train=False, key=None):
        x, params = self._operands(variables, x, train, key)
        z = x @ params["W"]
        if self.has_bias:
            z = z + params["b"]
        return z

    def apply(self, variables, x, *, train=False, key=None, mask=None):
        z = self.pre_output(variables, x, train=train, key=key)
        return self.act_fn(z), variables.get("state", {})


@register_serde
@dataclass
class OutputLayer(DenseLayer):
    """Dense + loss head (reference ``nn/conf/layers/OutputLayer``).
    ``loss_weights`` is the reference's per-output weight vector
    (e.g. ``LossMCXENT(weights)`` for class imbalance): the per-unit loss
    is scaled column-wise before reduction.

    ``pred_heads`` > 1 makes the ``n_out`` units that many prediction
    heads of ``n_out / pred_heads`` classes each (a multi-token head: head
    ``n`` at a position predicts the ``n``-th target after it): the
    activation runs per head, labels and label mask carry a trailing axis
    of ``pred_heads``, and the loss is the mean over the targets the mask
    keeps (where the single head's score, the reference's, sums an
    example's units and averages over the examples)."""
    loss: str = "mcxent"
    loss_weights: Optional[Sequence[float]] = None
    pred_heads: int = 1

    def _by_head(self, z):
        if self.pred_heads == 1:
            return z
        return z.reshape(*z.shape[:-1], self.pred_heads,
                         self.n_out // self.pred_heads)

    def apply(self, variables, x, *, train=False, key=None, mask=None):
        if self.pred_heads == 1:
            return super().apply(variables, x, train=train, key=key,
                                 mask=mask)
        z = self.pre_output(variables, x, train=train, key=key)
        return (self.act_fn(self._by_head(z)).reshape(z.shape),
                variables.get("state", {}))

    def _chunk_rows(self, x, labels, mask, act):
        """Time steps a chunk where this head's loss runs chunk by chunk
        (``losses.head_rows_per_chunk``), by what the call shows: a
        softmax over integer labels ``[b, t]``, one head, no column
        weights, logits that float16's loss scale need not protect, and
        float32 logits too large to form whole.  ``None``: the plain
        product and loss."""
        if not (isinstance(self.loss, str)
                and self.loss.lower() == "sparse_mcxent"
                and isinstance(act, str) and act.lower() == "softmax"
                and self.pred_heads == 1 and self.loss_weights is None
                and x.ndim == 3 and labels.shape == x.shape[:2]
                and (mask is None or mask.shape == labels.shape)
                and x.dtype != jnp.float16):
            return None
        return _losses.head_rows_per_chunk(*labels.shape, self.n_out)

    def _count_chunks(self, rows: int, chunks: int) -> None:
        """One head traced with its logits formed chunk by chunk:
        trace-time, like ``mla_layers_traced_total``."""
        from ...observability.registry import default_registry
        reg = default_registry()
        if reg.enabled:
            reg.counter("head_chunks_traced_total",
                        "Output layers traced with their logits formed "
                        "chunk by chunk, by the logits' rows and "
                        "classes and the number of chunks",
                        ("rows", "classes", "chunks")).labels(
                            str(rows), str(self.n_out), str(chunks)).inc()

    def compute_loss(self, variables, x, labels, *, train=False, key=None,
                     mask=None, average=True):
        """The head's score.  As a rule the whole ``[.., n_out]``
        pre-activation is formed and handed to the loss.  A softmax head
        over integer labels whose float32 logits would pass
        ``losses.HEAD_CHUNK_BYTES`` forms no whole logits, neither here
        nor in the backward pass: ``losses.chunked_softmax_xent`` walks
        chunks of time steps (counter ``head_chunks_traced_total``)."""
        act = self.resolved("activation", "identity")
        rows = self._chunk_rows(x, labels, mask, act)
        if rows is not None:
            self._count_chunks(labels.size, labels.shape[1] // rows)
            x, params = self._operands(variables, x, train, key)
            return _losses.chunked_softmax_xent(
                x, params["W"], params["b"] if self.has_bias else None, labels,
                _losses.position_weights(mask, labels.shape), rows)
        z = self._by_head(self.pre_output(variables, x, train=train,
                                          key=key))
        if self.loss_weights is not None:
            w = jnp.asarray(self.loss_weights, z.dtype)
            if w.shape[-1] != self.n_out:
                raise ValueError(
                    f"layer '{self.name}': {w.shape[-1]} loss weights for "
                    f"{self.n_out} outputs")
            loss = _losses.get(self.loss)(labels, z, act, mask,
                                          unit_weights=w)
        else:
            loss = _losses.get(self.loss)(labels, z, act, mask)
        if self.pred_heads > 1:
            # the loss came as a sum over the kept targets divided by the
            # examples that keep any
            if mask is None:
                return loss * (z.shape[0] / math.prod(z.shape[:-1]))
            m = mask.astype(loss.dtype)
            rows = jnp.sum(jnp.max(m.reshape(m.shape[0], -1), axis=1))
            return loss * jnp.maximum(rows, 1.0) / jnp.maximum(jnp.sum(m),
                                                               1.0)
        return loss


@register_serde
@dataclass
class CenterLossOutputLayer(OutputLayer):
    """Softmax + center loss (reference
    ``nn/layers/training/CenterLossOutputLayer.java`` / conf
    ``CenterLossOutputLayer``): intra-class compactness term
    λ/2·||f − c_y||².  Centers live as a param whose gradient is decoupled
    from the feature gradient via stop_gradient — the α-rate moving-average
    center update of the reference becomes plain SGD on the center term."""
    alpha: float = 0.05
    lambda_: float = 2e-4

    def init(self, key, itype):
        out = super().init(key, itype)
        out["params"]["centers"] = jnp.zeros((self.n_out, self.n_in),
                                             self._dtype())
        return out

    def regularization_score(self, params):
        # centers are statistics, not weights — exclude from l1/l2
        return super().regularization_score(
            {k: v for k, v in params.items() if k != "centers"})

    def compute_loss(self, variables, x, labels, *, train=False, key=None,
                     mask=None, average=True):
        base = super().compute_loss(variables, x, labels, train=train,
                                    key=key, mask=mask, average=average)
        centers = variables["params"]["centers"]
        c_sel = labels @ centers                     # one-hot row-select
        diff_f = x - jax.lax.stop_gradient(c_sel)    # pulls features to centers
        diff_c = jax.lax.stop_gradient(x) - c_sel    # pulls centers to features
        per_f = jnp.sum(diff_f ** 2, axis=-1)
        per_c = jnp.sum(diff_c ** 2, axis=-1)
        if mask is not None:
            w = mask.reshape(mask.shape[0], -1)[:, 0]  # per-example weight
            denom = jnp.maximum(jnp.sum(w), 1.0)
            mean_f = jnp.sum(w * per_f) / denom
            mean_c = jnp.sum(w * per_c) / denom
        else:
            mean_f, mean_c = jnp.mean(per_f), jnp.mean(per_c)
        l_feat = 0.5 * self.lambda_ * mean_f
        l_cent = 0.5 * self.alpha * mean_c
        # value-neutral center update: contributes gradient (to centers only)
        # but zero to the reported score — matching the reference, where the
        # α-rate center update happens outside the loss
        return base + l_feat + l_cent - jax.lax.stop_gradient(l_cent)


@register_serde
@dataclass
class LossLayer(BaseLayerConf):
    """Loss-only head, no params (reference ``nn/conf/layers/LossLayer``)."""
    loss: str = "mse"

    def has_params(self):
        return False

    def apply(self, variables, x, *, train=False, key=None, mask=None):
        return self.act_fn(x), variables.get("state", {})

    def compute_loss(self, variables, x, labels, *, train=False, key=None,
                     mask=None, average=True):
        act = self.resolved("activation", "identity")
        return _losses.get(self.loss)(labels, x, act, mask)


@register_serde
@dataclass
class ActivationLayer(BaseLayerConf):
    def has_params(self):
        return False

    def apply(self, variables, x, *, train=False, key=None, mask=None):
        return self.act_fn(x), variables.get("state", {})


@register_serde
@dataclass
class DropoutLayer(BaseLayerConf):
    """Standalone dropout (reference ``nn/conf/layers/DropoutLayer``)."""

    def has_params(self):
        return False

    def apply(self, variables, x, *, train=False, key=None, mask=None):
        return self.maybe_dropout_input(key, self.act_fn(x), train), \
            variables.get("state", {})


def _embedding_invalid(msg: str):
    """Raise the serving stack's client-error type (a bad id batch is a
    caller bug, distinguishable from model-internal ValueErrors — the
    generation engine's InvalidInputError pattern)."""
    from ...parallel.inference import InvalidInputError
    raise InvalidInputError(msg)


def _validate_id_dtype(x, name: str, n_in: int):
    if not jnp.issubdtype(x.dtype, jnp.integer):
        _embedding_invalid(
            f"layer '{name}': embedding ids must be an integer dtype, got "
            f"{x.dtype} — a float id batch would silently truncate; pass "
            f"int ids, or a one-hot batch with trailing dim {n_in}")


def _validate_id_range(idx, name: str, n_in: int):
    """Concrete (host-visible) id batches are range-checked up front;
    traced ids are validated by the caller before dispatch (a traced
    gather clamps, so an in-program check could only corrupt silently)."""
    if isinstance(idx, jax.core.Tracer):
        return
    lo = int(jnp.min(idx)) if idx.size else 0
    hi = int(jnp.max(idx)) if idx.size else 0
    if lo < 0 or hi >= n_in:
        _embedding_invalid(
            f"layer '{name}': embedding ids out of range [{lo}, {hi}] for "
            f"vocabulary of {n_in} — the on-device gather would clamp "
            "silently")


def validate_host_ids(lc, x) -> None:
    """Boundary (host-side) id-range validation for embedding-first
    networks.  fit/output/score TRACE the forward, where a range check
    cannot run (the traced gather clamps silently), so the network
    entry points validate the concrete batch BEFORE dispatch — the
    generation engine's validate-at-admission pattern.  Device-resident
    batches (a ``DevicePrefetchIterator`` upstream) skip: materializing
    them here would stall the pipeline overlap, and their producers
    validated host-side.  Float/one-hot batches skip too — the dtype
    contract is static and already raises at trace time."""
    if x is None or isinstance(x, (list, tuple)) or \
            isinstance(x, jax.core.Tracer) or isinstance(x, jax.Array):
        return
    import numpy as np
    arr = np.asarray(x)
    if arr.ndim == 0 or arr.size == 0 or \
            not np.issubdtype(arr.dtype, np.integer):
        return
    lo, hi = int(arr.min()), int(arr.max())
    if lo < 0 or hi >= lc.n_in:
        _embedding_invalid(
            f"layer '{lc.name}': embedding ids out of range [{lo}, {hi}] "
            f"for vocabulary of {lc.n_in} — the on-device gather would "
            "clamp silently")


@register_serde
@dataclass
class EmbeddingLayer(BaseLayerConf):
    """Index → vector lookup (reference ``nn/conf/layers/EmbeddingLayer``).

    Input: integer indices [batch] / [batch, 1], or one-hot
    [batch, n_in]; output [batch, n_out].  Lookup is a gather — on TPU
    this stays on-device and differentiates to a scatter-add, replacing
    the reference's row-view update trick.

    ``sparse_grad=True`` opts the table into the densified sparse
    gradient path (``nn/sparse``): the train step exchanges coalesced
    touched-row index+value blocks instead of the dense ``[n_in,
    n_out]`` cotangent, and the updater touches only those rows (lazy
    row-sparse semantics — exact for stateless updaters; stateful
    mirrors skip untouched-row decay).  ``sparse_grad_capacity`` pads
    the per-step block to a fixed size (None = exact bound); a capacity
    below the bound is refused at trace time.  Requires the layer to be
    first in the stack (ids come straight from the batch) and no
    l1/l2 on the table (dense decay touches every row).
    """
    n_in: int = 0
    n_out: int = 0
    has_bias: bool = True
    sparse_grad: bool = False
    sparse_grad_capacity: Optional[int] = None

    def set_n_in(self, itype: InputType, override: bool = False) -> None:
        if self.n_in == 0 or override:
            self.n_in = itype.size

    def output_type(self, itype: InputType) -> InputType:
        return InputType.feed_forward(self.n_out)

    def init(self, key, itype):
        params = {"W": self.make_weight(key, (self.n_in, self.n_out))}
        if self.has_bias:
            params["b"] = self.make_bias((self.n_out,))
        return {"params": params, "state": {}}

    def decode_ids(self, x):
        """Id view of one input batch: [batch] int32 ids, or None for a
        one-hot batch.  Validates the id path's dtype (float ids used
        to truncate silently via astype) and, for concrete batches, the
        id range."""
        if x.ndim == 2 and x.shape[-1] == self.n_in and self.n_in > 1 and \
                not jnp.issubdtype(x.dtype, jnp.integer):
            return None                      # one-hot input
        if x.ndim == 2 and x.shape[-1] == 1:
            x = x[:, 0]                      # [b, 1] id column
        if x.ndim != 1:
            # integer [b, n_in] with n_in > 1 is the historical int
            # one-hot form — decode it like the float one-hot path
            if x.ndim == 2 and x.shape[-1] == self.n_in and self.n_in > 1:
                return None
            _embedding_invalid(
                f"layer '{self.name}': expected ids [batch]/[batch, 1] or "
                f"one-hot [batch, {self.n_in}], got shape {tuple(x.shape)}")
        _validate_id_dtype(x, self.name, self.n_in)
        idx = x.astype(jnp.int32)
        _validate_id_range(idx, self.name, self.n_in)
        return idx

    def apply(self, variables, x, *, train=False, key=None, mask=None):
        params = variables["params"]
        idx = self.decode_ids(x)
        if idx is None:
            idx = jnp.argmax(x, axis=-1)     # one-hot input
        if self.sparse_grad:
            from .. import sparse as _sparse
            z = _sparse.embedding_lookup(params["W"], idx)
        else:
            z = params["W"][idx]
        if self.has_bias:
            z = z + params["b"]
        return self.act_fn(z), variables.get("state", {})


@register_serde
@dataclass
class EmbeddingSequenceLayer(BaseLayerConf):
    """Token-id sequence → embedding sequence: [b, t] int (or one-hot
    [b, t, n_in]) → [b, t, n_out] (reference ``EmbeddingSequenceLayer``).
    Gather on device; backward is a scatter-add.

    An exactly-one-hot-shaped [b, t, n_in] input decodes to ids
    (argmax) and rides the same gather — the historical
    ``x @ W`` matmul is O(b·t·n_in·n_out) dense MXU work (ruinous under
    a bf16 policy at real vocab sizes) for what is a lookup.  Callers
    that feed SOFT distributions over the vocabulary (expected
    embeddings, a semantic the matmul computes and argmax does not) opt
    back in with ``one_hot_matmul=True``.

    ``sparse_grad`` / ``sparse_grad_capacity``: see
    :class:`EmbeddingLayer` — same densified-gradient contract over the
    [b, t] id path.
    """
    INPUT_KIND = "rnn"

    n_in: int = 0     # vocabulary size
    n_out: int = 0    # embedding dim
    one_hot_matmul: bool = False
    sparse_grad: bool = False
    sparse_grad_capacity: Optional[int] = None
    scale: float = 1.0    # on the looked-up rows (sqrt(n_out) in some LMs)

    def set_n_in(self, itype: InputType, override: bool = False) -> None:
        if self.n_in == 0 or override:
            self.n_in = itype.size

    def output_type(self, itype: InputType) -> InputType:
        return InputType.recurrent(self.n_out, itype.timesteps)

    def init(self, key, itype):
        return {"params": {"W": self.make_weight(key,
                                                 (self.n_in, self.n_out))},
                "state": {}}

    def decode_ids(self, x):
        """Id view of one input batch: [b, t] int32 ids, or None when
        the batch must ride the one-hot matmul (``one_hot_matmul=True``,
        or a 3-D input that is not one-hot-shaped)."""
        if x.ndim == 3:
            if self.n_in > 0 and x.shape[-1] != self.n_in:
                # a stale tokenizer / vocab-size mismatch would otherwise
                # surface as a cryptic dot_general shape error deep in
                # the trace
                _embedding_invalid(
                    f"layer '{self.name}': 3-D input has trailing dim "
                    f"{x.shape[-1]} but the vocabulary is {self.n_in} — "
                    f"expected one-hot [batch, time, {self.n_in}] (or "
                    "integer ids [batch, time])")
            if self.one_hot_matmul or self.n_in <= 0:
                return None
            return jnp.argmax(x, axis=-1)
        if x.ndim != 2:
            _embedding_invalid(
                f"layer '{self.name}': expected ids [batch, time] or "
                f"one-hot [batch, time, {self.n_in}], got shape "
                f"{tuple(x.shape)}")
        _validate_id_dtype(x, self.name, self.n_in)
        idx = x.astype(jnp.int32)
        _validate_id_range(idx, self.name, self.n_in)
        return idx

    def apply(self, variables, x, *, train=False, key=None, mask=None):
        W = variables["params"]["W"]
        idx = self.decode_ids(x)
        if idx is None:           # explicit opt-in (soft distributions)
            z = x.astype(W.dtype) @ W
        elif self.sparse_grad:
            from .. import sparse as _sparse
            z = _sparse.embedding_lookup(W, idx)
        else:
            z = W[idx]
        if self.scale != 1.0:
            z = z * jnp.asarray(self.scale, z.dtype)
        return self.act_fn(z), variables.get("state", {})
