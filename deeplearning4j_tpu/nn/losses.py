"""Loss functions.

Covers the reference's ILossFunction set (nd4j ``LossFunctions.LossFunction``
used throughout ``nn/conf/layers/OutputLayer``): MSE, MAE (L1), XENT (binary
cross-entropy), MCXENT (multi-class cross-entropy), NEGATIVELOGLIKELIHOOD,
SQUARED_LOSS, HINGE, SQUARED_HINGE, KL_DIVERGENCE, POISSON, COSINE_PROXIMITY,
MEAN_ABSOLUTE_PERCENTAGE_ERROR, MEAN_SQUARED_LOGARITHMIC_ERROR, L2, L1,
SPARSE_MCXENT, plus FMEASURE approximation and WASSERSTEIN.

Each loss is ``fn(labels, preoutput, activation_fn, mask) -> scalar`` computing
the *mean over examples* of the per-example score (summed over output units),
matching the reference's score aggregation (``BaseOutputLayer.computeScore``
sums per-example then averages over minibatch). Losses consume *pre-activation*
output and apply the activation internally so that fused, numerically-stable
softmax/sigmoid cross-entropy forms can be used — the TPU-friendly equivalent
of the reference's ``ILossFunction.computeGradient`` hand-derived fused grads.
"""
from __future__ import annotations

import functools
from typing import Callable, Dict, Optional

import jax
import jax.numpy as jnp

from . import activations

Array = jax.Array

_EPS = 1e-7

_REGISTRY: Dict[str, Callable] = {}

_LOW_PRECISION = ("bfloat16", "float16")


def _f32_loss_inputs(fn: Callable) -> Callable:
    """Loss reductions always run in float32: a low-precision stack keeps
    its matmuls in bf16/f16, but the fused softmax/log-softmax and the
    masked-mean reductions inside every loss are exactly the cancellations
    low precision gets wrong (nn/precision.py — the PrecisionPolicy
    contract).  Full-precision inputs pass through untouched, so f32 nets
    are bit-identical to the pre-shim behavior.

    This is where a head's logits are cast WHOLE: ``preout`` is the full
    ``[rows, classes]`` array of the layer's product.  A softmax head whose
    float32 logits would pass ``HEAD_CHUNK_BYTES`` never comes here:
    ``OutputLayer.compute_loss`` hands its operands to
    :func:`chunked_softmax_xent`, which forms the logits, their float32
    statistics and their cotangent a chunk of rows at a time."""
    @functools.wraps(fn)
    def wrapped(labels, preout, *args, **kwargs):
        if hasattr(preout, "dtype") and str(preout.dtype) in _LOW_PRECISION:
            preout = preout.astype(jnp.float32)
            if hasattr(labels, "dtype") and \
                    str(labels.dtype) in _LOW_PRECISION:
                labels = labels.astype(jnp.float32)
        return fn(labels, preout, *args, **kwargs)

    return wrapped


def register(name: str):
    def deco(fn):
        _REGISTRY[name.lower()] = _f32_loss_inputs(fn)
        return fn
    return deco


def get(name) -> Callable:
    if callable(name):
        return name
    try:
        return _REGISTRY[name.lower()]
    except KeyError:
        raise ValueError(f"Unknown loss '{name}'. Available: {sorted(_REGISTRY)}") from None


def names():
    return sorted(_REGISTRY)


def _mask_like(mask: Array, dtype, ndim: int) -> Array:
    """``mask`` in ``dtype`` with trailing axes up to ``ndim``."""
    mask = mask.astype(dtype)
    while mask.ndim < ndim:
        mask = mask[..., None]
    return mask


def _kept_examples(mask: Array) -> Array:
    """The number of examples that keep any unit (rows with any mask on;
    a weighted mask counts a row by its largest weight), at least one."""
    m = mask.reshape(mask.shape[0], -1).max(axis=1)
    return jnp.maximum(m.sum(), 1.0)


def _apply_mask_and_mean(per_unit: Array, mask: Optional[Array],
                         unit_weights: Optional[Array] = None) -> Array:
    """Sum per-unit scores over feature axes, average over (masked) examples.

    per_unit has shape [batch, ...features]. mask broadcasts against it (e.g.
    [batch] or [batch, 1] per-example masks, or full per-unit masks).
    unit_weights: per-output-column scaling (the reference ILossFunction
    weights vector), broadcast over the trailing axis.
    """
    if unit_weights is not None:
        per_unit = per_unit * unit_weights
    if mask is not None:
        mask = _mask_like(mask, per_unit.dtype, per_unit.ndim)
        per_unit = per_unit * mask
        per_example = per_unit.reshape(per_unit.shape[0], -1).sum(axis=1)
        # average over number of *included* examples (counted before the
        # sum: the order the traced program has had)
        kept = _kept_examples(mask)
        return per_example.sum() / kept
    per_example = per_unit.reshape(per_unit.shape[0], -1).sum(axis=1)
    return per_example.mean()


def position_weights(mask: Optional[Array], shape, dtype=jnp.float32) -> Array:
    """The rule of :func:`_apply_mask_and_mean` as one weight a position:
    ``sum(per_position * position_weights(mask, per_position.shape))`` is
    the score it gives (a sum over an example's positions, a mean over the
    examples that keep any)."""
    if mask is None:
        return jnp.full(shape, 1.0 / shape[0], dtype)
    mask = _mask_like(mask, dtype, len(shape))
    return jnp.broadcast_to(mask / _kept_examples(mask), shape)


@register("mse")
@register("squared_loss")
def mse(labels, preout, activation="identity", mask=None, unit_weights=None):
    out = activations.get(activation)(preout)
    return _apply_mask_and_mean((out - labels) ** 2, mask, unit_weights)


@register("l2")
def l2(labels, preout, activation="identity", mask=None, unit_weights=None):
    return mse(labels, preout, activation, mask)


@register("mae")
@register("l1")
def mae(labels, preout, activation="identity", mask=None, unit_weights=None):
    out = activations.get(activation)(preout)
    return _apply_mask_and_mean(jnp.abs(out - labels), mask, unit_weights)


@register("mape")
@register("mean_absolute_percentage_error")
def mape(labels, preout, activation="identity", mask=None, unit_weights=None):
    out = activations.get(activation)(preout)
    return _apply_mask_and_mean(100.0 * jnp.abs((out - labels) / (labels + _EPS)), mask, unit_weights)


@register("msle")
@register("mean_squared_logarithmic_error")
def msle(labels, preout, activation="identity", mask=None, unit_weights=None):
    out = activations.get(activation)(preout)
    return _apply_mask_and_mean((jnp.log1p(jnp.maximum(out, -1 + _EPS)) - jnp.log1p(jnp.maximum(labels, -1 + _EPS))) ** 2, mask, unit_weights)


@register("xent")
def xent(labels, preout, activation="sigmoid", mask=None, unit_weights=None):
    """Binary cross-entropy. Fused stable form when activation is sigmoid."""
    if (isinstance(activation, str) and activation.lower() == "sigmoid"):
        # log(1+exp(-|x|)) formulation
        per = jnp.maximum(preout, 0) - preout * labels + jnp.log1p(jnp.exp(-jnp.abs(preout)))
    else:
        out = jnp.clip(activations.get(activation)(preout), _EPS, 1 - _EPS)
        per = -(labels * jnp.log(out) + (1 - labels) * jnp.log(1 - out))
    return _apply_mask_and_mean(per, mask, unit_weights)


@register("mcxent")
@register("negativeloglikelihood")
def mcxent(labels, preout, activation="softmax", mask=None, unit_weights=None):
    """Multi-class cross-entropy; fused log-softmax when activation is softmax."""
    if isinstance(activation, str) and activation.lower() == "softmax":
        logp = jax.nn.log_softmax(preout, axis=-1)
        per = -(labels * logp)
    else:
        out = jnp.clip(activations.get(activation)(preout), _EPS, 1.0)
        per = -(labels * jnp.log(out))
    return _apply_mask_and_mean(per, mask, unit_weights)


@register("sparse_mcxent")
def sparse_mcxent(labels, preout, activation="softmax", mask=None, unit_weights=None):
    """labels are integer class indices [batch, ...]."""
    logp = jax.nn.log_softmax(preout, axis=-1)
    per = -jnp.take_along_axis(logp, labels[..., None].astype(jnp.int32), axis=-1)[..., 0]
    return _apply_mask_and_mean(per[..., None], mask, unit_weights)


# ---------------------------------------------------------------- the head
#: float32 logits of more bytes than this are never formed whole: a softmax
#: head over them runs chunk by chunk (:func:`chunked_softmax_xent`).  Set
#: on the chip (PERF.md section 6, PRs 36 and 37).
HEAD_CHUNK_BYTES = 256 * 2 ** 20
#: a chunk of fewer logits rows than this starves the matrix unit: a head
#: whose time axis only such chunks divide takes the whole-array path
_MIN_CHUNK_ROWS = 256


def head_rows_per_chunk(batch: int, steps: int, classes: int) -> Optional[int]:
    """How many time steps a chunk of a ``[batch, steps, classes]`` head
    takes: the most that divide ``steps`` and keep a chunk's float32 logits
    within ``HEAD_CHUNK_BYTES``; ``None`` where the logits fit whole, or no
    divisor leaves a chunk ``_MIN_CHUNK_ROWS`` rows (then the head is the
    plain product and loss)."""
    row_bytes = 4 * batch * classes
    if steps * row_bytes <= HEAD_CHUNK_BYTES:
        return None
    rows = next((r for r in range(HEAD_CHUNK_BYTES // row_bytes, 0, -1)
                 if steps % r == 0), 0)
    return rows if rows * batch >= _MIN_CHUNK_ROWS else None


def _time_chunks(a: Array, n: int) -> Array:
    """``[b, t, ...] -> [n, b, t / n, ...]``: the time axis cut, the batch
    axis whole (a batch sharded over ``data`` stays sharded)."""
    b, t = a.shape[:2]
    return jnp.moveaxis(a.reshape(b, n, t // n, *a.shape[2:]), 1, 0)


def _time_joined(a: Array) -> Array:
    """The inverse of :func:`_time_chunks`."""
    n, b, r = a.shape[:3]
    return jnp.moveaxis(a, 0, 1).reshape(b, n * r, *a.shape[3:])


def _head_chunk(x, W, b, y, w, grads: bool):
    """One chunk of rows of a softmax head: ``(weighted loss, loss a
    position)`` and, with ``grads``, ``(dx, dW, db)`` of the weighted loss.
    The product runs in the operands' type, as ``DenseLayer.pre_output``'s;
    every statistic of the softmax, the loss and the sums in float32."""
    z = x @ W
    if b is not None:
        z = z + b
    stat = jnp.promote_types(z.dtype, jnp.float32)
    zf = z.astype(stat)
    hit = jax.lax.broadcasted_iota(jnp.int32, z.shape, z.ndim - 1) \
        == y[..., None]
    m = jnp.max(zf, axis=-1, keepdims=True)
    lse = m + jnp.log(jnp.sum(jnp.exp(zf - m), axis=-1, keepdims=True))
    # the label's logit by a masked sum: one pass with the sum above, no
    # gather out of the chunk
    picked = jnp.sum(jnp.where(hit, zf, 0), axis=-1)
    nll = lse[..., 0] - picked
    loss = jnp.sum(nll * w)
    if not grads:
        return loss, nll
    # the exponential once more and not kept: a pass over the logits in
    # their own type, no float32 array of their size
    dz = ((jnp.exp(zf - lse) - hit.astype(stat))
          * w[..., None]).astype(z.dtype)
    dx = jnp.einsum("...v,dv->...d", dz, W).astype(x.dtype)
    dW = jnp.einsum("...d,...v->dv", x, dz, preferred_element_type=stat)
    db = None if b is None else jnp.sum(dz.astype(stat),
                                        axis=tuple(range(dz.ndim - 1)))
    return loss, nll, dx, dW, db


def _head_walk(x, W, b, labels, weights, rows: int, grads: bool):
    """The walk over chunks of ``rows`` time steps: ``(loss, loss a
    position [b, t])`` and, with ``grads``, ``(dx, dW, db)`` with the
    sums over chunks carried in float32.  A ``lax.scan`` unrolled whole,
    so a static loop: around a ``while`` the TPU's compiler schedules the
    rest of the step worse (it rematerializes more of the blocks than the
    whole-array head made it to), and the chunks are few (the logits'
    bytes over ``HEAD_CHUNK_BYTES``).  Which form and how many chunks leave
    the compiler the room it wants is no smooth function of either (PERF.md
    section 6, PRs 36 and 37: the census)."""
    n = x.shape[1] // rows
    stat = jnp.promote_types(jnp.result_type(x, W), jnp.float32)
    labels = labels.astype(jnp.int32)
    weights = weights.astype(stat)
    zero = jnp.zeros((), stat)
    if not grads:
        def body(total, c):
            loss, nll = _head_chunk(c[0], W, b, c[1], c[2], False)
            return total + loss, nll
        init = zero
    else:
        def body(carry, c):
            total, dW, db = carry
            loss, nll, dx, dW_c, db_c = _head_chunk(c[0], W, b, c[1], c[2],
                                                    True)
            return (total + loss, dW + dW_c,
                    None if b is None else db + db_c), (nll, dx)
        init = (zero, jnp.zeros(W.shape, stat),
                None if b is None else jnp.zeros(b.shape, stat))
    carry, out = jax.lax.scan(
        body, init, tuple(_time_chunks(a, n) for a in (x, labels, weights)),
        unroll=True)
    if not grads:
        return carry, _time_joined(out)
    total, dW, db = carry
    return total, _time_joined(out[0]), _time_joined(out[1]), dW, db


@functools.partial(jax.custom_vjp, nondiff_argnums=(5,))
def chunked_softmax_xent(x, W, b, labels, weights, rows_per_chunk: int):
    """A softmax head and its sparse cross-entropy without the logits
    whole: ``sum(weights * -log softmax(x W + b)[labels])`` over ``x [b, t,
    D]``, ``W [D, V]``, ``b [V]`` or ``None``, integer ``labels [b, t]`` and
    float32 ``weights [b, t]`` (:func:`position_weights` of the label
    mask), computed over chunks of ``rows_per_chunk`` time steps (a divisor
    of ``t``; :func:`head_rows_per_chunk` picks it by the logits' size).

    Under differentiation the forward walk forms each chunk's logits, their
    float32 log-sum-exp, the chunk's cotangent ``(softmax - onehot) *
    weight`` in the logits' own type, and from it ``dx`` (written chunk by
    chunk) and the float32 sums ``dW`` and ``db``: so the logits and their
    cotangent exist a chunk at a time and the head leaves the backward pass
    nothing to save, replay or rematerialize but its finished gradients,
    which the backward rule scales by the incoming cotangent.  Without
    differentiation only the loss walk runs."""
    return _head_walk(x, W, b, labels, weights, rows_per_chunk, False)[0]


def _chunked_fwd(x, W, b, labels, weights, rows_per_chunk):
    loss, nll, dx, dW, db = _head_walk(x, W, b, labels, weights,
                                       rows_per_chunk, True)
    # the finished gradients, in their operands' types, are all that is kept
    return loss, (nll, dx, dW.astype(W.dtype),
                  None if b is None else db.astype(b.dtype))


def _chunked_bwd(rows_per_chunk, saved, g):
    # under ``value_and_grad`` ``g`` is the constant 1 and the compiler
    # folds the products away (the compiled step is the same without them)
    nll, *grads = saved
    return (*(None if a is None else (a * g).astype(a.dtype) for a in grads),
            None, nll * g)


chunked_softmax_xent.defvjp(_chunked_fwd, _chunked_bwd)


@register("hinge")
def hinge(labels, preout, activation="identity", mask=None, unit_weights=None):
    out = activations.get(activation)(preout)
    # labels in {-1, +1} (reference converts 0/1)
    lab = jnp.where(labels > 0, 1.0, -1.0)
    return _apply_mask_and_mean(jnp.maximum(0.0, 1.0 - lab * out), mask, unit_weights)


@register("squared_hinge")
def squared_hinge(labels, preout, activation="identity", mask=None, unit_weights=None):
    out = activations.get(activation)(preout)
    lab = jnp.where(labels > 0, 1.0, -1.0)
    return _apply_mask_and_mean(jnp.maximum(0.0, 1.0 - lab * out) ** 2, mask, unit_weights)


@register("kl_divergence")
@register("kld")
def kld(labels, preout, activation="softmax", mask=None, unit_weights=None):
    out = jnp.clip(activations.get(activation)(preout), _EPS, 1.0)
    lab = jnp.clip(labels, _EPS, 1.0)
    return _apply_mask_and_mean(lab * (jnp.log(lab) - jnp.log(out)), mask, unit_weights)


@register("poisson")
def poisson(labels, preout, activation="identity", mask=None, unit_weights=None):
    out = activations.get(activation)(preout)
    return _apply_mask_and_mean(out - labels * jnp.log(jnp.maximum(out, _EPS)), mask, unit_weights)


@register("cosine_proximity")
def cosine_proximity(labels, preout, activation="identity", mask=None, unit_weights=None):
    out = activations.get(activation)(preout)
    num = jnp.sum(labels * out, axis=-1)
    den = jnp.linalg.norm(labels, axis=-1) * jnp.linalg.norm(out, axis=-1) + _EPS
    return _apply_mask_and_mean((-num / den)[..., None], mask, unit_weights)


@register("wasserstein")
def wasserstein(labels, preout, activation="identity", mask=None, unit_weights=None):
    out = activations.get(activation)(preout)
    return _apply_mask_and_mean(labels * out, mask, unit_weights)


@register("fmeasure")
def fmeasure(labels, preout, activation="sigmoid", mask=None, unit_weights=None):
    """Differentiable soft-F_beta loss (beta=1), reference LossFMeasure."""
    out = activations.get(activation)(preout)
    tp = jnp.sum(labels * out)
    fp = jnp.sum((1 - labels) * out)
    fn = jnp.sum(labels * (1 - out))
    f1 = (2 * tp) / jnp.maximum(2 * tp + fp + fn, _EPS)
    return 1.0 - f1
