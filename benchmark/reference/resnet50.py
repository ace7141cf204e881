"""Plain reference for the ``resnet50`` family: He et al.'s 50-layer
bottleneck network as the configuration file states it, its loss, its
gradients and SGD with Nesterov momentum, in straightforward ``jax.numpy``.
It imports nothing of the program.

As the configuration's ``assumed`` lists: every convolution has a bias and
is followed by batch normalisation over the batch and both image axes with
the batch's own (biased) variance; the stride of a stage's first block sits
on its first 1x1 convolution and on its projection, as in the paper;
``same`` padding puts the odd pixel at the bottom and the right; the loss is
the mean over the batch of ``-sum(labels * log_softmax(logits))``.

``precision`` names the type that a pipeline of that precision computes and
stores in.  Every convolution and the final matrix product round their
operands to it, and what a layer hands to the next (a convolution's result,
a normalised and activated image, a block's sum, the pooled features, the
logits) is rounded to it on the way forward, as is its cotangent on the way
back; sums, statistics and the loss stay in float32:

  float32         nothing is rounded, ``Precision.HIGHEST`` (the reference)
  bfloat16        what the configuration states
  float8_e4m3fn   the control, one step below; a tensor is scaled into the
                  type's range before it is rounded, as fp8 pipelines do

Rounding the operands alone does not tell the two apart on this network
(PERF.md, PR 25): its sums run over thousands of terms and batch
normalisation rescales what comes out, so what a pipeline *stores* between
layers is where its precision shows.

Batch normalisation couples the examples of a step, so the batch is never
split: each bottleneck is rematerialised instead (``jax.checkpoint``), and
the float32 activations of one block are all that the backward pass adds to
the blocks' inputs.  A stage's blocks after its first have one shape and are
scanned, which keeps the compiled reference small.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

F32 = jnp.float32


def layers(cfg: dict):
    """(name, kind, shape-or-arguments) of every layer that holds weights,
    in forward order.  A convolution is ``(kh, kw, c_in, c_out, stride)``."""
    out = [("conv1", "conv", (7, 7, cfg["image_channels"],
                              cfg["stem_width"], 2))]
    c_in = cfg["stem_width"]
    for si, (blocks, width) in enumerate(zip(cfg["depths"], cfg["widths"])):
        c_out = width * cfg["expansion"]
        for bi in range(blocks):
            stride = 2 if (bi == 0 and si > 0) else 1
            name = f"s{si}b{bi}"
            out.append((name + "_a", "conv", (1, 1, c_in, width, stride)))
            out.append((name + "_b", "conv", (3, 3, width, width, 1)))
            out.append((name + "_c", "conv", (1, 1, width, c_out, 1)))
            if bi == 0:
                out.append((name + "_sc", "conv",
                            (1, 1, c_in, c_out, stride)))
            c_in = c_out
    out.append(("out", "dense", (c_in, cfg["num_classes"])))
    return out


def n_params(cfg: dict) -> int:
    total = 0
    for _name, kind, a in layers(cfg):
        if kind == "conv":
            total += a[0] * a[1] * a[2] * a[3] + a[3] + 2 * a[3]
        else:
            total += a[0] * a[1] + a[1]
    return total


def _static(cfg: dict):
    """The configuration's numbers and lists of numbers, hashable."""
    out = []
    for k, v in sorted(cfg.items()):
        if isinstance(v, list) and all(isinstance(i, (int, float)) for i in v):
            out.append((k, tuple(v)))
        elif isinstance(v, (int, float, str)):
            out.append((k, v))
    return tuple(out)


@functools.partial(jax.jit, static_argnums=0)
def _init(cfg_items, key):
    cfg = dict(cfg_items)
    spec = layers(cfg)
    keys = jax.random.split(key, len(spec))
    params = {}
    for k, (name, kind, a) in zip(keys, spec):
        if kind == "conv":
            kh, kw, c_in, c_out, _stride = a
            std = math.sqrt(2.0 / (kh * kw * c_in))
            params[name] = {
                "W": std * jax.random.normal(k, (kh, kw, c_in, c_out), F32),
                "b": jnp.zeros((c_out,), F32)}
            params[name + "_bn"] = {"gamma": jnp.ones((c_out,), F32),
                                    "beta": jnp.zeros((c_out,), F32)}
        else:
            n_in, n_out = a
            std = math.sqrt(2.0 / n_in)
            params[name] = {
                "W": std * jax.random.normal(k, (n_in, n_out), F32),
                "b": jnp.zeros((n_out,), F32)}
    return params


def init_params(cfg: dict, key):
    """All weights in one jitted call, float32, on the default device:
    He-normal convolutions and output matrix, zero biases, unit batch-norm
    gains, zero shifts.  ``{layer: {leaf: array}}``."""
    return _init(_static(cfg), key)


# ------------------------------------------------------------------ forward
def _round(t, dt):
    """``t`` as the type ``dt`` holds it, in float32 again."""
    if jnp.finfo(dt).bits >= 16:
        return t.astype(dt).astype(F32)
    top = jnp.max(jnp.abs(t))
    scale = jnp.where(top > 0, float(jnp.finfo(dt).max) / top, 1.0)
    return (t * scale).astype(dt).astype(F32) / scale


def _rounders(precision: str):
    """``(operand, stored)``: how a product's operands round (straight
    through: the backward pass sees the same rounded operands), and how what
    a layer hands on rounds, forward and, its cotangent, backward."""
    if precision == "float32":
        return (lambda t: t), (lambda t: t)
    dt = jnp.dtype(precision)

    def operand(t):
        return t + jax.lax.stop_gradient(_round(t, dt) - t)

    @jax.custom_vjp
    def stored(t):
        return _round(t, dt)
    stored.defvjp(lambda t: (_round(t, dt), None),
                  lambda _, g: (_round(g, dt),))
    return operand, stored


def _loss(cfg: dict, precision: str, params, x, y):
    eps = cfg["batch_norm_epsilon"]
    q_, stored = _rounders(precision)

    def conv_bn(x, p, n, stride, relu):
        z = jax.lax.conv_general_dilated(
            q_(x), q_(p["W"]), (stride, stride), "SAME",
            dimension_numbers=("NHWC", "HWIO", "NHWC"),
            precision=jax.lax.Precision.HIGHEST) + p["b"]
        z = stored(z)
        mean = jnp.mean(z, axis=(0, 1, 2))
        var = jnp.mean(jnp.square(z - mean), axis=(0, 1, 2))
        z = (z - mean) / jnp.sqrt(var + eps) * n["gamma"] + n["beta"]
        return stored(jnp.maximum(z, 0.0) if relu else z)

    def bottleneck(x, p, stride):
        """``p``: this block's layers, ``a`` ``b`` ``c`` and, where the
        block projects its input, ``sc``, each with its ``_bn``."""
        h = conv_bn(x, p["a"], p["a_bn"], stride, True)
        h = conv_bn(h, p["b"], p["b_bn"], 1, True)
        h = conv_bn(h, p["c"], p["c_bn"], 1, False)
        sc = conv_bn(x, p["sc"], p["sc_bn"], stride, False) \
            if "sc" in p else x
        return stored(jnp.maximum(h + sc, 0.0))

    x = conv_bn(x.astype(F32), params["conv1"], params["conv1_bn"], 2, True)
    x = jax.lax.reduce_window(x, -jnp.inf, jax.lax.max, (1, 3, 3, 1),
                              (1, 2, 2, 1), "SAME")
    def block_params(si, bi):
        prefix = f"s{si}b{bi}_"
        return {k[len(prefix):]: v for k, v in params.items()
                if k.startswith(prefix)}

    block = jax.checkpoint(bottleneck, static_argnums=2)
    for si, blocks in enumerate(cfg["depths"]):
        x = block(x, block_params(si, 0), 2 if si > 0 else 1)
        # the stage's other blocks have one shape: one body, scanned
        rest = [block_params(si, bi) for bi in range(1, blocks)]
        if rest:
            stacked = jax.tree_util.tree_map(lambda *a: jnp.stack(a), *rest)
            x, _ = jax.lax.scan(lambda h, p: (block(h, p, 1), None), x,
                                stacked)
    x = stored(jnp.mean(x, axis=(1, 2)))
    logits = stored(jnp.matmul(q_(x), q_(params["out"]["W"]),
                               precision=jax.lax.Precision.HIGHEST)
                    + params["out"]["b"])
    logp = jax.nn.log_softmax(logits, axis=-1)
    return jnp.mean(-jnp.sum(y.astype(F32) * logp, axis=-1))


@functools.partial(jax.jit, static_argnums=(0, 1))
def loss_and_grads(cfg_items, precision, params, x, y):
    """Batch loss and its gradient, the whole batch at once."""
    return jax.value_and_grad(
        lambda p: _loss(dict(cfg_items), precision, p, x, y))(params)


# ----------------------------------------------------------------- optimizer
@functools.partial(jax.jit, static_argnums=0, donate_argnums=(1, 2))
def _nesterov(opt_items, params, trace, grads):
    """SGD with Nesterov momentum: the trace gathers ``g + m * trace``, the
    step is ``-lr * (g + m * trace)`` with the trace just gathered."""
    o = dict(opt_items)
    lr, m = F32(o["learning_rate"]), F32(o["momentum"])
    tm = jax.tree_util.tree_map
    trace = tm(lambda t, g: g + m * t, trace, grads)
    params = tm(lambda p, g, t: p - lr * (g + m * t), params, grads, trace)
    return params, trace


@jax.jit
def leaf_norms(tree):
    return {f"{layer}.{leaf}": jnp.sqrt(jnp.sum(jnp.square(a)))
            for layer, leaves in tree.items() for leaf, a in leaves.items()}


@jax.jit
def _delta_norms(after, before):
    return leaf_norms(jax.tree_util.tree_map(lambda a, b: a - b, after,
                                             before))


def _host(norms: dict) -> dict:
    return {k: float(v) for k, v in jax.device_get(norms).items()}


def train_steps(cfg: dict, key, batches, precision: str = "float32",
                keep_rows=None):
    """Follow one optimizer step on each of ``batches`` from the weights
    ``init_params(cfg, key)``.  Returns the loss of each step, the norm of
    every leaf of the first gradient and of the momentum's trace after the
    last step, and of the change of every leaf over all the steps.
    ``keep_rows`` plants a fault: only those rows of each batch are used,
    the mean taken over them."""
    opt = tuple(sorted((k, v) for k, v in cfg["optimizer"].items()
                       if k != "kind"))
    items = _static(cfg)
    params = init_params(cfg, key)
    trace = jax.tree_util.tree_map(jnp.zeros_like, params)
    losses, grad_norms = [], None
    for x, y in batches:
        if keep_rows is not None:
            x, y = x[jnp.asarray(keep_rows)], y[jnp.asarray(keep_rows)]
        loss, grads = loss_and_grads(items, precision, params, x, y)
        losses.append(float(loss))
        if grad_norms is None:
            grad_norms = _host(leaf_norms(grads))
        params, trace = _nesterov(opt, params, trace, grads)
        del grads
    trace_norms = _host(leaf_norms(trace))
    del trace
    delta = _host(_delta_norms(params, init_params(cfg, key)))
    return {"losses": losses, "grad_norms": grad_norms,
            "trace_norms": trace_norms, "delta_norms": delta}
