"""Plain reference for the ``gpt2`` family: a pre-norm decoder LM as the
configuration file states it, its next-token loss, its gradients and Adam,
in straightforward ``jax.numpy``.  It imports nothing of the program.

Departures from OpenAI's GPT-2, all listed in the configuration's
``assumed``: sinusoidal positions (no ``wpe``), an untied output head with a
bias, no final LayerNorm.  The loss is the sum over a row's tokens of the
next-token cross-entropy, averaged over the rows of a batch.

``precision`` says how every matrix product rounds its operands:

  float32         operands as they are, ``Precision.HIGHEST`` (the reference)
  bfloat16        operands rounded to bfloat16 (what the configuration states)
  float8_e4m3fn   operands rounded to fp8 (the control: one step below)

Rounding is straight-through, so the backward pass sees the same rounding
on its operands and no rounding of the cotangents themselves.  One row is
differentiated at a time and each block is rematerialised, so the float32
score matrices of one row and one layer are all that is live.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

F32 = jnp.float32
BLOCK_MATRICES = ("Wq", "Wk", "Wv", "Wo", "W1", "W2")


def shapes(cfg: dict) -> dict:
    """Name -> shape of every parameter; the blocks' leaves are stacked
    over the layers."""
    e, v, n, f = (cfg["n_embd"], cfg["vocab_size"], cfg["n_layer"],
                  cfg["n_inner"])
    return {
        "wte": (v, e), "head_W": (e, v), "head_b": (v,),
        "blocks": {
            "Wq": (n, e, e), "Wk": (n, e, e), "Wv": (n, e, e),
            "Wo": (n, e, e), "bq": (n, e), "bk": (n, e), "bv": (n, e),
            "bo": (n, e), "W1": (n, e, f), "b1": (n, f), "W2": (n, f, e),
            "b2": (n, e), "ln1_g": (n, e), "ln1_b": (n, e),
            "ln2_g": (n, e), "ln2_b": (n, e)},
    }


def n_params(cfg: dict) -> int:
    return sum(math.prod(s) for s in jax.tree_util.tree_leaves(
        shapes(cfg), is_leaf=lambda x: isinstance(x, tuple)))


@functools.partial(jax.jit, static_argnums=0)
def _init(cfg_items, key):
    cfg = dict(cfg_items)
    sh = shapes(cfg)

    def xavier(k, shape):
        lim = math.sqrt(6.0 / (shape[-2] + shape[-1]))
        return jax.random.uniform(k, shape, F32, -lim, lim)

    keys = iter(jax.random.split(key, 16))
    out = {"wte": xavier(next(keys), sh["wte"]),
           "head_W": xavier(next(keys), sh["head_W"]),
           "head_b": jnp.zeros(sh["head_b"], F32), "blocks": {}}
    for name, shape in sh["blocks"].items():
        if name in BLOCK_MATRICES:
            out["blocks"][name] = xavier(next(keys), shape)
        elif name.endswith("_g"):
            out["blocks"][name] = jnp.ones(shape, F32)
        else:
            out["blocks"][name] = jnp.zeros(shape, F32)
    return out


def _static(cfg: dict):
    return tuple(sorted((k, v) for k, v in cfg.items()
                        if isinstance(v, (int, float, str))))


def init_params(cfg: dict, key):
    """All weights in one jitted call, float32, on the default device:
    Xavier-uniform matrices, zero biases, unit LayerNorm gains."""
    return _init(_static(cfg), key)


# ------------------------------------------------------------------ forward
def _rounder(precision: str):
    if precision == "float32":
        return lambda t: t
    dt = jnp.dtype(precision)

    def q(t):
        return t + jax.lax.stop_gradient(t.astype(dt).astype(F32) - t)
    return q


def _positions(t: int, e: int):
    pos = jnp.arange(t, dtype=F32)[:, None]
    i = jnp.arange(e, dtype=F32)[None, :]
    angle = pos / jnp.power(F32(10000.0), (2.0 * jnp.floor(i / 2.0)) / e)
    return jnp.where(jnp.mod(i, 2.0) == 0, jnp.sin(angle), jnp.cos(angle))


def _layer_norm(x, g, b, eps):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps) * g + b


def _gelu_new(x):
    c = math.sqrt(2.0 / math.pi)
    return 0.5 * x * (1.0 + jnp.tanh(c * (x + 0.044715 * x * x * x)))


def _row_loss(cfg: dict, precision: str, params, x_row, y_row):
    """Summed next-token loss of ONE row of token ids ``[t]``."""
    e, h, eps = cfg["n_embd"], cfg["n_head"], cfg["layer_norm_epsilon"]
    d = e // h
    t = x_row.shape[0]
    q_ = _rounder(precision)

    def mm(a, b):
        return jnp.matmul(q_(a), q_(b), precision=jax.lax.Precision.HIGHEST)

    causal = jnp.tril(jnp.ones((t, t), bool))

    def block(x, p):
        xn = _layer_norm(x, p["ln1_g"], p["ln1_b"], eps)
        heads = lambda w, b: (mm(xn, w) + b).reshape(t, h, d).transpose(
            1, 0, 2)
        q, k, v = (heads(p["Wq"], p["bq"]), heads(p["Wk"], p["bk"]),
                   heads(p["Wv"], p["bv"]))
        s = mm(q, k.transpose(0, 2, 1)) / math.sqrt(d)
        s = jnp.where(causal[None], s, F32(-1e30))
        o = mm(jax.nn.softmax(s, axis=-1), v)
        x = x + mm(o.transpose(1, 0, 2).reshape(t, e), p["Wo"]) + p["bo"]
        xn = _layer_norm(x, p["ln2_g"], p["ln2_b"], eps)
        return x + mm(_gelu_new(mm(xn, p["W1"]) + p["b1"]),
                      p["W2"]) + p["b2"], None

    x = params["wte"][x_row] + _positions(t, e)
    x, _ = jax.lax.scan(jax.checkpoint(block), x, params["blocks"])
    logits = mm(x, params["head_W"]) + params["head_b"]
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.sum(jnp.take_along_axis(logp, y_row[:, None], axis=-1))


@functools.partial(jax.jit, static_argnums=(0, 1))
def _row_grad(cfg_items, precision, params, x_row, y_row):
    return jax.value_and_grad(
        lambda p: _row_loss(dict(cfg_items), precision, p, x_row, y_row)
    )(params)


@functools.partial(jax.jit, donate_argnums=(0,))
def _accumulate(total, part, weight):
    return jax.tree_util.tree_map(lambda a, b: a + weight * b, total, part)


def loss_and_grads(cfg: dict, params, x, y, precision: str = "float32"):
    """Batch loss (mean over rows) and its gradient, a row at a time."""
    rows = int(x.shape[0])
    loss, grads = 0.0, None
    for r in range(rows):
        l_r, g_r = _row_grad(_static(cfg), precision, params,
                             jnp.asarray(x[r], jnp.int32),
                             jnp.asarray(y[r], jnp.int32))
        loss = loss + l_r / rows
        if grads is None:
            grads = jax.tree_util.tree_map(lambda a: a / rows, g_r)
        else:
            grads = _accumulate(grads, g_r, F32(1.0 / rows))
    return loss, grads


# ----------------------------------------------------------------- optimizer
@functools.partial(jax.jit, static_argnums=0, donate_argnums=(1, 2, 3))
def _adam(opt_items, params, m, v, grads, step):
    o = dict(opt_items)
    b1, b2, lr, eps = (F32(o["beta1"]), F32(o["beta2"]),
                       F32(o["learning_rate"]), F32(o["epsilon"]))
    step = step.astype(F32)
    tm = jax.tree_util.tree_map
    m = tm(lambda a, g: b1 * a + (1 - b1) * g, m, grads)
    v = tm(lambda a, g: b2 * a + (1 - b2) * g * g, v, grads)
    c1, c2 = 1 - b1 ** step, 1 - b2 ** step
    params = tm(lambda p, a, b: p - lr * (a / c1) / (jnp.sqrt(b / c2) + eps),
                params, m, v)
    return params, m, v


@jax.jit
def leaf_norms(tree):
    """Name -> norm, one entry per leaf of the program's own tree: a
    stacked block leaf gives one norm per layer."""
    out = {}
    for name, leaf in tree.items():
        if name == "blocks":
            for k, a in leaf.items():
                out["blocks." + k] = jnp.sqrt(jnp.sum(
                    jnp.square(a.reshape(a.shape[0], -1)), axis=1))
        else:
            out[name] = jnp.sqrt(jnp.sum(jnp.square(leaf)))
    return out


@jax.jit
def _delta_norms(after, before):
    return leaf_norms(jax.tree_util.tree_map(lambda a, b: a - b, after,
                                             before))


def flat_names(norms: dict) -> dict:
    """``blocks.Wq`` -> ``blocks.Wq.0`` ... as host floats."""
    import numpy as np
    out = {}
    for name, v in norms.items():
        v = np.asarray(v, np.float64)
        if v.ndim == 0:
            out[name] = float(v)
        else:
            out.update({f"{name}.{i}": float(a) for i, a in enumerate(v)})
    return out


def train_steps(cfg: dict, key, batches, precision: str = "float32",
                keep_rows=None):
    """Follow the first ``len(batches)`` optimizer steps from the weights
    ``init_params(cfg, key)``.  Returns the loss of each step, the norm of
    every leaf of the first gradient, and of the change of every leaf over
    all the steps.  ``keep_rows`` plants a fault: only those rows of each
    batch are used, the mean taken over them."""
    opt = tuple(sorted((k, v) for k, v in cfg["optimizer"].items()
                       if k != "kind"))
    params = init_params(cfg, key)
    zeros = lambda: jax.tree_util.tree_map(jnp.zeros_like, params)
    m, v = zeros(), zeros()
    losses, grad_norms = [], None
    for i, (x, y) in enumerate(batches):
        if keep_rows is not None:
            x, y = x[keep_rows], y[keep_rows]
        loss, grads = loss_and_grads(cfg, params, x, y, precision)
        losses.append(float(loss))
        if i == 0:
            grad_norms = flat_names(leaf_norms(grads))
        params, m, v = _adam(opt, params, m, v, grads,
                             jnp.asarray(i + 1, jnp.int32))
        del grads
    del m, v
    delta = flat_names(_delta_norms(params, init_params(cfg, key)))
    return {"losses": losses, "grad_norms": grad_norms,
            "delta_norms": delta}
