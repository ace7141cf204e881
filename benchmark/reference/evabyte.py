"""Plain reference for the ``evabyte`` family: EvaByte's byte-level decoder
as the configuration file states it, its multi-byte loss, its gradients and
the first Adam step, in straightforward ``jax.numpy``.  It imports nothing
of the program.

The block (all projections without bias, ``h`` is ``[T, e]``)::

    a   = h + Attn(RMS(h; g1))
    out = a + W2 (silu(Wg RMS(a; g2)) * (W1 RMS(a; g2)))
    RMS(x; g) = x * rsqrt(mean(x^2) + eps) * (1 + g)

``Attn`` is EVA attention (Zheng, Yuan, Wang, Kong, "Efficient Attention
via Control Variates", ICLR 2023, arXiv:2302.04542) in the deterministic
form of the model's released ``eva_pt_ref.py``.  Of the paper it keeps
section 4.2's partition of the keys into a local set attended exactly
(equation 14's first sum: here the query's own window of ``window_size``
keys, causally) and chunks that each enter the softmax as ONE term
(equation 14's second sum; equations 15-16), a chunk's term having the key
``ks_c = sum_j a_j k_j + mu`` and the value ``vs_c = sum_j a_j v_j``, ``a =
softmax_{j in c}(k_j . phi)``, with learned ``phi, mu`` per head (the
paper's per-chunk control variate with the pooled key as the proposal's
mean, section 4.3 and appendix G's parameterisation).  Departures from the
paper, all the released model's: no random feature is sampled (the pooled
key stands for the chunk in ``exp(q . ks_c / sqrt(d))``, equation 16's
expectation at its mean); chunks are causal (a query sees the summaries of
the windows before its own only); keys are pooled after the rotary turn.

Query ``i`` of window ``w = i // window_size`` therefore sees the keys ``{k_j:
j // window_size = w, j <= i}`` and the summaries ``{ks_c: chunk_size * c //
window_size < w}``, in one softmax of ``q_i . key / sqrt(d)`` over both.
Rotary positions are rotate-half, base ``rope_theta``, absolute.  The model
is the embedding, the blocks, a final RMSNorm and an untied head of
``num_pred_heads * vocab_size`` columns: head ``n`` at position ``t``
predicts byte ``t + 1 + n``, and the loss is the mean cross-entropy over all
heads and positions whose target lies inside the sequence, over all rows.
What the configuration's ``assumed`` lists is assumed here too.

``precision`` rounds every matrix product's operands as
``reference/gpt2.py`` does: ``float32`` at ``Precision.HIGHEST`` (the
reference), ``bfloat16`` (what the configuration states), ``float8_e4m3fn``
(the control).  One row is differentiated at a time, each block is
rematerialised and inside it each window's attention, so one window's
float32 scores (heads x window x (window + summaries)) are all that is
live.  Adam's moments are not kept: the steps followed are one, and Adam's
first update is ``-lr * g / (|g| + eps)`` from the gradient alone.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

F32 = jnp.float32
BLOCK_MATRICES = ("Wq", "Wk", "Wv", "Wo", "Wg", "W1", "W2")
HIGHEST = jax.lax.Precision.HIGHEST


def shapes(cfg: dict) -> dict:
    """Name -> shape of every parameter; the blocks' leaves are stacked
    over the layers."""
    e, v, n, f = (cfg["hidden_size"], cfg["vocab_size"],
                  cfg["num_hidden_layers"], cfg["intermediate_size"])
    h, d = cfg["num_attention_heads"], cfg["head_dim"]
    return {
        "wte": (v, e), "norm_g": (e,),
        "head_W": (e, cfg["num_pred_heads"] * v),
        "blocks": {
            "Wq": (n, e, h * d), "Wk": (n, e, h * d), "Wv": (n, e, h * d),
            "Wo": (n, h * d, e), "phi": (n, h, d), "mu": (n, h, d),
            "Wg": (n, e, f), "W1": (n, e, f), "W2": (n, f, e),
            "ln1_g": (n, e), "ln2_g": (n, e)},
    }


def n_params(cfg: dict) -> int:
    return sum(math.prod(s) for s in jax.tree_util.tree_leaves(
        shapes(cfg), is_leaf=lambda x: isinstance(x, tuple)))


def _static(cfg: dict):
    return tuple(sorted((k, v) for k, v in cfg.items()
                        if isinstance(v, (int, float, str))))


@functools.partial(jax.jit, static_argnums=0)
def _init(cfg_items, key):
    cfg = dict(cfg_items)
    sh = shapes(cfg)
    std, d = cfg["init_std"], cfg["head_dim"]
    keys = iter(jax.random.split(key, 16))

    def matrix(shape):
        return std * jax.random.normal(next(keys), shape, F32)

    out = {"wte": matrix(sh["wte"]), "head_W": matrix(sh["head_W"]),
           "norm_g": jnp.zeros(sh["norm_g"], F32), "blocks": {}}
    for name, shape in sh["blocks"].items():
        if name in BLOCK_MATRICES:
            out["blocks"][name] = matrix(shape)
        elif name in ("phi", "mu"):
            out["blocks"][name] = jnp.clip(jax.random.normal(
                next(keys), shape, F32), -1.0, 1.0) * d ** -0.5
        else:
            out["blocks"][name] = jnp.zeros(shape, F32)
    return out


def init_params(cfg: dict, key):
    """All weights in one jitted call, float32, on the default device:
    normal matrices of the published ``init_std``, zero gains (RMSNorm's
    unit offset makes that the identity scale), ``phi`` and ``mu`` normal,
    clipped to [-1, 1], times ``head_dim ** -0.5``."""
    return _init(_static(cfg), key)


# ------------------------------------------------------------------ forward
def _rounder(precision: str):
    if precision == "float32":
        return lambda t: t
    dt = jnp.dtype(precision)

    def q(t):
        return t + jax.lax.stop_gradient(t.astype(dt).astype(F32) - t)
    return q


def _rms(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * (1.0 + g)


def _rotary(x, theta):
    """Rotate-half rotary positions on ``[h, t, d]``."""
    t, d = x.shape[-2], x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=F32) / d))
    angle = jnp.arange(t, dtype=F32)[:, None] * inv[None, :]
    cos = jnp.concatenate([jnp.cos(angle), jnp.cos(angle)], axis=-1)
    sin = jnp.concatenate([jnp.sin(angle), jnp.sin(angle)], axis=-1)
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return x * cos + jnp.concatenate([-x2, x1], axis=-1) * sin


def target_mask(t: int, heads: int):
    """``[t, heads]``: 1 where byte ``t + 1 + n`` lies inside the row."""
    at = jnp.arange(t)[:, None] + 1 + jnp.arange(heads)[None, :]
    return (at < t).astype(F32)


def _row_forward(cfg: dict, precision: str, params, x_row,
                 summaries: bool = True):
    """Logits ``[t, heads, vocab]`` of ONE row of byte ids ``[t]``."""
    e, h, d = (cfg["hidden_size"], cfg["num_attention_heads"],
               cfg["head_dim"])
    eps, theta = cfg["rms_norm_eps"], float(cfg["rope_theta"])
    window, chunk = cfg["window_size"], cfg["chunk_size"]
    t = x_row.shape[0]
    q_ = _rounder(precision)

    def mm(a, b):
        return jnp.matmul(q_(a), q_(b), precision=HIGHEST)

    @jax.checkpoint
    def attend_window(qw, kw, vw, ks, vs):
        """One window's queries ``[h, w, d]`` over its own keys, causally,
        and the summaries before it (none for the first), one softmax."""
        w = qw.shape[1]
        s = mm(qw, kw.transpose(0, 2, 1)) / math.sqrt(d)
        s = jnp.where(jnp.tril(jnp.ones((w, w), bool))[None], s, -jnp.inf)
        if ks.shape[1]:
            s = jnp.concatenate(
                [s, mm(qw, ks.transpose(0, 2, 1)) / math.sqrt(d)], axis=-1)
            vw = jnp.concatenate([vw, vs], axis=1)
        return mm(jax.nn.softmax(s, axis=-1), vw)

    def block(x, p):
        xn = _rms(x, p["ln1_g"], eps)
        heads = lambda w: mm(xn, w).reshape(t, h, d).transpose(1, 0, 2)
        q, k, v = (_rotary(heads(p["Wq"]), theta),
                   _rotary(heads(p["Wk"]), theta), heads(p["Wv"]))
        kc = k.reshape(h, t // chunk, chunk, d)
        vc = v.reshape(h, t // chunk, chunk, d)
        a = jax.nn.softmax(jnp.einsum("hncd,hd->hnc", kc, p["phi"],
                                      precision=HIGHEST), axis=-1)
        ks = jnp.einsum("hnc,hncd->hnd", a, kc, precision=HIGHEST) \
            + p["mu"][:, None, :]
        vs = jnp.einsum("hnc,hncd->hnd", a, vc, precision=HIGHEST)
        out = []
        for start in range(0, t, window):
            rows = slice(start, min(start + window, t))
            n = start // chunk if summaries else 0
            out.append(attend_window(q[:, rows], k[:, rows], v[:, rows],
                                     ks[:, :n], vs[:, :n]))
        o = jnp.concatenate(out, axis=1)
        x = x + mm(o.transpose(1, 0, 2).reshape(t, h * d), p["Wo"])
        xn = _rms(x, p["ln2_g"], eps)
        return x + mm(jax.nn.silu(mm(xn, p["Wg"])) * mm(xn, p["W1"]),
                      p["W2"]), None

    x = params["wte"][x_row]
    x, _ = jax.lax.scan(jax.checkpoint(block), x, params["blocks"])
    logits = mm(_rms(x, params["norm_g"], eps), params["head_W"])
    return logits.reshape(t, cfg["num_pred_heads"], cfg["vocab_size"])


def _row_loss(cfg, precision, summaries, params, x_row, y_row, mask):
    """Summed cross-entropy of ONE row over the targets ``mask`` keeps."""
    logp = jax.nn.log_softmax(
        _row_forward(cfg, precision, params, x_row, summaries), axis=-1)
    picked = jnp.take_along_axis(logp, y_row[..., None], axis=-1)[..., 0]
    return -jnp.sum(picked * mask)


@functools.partial(jax.jit, static_argnums=(0, 1, 2))
def row_logits(cfg_items, precision, summaries, params, x_row):
    return _row_forward(dict(cfg_items), precision, params, x_row, summaries)


@functools.partial(jax.jit, static_argnums=(0, 1, 2))
def _row_grad(cfg_items, precision, summaries, params, x_row, y_row, mask):
    return jax.value_and_grad(
        lambda p: _row_loss(dict(cfg_items), precision, summaries, p, x_row,
                            y_row, mask))(params)


@functools.partial(jax.jit, donate_argnums=(0,))
def _accumulate(total, part, weight):
    return jax.tree_util.tree_map(lambda a, b: a + weight * b, total, part)


def loss_and_grads(cfg: dict, params, x, y, precision: str = "float32",
                   fault: str = None):
    """Batch loss (mean over the kept targets of all rows) and its
    gradient, a row at a time.  ``x`` is ``[rows, t]`` byte ids, ``y``
    ``[rows, t, heads]`` targets.  ``fault`` plants one: ``half_targets``
    keeps the targets of the first half of each row's positions only,
    ``no_summaries`` leaves the chunk summaries out of the attention."""
    rows, t = int(x.shape[0]), int(x.shape[1])
    mask = target_mask(t, cfg["num_pred_heads"])
    if fault == "half_targets":
        mask = mask * (jnp.arange(t) < t // 2)[:, None]
    elif fault not in (None, "no_summaries"):
        raise ValueError(f"no such fault: {fault!r}")
    weight = F32(1.0) / (rows * jnp.sum(mask))
    loss, grads = 0.0, None
    for r in range(rows):
        l_r, g_r = _row_grad(_static(cfg), precision,
                             fault != "no_summaries", params,
                             jnp.asarray(x[r], jnp.int32),
                             jnp.asarray(y[r], jnp.int32), mask)
        loss = loss + l_r * weight
        if grads is None:
            grads = jax.tree_util.tree_map(lambda a: a * weight, g_r)
        else:
            grads = _accumulate(grads, g_r, weight)
    return loss, grads


# ----------------------------------------------------------------- optimizer
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


@functools.partial(jax.jit, static_argnums=0)
def _first_adam_delta_norms(opt_items, grads):
    """Norms of Adam's first update: with zero moments ``m / (1 - b1) =
    g`` and ``v / (1 - b2) = g^2``, so the step is ``-lr g / (|g| +
    eps)``, whatever the betas."""
    o = dict(opt_items)
    lr, eps = F32(o["learning_rate"]), F32(o["epsilon"])
    return leaf_norms(jax.tree_util.tree_map(
        lambda g: lr * g / (jnp.abs(g) + eps), grads))


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
                fault: str = None):
    """Follow the first optimizer step (``batches`` holds one ``(x, y)``)
    from the weights ``init_params(cfg, key)``.  Returns the step's loss,
    the norm of every leaf of its gradient, and of the change Adam makes
    to every leaf."""
    if len(batches) != 1:
        raise ValueError("the evabyte reference keeps no Adam moments and "
                         "follows one step")
    opt = tuple(sorted((k, v) for k, v in cfg["optimizer"].items()
                       if k != "kind"))
    x, y = batches[0]
    loss, grads = loss_and_grads(cfg, init_params(cfg, key), x, y, precision,
                                 fault)
    return {"losses": [float(loss)],
            "grad_norms": flat_names(leaf_norms(grads)),
            "delta_norms": flat_names(_first_adam_delta_norms(opt, grads))}
