"""Plain reference for the ``ouro`` family: ByteDance's Ouro looped decoder
(``model_type`` ``ouro``; "Scaling Latent Reasoning via Looped Language
Models", arXiv:2510.25741) as the configuration file states it, the
exit-weighted loss of the paper's stage I, its gradients and the first Adam
step, in straightforward ``jax.numpy``.  It imports nothing of the program.

The stream ``x`` is ``[T, e]``; every projection is without bias, every
``N`` a gain-only RMSNorm, ``N(x; w) = x * rsqrt(mean(x^2) + eps) * w``::

    x = wte[ids]
    for pass r = 1 .. R (R = total_ut_steps), on the SAME weights:
        for layer l = 1 .. L:
            a = x + N2_l(Attn_l(N1_l x))
            x = a + N4_l(MLP_l(N3_l a))
        h_r = N(x; norm_w);   x <- h_r    (the next pass reads the normed state)
        z_r = h_r head_W;     lambda_r = sigmoid(h_r gate_w + gate_b)

``Attn``: ``q, k, v = x Wq, x Wk, x Wv`` as 16 heads of 128 (as many K/V
heads); rotary positions over the whole head (rotate-half, base
``rope_theta``, absolute, the same in every pass); ``softmax(q k^T /
sqrt(128))`` causal; ``Wo``.  ``MLP``: ``W2 (silu(Wg x) * (W1 x))``.

The exit distribution of a position: ``S_0 = 1``, ``S_r = S_{r-1} (1 -
lambda_r)``; ``p_r = lambda_r S_{r-1}`` for ``r < R`` and ``p_R = S_{R-1}``
(the last pass takes what is left; its own gate is not read).  The loss of
a position is ``sum_r p_r CE(z_r, y) - exit_beta H(p)``, ``H(p) = -sum_r p_r
log p_r``, and the gradient flows into the gate through ``p``.  Positions
are summed and rows averaged, as the program's ``sparse_mcxent`` counts.
What the configuration's ``assumed`` lists is assumed here too.

``precision`` rounds every matrix product's operands as
``reference/gpt2.py`` does: ``float32`` at ``Precision.HIGHEST`` (the
reference), ``bfloat16`` (what the configuration states), ``float8_e4m3fn``
(the control).  The weights are tied by using the same arrays in every
pass, so their gradient is the float32 sum over the passes.  One row is
differentiated at a time; each layer-pass is rematerialised, inside it
each block of ``ATTN_BLOCK`` queries, and each exit's logits a block of
``HEAD_BLOCK`` rows, so one block's float32 scores or logits are all that
is live.  Adam's moments are not kept: the steps followed are one, and
Adam's first update is ``-lr * g / (|g| + eps)`` from the gradient alone.
``fault`` plants one: ``passes_3`` walks one pass fewer (and scores as many
exits), ``last_pass_grad`` gives the layers' weights the gradient of the
last pass alone (the sum over the passes left out), ``gate_detached`` lets
no gradient through ``p``.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

F32 = jnp.float32
HIGHEST = jax.lax.Precision.HIGHEST
ATTN_BLOCK = 1024
HEAD_BLOCK = 2048
NORMS = ("n1", "n2", "n3", "n4")
FAULTS = (None, "passes_3", "last_pass_grad", "gate_detached")


def layer_shapes(cfg: dict) -> dict:
    e, d, f = cfg["hidden_size"], cfg["head_dim"], cfg["intermediate_size"]
    h, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    return {"Wq": (e, h * d), "Wk": (e, kv * d), "Wv": (e, kv * d),
            "Wo": (h * d, e), "Wg": (e, f), "W1": (e, f), "W2": (f, e),
            "n1": (e,), "n2": (e,), "n3": (e,), "n4": (e,)}


def shapes(cfg: dict) -> dict:
    e, v = cfg["hidden_size"], cfg["vocab_size"]
    return {"wte": (v, e), "norm_w": (e,), "head_W": (e, v),
            "gate_w": (e, 1), "gate_b": (1,),
            "layers": [layer_shapes(cfg)
                       for _ in range(cfg["num_hidden_layers"])]}


def n_params(cfg: dict) -> int:
    return sum(math.prod(s) for s in jax.tree_util.tree_leaves(
        shapes(cfg), is_leaf=lambda x: isinstance(x, tuple)))


def _static(cfg: dict):
    """The configuration's numbers as a hashable static argument."""
    keys = ("hidden_size", "head_dim", "intermediate_size",
            "num_attention_heads", "num_key_value_heads",
            "num_hidden_layers", "vocab_size", "rms_norm_eps", "rope_theta",
            "total_ut_steps", "exit_beta", "init_std")
    return tuple((k, cfg[k]) for k in keys)


@functools.partial(jax.jit, static_argnums=0)
def _init(cfg_items, key):
    cfg = dict(cfg_items)
    std = cfg["init_std"]
    leaves, tree = jax.tree_util.tree_flatten_with_path(
        shapes(cfg), is_leaf=lambda x: isinstance(x, tuple))
    out = []
    for i, (path, shape) in enumerate(leaves):
        name = path[-1].key
        if name in NORMS or name == "norm_w":
            out.append(jnp.ones(shape, F32))
        elif name == "gate_b":
            out.append(jnp.zeros(shape, F32))
        else:
            out.append(std * jax.random.normal(jax.random.fold_in(key, i),
                                               shape, F32))
    return jax.tree_util.tree_unflatten(tree, out)


def init_params(cfg: dict, key):
    """All weights in one jitted call, float32, on the default device:
    normal matrices of ``init_std`` (the gate's too), unit norm weights, a
    zero gate bias."""
    return _init(_static(cfg), key)


# ------------------------------------------------------------------ forward
def _rounder(precision: str):
    if precision == "float32":
        return lambda t: t
    dt = jnp.dtype(precision)

    def q(t):
        return t + jax.lax.stop_gradient(t.astype(dt).astype(F32) - t)
    return q


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * w


def _rotary(x, theta):
    """Rotate-half rotary positions on ``[h, t, d]``."""
    t, d = x.shape[-2], x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=F32) / d))
    angle = jnp.arange(t, dtype=F32)[:, None] * inv[None, :]
    cos = jnp.concatenate([jnp.cos(angle), jnp.cos(angle)], axis=-1)
    sin = jnp.concatenate([jnp.sin(angle), jnp.sin(angle)], axis=-1)
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return x * cos + jnp.concatenate([-x2, x1], axis=-1) * sin


def exit_distribution(lam):
    """``p [R, t]`` from the gates ``lam [R, t]``: ``p_r = lam_r prod_{j<r}
    (1 - lam_j)`` before the last pass, which takes what is left."""
    ps, stay = [], jnp.ones_like(lam[0])
    for r in range(lam.shape[0] - 1):
        ps.append(lam[r] * stay)
        stay = stay * (1.0 - lam[r])
    ps.append(stay)
    return jnp.stack(ps)


def _states(cfg: dict, precision: str, fault, params, x_row):
    """The normed state after every pass, ``[R, t, e]``, of ONE row of
    token ids ``[t]``."""
    d, h = cfg["head_dim"], cfg["num_attention_heads"]
    kv = cfg["num_key_value_heads"]
    eps, theta = cfg["rms_norm_eps"], float(cfg["rope_theta"])
    passes = cfg["total_ut_steps"] - (1 if fault == "passes_3" else 0)
    t = x_row.shape[0]
    q_ = _rounder(precision)

    def mm(a, b):
        return jnp.matmul(q_(a), q_(b), precision=HIGHEST)

    @functools.partial(jax.checkpoint, static_argnums=(3,))
    def attend(qb, kb, vb, q0):
        """Queries ``[h, n, d]`` from position ``q0`` over the keys ``[kv,
        m, d]`` from position 0, under a dense causal mask."""
        n, m = qb.shape[1], kb.shape[1]
        qg = qb.reshape(kv, h // kv, n, d)
        s = jnp.einsum("ghnd,gmd->ghnm", q_(qg), q_(kb),
                       precision=HIGHEST) / math.sqrt(d)
        seen = jnp.arange(m)[None, :] <= q0 + jnp.arange(n)[:, None]
        s = jnp.where(seen[None, None], s, -jnp.inf)
        o = jnp.einsum("ghnm,gmd->ghnd", q_(jax.nn.softmax(s, axis=-1)),
                       q_(vb), precision=HIGHEST)
        return o.reshape(h, n, d)

    def attention(p, xn):
        def heads(w, n):
            return mm(xn, w).reshape(t, n, d).transpose(1, 0, 2)
        q = _rotary(heads(p["Wq"], h), theta)
        k = _rotary(heads(p["Wk"], kv), theta)
        v = heads(p["Wv"], kv)
        out = []
        for q0 in range(0, t, ATTN_BLOCK):
            q1 = min(q0 + ATTN_BLOCK, t)
            out.append(attend(q[:, q0:q1], k[:, :q1], v[:, :q1], q0))
        o = jnp.concatenate(out, axis=1).transpose(1, 0, 2).reshape(t, h * d)
        return mm(o, p["Wo"])

    @jax.checkpoint
    def layer(x, p):
        a = x + _rms(attention(p, _rms(x, p["n1"], eps)), p["n2"], eps)
        an = _rms(a, p["n3"], eps)
        y = mm(jax.nn.silu(mm(an, p["Wg"])) * mm(an, p["W1"]), p["W2"])
        return a + _rms(y, p["n4"], eps)

    x = params["wte"][x_row]
    states = []
    for r in range(passes):
        layers = params["layers"]
        if fault == "last_pass_grad" and r < passes - 1:
            layers = jax.lax.stop_gradient(layers)
        for p in layers:
            x = layer(x, p)
        x = _rms(x, params["norm_w"], eps)
        states.append(x)
    return jnp.stack(states), mm


def _row_exits(cfg, precision, fault, params, x_row):
    """``(logits [R, t, V], p [R, t])`` of ONE row, the logits whole: for
    small sizes."""
    hs, mm = _states(cfg, precision, fault, params, x_row)
    lam = jax.nn.sigmoid(mm(hs, params["gate_w"])[..., 0]
                         + params["gate_b"])
    return mm(hs, params["head_W"]), exit_distribution(lam)


def _row_loss(cfg, precision, fault, params, x_row, y_row, beta=None):
    """The exit-weighted loss of ONE row, summed over its positions, and
    ``(the mean of p over the positions [R], the loss's two parts)``."""
    beta = cfg["exit_beta"] if beta is None else beta
    hs, mm = _states(cfg, precision, fault, params, x_row)
    t = y_row.shape[0]

    @jax.checkpoint
    def nll_block(h_block, y_block):
        logp = jax.nn.log_softmax(mm(h_block, params["head_W"]), axis=-1)
        return -jnp.take_along_axis(logp, y_block[:, None], axis=-1)[:, 0]
    nll = jnp.stack([jnp.concatenate(
        [nll_block(h[i:i + HEAD_BLOCK], y_row[i:i + HEAD_BLOCK])
         for i in range(0, t, HEAD_BLOCK)]) for h in hs])       # [R, t]
    lam = jax.nn.sigmoid(mm(hs, params["gate_w"])[..., 0]
                         + params["gate_b"])
    p = exit_distribution(lam)
    if fault == "gate_detached":
        p = jax.lax.stop_gradient(p)
    entropy = -jnp.sum(jnp.where(p > 0, p * jnp.log(jnp.where(p > 0, p, 1)),
                                 0.0), axis=0)
    expected, h_sum = jnp.sum(p * nll), jnp.sum(entropy)
    return expected - beta * h_sum, (jnp.mean(p, axis=1),
                                     {"expected": expected,
                                      "entropy": h_sum})


@functools.partial(jax.jit, static_argnums=(0, 1, 2))
def row_exits(cfg_items, precision, fault, params, x_row):
    return _row_exits(dict(cfg_items), precision, fault, params, x_row)


@functools.partial(jax.jit, static_argnums=(0, 1, 2, 6))
def _row_grad(cfg_items, precision, fault, params, x_row, y_row, beta):
    return jax.value_and_grad(
        lambda p: _row_loss(dict(cfg_items), precision, fault, p, x_row,
                            y_row, beta), has_aux=True)(params)


@functools.partial(jax.jit, donate_argnums=(0,))
def _accumulate(total, part, weight):
    return jax.tree_util.tree_map(lambda a, b: a + weight * b, total, part)


def loss_and_grads(cfg: dict, params, x, y, precision: str = "float32",
                   fault: str = None, beta: float = None):
    """Batch loss (the mean over the rows of each row's summed loss), its
    gradient, the exits' mean shares ``[R]`` and the loss's two parts, a
    row at a time.  ``beta`` overrides the configuration's ``exit_beta``."""
    if fault not in FAULTS:
        raise ValueError(f"no such fault: {fault!r}")
    rows = int(x.shape[0])
    weight = F32(1.0 / rows)
    loss, grads, mass, parts = 0.0, None, 0.0, None
    for r in range(rows):
        (l_r, (m_r, parts_r)), g_r = _row_grad(
            _static(cfg), precision, fault, params,
            jnp.asarray(x[r], jnp.int32), jnp.asarray(y[r], jnp.int32),
            None if beta is None else float(beta))
        loss, mass = loss + l_r * weight, mass + m_r * weight
        parts_r = {k: v * weight for k, v in parts_r.items()}
        parts = parts_r if parts is None else {
            k: parts[k] + v for k, v in parts_r.items()}
        if grads is None:
            grads = jax.tree_util.tree_map(lambda a: a * weight, g_r)
        else:
            grads = _accumulate(grads, g_r, weight)
    return loss, grads, mass, parts


# ----------------------------------------------------------------- optimizer
def flat(tree) -> dict:
    """``layers.3.Wq`` -> leaf, ``wte`` -> leaf."""
    out = {}
    for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
        out[".".join(str(getattr(k, "key", getattr(k, "idx", k)))
                     for k in path)] = leaf
    return out


@jax.jit
def leaf_norms(tree):
    return {name: jnp.sqrt(jnp.sum(jnp.square(leaf)))
            for name, leaf in flat(tree).items()}


@functools.partial(jax.jit, static_argnums=0)
def _first_adam_delta_norms(opt_items, grads):
    """Norms of Adam's first update: with zero moments ``m / (1 - b1) =
    g`` and ``v / (1 - b2) = g^2``, so the step is ``-lr g / (|g| +
    eps)``, whatever the betas."""
    o = dict(opt_items)
    lr, eps = F32(o["learning_rate"]), F32(o["epsilon"])
    return leaf_norms(jax.tree_util.tree_map(
        lambda g: lr * g / (jnp.abs(g) + eps), grads))


def train_steps(cfg: dict, key, batches, precision: str = "float32",
                fault: str = None):
    """Follow the first optimizer step (``batches`` holds one ``(x, y)``)
    from the weights ``init_params(cfg, key)``.  Returns the step's loss,
    the norm of every leaf of its gradient and of the change Adam makes to
    every leaf, the exits' mean shares and the loss's two parts."""
    if len(batches) != 1:
        raise ValueError("the ouro reference keeps no Adam moments and "
                         "follows one step")
    opt = tuple(sorted((k, v) for k, v in cfg["optimizer"].items()
                       if k != "kind"))
    x, y = batches[0]
    loss, grads, mass, parts = loss_and_grads(
        cfg, init_params(cfg, key), x, y, precision, fault)
    host = jax.device_get
    return {"losses": [float(loss)],
            "grad_norms": {k: float(v) for k, v in
                           host(leaf_norms(grads)).items()},
            "delta_norms": {k: float(v) for k, v in host(
                _first_adam_delta_norms(opt, grads)).items()},
            "exit_mass": [float(v) for v in host(mass)],
            "loss_parts": {k: float(v) for k, v in host(parts).items()}}
