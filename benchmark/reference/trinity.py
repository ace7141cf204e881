"""Plain reference for the ``trinity`` family: Arcee Trinity's decoder
(``model_type`` ``afmoe``) as the configuration file states it, one chip's
share of it, its next-token loss, its gradients and the first Adam step,
in straightforward ``jax.numpy``.  It imports nothing of the program.

The stream ``h`` is ``[T, e]``; every projection is without bias::

    h0  = sqrt(e) * wte[ids]
    h   = h + N2(Attn(N1(h)))
    h   = h + N4(FFN(N3(h)))            N(x; w) = x * rsqrt(mean(x^2) + eps) * w
    out = N(h; norm_w) head_W

``Attn``: ``q = x Wq`` as 32 heads of 128, ``k = x Wk``, ``v = x Wv`` as 4;
query head ``j`` reads K/V head ``j // 8``.  q and k are normalised over
each head's 128 (``N`` with ``q_norm``, ``k_norm``), then, **on
``sliding_attention`` layers only**, turned by rotary positions
(rotate-half, base ``rope_theta``, absolute); ``full_attention`` layers
carry no positions.  Scores ``q . k / sqrt(128)``, causal; on a sliding
layer query ``i`` sees the keys ``i - sliding_window < j <= i`` (a dense
mask).  The output is ``(sigmoid(x Wgate) * A) Wo``.

``FFN`` of the first ``num_dense_layers`` layers: ``W2 (silu(Wg x) * (W1
x))``.  Of the others: ``s = sigmoid(x R)`` over all ``published
num_experts`` (128); the ``num_experts_per_tok`` largest of ``s + b`` are
chosen (``b`` the balancing buffer, zero); ``w = route_scale * s_sel /
sum(s_sel)``; ``y = Shared(x) + sum over the chosen experts e held here of
w_e Expert_e(x)``, the shared expert and each routed one a gated SiLU MLP
of ``moe_intermediate_size``.  This chip holds experts ``0 ..
num_experts - 1`` of the 128 (the configuration's ``num_experts`` is the
count held): what the absent ones would add is left out and the partial
``y`` goes on, as in the program.  The held experts are a plain loop, every
expert over every token, its weight nought where it was not chosen.

The loss is the program's ``sparse_mcxent``: the sum over a row's tokens
of the next-token cross-entropy over the vocabulary slice, the mean over
the rows.  What the configuration's ``assumed`` lists is assumed here too.

``precision`` rounds every matrix product's operands as
``reference/gpt2.py`` does: ``float32`` at ``Precision.HIGHEST`` (the
reference), ``bfloat16`` (what the configuration states), ``float8_e4m3fn``
(the control).  One row is differentiated at a time, each layer is
rematerialised and inside it each block of ``ATTN_BLOCK`` queries, so one
block's float32 scores (heads x block x keys) are all that is live.
Adam's moments are not kept: the steps followed are one, and Adam's first
update is ``-lr * g / (|g| + eps)`` from the gradient alone.  ``fault``
plants one: ``no_window`` lets the sliding layers see every earlier key,
``no_route_scale`` leaves ``route_scale`` out of the routed weights.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

F32 = jnp.float32
HIGHEST = jax.lax.Precision.HIGHEST
ATTN_BLOCK = 1024
NORMS = ("n1", "n2", "n3", "n4", "q_norm", "k_norm")
FAULTS = (None, "no_window", "no_route_scale")


def layer_kinds(cfg: dict):
    """``[(sliding?, routed?), ...]``, one a layer."""
    return [(kind == "sliding_attention", i >= cfg["num_dense_layers"])
            for i, kind in enumerate(
                cfg["layer_types"][:cfg["num_hidden_layers"]])]


def layer_shapes(cfg: dict, routed: bool) -> dict:
    e, d = cfg["hidden_size"], cfg["head_dim"]
    h, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    out = {"Wq": (e, h * d), "Wk": (e, kv * d), "Wv": (e, kv * d),
           "Wo": (h * d, e), "Wgate": (e, h * d), "q_norm": (d,),
           "k_norm": (d,), "n1": (e,), "n2": (e,), "n3": (e,), "n4": (e,)}
    if not routed:
        f = cfg["intermediate_size"]
        out.update(Wg=(e, f), W1=(e, f), W2=(f, e))
        return out
    f, n = cfg["moe_intermediate_size"], cfg["num_experts"]
    fs = cfg["num_shared_experts"] * f
    out.update(router=(e, cfg["published"]["num_experts"]),
               eg=(n, e, f), e1=(n, e, f), e2=(n, f, e),
               sg=(e, fs), s1=(e, fs), s2=(fs, e))
    return out


def shapes(cfg: dict) -> dict:
    e, v = cfg["hidden_size"], cfg["vocab_size"]
    return {"wte": (v, e), "norm_w": (e,), "head_W": (e, v),
            "layers": [layer_shapes(cfg, routed)
                       for _, routed in layer_kinds(cfg)]}


def n_params(cfg: dict) -> int:
    return sum(math.prod(s) for s in jax.tree_util.tree_leaves(
        shapes(cfg), is_leaf=lambda x: isinstance(x, tuple)))


def _static(cfg: dict):
    """The configuration as a hashable static argument."""
    def freeze(v):
        if isinstance(v, dict):
            return tuple(sorted((k, freeze(x)) for k, x in v.items()))
        if isinstance(v, list):
            return tuple(freeze(x) for x in v)
        return v
    return freeze({k: v for k, v in cfg.items()
                   if k not in ("assumed", "deployment", "optimizer")})


def _thaw(items) -> dict:
    cfg = dict(items)
    cfg["published"] = dict(cfg["published"])
    cfg["layer_types"] = list(cfg["layer_types"])
    return cfg


@functools.partial(jax.jit, static_argnums=0)
def _init(cfg_items, key):
    cfg = _thaw(cfg_items)
    std = cfg["init_std"]
    sh = shapes(cfg)
    leaves, tree = jax.tree_util.tree_flatten_with_path(
        sh, is_leaf=lambda x: isinstance(x, tuple))
    out = []
    for i, (path, shape) in enumerate(leaves):
        name = path[-1].key
        if name in NORMS or name == "norm_w":
            out.append(jnp.ones(shape, F32))
        else:
            out.append(std * jax.random.normal(jax.random.fold_in(key, i),
                                               shape, F32))
    return jax.tree_util.tree_unflatten(tree, out)


def init_params(cfg: dict, key):
    """All weights in one jitted call, float32, on the default device:
    normal matrices of ``init_std``, unit norm weights."""
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


def route(cfg: dict, scores, route_scale: float):
    """``(idx [T, k], w [T, k])`` from the sigmoid scores ``[T, 128]``."""
    bias = jnp.zeros((scores.shape[-1],), F32)
    _, idx = jax.lax.top_k(scores + bias, cfg["num_experts_per_tok"])
    sel = jnp.take_along_axis(scores, idx, axis=1)
    if cfg["route_norm"]:
        sel = sel / jnp.sum(sel, axis=1, keepdims=True)
    return idx, route_scale * sel


def _highest(a, b):
    return jnp.matmul(a, b, precision=HIGHEST)


def mlp(mm, x, wg, w1, w2):
    """The gated SiLU MLP, ``mm`` the matrix product."""
    return mm(jax.nn.silu(mm(x, wg)) * mm(x, w1), w2)


def routed_ffn(cfg: dict, p: dict, x, route_scale: float, mm=_highest):
    """``(y [T, e], idx [T, k])`` of a routed layer's FFN on ``x [T, e]``:
    the shared expert and the part the experts held (``p["eg"]`` ...,
    experts ``0 .. n - 1`` of the router's) give."""
    scores = jax.nn.sigmoid(mm(x, p["router"]))
    idx, w = route(cfg, scores, route_scale)

    def one(y, expert):
        n, eg, e1, e2 = expert
        mine = jnp.sum(jnp.where(idx == n, w, 0.0), axis=1)
        return y + mine[:, None] * mlp(mm, x, eg, e1, e2), None
    y, _ = jax.lax.scan(one, mlp(mm, x, p["sg"], p["s1"], p["s2"]),
                        (jnp.arange(p["eg"].shape[0]), p["eg"], p["e1"],
                         p["e2"]))
    return y, idx


def _row_forward(cfg: dict, precision: str, fault, params, x_row):
    """Logits ``[t, vocab]`` of ONE row of token ids ``[t]``, and the
    experts each token chose in each routed layer, ``[layers, t, k]``."""
    e, d = cfg["hidden_size"], cfg["head_dim"]
    h, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    eps, theta = cfg["rms_norm_eps"], float(cfg["rope_theta"])
    window = cfg["sliding_window"]
    route_scale = 1.0 if fault == "no_route_scale" else cfg["route_scale"]
    t = x_row.shape[0]
    q_ = _rounder(precision)

    def mm(a, b):
        return jnp.matmul(q_(a), q_(b), precision=HIGHEST)

    @functools.partial(jax.checkpoint, static_argnums=(3, 4, 5))
    def attend(qb, kb, vb, q0, k0, sliding):
        """Queries ``[h, n, d]`` from position ``q0`` over keys ``[kv, m,
        d]`` from position ``k0``, under a dense mask."""
        n, m = qb.shape[1], kb.shape[1]
        qg = qb.reshape(kv, h // kv, n, d)
        s = jnp.einsum("ghnd,gmd->ghnm", q_(qg), q_(kb),
                       precision=HIGHEST) / math.sqrt(d)
        i = q0 + jnp.arange(n)[:, None]
        j = k0 + jnp.arange(m)[None, :]
        seen = j <= i
        if sliding:
            seen = seen & (j > i - window)
        s = jnp.where(seen[None, None], s, -jnp.inf)
        o = jnp.einsum("ghnm,gmd->ghnd", q_(jax.nn.softmax(s, axis=-1)),
                       q_(vb), precision=HIGHEST)
        return o.reshape(h, n, d)

    def attention(p, xn, sliding):
        def heads(w, n):
            return mm(xn, w).reshape(t, n, d).transpose(1, 0, 2)
        q = _rms(heads(p["Wq"], h), p["q_norm"], eps)
        k = _rms(heads(p["Wk"], kv), p["k_norm"], eps)
        v = heads(p["Wv"], kv)
        if sliding:
            q, k = _rotary(q, theta), _rotary(k, theta)
        banded = sliding and fault != "no_window"
        out = []
        for q0 in range(0, t, ATTN_BLOCK):
            q1 = min(q0 + ATTN_BLOCK, t)
            k0 = max(0, q0 - window + 1) if banded else 0
            out.append(attend(q[:, q0:q1], k[:, k0:q1], v[:, k0:q1], q0, k0,
                              banded))
        o = jnp.concatenate(out, axis=1).transpose(1, 0, 2).reshape(t, h * d)
        return mm(o * jax.nn.sigmoid(mm(xn, p["Wgate"])), p["Wo"])

    def layer(x, p, sliding, routed):
        x = x + _rms(attention(p, _rms(x, p["n1"], eps), sliding),
                     p["n2"], eps)
        xn = _rms(x, p["n3"], eps)
        if routed:
            y, idx = routed_ffn(cfg, p, xn, route_scale, mm)
        else:
            y, idx = mlp(mm, xn, p["Wg"], p["W1"], p["W2"]), None
        return x + _rms(y, p["n4"], eps), idx

    x = math.sqrt(e) * params["wte"][x_row]
    chosen = []
    for p, (sliding, routed) in zip(params["layers"], layer_kinds(cfg)):
        x, idx = jax.checkpoint(layer, static_argnums=(2, 3))(
            x, p, sliding, routed)
        if routed:
            chosen.append(idx)
    return mm(_rms(x, params["norm_w"], eps), params["head_W"]), \
        jnp.stack(chosen)


def _row_loss(cfg, precision, fault, params, x_row, y_row):
    """Summed next-token cross-entropy of ONE row, and its routing."""
    logits, chosen = _row_forward(cfg, precision, fault, params, x_row)
    logp = jax.nn.log_softmax(logits, axis=-1)
    picked = jnp.take_along_axis(logp, y_row[:, None], axis=-1)[:, 0]
    return -jnp.sum(picked), chosen


@functools.partial(jax.jit, static_argnums=(0, 1, 2))
def row_logits(cfg_items, precision, fault, params, x_row):
    return _row_forward(_thaw(cfg_items), precision, fault, params, x_row)


@functools.partial(jax.jit, static_argnums=(0, 1, 2))
def _row_grad(cfg_items, precision, fault, params, x_row, y_row):
    return jax.value_and_grad(
        lambda p: _row_loss(_thaw(cfg_items), precision, fault, p, x_row,
                            y_row), has_aux=True)(params)


@functools.partial(jax.jit, donate_argnums=(0,))
def _accumulate(total, part, weight):
    return jax.tree_util.tree_map(lambda a, b: a + weight * b, total, part)


def loss_and_grads(cfg: dict, params, x, y, precision: str = "float32",
                   fault: str = None):
    """Batch loss (the mean over the rows of each row's summed loss), its
    gradient, and the routing ``[rows, layers, t, k]``, a row at a time."""
    if fault not in FAULTS:
        raise ValueError(f"no such fault: {fault!r}")
    rows = int(x.shape[0])
    weight = F32(1.0 / rows)
    loss, grads, chosen = 0.0, None, []
    for r in range(rows):
        (l_r, c_r), g_r = _row_grad(_static(cfg), precision, fault, params,
                                    jnp.asarray(x[r], jnp.int32),
                                    jnp.asarray(y[r], jnp.int32))
        loss = loss + l_r * weight
        chosen.append(c_r)
        if grads is None:
            grads = jax.tree_util.tree_map(lambda a: a * weight, g_r)
        else:
            grads = _accumulate(grads, g_r, weight)
    return loss, grads, jnp.stack(chosen)


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
    every leaf, and the experts every token chose in every routed layer."""
    if len(batches) != 1:
        raise ValueError("the trinity reference keeps no Adam moments and "
                         "follows one step")
    opt = tuple(sorted((k, v) for k, v in cfg["optimizer"].items()
                       if k != "kind"))
    x, y = batches[0]
    loss, grads, chosen = loss_and_grads(cfg, init_params(cfg, key), x, y,
                                         precision, fault)
    host = jax.device_get
    return {"losses": [float(loss)],
            "grad_norms": {k: float(v) for k, v in
                           host(leaf_norms(grads)).items()},
            "delta_norms": {k: float(v) for k, v in host(
                _first_adam_delta_norms(opt, grads)).items()},
            "route_choices": host(chosen)}
