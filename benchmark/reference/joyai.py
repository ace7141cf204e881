"""Plain reference for the ``joyai`` family: JoyAI-LLM-Flash's decoder
(``model_type`` ``joyai_llm_flash``, DeepSeek-V3's design, arXiv:2412.19437
sections 2.1 and 2.2) as the configuration file states it, one chip's share
of it, its two losses, its gradients and the first Adam step, in
straightforward ``jax.numpy``.  It imports nothing of the program.

One row is ``T + 1`` token ids ``t_0 .. t_T``.  The stream ``h`` is ``[T,
e]``; every projection is without bias, every norm ``N(x; w) = x *
rsqrt(mean(x^2) + eps) * w``::

    h0  = wte[t_0 .. t_{T-1}]
    h   = h + MLA(N(h; n1))
    h   = h + FFN(N(h; n2))                  (num_hidden_layers times)
    main logits = N(h; norm_w) head_W        position i predicts t_{i+1}

``MLA`` on ``x``: ``c_q = N(x Wqa; qa_norm)`` (``q_lora_rank``), ``q = c_q
Wqb`` as heads of ``[q_nope | q_rope]`` (``qk_nope_head_dim`` |
``qk_rope_head_dim``); ``[c_kv | k_r] = x Wkva`` (``kv_lora_rank`` |
``qk_rope_head_dim``); ``N(c_kv; kva_norm) Wkvb`` as heads of ``[k_nope |
v]`` (``qk_nope_head_dim`` | ``v_head_dim``).  ``q_rope`` of every head and
the ONE ``k_r`` a position, which every head shares, are turned by rotary
positions on adjacent pairs (``rope_interleave``: features ``2i``, ``2i +
1`` as a complex number times ``exp(i pos theta^(-2i/d))``, written here
as that complex product), base ``rope_theta``, no scaling.  ``k_h =
[k_nope_h | k_r]``; scores ``q . k / sqrt(qk_nope_head_dim +
qk_rope_head_dim)``, causal; the output ``concat_h(softmax v_h) Wo``.
This is the expanded form; nothing is absorbed into the latent.

``FFN`` of the first ``first_k_dense_replace`` layers: ``W2 (silu(Wg x) *
(W1 x))``.  Of the others: ``s = sigmoid(x R)`` over all ``published
n_routed_experts`` (256); the ``num_experts_per_tok`` largest of ``s + b``
are chosen (``b`` the ``noaux_tc`` correction bias, a zero buffer; with
``n_group = topk_group = 1`` there is no group to limit the choice to);
``w = routed_scaling_factor * s_sel / sum(s_sel)`` (``norm_topk_prob``);
``y = Shared(x) + sum over the chosen experts e held here of w_e
Expert_e(x)``.  This chip holds experts ``0 .. n_routed_experts - 1`` of
the 256 (the configuration's ``n_routed_experts`` is the count held): what
the absent ones would add is left out and the partial ``y`` goes on, as in
the program.  The held experts are a plain loop, every expert over every
token, its weight nought where it was not chosen.

The multi-token-prediction module (one, ``num_nextn_predict_layers``)::

    h'_i = [N(wte[t_{i+1}]; enorm) ; N(h_i; hnorm)] Weh      i = 0 .. T-1
    h''  = one routed block as above, on h'
    mtp logits = N(h''; mtp_norm_w) head_W   position i predicts t_{i+2}

with ``h`` the trunk's output BEFORE its final norm, and the trunk's own
``wte`` and ``head_W``.  ``L = L_main + mtp_loss_weight * L_mtp``; each
``L`` is the program's ``sparse_mcxent``, the SUM over a row's positions of
the cross-entropy over the vocabulary slice (``L_mtp`` over positions ``0
.. T-2``: the last has no token after next), the mean over rows.

Departures from the published description, each assumed in the
configuration's file too: the embedding comes first in the merge (the
released modelling code's order; the paper's equation 21 writes the hidden
state first); ``mtp_loss_weight`` 0.3 is DeepSeek-V3's early-training
value (section 4.2), the config gives none; the losses are sums over
positions, not means (Adam is indifferent to the factor); the correction
bias is zero and nothing updates it; no auxiliary balance loss.

``precision`` rounds every matrix product's operands as
``reference/gpt2.py`` does: ``float32`` at ``Precision.HIGHEST`` (the
reference), ``bfloat16`` (what the configuration states), ``float8_e4m3fn``
(the control).  One row is differentiated at a time; each layer is
rematerialised, and inside it each block of ``ATTN_BLOCK`` queries (one
block's float32 scores, heads x block x keys, are all that is live) and
each held expert's MLP; each stream's head and loss are rematerialised
too, so one stream's float32 logits are live at a time.
Adam's moments are not kept: the steps followed are one, and Adam's first
update is ``-lr * g / (|g| + eps)`` from the gradient alone.  ``fault``
plants one: ``no_k_rope`` leaves ``k_r`` out of the keys (zeros in its
place), ``no_mtp`` leaves the module's term out of the loss.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

F32 = jnp.float32
HIGHEST = jax.lax.Precision.HIGHEST
ATTN_BLOCK = 512
NORMS = ("n1", "n2", "qa_norm", "kva_norm", "enorm", "hnorm", "norm_w")
FAULTS = (None, "no_k_rope", "no_mtp")


def layer_shapes(cfg: dict, routed: bool) -> dict:
    e, h = cfg["hidden_size"], cfg["num_attention_heads"]
    dn, dr, dv = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                  cfg["v_head_dim"])
    rq, rkv = cfg["q_lora_rank"], cfg["kv_lora_rank"]
    out = {"Wqa": (e, rq), "qa_norm": (rq,), "Wqb": (rq, h * (dn + dr)),
           "Wkva": (e, rkv + dr), "kva_norm": (rkv,),
           "Wkvb": (rkv, h * (dn + dv)), "Wo": (h * dv, e),
           "n1": (e,), "n2": (e,)}
    if not routed:
        f = cfg["intermediate_size"]
        out.update(Wg=(e, f), W1=(e, f), W2=(f, e))
        return out
    f, n = cfg["moe_intermediate_size"], cfg["n_routed_experts"]
    fs = cfg["n_shared_experts"] * f
    out.update(router=(e, cfg["published"]["n_routed_experts"]),
               eg=(n, e, f), e1=(n, e, f), e2=(n, f, e),
               sg=(e, fs), s1=(e, fs), s2=(fs, e))
    return out


def shapes(cfg: dict) -> dict:
    e, v = cfg["hidden_size"], cfg["vocab_size"]
    out = {"wte": (v, e), "norm_w": (e,), "head_W": (e, v),
           "layers": [layer_shapes(cfg, i >= cfg["first_k_dense_replace"])
                      for i in range(cfg["num_hidden_layers"])]}
    if cfg["num_nextn_predict_layers"]:
        if cfg["num_nextn_predict_layers"] != 1:
            raise ValueError("the joyai reference has one MTP module")
        out["mtp"] = {"enorm": (e,), "hnorm": (e,), "Weh": (2 * e, e),
                      "norm_w": (e,), "block": layer_shapes(cfg, True)}
    return out


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
    return cfg


@functools.partial(jax.jit, static_argnums=0)
def _init(cfg_items, key):
    cfg = _thaw(cfg_items)
    std = cfg["init_std"]
    leaves, tree = jax.tree_util.tree_flatten_with_path(
        shapes(cfg), is_leaf=lambda x: isinstance(x, tuple))
    out = []
    for i, (path, shape) in enumerate(leaves):
        if path[-1].key in NORMS:
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


def rotary_pairs(x, theta):
    """Rotary positions on ``[..., t, d]``: features ``(2i, 2i + 1)`` as
    the complex number ``x_2i + i x_2i+1``, times ``exp(i t theta^(-2i /
    d))``."""
    t, d = x.shape[-2], x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=F32) / d))
    angle = jnp.arange(t, dtype=F32)[:, None] * inv[None, :]
    pairs = x.reshape(x.shape[:-1] + (d // 2, 2))
    z = jax.lax.complex(pairs[..., 0], pairs[..., 1]) * \
        jax.lax.complex(jnp.cos(angle), jnp.sin(angle))
    return jnp.stack([jnp.real(z), jnp.imag(z)], axis=-1).reshape(x.shape)


def route(cfg: dict, scores):
    """``(idx [T, k], w [T, k])`` from the sigmoid scores ``[T, 256]``."""
    bias = jnp.zeros((scores.shape[-1],), F32)
    _, idx = jax.lax.top_k(scores + bias, cfg["num_experts_per_tok"])
    sel = jnp.take_along_axis(scores, idx, axis=1)
    if cfg["norm_topk_prob"]:
        sel = sel / jnp.sum(sel, axis=1, keepdims=True)
    return idx, cfg["routed_scaling_factor"] * sel


def _highest(a, b):
    return jnp.matmul(a, b, precision=HIGHEST)


def mlp(mm, x, wg, w1, w2):
    """The gated SiLU MLP, ``mm`` the matrix product."""
    return mm(jax.nn.silu(mm(x, wg)) * mm(x, w1), w2)


def routed_ffn(cfg: dict, p: dict, x, mm=_highest, shared: bool = True):
    """``(y [T, e], idx [T, k])`` of a routed layer's FFN on ``x [T, e]``:
    the shared expert (left out with ``shared=False``) and the part the
    experts held (``p["eg"]`` ..., experts ``0 .. n - 1`` of the router's)
    give."""
    scores = jax.nn.sigmoid(mm(x, p["router"]))
    idx, w = route(cfg, scores)

    @jax.checkpoint
    def one(y, expert):
        n, eg, e1, e2 = expert
        mine = jnp.sum(jnp.where(idx == n, w, 0.0), axis=1)
        return y + mine[:, None] * mlp(mm, x, eg, e1, e2), None
    first = mlp(mm, x, p["sg"], p["s1"], p["s2"]) if shared \
        else jnp.zeros_like(x)
    y, _ = jax.lax.scan(one, first, (jnp.arange(p["eg"].shape[0]), p["eg"],
                                     p["e1"], p["e2"]))
    return y, idx


def latent_attention(cfg: dict, p: dict, x, mm=_highest, q_=lambda t: t,
                     fault=None):
    """``MLA(x)`` for ``x [T, e]``, the queries a block of ``ATTN_BLOCK``
    at a time under a dense causal mask."""
    h = cfg["num_attention_heads"]
    dn, dr, dv = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                  cfg["v_head_dim"])
    rkv, eps = cfg["kv_lora_rank"], cfg["rms_norm_eps"]
    theta = float(cfg["rope_theta"])
    t = x.shape[0]

    def heads(y, d):
        return y.reshape(t, h, d).transpose(1, 0, 2)          # [h, t, d]
    q = heads(mm(_rms(mm(x, p["Wqa"]), p["qa_norm"], eps), p["Wqb"]),
              dn + dr)
    kva = mm(x, p["Wkva"])
    kv = heads(mm(_rms(kva[:, :rkv], p["kva_norm"], eps), p["Wkvb"]),
               dn + dv)
    k_r = rotary_pairs(kva[:, rkv:], theta)                   # [t, dr]
    if fault == "no_k_rope":
        k_r = jnp.zeros_like(k_r)
    q = jnp.concatenate([q[..., :dn], rotary_pairs(q[..., dn:], theta)],
                        axis=-1)
    k = jnp.concatenate([kv[..., :dn],
                         jnp.broadcast_to(k_r[None], (h, t, dr))], axis=-1)
    v = kv[..., dn:]

    @functools.partial(jax.checkpoint, static_argnums=(3,))
    def attend(qb, kb, vb, q0):
        """Queries ``[h, n, 192]`` from position ``q0`` over the keys
        ``[h, m, 192]`` from position 0."""
        n, m = qb.shape[1], kb.shape[1]
        s = jnp.einsum("hnd,hmd->hnm", q_(qb), q_(kb),
                       precision=HIGHEST) / math.sqrt(dn + dr)
        seen = jnp.arange(m)[None, :] <= q0 + jnp.arange(n)[:, None]
        s = jnp.where(seen[None], s, -jnp.inf)
        return jnp.einsum("hnm,hmd->hnd", q_(jax.nn.softmax(s, axis=-1)),
                          q_(vb), precision=HIGHEST)
    out = []
    for q0 in range(0, t, ATTN_BLOCK):
        q1 = min(q0 + ATTN_BLOCK, t)
        out.append(attend(q[:, q0:q1], k[:, :q1], v[:, :q1], q0))
    o = jnp.concatenate(out, axis=1).transpose(1, 0, 2).reshape(t, h * dv)
    return mm(o, p["Wo"])


def _row_streams(cfg: dict, precision: str, fault, params, ids):
    """``(h [T, e], h_mtp [T, e] or None, chosen [layers, T, k], mm)`` of
    ONE row of ``T + 1`` token ids: the trunk's and the module's last
    hidden states, each BEFORE its final norm, and the experts each token
    chose in each routed layer, the module's block last."""
    eps = cfg["rms_norm_eps"]
    q_ = _rounder(precision)

    def mm(a, b):
        return jnp.matmul(q_(a), q_(b), precision=HIGHEST)

    def layer(x, p, routed):
        x = x + latent_attention(cfg, p, _rms(x, p["n1"], eps), mm, q_,
                                 fault)
        xn = _rms(x, p["n2"], eps)
        if routed:
            y, idx = routed_ffn(cfg, p, xn, mm)
        else:
            y, idx = mlp(mm, xn, p["Wg"], p["W1"], p["W2"]), None
        return x + y, idx
    layer = jax.checkpoint(layer, static_argnums=(2,))

    emb = params["wte"][ids]                                  # [T + 1, e]
    x, chosen = emb[:-1], []
    for i, p in enumerate(params["layers"]):
        x, idx = layer(x, p, i >= cfg["first_k_dense_replace"])
        if idx is not None:
            chosen.append(idx)
    x2 = None
    if "mtp" in params:
        m = params["mtp"]
        # the embedding first, then the hidden state (see the docstring)
        merged = mm(jnp.concatenate([_rms(emb[1:], m["enorm"], eps),
                                     _rms(x, m["hnorm"], eps)], axis=-1),
                    m["Weh"])
        x2, idx = layer(merged, m["block"], True)
        chosen.append(idx)
    return x, x2, jnp.stack(chosen), mm


def _row_forward(cfg: dict, precision: str, fault, params, ids):
    """``(main logits [T, v], mtp logits [T, v] or None, chosen)`` of ONE
    row: both streams through their own final norm and the one head."""
    eps = cfg["rms_norm_eps"]
    x, x2, chosen, mm = _row_streams(cfg, precision, fault, params, ids)
    main = mm(_rms(x, params["norm_w"], eps), params["head_W"])
    mtp = None if x2 is None else mm(
        _rms(x2, params["mtp"]["norm_w"], eps), params["head_W"])
    return main, mtp, chosen


def _xent_sum(logits, targets):
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.sum(jnp.take_along_axis(logp, targets[:, None],
                                        axis=-1)[:, 0])


def _row_loss(cfg, precision, fault, params, ids):
    """``(L_main + weight * L_mtp, (L_main, L_mtp, chosen))`` of ONE row."""
    eps = cfg["rms_norm_eps"]
    x, x2, chosen, mm = _row_streams(cfg, precision, fault, params, ids)

    @jax.checkpoint
    def stream_loss(h, norm_w, head_W, targets):
        return _xent_sum(mm(_rms(h, norm_w, eps), head_W), targets)
    l_main = stream_loss(x, params["norm_w"], params["head_W"], ids[1:])
    l_mtp = jnp.zeros((), F32)
    if x2 is not None:
        # position i of the module predicts t_{i+2}; the last has none
        l_mtp = stream_loss(x2[:-1], params["mtp"]["norm_w"],
                            params["head_W"], ids[2:])
    weight = 0.0 if fault == "no_mtp" else cfg["mtp_loss_weight"]
    return l_main + weight * l_mtp, (l_main, l_mtp, chosen)


@functools.partial(jax.jit, static_argnums=(0, 1, 2))
def row_logits(cfg_items, precision, fault, params, ids):
    return _row_forward(_thaw(cfg_items), precision, fault, params, ids)


@functools.partial(jax.jit, static_argnums=(0, 1, 2))
def _row_grad(cfg_items, precision, fault, params, ids):
    return jax.value_and_grad(
        lambda p: _row_loss(_thaw(cfg_items), precision, fault, p, ids),
        has_aux=True)(params)


@functools.partial(jax.jit, donate_argnums=(0,))
def _accumulate(total, part, weight):
    return jax.tree_util.tree_map(lambda a, b: a + weight * b, total, part)


def loss_and_grads(cfg: dict, params, ids, precision: str = "float32",
                   fault: str = None):
    """``(loss, (L_main, L_mtp), grads, chosen [rows, layers, T, k])`` of
    rows of ``T + 1`` ids, a row at a time: each loss the mean over the
    rows of the row's sum."""
    if fault not in FAULTS:
        raise ValueError(f"no such fault: {fault!r}")
    rows = int(ids.shape[0])
    weight = F32(1.0 / rows)
    loss = l_main = l_mtp = 0.0
    grads, chosen = None, []
    for r in range(rows):
        (l_r, (m_r, t_r, c_r)), g_r = _row_grad(
            _static(cfg), precision, fault, params,
            jnp.asarray(ids[r], jnp.int32))
        loss, l_main, l_mtp = (loss + l_r * weight, l_main + m_r * weight,
                               l_mtp + t_r * weight)
        chosen.append(c_r)
        if grads is None:
            grads = jax.tree_util.tree_map(lambda a: a * weight, g_r)
        else:
            grads = _accumulate(grads, g_r, weight)
    return loss, (l_main, l_mtp), grads, jnp.stack(chosen)


# ----------------------------------------------------------------- optimizer
def flat(tree) -> dict:
    """``layers.3.Wqa`` -> leaf, ``mtp.block.router`` -> leaf."""
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
    """Follow the first optimizer step (``batches`` holds one array of
    rows of ``T + 1`` ids) from the weights ``init_params(cfg, key)``.
    Returns the step's loss (and its two parts), the norm of every leaf of
    its gradient and of the change Adam makes to every leaf, and the
    experts every token chose in every routed layer."""
    if len(batches) != 1:
        raise ValueError("the joyai reference keeps no Adam moments and "
                         "follows one step")
    opt = tuple(sorted((k, v) for k, v in cfg["optimizer"].items()
                       if k != "kind"))
    loss, (l_main, l_mtp), grads, chosen = loss_and_grads(
        cfg, init_params(cfg, key), batches[0], precision, fault)
    host = jax.device_get
    return {"losses": [float(loss)],
            "loss_parts": {"main": float(l_main), "mtp": float(l_mtp)},
            "grad_norms": {k: float(v) for k, v in
                           host(leaf_norms(grads)).items()},
            "delta_norms": {k: float(v) for k, v in host(
                _first_adam_delta_norms(opt, grads)).items()},
            "route_choices": host(chosen)}
