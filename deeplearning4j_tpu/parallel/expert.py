"""Expert parallelism: mixture-of-experts FFN with all-to-all dispatch.

No reference equivalent (pre-transformer era) — this completes the
TPU-first parallelism classes (dp/tp/pp/sp/ep) alongside ``pipeline.py``
and ``sequence.py``.  Design follows the GShard/Switch dense-dispatch
formulation: top-1 routing, fixed expert capacity (static shapes for XLA),
dispatch/combine as einsums on the MXU, and two tiled ``lax.all_to_all``
collectives over the ``expert`` mesh axis so each device hosts a shard of
experts while tokens stay sharded over data — the collective rides ICI.

Use under ``shard_map`` with mesh axes ("data", "expert"); see
``make_moe_train_step`` and ``tests/test_expert.py``.

``routed_ffn`` is the other formulation, the one today's sparse decoders
train with: top-k routing with no capacity and no dropped token.  The
(token, slot) pairs are sorted by expert and the experts' groups go
through grouped matrix products (``jax.lax.ragged_dot``), so there is no
``[T, E, C]`` one-hot.  It is told which experts it holds
(``held=(first, count)`` of the router's ``experts_total`` outputs): it
routes over all of them, computes the part of the result that its own
experts give, and spends no product on a pair routed elsewhere.  On one
chip there is no exchange, and nothing stands in for the absent chips.
"""
from __future__ import annotations

import functools
import math
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

__all__ = ["init_moe_params", "moe_ffn", "make_moe_train_step",
           "overflowed", "pair_capacity", "route_top_k", "routed_ffn"]


def init_moe_params(key, n_experts: int, embed: int, hidden: int,
                    dtype=jnp.float32) -> Dict[str, jax.Array]:
    """Router + stacked expert FFN weights.  Under shard_map the expert
    dimension of w1/w2 is sharded over the 'expert' axis (each device
    holds n_experts / ep of them); the router is replicated."""
    kr, k1, k2 = jax.random.split(key, 3)
    s1 = 1.0 / np.sqrt(embed)
    s2 = 1.0 / np.sqrt(hidden)
    return {
        "router": jax.random.normal(kr, (embed, n_experts), dtype) * s1,
        "w1": jax.random.normal(k1, (n_experts, embed, hidden), dtype) * s1,
        "w2": jax.random.normal(k2, (n_experts, hidden, embed), dtype) * s2,
    }


def _dispatch_tensors(router_probs: jax.Array, capacity: int
                      ) -> Tuple[jax.Array, jax.Array]:
    """Top-1 dispatch/combine tensors [T, E, C] (Switch formulation):
    token t goes to its argmax expert at its position-in-expert slot,
    dropped when the expert is over capacity."""
    n_experts = router_probs.shape[-1]
    expert_idx = jnp.argmax(router_probs, axis=-1)            # [T]
    onehot = jax.nn.one_hot(expert_idx, n_experts,
                            dtype=router_probs.dtype)         # [T, E]
    pos = jnp.cumsum(onehot, axis=0) - 1.0                     # [T, E]
    keep = (pos < capacity).astype(router_probs.dtype) * onehot
    pos_oh = jax.nn.one_hot(pos.astype(jnp.int32), capacity,
                            dtype=router_probs.dtype)          # [T, E, C]
    dispatch = keep[..., None] * pos_oh                        # [T, E, C]
    gate = jnp.sum(router_probs * onehot, axis=-1)             # [T]
    combine = dispatch * gate[:, None, None]
    return dispatch, combine


def moe_ffn(params: Dict[str, jax.Array], x: jax.Array, capacity: int,
            expert_axis: Optional[str] = None,
            act=jax.nn.relu) -> Tuple[jax.Array, jax.Array]:
    """MoE FFN over local tokens x [T, D].

    Without ``expert_axis``: single-device path — w1/w2 hold ALL experts.
    With ``expert_axis`` (inside shard_map): w1/w2 hold this device's
    expert shard; two tiled all-to-alls move each token group to its
    expert's owner and back:

        [E, C, D] --a2a(split E, concat C)--> [E/ep, ep*C, D]   (to owners)
        [E/ep, ep*C, D] --a2a(split C, concat E)--> [E, C, D]   (back)

    Returns (output [T, D], Switch load-balancing aux loss scalar)."""
    probs = jax.nn.softmax(x @ params["router"], axis=-1)      # [T, E]
    n_experts = probs.shape[-1]
    dispatch, combine = _dispatch_tensors(probs, capacity)
    expert_in = jnp.einsum("tec,td->ecd", dispatch, x)         # [E, C, D]
    if expert_axis is not None:
        expert_in = lax.all_to_all(expert_in, expert_axis, split_axis=0,
                                   concat_axis=1, tiled=True)
    h = act(jnp.einsum("ecd,edh->ech", expert_in, params["w1"])
            + params.get("b1", 0))
    out = jnp.einsum("ech,ehd->ecd", h, params["w2"]) + params.get("b2", 0)
    if expert_axis is not None:
        out = lax.all_to_all(out, expert_axis, split_axis=1,
                             concat_axis=0, tiled=True)
    y = jnp.einsum("tec,ecd->td", combine, out)
    # Switch aux loss: fraction-routed × mean router prob, per expert
    frac = jnp.mean(
        jax.nn.one_hot(jnp.argmax(probs, -1), n_experts), axis=0)
    aux = n_experts * jnp.sum(frac * jnp.mean(probs, axis=0))
    return y, aux


def make_moe_train_step(capacity: int, lr: float = 0.1,
                        aux_weight: float = 0.01):
    """SPMD MoE regression train step for shard_map over ("data",
    "expert"): tokens sharded over data, expert weights over expert,
    router replicated.  Gradients: w1/w2 pmean over data (their expert
    shard is unique per expert-group), router pmean over both axes."""

    def step(params, x, y):
        def loss_fn(p):
            out, aux = moe_ffn(p, x, capacity, expert_axis="expert")
            return jnp.mean((out - y) ** 2) + aux_weight * aux

        loss, grads = jax.value_and_grad(loss_fn)(params)
        loss = lax.pmean(lax.pmean(loss, "data"), "expert")
        grads = {
            "router": lax.pmean(lax.pmean(grads["router"], "data"),
                                "expert"),
            "w1": lax.pmean(grads["w1"], "data"),
            "w2": lax.pmean(grads["w2"], "data"),
        }
        new_params = jax.tree_util.tree_map(
            lambda p, g: p - lr * g, params, grads)
        return new_params, loss

    return step


# ---------------------------------------------------------------------------
# Top-k routing without dropped tokens: sort by expert, grouped products
# ---------------------------------------------------------------------------

def route_top_k(logits, top_k: int, *, scoring: str = "softmax",
                bias=None, route_norm: bool = False,
                route_scale: float = 1.0):
    """``(idx [T, k] int32, w [T, k] float32)`` from router logits ``[T,
    E]``, all in float32: scores by ``scoring`` (``softmax`` over the
    experts or ``sigmoid`` of each), the ``top_k`` largest of ``scores +
    bias`` chosen (``bias``: a balancing buffer that moves the choice and
    not the weight), their own scores as weights, divided by their sum
    under ``route_norm``, times ``route_scale``."""
    logits = logits.astype(jnp.float32)
    if scoring == "sigmoid":
        scores = jax.nn.sigmoid(logits)
    elif scoring == "softmax":
        scores = jax.nn.softmax(logits, axis=-1)
    else:
        raise ValueError(f"unknown scoring '{scoring}'; expected softmax "
                         "or sigmoid")
    pick = scores if bias is None else scores + bias.astype(jnp.float32)
    _, idx = lax.top_k(pick, top_k)
    w = jnp.take_along_axis(scores, idx, axis=1)
    if route_norm:
        w = w / (jnp.sum(w, axis=1, keepdims=True) + 1e-20)
    return idx.astype(jnp.int32), w * route_scale


# Pair capacity of a layer that holds some of the experts it routes over,
# as a multiple of what an even routing sends it, in whole tiles of rows.
# (1.25 and 1.5 read 1-3 % faster on the benchmark's one routing, a router
# nothing balances, and overflowed there in up to an eighth of the
# layer-steps where 2 does in one of a hundred: PERF.md section 6, PR 34.)
SLACK = 2
_PAIR_TILE = 1024


def pair_capacity(pairs: int, held: int, total: int) -> int:
    """Rows of the routed FFN's buffers where ``held`` of the ``total``
    experts routed over live here and ``pairs`` (token, slot) pairs are
    routed in all: every pair where all experts are held, else ``SLACK``
    times the even routing's share, rounded up to a tile of rows."""
    if held >= total:
        return pairs
    tiles = math.ceil(SLACK * pairs * held / total / _PAIR_TILE)
    return min(pairs, tiles * _PAIR_TILE)


def overflowed(sizes, pairs: int, total: int):
    """Whether the held experts, which took ``sizes [H]`` of the ``pairs``
    routed over ``total`` experts, took more than their buffers hold: the
    one rule for the branch ``routed_ffn`` takes and for who counts it."""
    return jnp.sum(sizes) > pair_capacity(pairs, sizes.shape[0], total)


def _token_sum(rows, at, mine, scale=None):
    """``[T, D]`` float32: each token's own rows of ``rows [C, D]``, found
    at ``at [T, k]`` where ``mine``, weighted by ``scale [T, k]``; the
    other slots (pairs routed elsewhere, rows no product wrote) nought.
    An inverse gather into ``[T, k, D]`` summed over ``k``: on the chip the
    fastest of four ways to the same sum (``PERF.md`` section 5, PR 34)."""
    back = jnp.where(mine[..., None], rows[at], jnp.zeros((), rows.dtype))
    if scale is None:
        return jnp.sum(back, axis=1, dtype=jnp.float32)
    return jnp.einsum("tkd,tk->td", back, scale.astype(back.dtype),
                      preferred_element_type=jnp.float32)


# The dispatch and its transpose are each other's derivative: a row
# gather both ways, where autodiff's own transpose of a gather is a
# scatter-add over 4 KB rows.  ``pairs [C]``: the (token, slot) pair of
# each buffer row; ``at``, ``mine [T, k]``: the buffer row of each of a
# token's pairs, where it has one.
@jax.custom_vjp
def _dispatch(x, pairs, at, mine):
    return x[pairs // mine.shape[1]]


def _dispatch_fwd(x, pairs, at, mine):
    return _dispatch(x, pairs, at, mine), (at, mine)


def _dispatch_bwd(res, g):
    return _token_sum(g, *res).astype(g.dtype), None, None, None


_dispatch.defvjp(_dispatch_fwd, _dispatch_bwd)


@jax.custom_vjp
def _combine(ys, w, pairs, live, at, mine):
    """``[T, D]`` float32: each token's weighted sum of its rows of ``ys
    [C, D]``, ``live`` where a product wrote them."""
    return _token_sum(ys, at, mine, w)


def _combine_fwd(ys, w, pairs, live, at, mine):
    return _combine(ys, w, pairs, live, at, mine), \
        (ys, w, pairs, live, at, mine)


def _combine_bwd(res, g):
    # in the buffer's rows: one gather of g, and no [T, k, D] of either
    ys, w, pairs, live, at, mine = res
    gt = g[pairs // w.shape[1]].astype(jnp.float32)              # [C, D]
    wc = w.astype(ys.dtype).reshape(-1)[pairs].astype(jnp.float32)
    g_ys = jnp.where(live[:, None], gt * wc[:, None], 0.0).astype(ys.dtype)
    g_wc = jnp.sum(ys.astype(jnp.float32) * gt, axis=1)
    g_w = jnp.where(mine, g_wc[at], 0.0).astype(w.dtype)
    return (g_ys, g_w) + (None,) * 4


_combine.defvjp(_combine_fwd, _combine_bwd)


def _held_part(experts, x, w, key, order, inverse, here, sizes, *,
               rows: int, act):
    """``y [T, D]`` through buffers of the first ``rows`` sorted pairs:
    exact where the held experts took no more than that."""
    t, k = here.shape
    n_held = sizes.shape[0]
    gated, biased = "wg" in experts, "b1" in experts
    with jax.named_scope("moe_dispatch"):
        pairs = order[:rows]
        live = jnp.arange(rows, dtype=jnp.int32) < jnp.sum(sizes)
        # the held experts' pairs sort first: theirs lie under ``rows``
        at = jnp.minimum(inverse.reshape(t, k), rows - 1)
        xs = _dispatch(x, pairs, at, here)                       # [C, D]
        if biased:
            eid = jnp.minimum(key[pairs], n_held - 1)
    with jax.named_scope("moe_experts"):
        up = lax.ragged_dot(xs, experts["w1"], sizes)
        if biased:
            up = up + experts["b1"][eid, 0]
        hidden = act(lax.ragged_dot(xs, experts["wg"], sizes)) * up \
            if gated else act(up)
        ys = lax.ragged_dot(hidden, experts["w2"], sizes)
        if biased:
            ys = ys + experts["b2"][eid, 0]
    with jax.named_scope("moe_combine"):
        return _combine(ys, w, pairs, live, at, here).astype(x.dtype)


def routed_ffn(params: Dict[str, jax.Array], x: jax.Array, *, top_k: int,
               scoring: str = "softmax", route_norm: bool = False,
               route_scale: float = 1.0,
               held: Optional[Tuple[int, int]] = None, bias=None,
               act=jax.nn.silu) -> Tuple[jax.Array, jax.Array]:
    """Routed FFN over tokens ``x [T, D]`` with no token dropped.

    ``params``: ``router [D, E]`` over ALL ``E`` experts; ``w1 [H, D, F]``,
    ``w2 [H, F, D]`` of the ``H`` experts held here, experts ``first ..
    first + H - 1`` of the router's (``held=(first, H)``; all of them where
    not given); with ``wg [H, D, F]`` the experts are gated, ``w2 (act(wg
    x) * (w1 x))``, else ``w2 act(w1 x)``; ``b1 [H, 1, F]``, ``b2 [H, 1,
    D]`` are added where present.  Returns ``(y [T, D], tokens [H]
    int32)``: ``y_t = sum over the experts e chosen for t and held here of
    w_te Expert_e(x_t)``, and how many pairs each held expert took.

    Every token is routed (``route_top_k``); the ``T * top_k`` (token,
    slot) pairs are sorted by expert, the held experts' first and in order,
    pairs routed elsewhere last, and each held expert's group goes through
    the grouped products.  The buffers hold ``pair_capacity`` sorted pairs:
    all of them where every expert is held (then there is one path and no
    conditional), else ``SLACK`` times what an even routing sends here, so
    the gather into the experts' order, the products' operands and what the
    backward keeps have that many rows; only the per-token sum still
    gathers ``[T, top_k, D]`` on its way, out of the small buffer, and keeps
    none of it.  A routing that sends the held experts more is computed all
    the same, no pair dropped, by the same code on buffers of all ``T *
    top_k`` pairs under one ``lax.cond``; that branch is checkpointed, so it
    keeps nothing whole-size for the backward either, and costs about what
    every step cost before the buffers were sized (``PERF.md`` section 6,
    PRs 33 and 34).  ``overflowed(tokens, T * top_k, E)`` says which of the
    two a step took.
    """
    t, d = x.shape
    n_total = params["router"].shape[1]
    first, n_held = held or (0, n_total)
    if params["w1"].shape[0] != n_held or first + n_held > n_total:
        raise ValueError(f"held experts {(first, n_held)} do not match "
                         f"{params['w1'].shape[0]} expert weights of "
                         f"{n_total} routed")
    with jax.named_scope("moe_route"):
        logits = jnp.dot(x, params["router"],
                         preferred_element_type=jnp.float32)
        idx, w = route_top_k(logits, top_k, scoring=scoring, bias=bias,
                             route_norm=route_norm, route_scale=route_scale)
    n_pairs = t * top_k
    with jax.named_scope("moe_dispatch"):
        local = idx - first
        here = jnp.logical_and(local >= 0, local < n_held)       # [T, k]
        key = jnp.where(here, local, n_held).reshape(n_pairs)
        order = jnp.argsort(key, stable=True).astype(jnp.int32)
        inverse = jnp.zeros((n_pairs,), jnp.int32).at[order].set(
            jnp.arange(n_pairs, dtype=jnp.int32), unique_indices=True)
        sizes = jnp.sum(key[:, None] == jnp.arange(n_held,
                                                   dtype=jnp.int32)[None],
                        axis=0, dtype=jnp.int32)
    experts = {k: v for k, v in params.items() if k != "router"}
    rows = pair_capacity(n_pairs, n_held, n_total)
    operands = (experts, x, w, key, order, inverse, here, sizes)
    if rows == n_pairs:
        return _held_part(*operands, rows=rows, act=act), sizes
    y = lax.cond(
        overflowed(sizes, n_pairs, n_total),
        jax.checkpoint(functools.partial(_held_part, rows=n_pairs, act=act)),
        functools.partial(_held_part, rows=rows, act=act),
        *operands)
    return y, sizes
