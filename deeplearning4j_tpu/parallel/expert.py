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
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

__all__ = ["init_moe_params", "moe_ffn", "make_moe_train_step",
           "route_top_k", "routed_ffn"]


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


def _pairs_of(x, order, k: int):
    """Row ``order[i] // k`` of ``x`` for each sorted pair ``i``."""
    return x[order // k]


def _sum_pairs(ys, inverse, here, k: int):
    """Each token's ``k`` pairs, found again at ``inverse``, those routed
    elsewhere (``here`` false: rows no product wrote) taken as nought."""
    t = here.shape[0]
    back = ys[inverse].reshape(t, k, ys.shape[-1])
    return jnp.where(here[..., None], back, jnp.zeros((), ys.dtype))


# The dispatch and its transpose are each other's derivative: a row
# gather both ways, where autodiff's own transpose of a gather is a
# scatter-add over 4 KB rows.
@functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
def _dispatch(x, order, inverse, here, k):
    return _pairs_of(x, order, k)


def _dispatch_fwd(x, order, inverse, here, k):
    return _pairs_of(x, order, k), (order, inverse, here)


def _dispatch_bwd(k, res, g):
    order, inverse, here = res
    dx = jnp.sum(_sum_pairs(g, inverse, here, k), axis=1,
                 dtype=jnp.float32).astype(g.dtype)
    return dx, None, None, None


_dispatch.defvjp(_dispatch_fwd, _dispatch_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
def _undispatch(ys, order, inverse, here, k):
    """``[T, k, D]``: the sorted pairs' rows back beside their tokens."""
    return _sum_pairs(ys, inverse, here, k)


def _undispatch_fwd(ys, order, inverse, here, k):
    return _sum_pairs(ys, inverse, here, k), (order, inverse, here)


def _undispatch_bwd(k, res, g):
    order, inverse, here = res
    t = here.shape[0]
    g = jnp.where(here[..., None], g, jnp.zeros((), g.dtype))
    return g.reshape(t * k, g.shape[-1])[order], None, None, None


_undispatch.defvjp(_undispatch_fwd, _undispatch_bwd)


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
    the grouped products.  The buffers hold all ``T * top_k`` pairs, what
    no drop under any routing takes (``E / H`` times what an even routing
    sends here); the products walk the held groups alone, the gathers into
    and out of the experts' order move all of it.  (Recomputing the part
    between the sort and the sum in the backward pass, under
    ``jax.checkpoint``, was tried on the chip: the step that fits either
    way is 1.5 % slower with it, ``PERF.md`` section 6, PR 33.)
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
    with jax.named_scope("moe_dispatch"):
        local = idx - first
        here = jnp.logical_and(local >= 0, local < n_held)       # [T, k]
        key = jnp.where(here, local, n_held).reshape(t * top_k)
        order = jnp.argsort(key, stable=True).astype(jnp.int32)
        inverse = jnp.zeros((t * top_k,), jnp.int32).at[order].set(
            jnp.arange(t * top_k, dtype=jnp.int32), unique_indices=True)
        sizes = jnp.sum(key[:, None] == jnp.arange(n_held,
                                                   dtype=jnp.int32)[None],
                        axis=0, dtype=jnp.int32)

    gated, biased = "wg" in params, "b1" in params
    with jax.named_scope("moe_dispatch"):
        xs = _dispatch(x, order, inverse, here, top_k)           # [T*k, D]
        if biased:
            eid = jnp.minimum(key[order], n_held - 1)
    with jax.named_scope("moe_experts"):
        up = lax.ragged_dot(xs, params["w1"], sizes)
        if biased:
            up = up + params["b1"][eid, 0]
        hidden = act(lax.ragged_dot(xs, params["wg"], sizes)) * up \
            if gated else act(up)
        ys = lax.ragged_dot(hidden, params["w2"], sizes)
        if biased:
            ys = ys + params["b2"][eid, 0]
    with jax.named_scope("moe_combine"):
        back = _undispatch(ys, order, inverse, here, top_k)      # [T, k, D]
        y = jnp.einsum("tkd,tk->td", back, w.astype(back.dtype),
                       preferred_element_type=jnp.float32).astype(x.dtype)
    return y, sizes
