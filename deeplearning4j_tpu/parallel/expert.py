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
"""
from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

__all__ = ["init_moe_params", "moe_ffn", "make_moe_train_step"]


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
