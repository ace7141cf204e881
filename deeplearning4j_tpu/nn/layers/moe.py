"""Mixture-of-experts for the config DSL: the layer-level face of
``parallel/expert.py``.

No reference equivalent (pre-transformer era).  Two routings:

``RoutedExperts``, the one routed-FFN module, which ``TransformerBlock``
(``moe_top_k > 0``) and ``MixtureOfExpertsLayer`` (``top_k > 0``) both
call: top-k routing with softmax or sigmoid scores, no capacity and no
dropped token, shared experts beside the routed ones, and told which of
the router's experts it holds (``parallel/expert.routed_ffn``).  The
tokens each held expert took are threaded through layer *state*
(``expert_tokens``) as the auxiliary loss is, and beside them the count of
calls whose routing overflowed the buffers' pair capacity
(``expert_overflows``); both are published as gauges
(``moe_expert_tokens{layer, expert}``, ``moe_overflow_steps{layer}``)
where ``fit`` reads the loss.

The top-1 Switch path (``top_k = 0``, the default): a stack of expert FFNs
with a fixed capacity for static shapes, tokens over it dropped.  Its
load-balancing aux loss is threaded through layer *state* (``aux_loss``)
and added to the objective by the network loss (AUX_LOSS flag) —
state-threading keeps it remat/checkpoint safe.

Both work on FF [b, f] and RNN [b, t, f] inputs; for expert-parallel
sharding see parallel/expert.py's shard_map formulation with all-to-all.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from ...utils.serde import register_serde
from ..conf.input_type import InputType
from .base import BaseLayerConf

__all__ = ["MixtureOfExpertsLayer", "RoutedExperts", "publish_expert_tokens"]


@dataclass(frozen=True)
class RoutedExperts:
    """The routed FFN of a layer, not a layer itself: the layer owns the
    parameters and the state and hands them in.

    ``experts_total`` experts are routed over, ``top_k`` a token, by
    ``scoring`` (``route_top_k``); ``experts_held = (first, count)`` of
    them live here (all where ``None``), each ``hidden`` wide, gated
    (``w2 (act(wg x) * (w1 x))``) and biased as the owner says;
    ``shared_experts`` more, as one MLP ``shared_experts * hidden`` wide,
    see every token.  Parameters: ``router [n_in, experts_total]``, ``w1``,
    ``w2`` (``wg``; ``b1``, ``b2``) stacked over the held experts, ``s1``,
    ``s2`` (``sg``; ``sb1``, ``sb2``) of the shared ones.  State:
    ``route_bias [experts_total]``, the balancing buffer added to the
    scores for the choice alone (zero, and nothing here moves it),
    ``expert_tokens [count]``, the pairs each held expert took in the
    last call, and ``expert_overflows``, how many calls so far sent the
    held experts more pairs than ``pair_capacity`` (every expert held:
    never): those calls computed every pair all the same, on whole-size
    buffers, and a layer where the count keeps rising pays for them."""
    n_in: int
    hidden: int
    experts_total: int
    top_k: int
    scoring: str = "softmax"
    route_norm: bool = False
    route_scale: float = 1.0
    shared_experts: int = 0
    experts_held: Optional[Tuple[int, int]] = None
    gated: bool = False
    has_bias: bool = True
    n_out: int = 0                  # default n_in

    def held(self) -> Tuple[int, int]:
        return self.experts_held or (0, self.experts_total)

    def init(self, key, make_weight, make_bias):
        """``(params, state)`` from the owner's initialisers."""
        e, f, out = self.n_in, self.hidden, self.n_out or self.n_in
        first, n = self.held()
        if not 0 < self.top_k <= self.experts_total or first < 0 or \
                n < 1 or first + n > self.experts_total:
            raise ValueError(
                f"routed experts: top_k {self.top_k} of "
                f"{self.experts_total}, held {(first, n)}")
        kr, k1, k2, kg, ks = jax.random.split(key, 5)
        params = {"router": make_weight(kr, (e, self.experts_total)),
                  "w1": make_weight(k1, (n, e, f)),
                  "w2": make_weight(k2, (n, f, out))}
        if self.gated:
            params["wg"] = make_weight(kg, (n, e, f))
        if self.has_bias:
            params.update(b1=make_bias((n, 1, f)), b2=make_bias((n, 1, out)))
        if self.shared_experts:
            fs = self.shared_experts * f
            k1, k2, kg = jax.random.split(ks, 3)
            params.update(s1=make_weight(k1, (e, fs)),
                          s2=make_weight(k2, (fs, out)))
            if self.gated:
                params["sg"] = make_weight(kg, (e, fs))
            if self.has_bias:
                params.update(sb1=make_bias((fs,)), sb2=make_bias((out,)))
        state = {"route_bias": jnp.zeros((self.experts_total,),
                                         jnp.float32),
                 "expert_tokens": jnp.zeros((n,), jnp.int32),
                 "expert_overflows": jnp.zeros((), jnp.int32)}
        return params, state

    def route(self, p, state, x):
        """``(idx [T, k], w [T, k])`` the router gives tokens ``x``."""
        from ...parallel.expert import route_top_k
        return route_top_k(
            jnp.dot(x, p["router"], preferred_element_type=jnp.float32),
            self.top_k, scoring=self.scoring, bias=state.get("route_bias"),
            route_norm=self.route_norm, route_scale=self.route_scale)

    def apply(self, p, state, x, act):
        """``(y [T, n_out], new state)`` of tokens ``x [T, n_in]``: the
        shared experts' output and the held routed experts' part."""
        from ...observability.registry import default_registry
        from ...parallel.expert import (overflowed, pair_capacity,
                                        routed_ffn)
        first, n = self.held()
        pairs = x.shape[0] * self.top_k
        reg = default_registry()
        if reg.enabled:
            # trace-time, like scan_runs_traced_total
            labels = (str(n), str(self.experts_total), str(self.top_k))
            reg.counter("moe_layers_traced_total",
                        "Routed FFNs traced into a program, by the experts "
                        "held, the experts routed over and the experts a "
                        "token", ("held", "total", "top_k")).labels(
                            *labels).inc()
            reg.gauge("moe_pair_capacity",
                      "(token, slot) pairs the buffers of the routed FFN "
                      "last traced hold", ("held", "total", "top_k")).labels(
                          *labels).set(
                              pair_capacity(pairs, n, self.experts_total))
        routed = {k: p[k] for k in ("router", "w1", "w2", "wg", "b1", "b2")
                  if k in p}
        y, tokens = routed_ffn(
            routed, x, top_k=self.top_k, scoring=self.scoring,
            route_norm=self.route_norm, route_scale=self.route_scale,
            held=(first, n), bias=state.get("route_bias"), act=act)
        if self.shared_experts:
            with jax.named_scope("moe_shared"):
                up = x @ p["s1"]
                if self.has_bias:
                    up = up + p["sb1"]
                hidden = act(x @ p["sg"]) * up if self.gated else act(up)
                shared = hidden @ p["s2"]
                if self.has_bias:
                    shared = shared + p["sb2"]
            y = y + shared
        overflows = state.get("expert_overflows", jnp.zeros((), jnp.int32))
        new_state = {"expert_tokens": tokens,
                     "expert_overflows": overflows + overflowed(
                         tokens, pairs, self.experts_total).astype(jnp.int32)}
        if "route_bias" in state:
            new_state["route_bias"] = state["route_bias"]
        return y, new_state


def publish_expert_tokens(model) -> None:
    """Gauges ``moe_expert_tokens{layer, expert}`` and
    ``moe_overflow_steps{layer}`` from the state of every layer that
    threads ``expert_tokens``: called where ``fit`` has just read the loss,
    so the step that wrote them is done and the read waits for nothing."""
    from ...observability.registry import default_registry
    reg = default_registry()
    layers = {name: (st["expert_tokens"], st.get("expert_overflows", 0))
              for name, st in (getattr(model, "state", None) or {}).items()
              if isinstance(st, dict) and "expert_tokens" in st}
    if not layers or not reg.enabled:
        return
    gauge = reg.gauge("moe_expert_tokens",
                      "(token, slot) pairs each held expert took in the "
                      "last step", ("layer", "expert"))
    steps = reg.gauge("moe_overflow_steps",
                      "Steps so far whose routing sent the held experts more "
                      "pairs than moe_pair_capacity (each computed in full, "
                      "on whole-size buffers)", ("layer",))
    host = jax.device_get(layers)
    children = [(gauge.labels(name, str(i)), float(v))
                for name, (counts, _) in host.items()
                for i, v in enumerate(counts)]
    children += [(steps.labels(name), float(over))
                 for name, (_, over) in host.items()]
    for child, value in children:
        child.set(value)


@register_serde
@dataclass
class MixtureOfExpertsLayer(BaseLayerConf):
    """params: router [f, E], w1 [E, f, hidden], b1, w2 [E, hidden, n_out],
    b2.  With ``top_k = 0`` the top-1 capacity path: ``capacity_factor``
    sizes each expert's token budget as ``capacity_factor * tokens /
    n_experts`` and tokens over it are dropped.  With ``top_k > 0`` the
    one routed module, ``RoutedExperts``: ``n_experts`` routed over,
    ``top_k`` a token by ``scoring``, no token dropped, ``experts_held =
    (first, count)`` of them held here (w1, b1, w2, b2 then stack
    ``count``), ``shared_experts`` beside them (s1, sb1, s2, sb2)."""
    INPUT_KIND = "any"   # FF [b,f] and RNN [b,t,f] both handled natively
    AUX_LOSS = True

    n_in: int = 0
    n_out: int = 0
    n_experts: int = 4
    hidden: int = 0                 # defaults to 4 * n_in
    capacity_factor: float = 1.25
    aux_loss_weight: float = 0.01
    top_k: int = 0                  # 0: the top-1 capacity path
    scoring: str = "softmax"        # softmax|sigmoid
    route_norm: bool = False
    route_scale: float = 1.0
    shared_experts: int = 0
    experts_held: Optional[Tuple[int, int]] = None

    def _routed(self) -> RoutedExperts:
        return RoutedExperts(
            n_in=self.n_in, hidden=self.hidden or 4 * self.n_in,
            experts_total=self.n_experts, top_k=self.top_k,
            scoring=self.scoring, route_norm=self.route_norm,
            route_scale=self.route_scale,
            shared_experts=self.shared_experts,
            experts_held=(tuple(self.experts_held) if self.experts_held
                          else None), n_out=self.n_out)

    def set_n_in(self, itype: InputType, override: bool = False) -> None:
        if self.n_in == 0 or override:
            self.n_in = itype.size if itype.kind in ("ff", "rnn") else \
                itype.flat_size()

    def output_type(self, itype: InputType) -> InputType:
        if itype.kind == "rnn":
            return InputType.recurrent(self.n_out, itype.timesteps)
        return InputType.feed_forward(self.n_out)

    def init(self, key, itype):
        if self.n_in <= 0 or self.n_out <= 0:
            raise ValueError(
                f"layer '{self.name}': n_in/n_out unset — declare the "
                "network input type")
        if self.top_k > 0:
            params, state = self._routed().init(key, self.make_weight,
                                                self.make_bias)
            return {"params": params, "state": state}
        h = self.hidden or 4 * self.n_in
        kr, k1, k2 = jax.random.split(key, 3)
        params = {
            "router": self.make_weight(kr, (self.n_in, self.n_experts)),
            "w1": self.make_weight(k1, (self.n_experts, self.n_in, h)),
            "b1": self.make_bias((self.n_experts, 1, h)),
            "w2": self.make_weight(k2, (self.n_experts, h, self.n_out)),
            "b2": self.make_bias((self.n_experts, 1, self.n_out)),
        }
        return {"params": params,
                "state": {"aux_loss": jnp.zeros((), self._dtype())}}

    def apply(self, variables, x, *, train=False, key=None, mask=None):
        from ...parallel.expert import moe_ffn
        params = variables["params"]
        x = self.maybe_dropout_input(key, x, train)
        if x.ndim == 4:   # CNN [b,h,w,c] -> flat [b, h*w*c] (set_n_in used
            x = x.reshape(x.shape[0], -1)  # flat_size for cnn input types)
        shape = x.shape
        x2d = x.reshape(-1, shape[-1])
        if self.top_k > 0:
            y, new_state = self._routed().apply(
                params, variables.get("state", {}), x2d, self.act_fn)
            return y.reshape(shape[:-1] + (self.n_out,)), new_state
        t = x2d.shape[0]
        capacity = max(int(self.capacity_factor * t / self.n_experts), 1)
        y, aux = moe_ffn(params, x2d, capacity, act=self.act_fn)
        new_state = {"aux_loss": (self.aux_loss_weight * aux).astype(
            jnp.result_type(x))}
        return y.reshape(shape[:-1] + (self.n_out,)), new_state
