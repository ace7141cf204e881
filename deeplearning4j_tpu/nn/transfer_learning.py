"""Transfer learning — clone + modify trained nets.

Reference ``nn/transferlearning/TransferLearning.java:32`` (MLN Builder +
GraphBuilder), ``FineTuneConfiguration.java``, ``TransferLearningHelper.java``.
Functional-pytree twist: "copying params" is just re-keying array leaves into
the new net's tree; freezing is the FrozenLayer wrapper (stop_gradient +
optax.set_to_zero — see nn/layers/misc.py).

Compile-cache interaction: the builders deep-copy the source conf and apply
every edit (fine-tune overrides, nOutReplace, freezing) BEFORE constructing
the new network, so the edited topology signs differently and lands in its
own slot of the process-global trace cache (nn/compile_cache) — the source
net keeps its compiled programs.  Anyone mutating a LIVE net's conf/layer
confs directly must call ``net.invalidate_compile_cache()`` afterwards, or
the net keeps executing the pre-edit programs.
"""
from __future__ import annotations

import copy
from typing import Any, Dict, List, Optional

import jax
import jax.numpy as jnp

from .layers.base import INHERITED_DEFAULTS
from .layers.misc import FrozenLayer
from ._common import hyperparam_conf
from .multilayer import MultiLayerNetwork


def _copy_tree(t):
    return jax.tree_util.tree_map(lambda a: jnp.array(a), t)


def _apply_fine_tune(conf, layers, overrides: Dict[str, Any]):
    """FineTuneConfiguration semantics: overrides REPLACE existing values on
    the conf defaults and on every (non-frozen) layer."""
    for k, v in overrides.items():
        if k == "seed":
            conf.seed = int(v)
            continue
        if k not in INHERITED_DEFAULTS:
            raise ValueError(f"unknown fine-tune override '{k}'")
        conf.defaults[k] = v
        for lc in layers:
            if isinstance(lc, FrozenLayer):
                continue
            hc = hyperparam_conf(lc)
            if hc is not None and hasattr(hc, k):
                setattr(hc, k, v)


class TransferLearning:
    """Namespace matching the reference entry point."""

    class Builder:
        """MLN transfer-learning builder."""

        def __init__(self, net: MultiLayerNetwork):
            self._net = net
            if net.conf.loop:
                raise ValueError(
                    "transfer learning cannot edit a list with a looped "
                    "range: adding or removing layers moves its bounds")
            self._conf = copy.deepcopy(net.conf)
            # (new_layer_conf, old_index or None, needs_reinit)
            self._plan: List[List[Any]] = [
                [lc, i, False] for i, lc in enumerate(self._conf.layers)]
            self._fine_tune: Dict[str, Any] = {}
            self._frozen_until = -1

        def fine_tune_configuration(self, **overrides) -> "TransferLearning.Builder":
            self._fine_tune.update(overrides)
            return self

        def set_feature_extractor(self, layer_index: int) -> "TransferLearning.Builder":
            """Freeze layers 0..layer_index inclusive."""
            self._frozen_until = int(layer_index)
            return self

        def remove_output_layer(self) -> "TransferLearning.Builder":
            return self.remove_layers_from_output(1)

        def remove_layers_from_output(self, n: int) -> "TransferLearning.Builder":
            if n > len(self._plan):
                raise ValueError(f"cannot remove {n} of {len(self._plan)} layers")
            del self._plan[len(self._plan) - n:]
            return self

        def add_layer(self, layer_conf) -> "TransferLearning.Builder":
            self._plan.append([layer_conf, None, True])
            return self

        def n_out_replace(self, layer_index: int, n_out: int,
                          weight_init: Optional[str] = None
                          ) -> "TransferLearning.Builder":
            """Replace layer's n_out; it and the next layer re-initialize
            (reference nOutReplace)."""
            entry = self._plan[layer_index]
            lc = copy.deepcopy(entry[0])
            lc.n_out = int(n_out)
            if weight_init is not None:
                hc = hyperparam_conf(lc)
                if hc is not None:
                    hc.weight_init = weight_init
            self._plan[layer_index] = [lc, None, True]
            if layer_index + 1 < len(self._plan):
                nxt = self._plan[layer_index + 1]
                nlc = copy.deepcopy(nxt[0])
                if hasattr(nlc, "n_in"):
                    nlc.n_in = 0  # sentinel: re-infer from new upstream width
                self._plan[layer_index + 1] = [nlc, None, True]
            return self

        def build(self) -> MultiLayerNetwork:
            new_layers = []
            for i, (lc, old_idx, reinit) in enumerate(self._plan):
                if old_idx is not None and i <= self._frozen_until:
                    lc = FrozenLayer(underlying=lc, name=lc.name)
                new_layers.append(lc)
            conf = self._conf
            conf.layers = new_layers
            _apply_fine_tune(conf, new_layers, self._fine_tune)
            # drop auto-inserted preprocessors from the first structural
            # change onward — resolve() re-infers them for the new layout
            first_changed = len(self._plan)
            for i, (_, old_idx, reinit) in enumerate(self._plan):
                if old_idx is None or reinit:
                    first_changed = i
                    break
            conf.input_preprocessors = {
                k: v for k, v in conf.input_preprocessors.items()
                if int(k) < first_changed}
            conf.layer_input_types = []
            conf.resolve()
            net = MultiLayerNetwork(conf).init()
            # graft retained params over the fresh init
            for i, (lc, old_idx, reinit) in enumerate(self._plan):
                if old_idx is None or reinit:
                    continue
                net.params[f"layer_{i}"] = _copy_tree(
                    self._net.params[f"layer_{old_idx}"])
                net.state[f"layer_{i}"] = _copy_tree(
                    self._net.state[f"layer_{old_idx}"])
            # updater state was built for the fresh tree; rebuild so frozen
            # labels and shapes match the grafted params
            net.opt_state = net._tx.init(net.params)
            return net

    class GraphBuilder:
        """ComputationGraph transfer-learning builder."""

        def __init__(self, net):
            from .computation_graph import ComputationGraph
            self._net = net
            self._conf = copy.deepcopy(net.conf)
            self._fine_tune: Dict[str, Any] = {}
            self._frozen: set = set()
            self._reinit: set = set()
            self._removed: set = set()

        def fine_tune_configuration(self, **overrides) -> "TransferLearning.GraphBuilder":
            self._fine_tune.update(overrides)
            return self

        def set_feature_extractor(self, *vertex_names: str) -> "TransferLearning.GraphBuilder":
            """Freeze the named vertices and everything upstream of them."""
            conf = self._conf
            target = set(vertex_names)
            # walk upstream
            frontier = list(target)
            while frontier:
                v = frontier.pop()
                if v in self._frozen or v not in conf.vertices:
                    continue
                self._frozen.add(v)
                frontier.extend(conf.vertex_inputs.get(v, []))
            return self

        def remove_vertex_and_connections(self, name: str) -> "TransferLearning.GraphBuilder":
            conf = self._conf
            if name not in conf.vertices:
                raise ValueError(f"no vertex '{name}'")
            dead = {name}
            # drop downstream vertices that lose an input
            changed = True
            while changed:
                changed = False
                for v, ins in conf.vertex_inputs.items():
                    if v not in dead and any(s in dead for s in ins):
                        dead.add(v)
                        changed = True
            for v in dead:
                conf.vertices.pop(v, None)
                conf.vertex_inputs.pop(v, None)
                self._removed.add(v)
            conf.network_outputs = [o for o in conf.network_outputs
                                    if o not in dead]
            return self

        def add_layer(self, name: str, layer, *inputs: str) -> "TransferLearning.GraphBuilder":
            from .conf.computation_graph import LayerVertex
            if layer.name is None:
                layer.name = name
            return self.add_vertex(name, LayerVertex(layer=layer), *inputs)

        def add_vertex(self, name: str, vertex, *inputs: str) -> "TransferLearning.GraphBuilder":
            conf = self._conf
            if name in conf.vertices:
                raise ValueError(f"duplicate vertex '{name}'")
            conf.vertices[name] = vertex
            conf.vertex_inputs[name] = list(inputs)
            self._reinit.add(name)
            return self

        def set_outputs(self, *names: str) -> "TransferLearning.GraphBuilder":
            self._conf.network_outputs = list(names)
            return self

        def build(self):
            from .computation_graph import ComputationGraph
            from .conf.computation_graph import LayerVertex
            conf = self._conf
            for name in self._frozen:
                v = conf.vertices.get(name)
                if isinstance(v, LayerVertex) and not isinstance(v.layer, FrozenLayer):
                    v.layer = FrozenLayer(underlying=v.layer, name=v.layer.name)
            layers = [v.layer for v in conf.vertices.values()
                      if isinstance(v, LayerVertex)]
            _apply_fine_tune(conf, layers, self._fine_tune)
            conf.topological_order = []
            conf.vertex_input_types = {}
            conf.resolve()
            net = ComputationGraph(conf).init()
            for name in conf.vertices:
                if name in self._reinit or name in self._removed:
                    continue
                if name in self._net.params:
                    net.params[name] = _copy_tree(self._net.params[name])
                    net.state[name] = _copy_tree(self._net.state[name])
            net.opt_state = net._tx.init(net.params)
            return net


class TransferLearningHelper:
    """Featurization helper (reference ``TransferLearningHelper.java``):
    run inputs through the frozen front of a net once, train only the tail on
    the cached features."""

    def __init__(self, net: MultiLayerNetwork, frozen_until: Optional[int] = None):
        if frozen_until is None:
            frozen_until = -1
            for i, lc in enumerate(net.conf.layers):
                if isinstance(lc, FrozenLayer):
                    frozen_until = i
        self.net = net
        self.frozen_until = frozen_until

    def featurize(self, x):
        """Activations at the frozen boundary."""
        acts, _ = self.net._forward(self.net.params, self.net.state,
                                    jnp.asarray(x), train=False, key=None,
                                    to_layer=self.frozen_until + 1)
        return acts

    def fit_featurized(self, features, labels, epochs: int = 1):
        """Train the unfrozen tail directly on featurized data: the frozen
        front is skipped entirely (the reference's point — no wasted fwd
        passes through frozen layers)."""
        import numpy as np
        from .conf.multi_layer import MultiLayerConfiguration
        k = self.frozen_until + 1
        tail_confs = [copy.deepcopy(
            lc.underlying if isinstance(lc, FrozenLayer) else lc)
            for lc in self.net.conf.layers[k:]]
        tail_conf = MultiLayerConfiguration(
            layers=tail_confs, defaults=dict(self.net.conf.defaults),
            seed=self.net.conf.seed)
        tail_conf.resolve()
        tail = MultiLayerNetwork(tail_conf).init()
        for j in range(len(tail_confs)):
            tail.params[f"layer_{j}"] = _copy_tree(
                self.net.params[f"layer_{k + j}"])
            tail.state[f"layer_{j}"] = _copy_tree(
                self.net.state[f"layer_{k + j}"])
        tail.opt_state = tail._tx.init(tail.params)
        tail.fit(features, labels, epochs=epochs)
        for j in range(len(tail_confs)):
            self.net.params[f"layer_{k + j}"] = tail.params[f"layer_{j}"]
            self.net.state[f"layer_{k + j}"] = tail.state[f"layer_{j}"]
        return self.net
