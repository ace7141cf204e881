"""ComputationGraph configuration: vertices + fluent GraphBuilder.

TPU-native analogue of ``nn/conf/ComputationGraphConfiguration.java:59`` and
the vertex configs in ``nn/conf/graph/`` (ElementWiseVertex, MergeVertex,
SubsetVertex, StackVertex/UnstackVertex, ScaleVertex/ShiftVertex,
L2NormalizeVertex, L2Vertex, ReshapeVertex, PreprocessorVertex, PoolHelper,
plus the rnn vertices ``nn/conf/graph/rnn/LastTimeStepVertex`` and
``DuplicateToTimeSeriesVertex``), and two rnn vertices the reference has
no need of, ``TimeSliceVertex`` and ``TimeConcatVertex``: a range of a
sequence's steps, and sequences laid end to end (one embedding and one
output layer serving two streams of one row of tokens).

Design: the graph is data — a dict of named vertex configs plus an input-name
map.  Topological order and all shapes (InputTypes) are resolved at
configuration time, so the runtime trace is a static unrolled DAG that XLA
sees as one fused program (the reference instead walks the topological order
per-call in Java, ``nn/graph/ComputationGraph.java:1191``).

Every vertex is a pure function ``apply(variables, inputs, ...)`` — no
in-place epsilon accumulation; fan-in gradients are summed by jax.grad
automatically (the reference hand-accumulates epsilons at fan-in vertices).
"""
from __future__ import annotations

import copy
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from ...utils import serde
from ...utils.serde import register_serde
from .input_type import InputType
from .multi_layer import _auto_preprocessor
from .preprocessors import InputPreProcessor
from ..layers.base import BaseLayerConf, LayerConf

Array = jax.Array


# ---------------------------------------------------------------------------
# vertex configs
# ---------------------------------------------------------------------------

@dataclass
class GraphVertexConf:
    """Base vertex (reference ``nn/conf/graph/GraphVertex.java``)."""

    def n_inputs(self) -> Tuple[int, int]:
        """(min, max) accepted input count; max=-1 means unbounded."""
        return (1, 1)

    def output_type(self, itypes: List[InputType]) -> InputType:
        return itypes[0]

    def has_params(self) -> bool:
        return False

    def init(self, key, itypes: List[InputType]) -> Dict[str, Any]:
        return {"params": {}, "state": {}}

    def apply(self, variables, inputs: List[Array], *, train=False, key=None,
              masks: Optional[List[Optional[Array]]] = None
              ) -> Tuple[Array, Dict[str, Array]]:
        raise NotImplementedError

    def feed_forward_mask(self, masks: List[Optional[Array]],
                          inputs: Optional[List[Array]] = None
                          ) -> Optional[Array]:
        """Propagate masks; ``inputs`` are the runtime input activations (for
        vertices whose mask shape depends on input shapes)."""
        for m in masks:
            if m is not None:
                return m
        return None

    def regularization_score(self, params) -> Array:
        return jnp.zeros((), jnp.float32)


@register_serde
@dataclass
class LayerVertex(GraphVertexConf):
    """Wraps a LayerConf (reference ``nn/conf/graph/LayerVertex.java``)."""
    layer: LayerConf = None
    preprocessor: Optional[InputPreProcessor] = None

    def output_type(self, itypes):
        it = itypes[0]
        if self.preprocessor is not None:
            it = self.preprocessor.output_type(it)
        return self.layer.output_type(it)

    def has_params(self) -> bool:
        return self.layer.has_params()

    def init(self, key, itypes):
        it = itypes[0]
        if self.preprocessor is not None:
            it = self.preprocessor.output_type(it)
        return self.layer.init(key, it)

    def apply(self, variables, inputs, *, train=False, key=None, masks=None):
        x = inputs[0]
        mask = masks[0] if masks else None
        if self.preprocessor is not None:
            x = self.preprocessor.pre_process(x, mask)
            if mask is not None:
                mask = self.preprocessor.feed_forward_mask(mask, None)
        return self.layer.apply(variables, x, train=train, key=key, mask=mask)

    def compute_loss(self, variables, x, labels, *, train=False, key=None,
                     mask=None):
        if self.preprocessor is not None:
            x = self.preprocessor.pre_process(x, mask)
            if mask is not None:
                mask = self.preprocessor.feed_forward_mask(mask, None)
        return self.layer.compute_loss(variables, x, labels, train=train,
                                       key=key, mask=mask)

    def feed_forward_mask(self, masks, inputs=None):
        mask = masks[0] if masks else None
        if mask is not None and self.preprocessor is not None:
            mask = self.preprocessor.feed_forward_mask(mask, None)
        if mask is not None:
            mask = self.layer.feed_forward_mask(mask, None)
        return mask

    def regularization_score(self, params) -> Array:
        return self.layer.regularization_score(params)


@register_serde
@dataclass
class ElementWiseVertex(GraphVertexConf):
    """Pointwise combine: Add/Subtract/Product/Average/Max
    (reference ``nn/conf/graph/ElementWiseVertex.java``)."""
    op: str = "add"

    def n_inputs(self):
        return (2, 2) if self.op == "subtract" else (2, -1)

    def apply(self, variables, inputs, *, train=False, key=None, masks=None):
        op = self.op.lower()
        out = inputs[0]
        if op == "add":
            for x in inputs[1:]:
                out = out + x
        elif op == "subtract":
            out = inputs[0] - inputs[1]
        elif op == "product":
            for x in inputs[1:]:
                out = out * x
        elif op == "average":
            out = sum(inputs) / len(inputs)
        elif op == "max":
            for x in inputs[1:]:
                out = jnp.maximum(out, x)
        else:
            raise ValueError(f"unknown elementwise op '{self.op}'")
        return out, variables.get("state", {})


@register_serde
@dataclass
class MergeVertex(GraphVertexConf):
    """Concatenate along the feature axis — last axis for FF/RNN/CNN(NHWC)
    (reference ``nn/conf/graph/MergeVertex.java`` concatenates dim 1 in NCHW;
    NHWC's channel-minor layout makes that the last axis here)."""

    def n_inputs(self):
        return (1, -1)

    def output_type(self, itypes):
        first = itypes[0]
        if first.kind == "ff":
            return InputType.feed_forward(sum(t.size for t in itypes))
        if first.kind == "rnn":
            return InputType.recurrent(sum(t.size for t in itypes), first.timesteps)
        if first.kind == "cnn":
            return InputType.convolutional(first.height, first.width,
                                           sum(t.channels for t in itypes))
        raise ValueError(f"MergeVertex: unsupported input kind {first.kind}")

    def apply(self, variables, inputs, *, train=False, key=None, masks=None):
        return jnp.concatenate(inputs, axis=-1), variables.get("state", {})


@register_serde
@dataclass
class SubsetVertex(GraphVertexConf):
    """Feature-range slice [from, to] inclusive
    (reference ``nn/conf/graph/SubsetVertex.java``)."""
    from_idx: int = 0
    to_idx: int = 0

    def output_type(self, itypes):
        n = self.to_idx - self.from_idx + 1
        t = itypes[0]
        if t.kind == "ff":
            return InputType.feed_forward(n)
        if t.kind == "rnn":
            return InputType.recurrent(n, t.timesteps)
        if t.kind == "cnn":
            return InputType.convolutional(t.height, t.width, n)
        raise ValueError(t.kind)

    def apply(self, variables, inputs, *, train=False, key=None, masks=None):
        x = inputs[0]
        return (jax.lax.slice_in_dim(x, self.from_idx, self.to_idx + 1, axis=x.ndim - 1),
                variables.get("state", {}))


@register_serde
@dataclass
class StackVertex(GraphVertexConf):
    """Concatenate along the BATCH axis (reference ``StackVertex.java`` —
    used for sharing one layer across several inputs)."""

    def n_inputs(self):
        return (1, -1)

    def output_type(self, itypes):
        return itypes[0]

    def apply(self, variables, inputs, *, train=False, key=None, masks=None):
        return jnp.concatenate(inputs, axis=0), variables.get("state", {})

    def feed_forward_mask(self, masks, inputs=None):
        if all(m is None for m in masks):
            return None
        # unmasked inputs contribute all-ones (reference semantics): dropping
        # the combined mask would silently unmask the padded inputs
        proto = next(m for m in masks if m is not None)
        out = []
        for i, m in enumerate(masks):
            if m is None:
                if inputs is None:
                    raise ValueError(
                        "StackVertex: mixed masked/unmasked inputs need "
                        "runtime shapes to synthesize all-ones masks")
                out.append(jnp.ones((inputs[i].shape[0],) + proto.shape[1:],
                                    proto.dtype))
            else:
                out.append(m)
        return jnp.concatenate(out, axis=0)


@register_serde
@dataclass
class UnstackVertex(GraphVertexConf):
    """Inverse of StackVertex: take batch-slab ``from_idx`` of ``stack_size``
    equal slabs (reference ``UnstackVertex.java``)."""
    from_idx: int = 0
    stack_size: int = 1

    def apply(self, variables, inputs, *, train=False, key=None, masks=None):
        x = inputs[0]
        step = x.shape[0] // self.stack_size
        return (jax.lax.slice_in_dim(x, self.from_idx * step,
                                     (self.from_idx + 1) * step, axis=0),
                variables.get("state", {}))

    def feed_forward_mask(self, masks, inputs=None):
        m = masks[0] if masks else None
        if m is None:
            return None
        step = m.shape[0] // self.stack_size
        return jax.lax.slice_in_dim(m, self.from_idx * step,
                                    (self.from_idx + 1) * step, axis=0)


@register_serde
@dataclass
class ScaleVertex(GraphVertexConf):
    """Multiply by a fixed scalar (reference ``ScaleVertex.java``)."""
    scale_factor: float = 1.0

    def apply(self, variables, inputs, *, train=False, key=None, masks=None):
        return inputs[0] * self.scale_factor, variables.get("state", {})


@register_serde
@dataclass
class ShiftVertex(GraphVertexConf):
    """Add a fixed scalar (reference ``ShiftVertex.java``)."""
    shift_factor: float = 0.0

    def apply(self, variables, inputs, *, train=False, key=None, masks=None):
        return inputs[0] + self.shift_factor, variables.get("state", {})


@register_serde
@dataclass
class L2NormalizeVertex(GraphVertexConf):
    """x / ||x||_2 per example (reference ``L2NormalizeVertex.java``)."""
    eps: float = 1e-8

    def apply(self, variables, inputs, *, train=False, key=None, masks=None):
        x = inputs[0]
        axes = tuple(range(1, x.ndim))
        norm = jnp.sqrt(jnp.sum(x * x, axis=axes, keepdims=True))
        return x / (norm + self.eps), variables.get("state", {})


@register_serde
@dataclass
class L2Vertex(GraphVertexConf):
    """Pairwise L2 distance between two activations → [batch, 1]
    (reference ``L2Vertex.java``)."""
    eps: float = 1e-8

    def n_inputs(self):
        return (2, 2)

    def output_type(self, itypes):
        return InputType.feed_forward(1)

    def apply(self, variables, inputs, *, train=False, key=None, masks=None):
        a = inputs[0].reshape(inputs[0].shape[0], -1)
        b = inputs[1].reshape(inputs[1].shape[0], -1)
        d = a - b
        # eps inside sqrt keeps the gradient finite at d == 0
        out = jnp.sqrt(jnp.sum(d * d, axis=1, keepdims=True) + self.eps)
        return out, variables.get("state", {})


@register_serde
@dataclass
class ReshapeVertex(GraphVertexConf):
    """Reshape per example; shape excludes batch dim
    (reference ``ReshapeVertex.java``)."""
    shape: List[int] = field(default_factory=list)

    def output_type(self, itypes):
        s = self.shape
        if len(s) == 1:
            return InputType.feed_forward(s[0])
        if len(s) == 2:
            return InputType.recurrent(s[1], s[0])
        if len(s) == 3:
            return InputType.convolutional(s[0], s[1], s[2])
        raise ValueError(f"ReshapeVertex: bad shape {s}")

    def apply(self, variables, inputs, *, train=False, key=None, masks=None):
        x = inputs[0]
        return x.reshape((x.shape[0],) + tuple(self.shape)), variables.get("state", {})


@register_serde
@dataclass
class PreprocessorVertex(GraphVertexConf):
    """Standalone InputPreProcessor as a vertex (reference
    ``PreprocessorVertex.java``)."""
    preprocessor: InputPreProcessor = None

    def output_type(self, itypes):
        return self.preprocessor.output_type(itypes[0])

    def apply(self, variables, inputs, *, train=False, key=None, masks=None):
        mask = masks[0] if masks else None
        return self.preprocessor.pre_process(inputs[0], mask), variables.get("state", {})


@register_serde
@dataclass
class PoolHelperVertex(GraphVertexConf):
    """Strip first row+column of a CNN activation (reference
    ``PoolHelperVertex.java`` — compatibility shim for imported GoogLeNet)."""

    def output_type(self, itypes):
        t = itypes[0]
        return InputType.convolutional(t.height - 1, t.width - 1, t.channels)

    def apply(self, variables, inputs, *, train=False, key=None, masks=None):
        return inputs[0][:, 1:, 1:, :], variables.get("state", {})


@register_serde
@dataclass
class LastTimeStepVertex(GraphVertexConf):
    """RNN [b,t,f] → FF [b,f] at the last *unmasked* step (reference
    ``nn/conf/graph/rnn/LastTimeStepVertex.java``).  ``mask_input`` names the
    network input whose mask determines sequence lengths."""
    mask_input: Optional[str] = None

    def output_type(self, itypes):
        t = itypes[0]
        return InputType.feed_forward(t.size)

    def apply(self, variables, inputs, *, train=False, key=None, masks=None):
        x = inputs[0]
        mask = masks[0] if masks else None
        if mask is None:
            out = x[:, -1, :]
        else:
            # index of last nonzero mask entry per example
            idx = x.shape[1] - 1 - jnp.argmax(mask[:, ::-1], axis=1)
            out = jax.vmap(lambda seq, i: seq[i])(x, idx.astype(jnp.int32))
        return out, variables.get("state", {})

    def feed_forward_mask(self, masks, inputs=None):
        return None  # time axis consumed


@register_serde
@dataclass
class DuplicateToTimeSeriesVertex(GraphVertexConf):
    """FF [b,f] → RNN [b,t,f] by repetition; t taken from the named network
    input (reference ``rnn/DuplicateToTimeSeriesVertex.java``)."""
    ts_input: str = ""
    timesteps: int = -1  # resolved from ts_input's InputType at build time

    def n_inputs(self):
        # optional second input: the time-series whose length to copy (kept
        # as a real graph edge so the shape is dynamic-batch-safe)
        return (1, 2)

    def output_type(self, itypes):
        t = itypes[0]
        return InputType.recurrent(t.size, self.timesteps)

    def apply(self, variables, inputs, *, train=False, key=None, masks=None):
        x = inputs[0]      # [b, f]
        t = inputs[1].shape[1] if len(inputs) > 1 else self.timesteps
        if t is None or t < 0:
            raise ValueError(
                "DuplicateToTimeSeriesVertex needs static timesteps or the "
                "ts_input wired as a second graph input")
        return jnp.repeat(x[:, None, :], t, axis=1), variables.get("state", {})


@register_serde
@dataclass
class TimeSliceVertex(GraphVertexConf):
    """Steps ``start`` up to (not including) ``stop`` of a sequence, as a
    Python slice reads them (``stop`` ``None``: to the end; negative: from
    the end): RNN ``[b, t, f]`` -> ``[b, t', f]``.  The mask is cut the
    same way."""
    start: int = 0
    stop: Optional[int] = None

    def _cut(self, x):
        return x[:, self.start:self.stop]

    def output_type(self, itypes):
        t = itypes[0]
        steps = t.timesteps
        if steps is not None and steps > 0:
            steps = len(range(steps)[self.start:self.stop])
        return InputType.recurrent(t.size, steps)

    def apply(self, variables, inputs, *, train=False, key=None, masks=None):
        return self._cut(inputs[0]), variables.get("state", {})

    def feed_forward_mask(self, masks, inputs=None):
        m = masks[0] if masks else None
        return None if m is None else self._cut(m)


@register_serde
@dataclass
class TimeConcatVertex(GraphVertexConf):
    """Sequences of one width laid end to end along TIME: ``[b, t1, f]``,
    ``[b, t2, f]``, ... -> ``[b, t1 + t2 + ..., f]``, so that one layer
    (an output layer, say) serves several streams in one call.  An input
    without a mask counts as all ones where another brings one."""

    def n_inputs(self):
        return (1, -1)

    def output_type(self, itypes):
        steps = [t.timesteps for t in itypes]
        known = all(s is not None and s > 0 for s in steps)
        return InputType.recurrent(itypes[0].size,
                                   sum(steps) if known else -1)

    def apply(self, variables, inputs, *, train=False, key=None, masks=None):
        return jnp.concatenate(inputs, axis=1), variables.get("state", {})

    def feed_forward_mask(self, masks, inputs=None):
        if all(m is None for m in masks):
            return None
        if inputs is None:
            raise ValueError("TimeConcatVertex: mixed masked/unmasked "
                             "inputs need runtime shapes")
        proto = next(m for m in masks if m is not None)
        return jnp.concatenate(
            [jnp.ones(x.shape[:2], proto.dtype) if m is None else m
             for m, x in zip(masks, inputs)], axis=1)


# ---------------------------------------------------------------------------
# configuration + builder
# ---------------------------------------------------------------------------

@register_serde
@dataclass
class ComputationGraphConfiguration:
    """The graph as data (reference ``ComputationGraphConfiguration.java:59``)."""
    vertices: Dict[str, GraphVertexConf] = field(default_factory=dict)
    vertex_inputs: Dict[str, List[str]] = field(default_factory=dict)
    network_inputs: List[str] = field(default_factory=list)
    network_outputs: List[str] = field(default_factory=list)
    input_types: List[Optional[InputType]] = field(default_factory=list)
    backprop_type: str = "standard"
    tbptt_fwd_length: int = 20
    tbptt_back_length: int = 20
    defaults: Dict[str, Any] = field(default_factory=dict)
    seed: int = 12345
    # vertex -> the name scope its operations are traced under, outside the
    # scope of its class: a module made of several vertices reads as one
    vertex_scopes: Dict[str, str] = field(default_factory=dict)
    # resolved:
    topological_order: List[str] = field(default_factory=list)
    vertex_input_types: Dict[str, List[Any]] = field(default_factory=dict)

    # ---- serde ----
    def to_json(self) -> str:
        return serde.to_json(self)

    @staticmethod
    def from_json(s: str) -> "ComputationGraphConfiguration":
        conf = serde.from_json(s)
        assert isinstance(conf, ComputationGraphConfiguration)
        return conf

    def to_yaml(self) -> str:
        return serde.to_yaml(self)

    @staticmethod
    def from_yaml(s: str) -> "ComputationGraphConfiguration":
        return serde.from_yaml(s)

    # ---- resolution ----
    def topo_sort(self) -> List[str]:
        """Kahn's algorithm (reference topologicalSortOrder :1191)."""
        indeg = {}
        children: Dict[str, List[str]] = {}
        for name, ins in self.vertex_inputs.items():
            indeg[name] = 0
            for src in ins:
                if src in self.vertices:
                    indeg[name] += 1
                    children.setdefault(src, []).append(name)
                elif src not in self.network_inputs:
                    raise ValueError(
                        f"vertex '{name}' input '{src}' is neither a vertex "
                        "nor a network input")
        ready = sorted(n for n, d in indeg.items() if d == 0)
        order = []
        while ready:
            n = ready.pop(0)
            order.append(n)
            for c in children.get(n, []):
                indeg[c] -= 1
                if indeg[c] == 0:
                    ready.append(c)
        if len(order) != len(self.vertices):
            cyc = set(self.vertices) - set(order)
            raise ValueError(f"graph has a cycle involving {sorted(cyc)}")
        return order

    def resolve(self) -> None:
        for name in self.network_outputs:
            if name not in self.vertices:
                raise ValueError(f"network output '{name}' is not a vertex")
        from .multi_layer import validate_layer_names
        for v in self.vertices.values():
            lc = getattr(v, "layer", None)
            # duck-typed: wrapper layers delegate to the layer they wrap
            if hasattr(lc, "apply_global_defaults"):
                lc.apply_global_defaults(self.defaults)
            validate_layer_names(lc)
        self.topological_order = self.topo_sort()

        # input types per network input
        it_by_name: Dict[str, Optional[InputType]] = {}
        for i, n in enumerate(self.network_inputs):
            it_by_name[n] = (self.input_types[i]
                             if i < len(self.input_types) else None)

        self.vertex_input_types = {}
        for name in self.topological_order:
            v = self.vertices[name]
            ins = self.vertex_inputs[name]
            itypes = [it_by_name.get(src) for src in ins]
            lo, hi = v.n_inputs()
            if len(ins) < lo or (hi != -1 and len(ins) > hi):
                raise ValueError(
                    f"vertex '{name}' takes {lo}..{'∞' if hi == -1 else hi} "
                    f"inputs, got {len(ins)}")
            if all(t is not None for t in itypes):
                if isinstance(v, LayerVertex):
                    if v.preprocessor is None:
                        v.preprocessor = _auto_preprocessor(itypes[0], v.layer)
                    it = itypes[0]
                    if v.preprocessor is not None:
                        it = v.preprocessor.output_type(it)
                    v.layer.set_n_in(it, override=False)
                if isinstance(v, DuplicateToTimeSeriesVertex):
                    ref = it_by_name.get(v.ts_input)
                    if ref is not None:
                        v.timesteps = ref.timesteps
                self.vertex_input_types[name] = itypes
                it_by_name[name] = v.output_type(itypes)
            else:
                self.vertex_input_types[name] = itypes
                it_by_name[name] = None

    def vertex_output_type(self, name: str) -> Optional[InputType]:
        itypes = self.vertex_input_types.get(name)
        if itypes is None or any(t is None for t in itypes):
            return None
        return self.vertices[name].output_type(itypes)


class GraphBuilder:
    """Fluent builder (reference ComputationGraphConfiguration.GraphBuilder)."""

    def __init__(self, defaults: Dict[str, Any] = None, seed: int = 12345):
        self._defaults = dict(defaults or {})
        self._seed = seed
        self._vertices: Dict[str, GraphVertexConf] = {}
        self._vertex_inputs: Dict[str, List[str]] = {}
        self._inputs: List[str] = []
        self._outputs: List[str] = []
        self._input_types: List[Optional[InputType]] = []
        self._backprop_type = "standard"
        self._tbptt_fwd = 20
        self._tbptt_back = 20
        self._scopes: Dict[str, str] = {}

    def add_inputs(self, *names: str) -> "GraphBuilder":
        self._inputs.extend(names)
        return self

    def set_input_types(self, *itypes: InputType) -> "GraphBuilder":
        self._input_types = list(itypes)
        return self

    def add_layer(self, name: str, layer: LayerConf, *inputs: str,
                  preprocessor: Optional[InputPreProcessor] = None,
                  scope: Optional[str] = None) -> "GraphBuilder":
        if layer.name is None:
            layer.name = name
        return self.add_vertex(name, LayerVertex(layer=layer,
                                                 preprocessor=preprocessor),
                               *inputs, scope=scope)

    def add_vertex(self, name: str, vertex: GraphVertexConf, *inputs: str,
                   scope: Optional[str] = None) -> "GraphBuilder":
        """``scope`` names the ``jax.named_scope`` the vertex is traced
        under (outside its class's own): vertices that share one read as
        one module in a profile."""
        if name in self._vertices:
            raise ValueError(f"duplicate vertex name '{name}'")
        if not inputs:
            raise ValueError(f"vertex '{name}' needs at least one input")
        self._vertices[name] = vertex
        self._vertex_inputs[name] = list(inputs)
        if scope:
            self._scopes[name] = scope
        return self

    def set_outputs(self, *names: str) -> "GraphBuilder":
        self._outputs = list(names)
        return self

    def backprop_type(self, t: str, fwd: int = 20, back: int = 20) -> "GraphBuilder":
        self._backprop_type = t
        self._tbptt_fwd = fwd
        self._tbptt_back = back
        return self

    def build(self) -> ComputationGraphConfiguration:
        conf = ComputationGraphConfiguration(
            vertices=self._vertices,
            vertex_inputs=self._vertex_inputs,
            network_inputs=self._inputs,
            network_outputs=self._outputs,
            input_types=self._input_types,
            backprop_type=self._backprop_type,
            tbptt_fwd_length=self._tbptt_fwd,
            tbptt_back_length=self._tbptt_back,
            defaults=dict(self._defaults),
            seed=self._seed,
            vertex_scopes=dict(self._scopes),
        )
        conf.resolve()
        return conf
