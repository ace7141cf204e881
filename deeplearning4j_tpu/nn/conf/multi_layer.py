"""Network configuration: global defaults + sequential layer stack.

Analogue of ``nn/conf/NeuralNetConfiguration.java:78`` (Builder + ListBuilder)
and ``nn/conf/MultiLayerConfiguration.java:55``.  The builder resolves, at
configuration time: global-default inheritance into each layer, static shape
inference via InputType, automatic preprocessor insertion between layer
families, and n_in inference — all before a single array exists, exactly as
the reference does, which also guarantees jit-compatible static shapes.

JSON/YAML round-trip via utils.serde mirrors ``toJson/fromJson``
(``MultiLayerConfiguration.java:120,138``).
"""
from __future__ import annotations

import copy
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional

from ...utils import serde
from ...utils.serde import register_serde
from .input_type import InputType
from .preprocessors import (CnnFlatToCnnPreProcessor, CnnToFeedForwardPreProcessor,
                            CnnToRnnPreProcessor, FeedForwardToRnnPreProcessor,
                            InputPreProcessor, RnnToCnnPreProcessor,
                            RnnToFeedForwardPreProcessor)
from ..layers.base import BaseLayerConf, LayerConf


def validate_layer_names(lc, _seen: Optional[set] = None) -> None:
    """Fail at CONFIG time on unknown activation/loss names, not at the
    first fit() (the reference validates configs up front —
    ``nn/conf/layers/LayerValidation.java``).  Recurses through wrapper
    layers (Bidirectional ``fwd``, Frozen/LastTimeStep ``underlying``,
    graph LayerVertex ``layer``) to any depth; a visited-id set guards
    against config cycles."""
    if lc is None:
        return
    if _seen is None:
        _seen = set()
    if id(lc) in _seen:
        return
    _seen.add(id(lc))
    from ..activations import get as _get_act
    from ..losses import get as _get_loss
    act = getattr(lc, "activation", None)
    if isinstance(act, str):
        _get_act(act)
    loss = getattr(lc, "loss", None)
    if isinstance(loss, str):
        _get_loss(loss)
    for attr in ("fwd", "underlying", "layer"):
        inner = getattr(lc, attr, None)
        if inner is not lc and isinstance(inner, LayerConf):
            validate_layer_names(inner, _seen)


def _auto_preprocessor(prev: InputType, layer: LayerConf) -> Optional[InputPreProcessor]:
    """Insert a reshape adapter when layer families change
    (reference ``nn/conf/layers/InputTypeUtil.java`` + per-layer
    getPreProcessorForInputType)."""
    want = getattr(layer, "INPUT_KIND", "any")
    if want == "any" or prev.kind == want:
        return None
    if want == "ff":
        if prev.kind == "cnn":
            return CnnToFeedForwardPreProcessor(prev.height, prev.width, prev.channels)
        if prev.kind == "cnnflat":
            return None  # already flat
        if prev.kind == "rnn":
            return RnnToFeedForwardPreProcessor()
    elif want == "cnn":
        if prev.kind == "cnnflat":
            return CnnFlatToCnnPreProcessor(prev.height, prev.width, prev.channels)
        if prev.kind == "ff":
            raise ValueError(
                f"cannot infer CNN dims from FF input for layer '{layer.name}'; "
                "add an explicit FeedForwardToCnnPreProcessor")
    elif want == "rnn":
        if prev.kind == "ff":
            return FeedForwardToRnnPreProcessor()
        if prev.kind == "cnn":
            return CnnToRnnPreProcessor(prev.height, prev.width, prev.channels)
    raise ValueError(
        f"no automatic preprocessor from {prev.kind} input to '{want}' layer "
        f"'{layer.name}'")


@register_serde
@dataclass
class MultiLayerConfiguration:
    layers: List[LayerConf] = field(default_factory=list)
    input_type: Optional[InputType] = None
    # int-keyed dict serializes with str keys in json; normalize on access
    input_preprocessors: Dict[str, InputPreProcessor] = field(default_factory=dict)
    backprop_type: str = "standard"           # "standard" | "tbptt"
    tbptt_fwd_length: int = 20
    tbptt_back_length: int = 20
    defaults: Dict[str, Any] = field(default_factory=dict)
    seed: int = 12345
    # ``[first, stop, passes]``: the layers ``first .. stop - 1`` are walked
    # ``passes`` times on their one set of parameters, each pass reading the
    # last one's output; the passes' outputs go on joined in time
    # (``ListBuilder.loop``)
    loop: Optional[List[int]] = None
    # resolved by build():
    layer_input_types: List[InputType] = field(default_factory=list)

    # ---- serde --------------------------------------------------------------
    def to_json(self) -> str:
        return serde.to_json(self)

    @staticmethod
    def from_json(s: str) -> "MultiLayerConfiguration":
        conf = serde.from_json(s)
        assert isinstance(conf, MultiLayerConfiguration)
        return conf

    def to_yaml(self) -> str:
        return serde.to_yaml(self)

    @staticmethod
    def from_yaml(s: str) -> "MultiLayerConfiguration":
        return serde.from_yaml(s)

    # ---- shape resolution ---------------------------------------------------
    def preprocessor(self, i: int) -> Optional[InputPreProcessor]:
        return self.input_preprocessors.get(str(i))

    def looped(self) -> Optional[tuple]:
        """``(first, stop, passes)`` of the looped range, or ``None`` where
        the list is walked once (one pass is no loop)."""
        if not self.loop or int(self.loop[2]) == 1:
            return None
        return tuple(int(v) for v in self.loop)

    def _check_loop(self) -> None:
        """What a looped range cannot hold is refused here, by name."""
        first, stop, passes = (int(v) for v in self.loop)
        n = len(self.layers)
        if not (0 <= first < stop < n and passes >= 1):
            raise ValueError(
                f"loop({first}, {stop}, {passes}): the range has to lie "
                f"inside the {n} layers and before the last (the passes' "
                "outputs need a layer to go to), with at least one pass")
        if self.backprop_type == "tbptt":
            raise ValueError(
                "a looped range cannot train by truncated BPTT: a recurrent "
                "carry a pass is not written; use backprop_type 'standard'")
        for lc in self.layers[first:stop]:
            if getattr(lc, "AUX_LOSS", False):
                raise ValueError(
                    f"layer '{lc.name}' threads an auxiliary loss "
                    "(AUX_LOSS) and cannot lie in a looped range: one "
                    "state entry cannot hold a term a pass")

    def resolve(self) -> None:
        """Apply defaults, insert preprocessors, infer n_in, record itypes."""
        for lc in self.layers:
            # duck-typed: wrappers (Bidirectional, LastTimeStep, Frozen)
            # delegate defaults to the layer they wrap
            if hasattr(lc, "apply_global_defaults"):
                lc.apply_global_defaults(self.defaults)
            validate_layer_names(lc)
        if self.loop:
            self._check_loop()
        first, stop, passes = self.looped() or (None, None, 1)
        self.layer_input_types = []
        itype = self.input_type
        for i, lc in enumerate(self.layers):
            if i == stop and itype is not None:
                # the passes' outputs, joined in time
                before = self.layer_input_types[first]
                if before.kind != "rnn" or itype != before:
                    raise ValueError(
                        f"loop({first}, {stop}, {passes}): a pass has to "
                        "hand the next what it took, [batch, time, "
                        f"features]; the range takes {before} and gives "
                        f"{itype}")
                itype = InputType.recurrent(
                    itype.size, itype.timesteps * passes
                    if itype.timesteps > 0 else -1)
            if itype is not None:
                if str(i) not in self.input_preprocessors:
                    pp = _auto_preprocessor(itype, lc)
                    if pp is not None:
                        self.input_preprocessors[str(i)] = pp
                pp = self.preprocessor(i)
                if pp is not None:
                    itype = pp.output_type(itype)
                lc.set_n_in(itype, override=False)
                self.layer_input_types.append(itype)
                itype = lc.output_type(itype)
            else:
                # no declared input type (reference: user sets nIn explicitly);
                # chain output types forward once a layer determines its own.
                self.layer_input_types.append(None)
                try:
                    itype = lc.output_type(itype)
                except Exception:
                    itype = None
        if stop is not None and any(self.preprocessor(i) is not None
                                    for i in range(first, stop)):
            raise ValueError(
                f"loop({first}, {stop}, {passes}): a preprocessor inside "
                "the range (or before its first layer) would run every "
                "pass; start the range after it")


class ListBuilder:
    """Fluent layer-stack builder (reference NeuralNetConfiguration.ListBuilder)."""

    def __init__(self, defaults: Dict[str, Any], seed: int):
        self._defaults = defaults
        self._seed = seed
        self._layers: List[LayerConf] = []
        self._input_type: Optional[InputType] = None
        self._preprocessors: Dict[str, InputPreProcessor] = {}
        self._backprop_type = "standard"
        self._tbptt_fwd = 20
        self._tbptt_back = 20
        self._loop: Optional[List[int]] = None

    def layer(self, conf: LayerConf, index: Optional[int] = None) -> "ListBuilder":
        """Append, or place at ``index`` (reference ListBuilder.layer(int, Layer)
        semantics: set the layer at that position, padding is not allowed)."""
        if conf.name is None:
            conf.name = f"layer{index if index is not None else len(self._layers)}"
        if index is None or index == len(self._layers):
            self._layers.append(conf)
        elif 0 <= index < len(self._layers):
            self._layers[index] = conf
        else:
            raise ValueError(
                f"layer index {index} out of range (have {len(self._layers)} layers)")
        return self

    def set_input_type(self, itype: InputType) -> "ListBuilder":
        self._input_type = itype
        return self

    def input_pre_processor(self, index: int, pp: InputPreProcessor) -> "ListBuilder":
        self._preprocessors[str(index)] = pp
        return self

    def backprop_type(self, t: str, fwd: int = 20, back: int = 20) -> "ListBuilder":
        self._backprop_type = t
        self._tbptt_fwd = fwd
        self._tbptt_back = back
        return self

    def loop(self, first: int, stop: int, passes: int) -> "ListBuilder":
        """Walk the layers ``first .. stop - 1`` ``passes`` times on their
        one set of parameters (a looped, weight-shared, recurrent-depth
        stack): each pass reads the last one's output, and the passes'
        outputs go on to layer ``stop`` joined in time, pass-major, ``[b,
        passes * t, d]``.  The range's parameters, state and optimizer
        state exist once; a weight's gradient is the sum over the passes
        (``nn/multilayer._Walk.loop``)."""
        self._loop = [int(first), int(stop), int(passes)]
        return self

    def build(self) -> MultiLayerConfiguration:
        conf = MultiLayerConfiguration(
            layers=self._layers,
            input_type=self._input_type,
            input_preprocessors=self._preprocessors,
            backprop_type=self._backprop_type,
            tbptt_fwd_length=self._tbptt_fwd,
            tbptt_back_length=self._tbptt_back,
            defaults=dict(self._defaults),
            seed=self._seed,
            loop=self._loop,
        )
        conf.resolve()
        return conf


class NeuralNetConfiguration:
    """Entry point: ``NeuralNetConfiguration.builder()`` fluent API."""

    class Builder:
        def __init__(self):
            self._defaults: Dict[str, Any] = {}
            self._seed = 12345

        # global defaults — each maps onto the same-named reference builder call
        def seed(self, s: int):
            self._seed = int(s)
            return self

        def activation(self, a):
            self._defaults["activation"] = a
            return self

        def weight_init(self, w, dist=None):
            self._defaults["weight_init"] = w
            if dist is not None:
                self._defaults["weight_dist"] = dist
            return self

        def bias_init(self, b: float):
            self._defaults["bias_init"] = float(b)
            return self

        def updater(self, u):
            self._defaults["updater"] = u
            return self

        def bias_updater(self, u):
            self._defaults["bias_updater"] = u
            return self

        def l1(self, v: float):
            self._defaults["l1"] = float(v)
            return self

        def l2(self, v: float):
            self._defaults["l2"] = float(v)
            return self

        def l1_bias(self, v: float):
            self._defaults["l1_bias"] = float(v)
            return self

        def l2_bias(self, v: float):
            self._defaults["l2_bias"] = float(v)
            return self

        def dropout(self, d):
            self._defaults["dropout"] = d
            return self

        def weight_noise(self, wn):
            self._defaults["weight_noise"] = wn
            return self

        def constraints(self, cs):
            self._defaults["constraints"] = cs
            return self

        def gradient_normalization(self, gn, threshold: float = 1.0):
            self._defaults["gradient_normalization"] = gn
            self._defaults["gradient_normalization_threshold"] = float(threshold)
            return self

        def dtype(self, dt: str):
            self._defaults["dtype"] = dt
            return self

        def cache_mode(self, mode: str):
            """Activation memory policy (reference ``nn/conf/CacheMode.java``
            + WorkspaceMode): 'none' (default — XLA's buffer allocator
            manages activations) or 'remat' (``jax.checkpoint`` per layer:
            recompute activations in the backward pass, trading FLOPs for
            HBM — the TPU equivalent of cached workspaces)."""
            if mode not in ("none", "remat"):
                raise ValueError(f"cache_mode must be 'none' or 'remat', "
                                 f"got '{mode}'")
            self._defaults["cache_mode"] = mode
            return self

        def compute_dtype(self, dt: str):
            """Mixed precision: master params/optimizer state stay float32,
            forward+backward run in ``dt`` (normally 'bfloat16' — the TPU
            MXU's native input type).  Normalization statistics are kept
            float32.  The reference has no equivalent (CUDA fp32); this
            is shorthand for :meth:`precision` — use that for loss
            scaling or per-layer overrides."""
            self._defaults["compute_dtype"] = str(dt)
            return self

        def precision(self, policy):
            """First-class mixed-precision policy (``nn/precision``):
            a ``PrecisionPolicy`` instance, or a shorthand string —
            'bfloat16' (bf16 compute / f32 masters, no scaling),
            'float16' (f16 compute with dynamic loss scaling), 'float32'
            (full precision).  BatchNorm and loss/softmax reductions stay
            f32; the policy participates in the compile-cache topology
            signature, so variants never share a trace."""
            from ..precision import PrecisionPolicy, named_policy
            if isinstance(policy, str):
                policy = named_policy(policy)
            if not isinstance(policy, PrecisionPolicy):
                raise ValueError(
                    "precision() takes a PrecisionPolicy or a dtype "
                    f"shorthand string, got {type(policy).__name__}")
            self._defaults["precision"] = policy
            # mirror the legacy knob for consumers that only need the
            # compute dtype (memory reports, zoo model builders)
            if policy.compute_dtype:
                self._defaults["compute_dtype"] = policy.compute_dtype
            return self

        def scan_layers(self, mode):
            """Scan-over-layers control (``nn/scan_layers``): ``False``
            (or ``0``, mirroring ``DL4J_TPU_SCAN_LAYERS=0``) disables for
            this conf, ``True`` uses the process default minimum run
            length (``DL4J_TPU_SCAN_MIN``, default 4), an int >= 2
            overrides the minimum homogeneous-run length."""
            if not isinstance(mode, (bool, int)):
                raise ValueError("scan_layers(True|False|min_run_length)")
            if not isinstance(mode, bool):
                if mode == 0:
                    mode = False       # env-flag parity: 0 means off
                elif mode < 2:
                    raise ValueError(
                        "scan_layers min run length must be >= 2 "
                        "(a 1-layer 'run' cannot scan); use False/0 to "
                        "disable")
            self._defaults["scan_layers"] = mode
            return self

        def optimization_algo(self, algo: str, max_iterations: int = 100):
            """Pick the solver (reference ``OptimizationAlgorithm``):
            'sgd' (default, jitted minibatch path) or the legacy
            full-batch methods 'lbfgs' / 'conjugate_gradient' /
            'line_gradient_descent' (train/solvers.py)."""
            self._defaults["optimization_algo"] = str(algo).lower()
            self._defaults["max_iterations"] = int(max_iterations)
            return self

        def list(self) -> ListBuilder:
            return ListBuilder(self._defaults, self._seed)

        def graph_builder(self):
            from .computation_graph import GraphBuilder
            return GraphBuilder(self._defaults, self._seed)

    @staticmethod
    def builder() -> "NeuralNetConfiguration.Builder":
        return NeuralNetConfiguration.Builder()
