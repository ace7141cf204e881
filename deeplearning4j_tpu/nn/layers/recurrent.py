"""Recurrent layers: SimpleRnn, LSTM, GravesLSTM, bidirectional wrappers.

Reference: ``nn/layers/recurrent/LSTMHelpers.java:58`` (shared fwd :68-/bwd
:392- math, IFOG gate order, peepholes via axpy :235-236,260,303),
``GravesLSTM.java:46``, ``GravesBidirectionalLSTM.java`` (fwd+bwd outputs
ADDed, :224), ``nn/conf/layers/{LSTM,GravesLSTM,GravesBidirectionalLSTM}``.

TPU-native design: one ``lax.scan`` over the time axis per layer — XLA compiles
the cell into a single fused step program (the cuDNN-LSTM-helper role), with
the input projection ``x @ W`` hoisted OUT of the scan as one big [b*t, 4h]
matmul that tiles onto the MXU.  State (h, c) is an explicit functional carry:

    init_carry(batch)                         -> carry
    scan(params, x, carry, mask)              -> (y [b,t,h], final_carry)

``apply`` runs with a zero carry (reference fit() semantics: no cross-batch
state).  Truncated-BPTT chunk state and ``rnnTimeStep`` streaming inference
(reference MultiLayerNetwork.java:2690 stateMap) thread the carry explicitly
through MultiLayerNetwork.

Masking: for padded step t with mask 0, output is zeroed and the carry holds
its previous value (reference variable-length semantics).
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from ...utils.serde import register_serde
from .. import activations as _act
from ..conf.input_type import InputType
from .base import BaseLayerConf, LayerConf
from .feedforward import OutputLayer


@dataclass
class BaseRecurrentLayer(BaseLayerConf):
    """Common recurrent contract (reference ``nn/api/layers/RecurrentLayer``).
    HAS_CARRY marks layers with streaming/tBPTT state (h, c); RnnOutputLayer
    reuses the shape plumbing but is stateless."""
    INPUT_KIND = "rnn"
    HAS_CARRY = False

    n_in: int = 0
    n_out: int = 0

    def set_n_in(self, itype: InputType, override: bool = False) -> None:
        if self.n_in == 0 or override:
            if itype.kind != "rnn":
                raise ValueError(
                    f"layer '{self.name}': recurrent layer expects RNN input, got {itype}")
            self.n_in = itype.size

    def output_type(self, itype: InputType) -> InputType:
        return InputType.recurrent(self.n_out, itype.timesteps)

    # -- carry protocol ------------------------------------------------------
    def init_carry(self, batch: int, dtype=jnp.float32):
        raise NotImplementedError

    def scan(self, params, x, carry, mask=None):
        """x: [b, t, f] -> (y [b, t, h], final_carry)."""
        raise NotImplementedError

    def apply(self, variables, x, *, train=False, key=None, mask=None):
        params = self.maybe_noise_weights(key, variables["params"], train)
        x = self.maybe_dropout_input(key, x, train)
        carry = self.init_carry(x.shape[0], x.dtype)
        y, _ = self.scan(params, x, carry, mask)
        return y, variables.get("state", {})

    def apply_with_carry(self, variables, x, carry, *, train=False, key=None,
                         mask=None):
        params = self.maybe_noise_weights(key, variables["params"], train)
        x = self.maybe_dropout_input(key, x, train)
        if carry is None:
            carry = self.init_carry(x.shape[0], x.dtype)
        y, new_carry = self.scan(params, x, carry, mask)
        return y, new_carry

    @staticmethod
    def _mask_step(m_t, h_new, h_prev, y_t):
        """Masked step: carry holds, output zeroed."""
        if m_t is None:
            return h_new, y_t
        m = m_t[:, None]
        return m * h_new + (1 - m) * h_prev, y_t * m


def _time_major(x):
    return jnp.swapaxes(x, 0, 1)


@register_serde
@dataclass
class SimpleRnn(BaseRecurrentLayer):
    """Vanilla RNN: h_t = act(x_t W + h_{t-1} U + b)
    (reference ``nn/conf/layers/recurrent/SimpleRnn``)."""
    HAS_CARRY = True

    def init(self, key, itype):
        if self.n_in <= 0 or self.n_out <= 0:
            raise ValueError(f"layer '{self.name}': n_in/n_out unset")
        k1, k2 = jax.random.split(key)
        return {"params": {
            "W": self.make_weight(k1, (self.n_in, self.n_out)),
            "U": self.make_weight(k2, (self.n_out, self.n_out)),
            "b": self.make_bias((self.n_out,)),
        }, "state": {}}

    def init_carry(self, batch, dtype=jnp.float32):
        return {"h": jnp.zeros((batch, self.n_out), dtype)}

    def scan(self, params, x, carry, mask=None):
        act = self.act_fn
        xz = x.astype(params["W"].dtype) @ params["W"] + params["b"]  # [b,t,h]
        xz_t = _time_major(xz)
        m_t = None if mask is None else _time_major(mask.astype(xz.dtype))

        def step(c, inp):
            xzt, mt = inp
            h_new = act(xzt + c["h"] @ params["U"])
            h, y = self._mask_step(mt, h_new, c["h"], h_new)
            return {"h": h}, y

        if m_t is None:
            def step_nm(c, xzt):
                h_new = act(xzt + c["h"] @ params["U"])
                return {"h": h_new}, h_new
            final, ys = lax.scan(step_nm, carry, xz_t)
        else:
            final, ys = lax.scan(step, carry, (xz_t, m_t))
        return _time_major(ys), final


@register_serde
@dataclass
class LSTM(BaseRecurrentLayer):
    """Standard LSTM, no peepholes (reference ``nn/conf/layers/LSTM`` — the
    cuDNN-compatible variant).  Gate order IFOG as in LSTMHelpers."""
    HAS_CARRY = True
    forget_gate_bias_init: float = 1.0
    gate_activation: str = "sigmoid"
    # optional accelerator fast path (the reference's reflective cuDNN
    # helper hook, ConvolutionLayer.java:74-84 pattern): "pallas" fuses the
    # recurrence into one kernel with U resident in VMEM; silently falls
    # back to lax.scan when unsupported (mask, peepholes, exotic
    # activations) — CudnnLSTMHelper.checkSupported semantics.
    helper: Optional[str] = None

    _PEEPHOLES = False

    def init(self, key, itype):
        if self.n_in <= 0 or self.n_out <= 0:
            raise ValueError(f"layer '{self.name}': n_in/n_out unset")
        k1, k2, k3 = jax.random.split(key, 3)
        h = self.n_out
        # biases at bias_init, forget-gate slice [h:2h] OVERWRITTEN with
        # forget_gate_bias_init (reference LSTMParamInitializer order)
        b = jnp.full((4 * h,), self.resolved("bias_init", 0.0), self._dtype())
        b = b.at[h:2 * h].set(self.forget_gate_bias_init)
        params = {
            "W": self.make_weight(k1, (self.n_in, 4 * h)),
            "U": self.make_weight(k2, (h, 4 * h)),
            "b": b,
        }
        if self._PEEPHOLES:
            params["p"] = jnp.zeros((3 * h,), self._dtype())  # pi, pf, po
        return {"params": params, "state": {}}

    def init_carry(self, batch, dtype=jnp.float32):
        h = self.n_out
        return {"h": jnp.zeros((batch, h), dtype), "c": jnp.zeros((batch, h), dtype)}

    def scan(self, params, x, carry, mask=None):
        if self.helper == "pallas":
            from ...ops import pallas_lstm
            if pallas_lstm.supports(
                    peepholes=self._PEEPHOLES,
                    gate_activation=self.gate_activation,
                    activation=self.resolved("activation", "tanh"),
                    masked=mask is not None):
                ys, hT, cT = pallas_lstm.lstm_forward_fast(
                    x.astype(jnp.float32),
                    params["W"].astype(jnp.float32),
                    params["U"].astype(jnp.float32),
                    params["b"].astype(jnp.float32),
                    carry["h"].astype(jnp.float32),
                    carry["c"].astype(jnp.float32))
                return ys, {"h": hT, "c": cT}
        h_units = self.n_out
        act = self.act_fn
        gate = _act.get(self.gate_activation)
        # hoist the input projection: one [b*t, 4h] MXU matmul
        xz = x.astype(params["W"].dtype) @ params["W"] + params["b"]
        xz_t = _time_major(xz)
        m_t = None if mask is None else _time_major(mask.astype(xz.dtype))
        peep = params.get("p") if self._PEEPHOLES else None

        def cell(c, xzt, mt):
            z = xzt + c["h"] @ params["U"]
            zi, zf, zo, zg = jnp.split(z, 4, axis=-1)
            if peep is not None:
                pi, pf, po = jnp.split(peep, 3)
                zi = zi + pi * c["c"]
                zf = zf + pf * c["c"]
            i = gate(zi)
            f = gate(zf)
            g = act(zg)
            c_new = f * c["c"] + i * g
            if peep is not None:
                zo = zo + po * c_new
            o = gate(zo)
            h_new = o * act(c_new)
            if mt is None:
                return {"h": h_new, "c": c_new}, h_new
            m = mt[:, None]
            return ({"h": m * h_new + (1 - m) * c["h"],
                     "c": m * c_new + (1 - m) * c["c"]}, h_new * m)

        if m_t is None:
            final, ys = lax.scan(lambda c, xzt: cell(c, xzt, None), carry, xz_t)
        else:
            final, ys = lax.scan(lambda c, inp: cell(c, *inp), carry, (xz_t, m_t))
        return _time_major(ys), final


@register_serde
@dataclass
class GravesLSTM(LSTM):
    """LSTM with peephole connections (reference ``GravesLSTM.java:46``,
    peephole math LSTMHelpers.java:235-236,260,303)."""
    _PEEPHOLES = True


@register_serde
@dataclass
class Bidirectional(LayerConf):
    """Bidirectional wrapper (reference ``nn/conf/layers/recurrent/Bidirectional``):
    runs the wrapped recurrent layer forwards and (a separate copy) backwards
    over time, combining with mode add/mul/average/concat."""
    fwd: Optional[BaseRecurrentLayer] = None
    mode: str = "concat"           # concat | add | mul | average

    def __post_init__(self):
        if self.fwd is not None and self.name is None:
            self.name = f"bi_{self.fwd.name or type(self.fwd).__name__}"

    # delegate config resolution to the wrapped layer
    def has_params(self):
        return True

    def apply_global_defaults(self, defaults):
        self.fwd.apply_global_defaults(defaults)

    def set_n_in(self, itype, override=False):
        self.fwd.set_n_in(itype, override)

    def output_type(self, itype: InputType) -> InputType:
        inner = self.fwd.output_type(itype)
        if self.mode == "concat":
            return InputType.recurrent(inner.size * 2, inner.timesteps)
        return inner

    def regularization_score(self, params):
        return (self.fwd.regularization_score(params.get("fwd", {})) +
                self.fwd.regularization_score(params.get("bwd", {})))

    def init(self, key, itype):
        k1, k2 = jax.random.split(key)
        vf = self.fwd.init(k1, itype)
        vb = self.fwd.init(k2, itype)
        return {"params": {"fwd": vf["params"], "bwd": vb["params"]},
                "state": {}}

    def _combine(self, yf, yb):
        if self.mode == "concat":
            return jnp.concatenate([yf, yb], axis=-1)
        if self.mode == "add":
            return yf + yb
        if self.mode == "mul":
            return yf * yb
        if self.mode == "average":
            return 0.5 * (yf + yb)
        raise ValueError(f"unknown bidirectional mode '{self.mode}'")

    def apply(self, variables, x, *, train=False, key=None, mask=None):
        p = variables["params"]
        kf, kb = (jax.random.split(key) if key is not None else (None, None))
        yf, _ = self.fwd.apply({"params": p["fwd"], "state": {}}, x,
                               train=train, key=kf, mask=mask)
        xr = jnp.flip(x, axis=1)
        mr = None if mask is None else jnp.flip(mask, axis=1)
        yb, _ = self.fwd.apply({"params": p["bwd"], "state": {}}, xr,
                               train=train, key=kb, mask=mr)
        yb = jnp.flip(yb, axis=1)
        return self._combine(yf, yb), variables.get("state", {})


@register_serde
@dataclass
class GravesBidirectionalLSTM(Bidirectional):
    """Convenience: bidirectional GravesLSTM combined by ADD
    (reference ``GravesBidirectionalLSTM.java:224`` fwdOutput.add(backOutput))."""
    n_in: int = 0
    n_out: int = 0
    mode: str = "add"

    def __post_init__(self):
        if self.fwd is None:
            self.fwd = GravesLSTM(n_in=self.n_in, n_out=self.n_out,
                                  name=f"{self.name or 'gbilstm'}_inner")
        super().__post_init__()

    def set_n_in(self, itype, override=False):
        super().set_n_in(itype, override)
        self.n_in = self.fwd.n_in


@register_serde
@dataclass
class RnnOutputLayer(OutputLayer):
    """Time-distributed dense + loss (reference ``nn/conf/layers/RnnOutputLayer``).
    Input [b, t, f] -> output [b, t, n_out]; label mask [b, t] supported.
    Reuses OutputLayer's head (the matmul is rank-agnostic); only the shape
    contract differs."""
    INPUT_KIND = "rnn"

    def set_n_in(self, itype: InputType, override: bool = False) -> None:
        if self.n_in == 0 or override:
            if itype.kind != "rnn":
                raise ValueError(
                    f"layer '{self.name}': RnnOutputLayer expects RNN input, got {itype}")
            self.n_in = itype.size

    def output_type(self, itype: InputType) -> InputType:
        return InputType.recurrent(self.n_out, itype.timesteps)


@register_serde
@dataclass
class ExitGateOutputLayer(RnnOutputLayer):
    """The head of a looped stack with a learned exit after every pass
    ("Scaling Latent Reasoning via Looped Language Models",
    arXiv:2510.25741, section 3).  The input is the passes' normed states
    joined in time, pass-major, ``[b, exits * t, d]`` (what a
    ``ListBuilder.loop`` range hands on); labels are integer ids ``[b, t]``
    and a label mask ``[b, t]``.

    Beside the head ``W [d, n_out]`` (and ``b``) the layer holds a gate
    ``w_g [d, 1]``, ``b_g [1]``: after pass ``r`` a position would exit
    with ``lambda_r = sigmoid(h_r w_g + b_g)``, so it exits at ``r`` with
    ``p_r = lambda_r prod_{j<r} (1 - lambda_j)`` and the last pass takes
    what is left, ``p_R = prod_{j<R} (1 - lambda_j)``.  The loss of a
    position is ``sum_r p_r CE(softmax(h_r W), y) - exit_beta H(p)``, every
    exit scored every step and the gate trained through ``p``; positions
    are summed and rows averaged as ``sparse_mcxent`` does.  The logits are
    never whole: ``p`` times the label mask's weights is the ``weights`` of
    ``losses.chunked_softmax_xent`` over the ``exits * t`` rows, whose
    cotangent (each row's cross-entropy) is the gate's gradient.

    ``apply`` gives the last pass's distribution, ``[b, t, n_out]``: what
    inference with an exit threshold of 1 returns.  The state carries
    ``exit_mass [exits]``, the mean of ``p_r`` over the last step's kept
    positions (gauge ``loop_exit_mass{pass}``, :func:`publish_exit_mass`)."""
    _BIAS_PARAMS = ("b", "b_g")
    loss: str = "sparse_mcxent"
    activation: Optional[str] = "softmax"
    exits: int = 1
    exit_beta: float = 0.0

    def output_type(self, itype: InputType) -> InputType:
        t = itype.timesteps
        return InputType.recurrent(self.n_out,
                                   t // self.exits if t > 0 else -1)

    def init(self, key, itype):
        if str(self.loss).lower() != "sparse_mcxent" or \
                str(self.resolved("activation", "")).lower() != "softmax" \
                or self.pred_heads != 1 or self.loss_weights is not None:
            raise ValueError(
                f"layer '{self.name}': the exit-gated head is a softmax "
                "over integer labels (sparse_mcxent), one head, no column "
                "weights")
        out = super().init(key, itype)
        out["params"]["w_g"] = self.make_weight(jax.random.fold_in(key, 1),
                                                (self.n_in, 1))
        out["params"]["b_g"] = self.make_bias((1,))
        out["state"] = {"exit_mass": jnp.zeros((self.exits,), jnp.float32)}
        return out

    def pre_output(self, variables, x, *, train=False, key=None):
        # the last pass's states
        last = x[:, x.shape[1] - x.shape[1] // self.exits:]
        return super().pre_output(variables, last, train=train, key=key)

    def exit_distribution(self, params, x):
        """``(p, log p)``, ``[b, exits, t]`` float32, from the states ``x
        [b, exits * t, d]``: the gate's product in the operands' type,
        summed in float32; everything after it float32, in logarithms
        (``log(1 - sigmoid(g)) = log_sigmoid(-g)``)."""
        b, rt, _ = x.shape
        g = jnp.einsum("btd,do->bto", x, params["w_g"],
                       preferred_element_type=jnp.float32)[..., 0]
        g = (g + params["b_g"].astype(jnp.float32)).reshape(
            b, self.exits, rt // self.exits)
        stay = jnp.cumsum(jax.nn.log_sigmoid(-g), axis=1)
        # exits before the last: log lambda_r + sum_{j<r} log(1 - lambda_j);
        # the last: sum_{j<R} log(1 - lambda_j)
        before = jnp.concatenate([jnp.zeros_like(stay[:, :1]),
                                  stay[:, :-1]], axis=1)
        logp = jnp.concatenate(
            [jax.nn.log_sigmoid(g[:, :-1]) + before[:, :-1],
             before[:, -1:]], axis=1)
        return jnp.exp(logp), logp

    def loss_and_state(self, variables, x, labels, *, train=False, key=None,
                       mask=None):
        from .. import losses as _losses
        b, rt, _ = x.shape
        t = rt // self.exits
        if mask is not None and mask.shape == (b, rt):
            # a feature mask that came through the loop, repeated a pass
            mask = mask[:, :t]
        if labels.shape != (b, t) or (mask is not None
                                      and mask.shape != (b, t)):
            raise ValueError(
                f"layer '{self.name}': integer labels (and a label mask) "
                f"[{b}, {t}] for {self.exits} exits over {rt} rows, got "
                f"{labels.shape}" + ("" if mask is None
                                     else f" and {mask.shape}"))
        x, params = self._operands(variables, x, train, key)
        w = _losses.position_weights(mask, (b, t))          # [b, t]
        with jax.named_scope("exit_gate"):
            p, logp = self.exit_distribution(params, x)
            entropy = -jnp.sum(p * logp, axis=1)                # [b, t]
            weights = (p * w[:, None, :]).reshape(b, rt)
            kept = jnp.sum((w > 0).astype(jnp.float32))
            mass = jnp.sum(p * (w > 0)[:, None, :], axis=(0, 2)) \
                / jnp.maximum(kept, 1.0)
        rows = _losses.head_rows_per_chunk(b, rt, self.n_out) or rt
        if rows < rt:
            self._count_chunks(b * rt, rt // rows)
        xent = _losses.chunked_softmax_xent(
            x, params["W"], params["b"] if self.has_bias else None,
            jnp.tile(labels, (1, self.exits)), weights, rows)
        with jax.named_scope("exit_gate"):
            loss = xent - self.exit_beta * jnp.sum(w * entropy)
        return loss, {"exit_mass": jax.lax.stop_gradient(mass)}

    def compute_loss(self, variables, x, labels, *, train=False, key=None,
                     mask=None, average=True):
        return self.loss_and_state(variables, x, labels, train=train,
                                   key=key, mask=mask)[0]


def publish_exit_mass(model) -> None:
    """Gauges ``loop_exit_mass{pass}`` from the state of a head that
    threads ``exit_mass``: called where ``fit`` has just read the loss, as
    ``moe.publish_expert_tokens`` is."""
    from ...observability.registry import default_registry
    reg = default_registry()
    masses = [st["exit_mass"]
              for st in (getattr(model, "state", None) or {}).values()
              if isinstance(st, dict) and "exit_mass" in st]
    if not masses or not reg.enabled:
        return
    gauge = reg.gauge("loop_exit_mass",
                      "Mean over the last step's positions of the share of "
                      "a token's prediction that exits after each pass of "
                      "a looped range", ("pass",))
    for r, v in enumerate(jax.device_get(masses[-1])):
        gauge.labels(str(r + 1)).set(float(v))


@register_serde
@dataclass
class LastTimeStep(LayerConf):
    """Wrapper: keep only the last (mask-aware) time step of a recurrent
    layer's output → FF (reference ``recurrent/LastTimeStep`` /
    ``LastTimeStepVertex``)."""
    underlying: Optional[LayerConf] = None

    @property
    def HAS_CARRY(self):  # delegate streaming/tBPTT state to the wrapped layer
        return getattr(self.underlying, "HAS_CARRY", False)

    def init_carry(self, batch, dtype=jnp.float32):
        return self.underlying.init_carry(batch, dtype)

    def apply_with_carry(self, variables, x, carry, *, train=False, key=None,
                         mask=None):
        y, new_carry = self.underlying.apply_with_carry(
            variables, x, carry, train=train, key=key, mask=mask)
        if mask is not None:
            # last NONZERO index (not count-1): robust to non-contiguous masks,
            # matching LastTimeStepVertex semantics
            idx = (mask.shape[1] - 1 -
                   jnp.argmax(mask[:, ::-1] > 0, axis=1)).astype(jnp.int32)
            out = jnp.take_along_axis(y, idx[:, None, None], axis=1)[:, 0]
        else:
            out = y[:, -1]
        return out, new_carry

    def has_params(self):
        return self.underlying.has_params()

    def apply_global_defaults(self, defaults):
        if hasattr(self.underlying, "apply_global_defaults"):
            self.underlying.apply_global_defaults(defaults)

    def set_n_in(self, itype, override=False):
        self.underlying.set_n_in(itype, override)

    def output_type(self, itype: InputType) -> InputType:
        inner = self.underlying.output_type(itype)
        return InputType.feed_forward(inner.size)

    def init(self, key, itype):
        return self.underlying.init(key, itype)

    def regularization_score(self, params):
        return self.underlying.regularization_score(params)

    def apply(self, variables, x, *, train=False, key=None, mask=None):
        y, state = self.underlying.apply(variables, x, train=train, key=key,
                                         mask=mask)
        if mask is not None:
            # last unmasked step per example
            # last NONZERO index (not count-1): robust to non-contiguous masks,
            # matching LastTimeStepVertex semantics
            idx = (mask.shape[1] - 1 -
                   jnp.argmax(mask[:, ::-1] > 0, axis=1)).astype(jnp.int32)
            out = jnp.take_along_axis(y, idx[:, None, None], axis=1)[:, 0]
        else:
            out = y[:, -1]
        return out, state

    def feed_forward_mask(self, mask, itype):
        return None
