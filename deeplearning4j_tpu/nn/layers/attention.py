"""Attention layer family: LayerNorm, RMSNorm, MultiHeadAttention,
TransformerBlock.

No counterpart in the reference (pre-transformer, SURVEY.md §5) — this is the
long-context capability the TPU build adds as first-class.  The layers follow
the same config-dataclass contract as every other layer
(``nn/layers/base.py``), so they compose with MultiLayerNetwork /
ComputationGraph, serde, transfer learning, and the zoo.

Attention impl tiers (select with ``attn_impl``):
  'reference' — jnp SDPA (``ops.attention.sdpa_reference``), always correct.
  'flash'     — pallas tiled kernel (``ops.flash_attention``), O(t) memory.
  'ring'      — ring attention over the mesh 'seq' axis (inside shard_map).
  'ulysses'   — all-to-all sequence parallelism (inside shard_map).
  'auto'      — ``auto_attention_impl``: flash on a TPU for unmasked
                sequences of at least ``DEFAULT_FLASH_MIN_SEQ`` tokens the
                kernel can tile, reference otherwise — the
                ``CudnnAlgoMode`` role.

Attention kinds (select with ``attention``): ``'full'``, every key (under
``causal`` every earlier key); ``'sliding'``, causal, the last ``window``
keys of each query (a band that moves with the query); and ``'eva'``, EVA
chunked linearized attention (``_eva_attention``): exact causal attention
inside a window, learned summaries of key chunks for everything before it.
All three reach the flash kernels; any other key set (a key-padding mask)
runs ``sdpa_reference`` under 'auto' and is refused by 'flash'.
``TransformerBlock(attention='latent')`` is a layer of its own,
``LatentAttention``: low-rank q, a joint latent for K and V, heads wider in
q and k than in v, rotary positions on part of a head.
"""
from __future__ import annotations

import os
from dataclasses import dataclass
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from ...utils.serde import register_serde
from ..conf.input_type import InputType
from .base import BaseLayerConf, LayerConf


@register_serde
@dataclass
class LayerNormLayer(BaseLayerConf):
    """Layer normalization over the feature axis (gamma/beta learned)."""
    n_out: int = 0
    eps: float = 1e-5

    def set_n_in(self, itype: InputType, override: bool = False) -> None:
        if self.n_out == 0 or override:
            self.n_out = itype.size

    def output_type(self, itype: InputType) -> InputType:
        return itype

    def init(self, key, itype):
        return {"params": {"gamma": jnp.ones((self.n_out,), self._dtype()),
                           "beta": jnp.zeros((self.n_out,), self._dtype())},
                "state": {}}

    def apply(self, variables, x, *, train=False, key=None, mask=None):
        p = variables["params"]
        y = _layer_norm(x, p["gamma"], p["beta"], self.eps)
        return y, variables.get("state", {})


def _layer_norm(x, gamma, beta, eps=1e-5):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.var(x, axis=-1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * gamma + beta


def _rms_norm(x, gain, eps=1e-5):
    """``x * rsqrt(mean(x^2) + eps) * (1 + gain)``: RMSNorm with a unit
    offset (a zero gain is the identity scale); the mean of squares in at
    least float32, the result in x's type."""
    xf = x.astype(jnp.promote_types(x.dtype, jnp.float32))
    y = xf * jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
    return (y * (1.0 + gain.astype(y.dtype))).astype(x.dtype)


@register_serde
@dataclass
class RMSNormLayer(BaseLayerConf):
    """Root-mean-square normalization over the feature axis with a
    learned gain stored as an offset from one (``_rms_norm``)."""
    _BIAS_PARAMS = ("gain",)
    n_out: int = 0
    eps: float = 1e-5

    def set_n_in(self, itype: InputType, override: bool = False) -> None:
        if self.n_out == 0 or override:
            self.n_out = itype.size

    def output_type(self, itype: InputType) -> InputType:
        return itype

    def init(self, key, itype):
        return {"params": {"gain": jnp.zeros((self.n_out,), self._dtype())},
                "state": {}}

    def apply(self, variables, x, *, train=False, key=None, mask=None):
        return (_rms_norm(x, variables["params"]["gain"], self.eps),
                variables.get("state", {}))


_ATTN_IMPLS = ("auto", "reference", "flash", "ring", "ulysses")

# 'auto' crossover: flash from the kernel's minimum tile (128) upward.
# The kernels' tiles (ops/flash_attention._auto_blocks) were swept on a
# TPU v5e under jax 0.9.0 / libtpu 0.0.34 on 2026-10-01 (PERF.md section
# 6, PR 28), from t=128 to t=8192 at head_dim 64; the crossover itself
# (flash against reference SDPA in a full step) was not measured then and
# rests on a comparison that predates today's code and compiler.  The
# env/field override remains for chips where it differs.  Role mirror:
# the reference's shape-based algorithm selection
# (``ConvolutionLayer.java:349`` CudnnAlgoMode).
DEFAULT_FLASH_MIN_SEQ = int(os.environ.get("DL4J_TPU_FLASH_MIN_SEQ", 128))


def auto_attention_impl(t_q: int, t_k: int, d: int, *, masked: bool,
                        flash_min_seq: Optional[int] = None,
                        d_v: Optional[int] = None) -> str:
    """What ``attn_impl='auto'`` runs for these shapes: ``'flash'`` when
    the backend is a TPU, the input is unmasked, the sequence is at or
    above the crossover (``flash_min_seq``, default
    ``DEFAULT_FLASH_MIN_SEQ``) and the kernel can tile it; else
    ``'reference'``.  The one place the choice is made, so a caller can
    ask what was chosen instead of guessing.

    Two attention kinds reach the kernels: full attention, causal or not,
    at the sequence's own length, and EVA attention, whose windows are
    causal calls at the window's length (the shapes to ask about are then
    the window's).  A key-padding mask, or any other key set, is
    ``masked``: 'auto' runs the O(t^2) reference for it and an explicit
    ``'flash'`` raises — it refuses, it never falls back."""
    threshold = (DEFAULT_FLASH_MIN_SEQ if flash_min_seq is None
                 else flash_min_seq)
    if masked or t_q < threshold or jax.default_backend() != "tpu":
        return "reference"
    from ...ops.flash_attention import flash_blocks
    try:
        flash_blocks(t_q, t_k, d, d_v=d_v)
    except ValueError:
        return "reference"
    return "flash"


def _run_attention(q, k, v, *, impl: str, causal: bool, mask, seq_axis: str,
                   flash_min_seq: Optional[int] = None,
                   window: Optional[int] = None):
    """Dispatch [b,h,t,d] q/k/v to the selected attention implementation.

    ``impl='auto'`` resolves through :func:`auto_attention_impl`.  An
    explicit ``impl='flash'`` never falls back: a mask, shapes the kernel
    cannot tile, or a backend that cannot run it all raise.  ``window``
    (causal) keeps the last ``window`` keys of each query; the call then
    runs under the scope ``attn_window``, else under ``attn_full``.  ``v``
    may be narrower or wider a head than q and k."""
    from ...ops.attention import sdpa_reference
    if impl not in _ATTN_IMPLS:
        raise ValueError(f"unknown attn_impl '{impl}'; expected one of "
                         f"{_ATTN_IMPLS}")
    if impl in ("ring", "ulysses"):
        from ...parallel.sequence import ring_self_attention, ulysses_attention
        if mask is not None or window is not None:
            raise ValueError("sequence-parallel attention does not take "
                             "key-padding masks (pad to shard boundary) "
                             "or a window")
        fn = ring_self_attention if impl == "ring" else ulysses_attention
        return fn(q, k, v, axis_name=seq_axis, causal=causal)
    if impl == "auto":
        impl = auto_attention_impl(
            q.shape[2], k.shape[2], q.shape[3], masked=mask is not None,
            flash_min_seq=flash_min_seq, d_v=v.shape[3])
    if impl == "flash":
        if mask is not None:
            raise ValueError("attn_impl='flash' does not take key-padding "
                             "masks; use 'reference'/'auto' or pre-mask inputs")
    with jax.named_scope("attn_window" if window else "attn_full"):
        if impl == "flash":
            # the kernel names its own output and log-sum-exp (_flash_fwd)
            from ...ops.flash_attention import flash_attention
            return flash_attention(q, k, v, causal=causal, window=window)
        return checkpoint_name(
            sdpa_reference(q, k, v, mask=mask, causal=causal, window=window),
            "attn_out")


def _rotary(x, theta: float):
    """Rotary positions on ``[b, h, t, d]``: rotate-half pairing (feature
    ``i`` with ``i + d/2``), absolute positions ``0..t-1``, angles in
    float32, the result in x's type.  (One table shared by q and k reads
    slower on the v5e than one each, fused into its user: PERF.md, PR 29.)"""
    t, d = x.shape[2], x.shape[3]
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    angle = jnp.arange(t, dtype=jnp.float32)[:, None] * inv[None, :]
    cos = jnp.concatenate([jnp.cos(angle)] * 2, axis=-1)
    sin = jnp.concatenate([jnp.sin(angle)] * 2, axis=-1)
    half = jnp.concatenate([-x[..., d // 2:], x[..., :d // 2]], axis=-1)
    return (x * cos + half * sin).astype(x.dtype)


def _rotary_pairs(x, theta: float):
    """Rotary positions on ``[b, h, t, d]`` by adjacent pairs (feature
    ``2i`` with ``2i + 1``, the published ``rope_interleave``): the pair as
    a complex number times ``exp(i * pos * theta^(-2i/d))``.  Absolute
    positions ``0..t-1``, angles in float32, the result in x's type.  ``x``
    is the part of a head that carries positions, and ``d`` its width."""
    t, d = x.shape[2], x.shape[3]
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    angle = jnp.arange(t, dtype=jnp.float32)[:, None] * inv[None, :]
    cos = jnp.repeat(jnp.cos(angle), 2, axis=-1)
    sin = jnp.repeat(jnp.sin(angle), 2, axis=-1)
    # each feature's partner, signed: (-x1, x0, -x3, x2, ...)
    partner = jnp.where(jnp.arange(d) % 2 == 0, -jnp.roll(x, -1, axis=-1),
                        jnp.roll(x, 1, axis=-1))
    return (x * cos + partner * sin).astype(x.dtype)


def _eva_attention(q, k, v, phi, mu, *, window: int, chunk: int, impl: str,
                   flash_min_seq: Optional[int] = None):
    """EVA chunked linearized attention (Zheng et al., ICLR 2023,
    arXiv:2302.04542, in the deterministic form of EvaByte's released
    reference) over ``[b, h, t, d]``; ``phi``, ``mu`` are ``[h, d]``.

    Every ``chunk`` keys are pooled into one summary key and value,
    ``ks_c = sum_j a_j k_j + mu``, ``vs_c = sum_j a_j v_j`` with ``a =
    softmax_j(k_j . phi)``.  Query ``i`` of window ``w = i // window``
    attends, in ONE softmax, to the keys ``j <= i`` of its own window and
    to the summaries of every chunk of the windows before it.  The window
    part is plain causal attention with the windows as rows of the batch
    (the flash kernels as they are, handing back their log-sum-exp); the
    summary part is a product of a window's queries with the ``w * window
    / chunk`` summaries before it; the two partial softmaxes merge by
    ``ops.attention.combine_blocks``.  The last window may be short."""
    from ...ops.attention import attn_block, combine_blocks, finalize_blocks
    b, h, t, d = q.shape
    if window % chunk or t % chunk:
        raise ValueError(f"eva attention needs the window ({window}) and "
                         f"the sequence ({t}) to be whole chunks of {chunk}")
    acc_dt = jnp.promote_types(q.dtype, jnp.float32)
    if impl == "auto":
        impl = auto_attention_impl(window, window, d, masked=False,
                                   flash_min_seq=flash_min_seq)
    if impl not in ("flash", "reference"):
        raise ValueError(f"eva attention runs 'flash' or 'reference', "
                         f"not attn_impl='{impl}'")

    with jax.named_scope("eva_pool"):
        kc = k.reshape(b, h, t // chunk, chunk, d)
        vc = v.reshape(b, h, t // chunk, chunk, d)
        # a sixteenth of k and v each: the first names a scanned run
        # keeps (TransformerBlock.SAVED_NAMES)
        a = checkpoint_name(jax.nn.softmax(jnp.einsum(
            "bhncd,hd->bhnc", kc, phi.astype(k.dtype),
            preferred_element_type=acc_dt), axis=-1).astype(k.dtype),
            "eva_a")
        ks = checkpoint_name(
            (jnp.einsum("bhnc,bhncd->bhnd", a, kc,
                        preferred_element_type=acc_dt)
             + mu.astype(acc_dt)[None, :, None, :]).astype(k.dtype),
            "eva_ks")
        vs = checkpoint_name(
            jnp.einsum("bhnc,bhncd->bhnd", a, vc,
                       preferred_element_type=acc_dt).astype(v.dtype),
            "eva_vs")

    def windows(x, start, stop, size):
        """Positions ``start..stop`` as rows of ``size``: [b, h*n, size, d]"""
        return x[:, :, start:stop].reshape(b, -1, size, d)

    def local(start, stop, size):
        """(acc, m, l) of causal attention inside windows of ``size``."""
        qw, kw, vw = (windows(x, start, stop, size) for x in (q, k, v))
        if impl == "flash":
            from ...ops.flash_attention import flash_attention
            o, lse = flash_attention(qw, kw, vw, causal=True,
                                     return_lse=True)
            part = o.astype(acc_dt), lse, jnp.ones_like(lse)
        else:
            part = attn_block(qw, kw, vw, causal=True)
        return tuple(x.reshape(b, h, stop - start, *x.shape[3:])
                     for x in part)

    whole = (t // window) * window
    with jax.named_scope("eva_window"):
        parts = [local(0, whole, window)] if whole else []
        if t > whole:
            parts.append(local(whole, t, t - whole))
        acc, m, l = (jnp.concatenate(x, axis=2) if len(x) > 1 else x[0]
                     for x in zip(*parts))

    def summaries(rows, n):
        """(acc, m, l) of ``rows``' queries over the first ``n`` summaries:
        scores and sums in float32, p in the operands' type for its
        product, as the kernels do."""
        s = jnp.einsum("bhqd,bhkd->bhqk", q[:, :, rows], ks[:, :, :n],
                       preferred_element_type=acc_dt) * d ** -0.5
        m_s = jnp.max(s, axis=-1)
        p = jnp.exp(s - m_s[..., None])
        return (jnp.einsum("bhqk,bhkd->bhqd", p.astype(v.dtype),
                           vs[:, :, :n], preferred_element_type=acc_dt),
                m_s, jnp.sum(p, axis=-1))

    out = [finalize_blocks(acc[:, :, :window], m[:, :, :window],
                           l[:, :, :window], q.dtype)]
    for start in range(window, t, window):
        rows = slice(start, min(start + window, t))
        with jax.named_scope("eva_summary"):
            summary = summaries(rows, start // chunk)
        with jax.named_scope("eva_merge"):
            out.append(finalize_blocks(*combine_blocks(
                acc[:, :, rows], m[:, :, rows], l[:, :, rows], *summary),
                q.dtype))
    return jnp.concatenate(out, axis=2) if len(out) > 1 else out[0]


def _kv_quantize(x):
    """Per-(row, head) absmax int8 quantization of a ``[..., d]`` K/V
    write: returns (q int8, scale f32 ``[...]``) with q*scale ≈ x."""
    amax = jnp.max(jnp.abs(x), axis=-1)
    scale = (jnp.maximum(amax, 1e-8) / 127.0).astype(jnp.float32)
    q = jnp.clip(jnp.round(x / scale[..., None]), -127, 127).astype(jnp.int8)
    return q, scale


@register_serde
@dataclass
class MultiHeadAttention(BaseLayerConf):
    """Multi-head self-attention over RNN-typed input [b, t, n_in].

    Projections pack all heads into single [n_in, h*d] matmuls (MXU-shaped);
    softmax statistics run in at least float32 even under bfloat16 params.

    ``positions='rotary'`` turns q and k by their absolute position
    (``_rotary``, base ``rope_theta``).  ``attention`` chooses the key set:
    ``'full'``, ``'sliding'`` (causal, the last ``window`` keys) or
    ``'eva'`` (``_eva_attention``: ``window``, ``chunk``, and two learned
    ``[h, d]`` leaves, ``phi`` and ``mu``; causal only).  All reach the
    flash kernels under ``attn_impl`` 'auto' or 'flash'; a key-padding
    mask sends 'auto' to the reference and makes 'flash' raise (it
    refuses, it never falls back), and 'eva' takes none.
    ``n_kv_heads`` fewer than ``n_heads`` gives grouped K/V heads: ``Wk``,
    ``Wv`` project to ``n_kv_heads * d`` and each K/V head serves
    ``n_heads / n_kv_heads`` query heads (expanded before the attention:
    the kernels see ``n_heads`` of each).  ``qk_norm`` normalises q and k
    over each head's ``d`` (``_rms_norm``, gains ``q_norm``, ``k_norm``)
    before the positions; ``out_gate`` multiplies the attention's output
    by ``sigmoid(x Wg)`` before the output projection.  All of these train
    and run forward; the KV-cache path (``attend_cached``) refuses them.

    HAS_CARRY: the carry is a KV cache ({k, v, pos}, capacity
    ``max_cache_len``) enabling incremental decoding through
    ``rnn_time_step`` — the attention-era face of the reference's stateful
    RNN inference.  Past ``max_cache_len`` the slice update saturates
    (oldest semantics undefined); size the cache for the longest sequence.
    """
    INPUT_KIND = "rnn"
    HAS_CARRY = True
    _BIAS_PARAMS = ("bq", "bk", "bv", "bo", "phi", "mu", "q_norm", "k_norm")

    n_in: int = 0
    n_out: int = 0              # model/embed dim of the output projection
    n_heads: int = 4
    head_dim: int = 0           # default n_out // n_heads
    causal: bool = False
    attn_impl: str = "auto"     # reference|flash|ring|ulysses|auto
    # 'auto' crossover override: flash at seq >= this (None = the measured
    # DEFAULT_FLASH_MIN_SEQ / env DL4J_TPU_FLASH_MIN_SEQ)
    flash_min_seq: Optional[int] = None
    seq_axis: str = "seq"
    has_bias: bool = True
    attn_dropout: Optional[float] = None   # retain prob on attention output
    max_cache_len: int = 512    # KV-cache capacity for incremental decode
    positions: str = "none"     # none|rotary
    rope_theta: float = 10000.0
    attention: str = "full"     # full|sliding|eva
    window: int = 0             # sliding, eva: keys attended exactly
    chunk: int = 0              # eva: keys pooled into one summary
    n_kv_heads: int = 0         # default n_heads
    qk_norm: bool = False       # RMSNorm over each head of q and k
    out_gate: bool = False      # sigmoid(x Wg) on the attention's output
    eps: float = 1e-5           # of qk_norm

    def set_n_in(self, itype: InputType, override: bool = False) -> None:
        if self.n_in == 0 or override:
            if itype.kind != "rnn":
                raise ValueError(f"layer '{self.name}': MultiHeadAttention "
                                 f"expects RNN input, got {itype}")
            self.n_in = itype.size
        if self.n_out == 0:
            self.n_out = self.n_in

    def output_type(self, itype: InputType) -> InputType:
        return InputType.recurrent(self.n_out, itype.timesteps)

    def _dims(self):
        d = self.head_dim or max(1, self.n_out // self.n_heads)
        return self.n_heads, d

    def _kv_heads(self) -> int:
        return self.n_kv_heads or self.n_heads

    def init(self, key, itype):
        h, d = self._dims()
        kv = self._kv_heads()
        if h % kv:
            raise ValueError(f"layer '{self.name}': {h} query heads are no "
                             f"multiple of {kv} K/V heads")
        ks = jax.random.split(key, 4)
        params = {
            "Wq": self.make_weight(ks[0], (self.n_in, h * d)),
            "Wk": self.make_weight(ks[1], (self.n_in, kv * d)),
            "Wv": self.make_weight(ks[2], (self.n_in, kv * d)),
            "Wo": self.make_weight(ks[3], (h * d, self.n_out)),
        }
        if self.has_bias:
            params.update(bq=self.make_bias((h * d,)),
                          bk=self.make_bias((kv * d,)),
                          bv=self.make_bias((kv * d,)),
                          bo=self.make_bias((self.n_out,)))
        if self.qk_norm:
            # gains as offsets from one (_rms_norm)
            params.update(q_norm=jnp.zeros((d,), self._dtype()),
                          k_norm=jnp.zeros((d,), self._dtype()))
        if self.out_gate:
            params["Wg"] = self.make_weight(jax.random.fold_in(key, 5),
                                            (self.n_in, h * d))
        if self.attention == "sliding":
            if not (self.causal and self.window > 0):
                raise ValueError(
                    f"layer '{self.name}': attention='sliding' is causal "
                    "and needs a window")
        elif self.attention == "eva":
            if not (self.causal and self.window and self.chunk):
                raise ValueError(
                    f"layer '{self.name}': attention='eva' is causal and "
                    "needs a window and a chunk")
            # normal, clipped to [-1, 1], times d^-1/2
            for name, k_ in zip(("phi", "mu"),
                                jax.random.split(jax.random.fold_in(key, 4))):
                params[name] = (jnp.clip(jax.random.normal(
                    k_, (h, d), self._dtype()), -1.0, 1.0) * d ** -0.5)
        elif self.attention != "full":
            raise ValueError(f"layer '{self.name}': unknown attention "
                             f"'{self.attention}'; expected full, sliding "
                             "or eva")
        if self.positions not in ("none", "rotary"):
            raise ValueError(f"layer '{self.name}': unknown positions "
                             f"'{self.positions}'; expected none or rotary")
        return {"params": params, "state": {}}

    def _heads(self, x, p, w, b):
        _, d = self._dims()
        y = x @ p[w]
        if self.has_bias:
            y = y + p[b]
        btime = y.shape[:-1]
        return y.reshape(*btime, -1, d).transpose(0, 2, 1, 3)  # [b,h,t,d]

    def attend(self, p, x, *, train=False, key=None, mask=None):
        """QKV projection → attention → output projection on [b,t,f] input."""
        q = self._heads(x, p, "Wq", "bq")
        k = self._heads(x, p, "Wk", "bk")
        v = self._heads(x, p, "Wv", "bv")
        if self.qk_norm:
            q = _rms_norm(q, p["q_norm"], self.eps)
            k = _rms_norm(k, p["k_norm"], self.eps)
        if self.positions == "rotary":
            q, k = _rotary(q, self.rope_theta), _rotary(k, self.rope_theta)
        # as the attention takes them: the layout a backward reads
        q = checkpoint_name(q, "attn_q")
        k = checkpoint_name(k, "attn_k")
        v = checkpoint_name(v, "attn_v")
        group = self.n_heads // self._kv_heads()
        if group > 1:
            # grouped K/V heads reach the kernels expanded: each K/V head
            # beside the query heads it serves
            k, v = (jnp.repeat(a, group, axis=1) for a in (k, v))
        if self.attention == "eva":
            if mask is not None:
                raise ValueError("attention='eva' takes no key-padding mask")
            o = _eva_attention(q, k, v, p["phi"], p["mu"],
                               window=self.window, chunk=self.chunk,
                               impl=self.attn_impl,
                               flash_min_seq=self.flash_min_seq)
        else:
            o = _run_attention(q, k, v, impl=self.attn_impl,
                               causal=self.causal, mask=mask,
                               seq_axis=self.seq_axis,
                               flash_min_seq=self.flash_min_seq,
                               window=(self.window
                                       if self.attention == "sliding"
                                       else None))
        b_, h, t, d = o.shape
        o = o.transpose(0, 2, 1, 3).reshape(b_, t, h * d)
        if self.out_gate:
            o = o * jax.nn.sigmoid(checkpoint_name(x @ p["Wg"], "attn_gate"))
        y = o @ p["Wo"]
        if self.has_bias:
            y = y + p["bo"]
        return self._maybe_attn_dropout(y, train, key)

    def _maybe_attn_dropout(self, y, train, key):
        if train and self.attn_dropout and key is not None:
            keep = self.attn_dropout
            mask_d = jax.random.bernoulli(jax.random.fold_in(key, 7), keep,
                                          y.shape)
            y = jnp.where(mask_d, y / keep, 0.0)
        return y

    def apply(self, variables, x, *, train=False, key=None, mask=None):
        p = self.maybe_noise_weights(key, variables["params"], train)
        x = self.maybe_dropout_input(key, x, train)
        y = self.attend(p, x, train=train, key=key, mask=mask)
        return self.act_fn(y), variables.get("state", {})

    # ---- KV-cache incremental decoding -----------------------------------
    def init_carry(self, batch: int, dtype=jnp.float32,
                   max_len: Optional[int] = None):
        """Zero carry.  ``max_len`` overrides the cache capacity (the
        generation subsystem sizes prefill carries to the prompt bucket
        and slot caches to the engine's ``max_seq``); ``attend_cached``
        derives the capacity from the carry itself, so carries of any
        length ride the same code."""
        h, d = self._dims()
        L = self.max_cache_len if max_len is None else int(max_len)
        return {"k": jnp.zeros((batch, h, L, d), dtype),
                "v": jnp.zeros((batch, h, L, d), dtype),
                "m": jnp.zeros((batch, L), jnp.float32),   # cache validity
                "pos": jnp.zeros((), jnp.int32)}

    def attend_cached(self, p, x, carry, *, mask=None):
        """Project the t new steps, extend the cache, attend q against the
        full prefix (``sdpa_reference`` with q_offset — one SDPA
        implementation).  Honors self.causal and key-padding masks; masked
        positions are recorded invalid in the cache.  Returns
        (y [b,t,n_out], new_carry).

        ``carry["pos"]`` is a scalar (every row at the same stream
        position — tBPTT chunks, ``rnn_time_step``) or a ``[b]`` vector
        (per-row positions — the generation engine's slot-batched decode,
        where every slot sits at its own sequence offset).  The vector
        form supports single-token steps only (t == 1): causality then
        reduces to the written-prefix mask, so one fixed-shape decode
        program serves every slot mix.

        A carry holding ``kp`` (a paged block pool) dispatches to
        :meth:`_attend_paged` instead — same contract, K/V gathered
        through a block table."""
        from ...ops.attention import sdpa_reference
        if self.positions != "none" or self.attention != "full" or \
                self._kv_heads() != self.n_heads or self.qk_norm or \
                self.out_gate:
            raise NotImplementedError(
                "the KV-cache path has no rotary positions, no sliding or "
                "eva attention, no grouped K/V heads, no q/k norm and no "
                "output gate yet: such a layer trains and runs forward "
                "only")
        if isinstance(carry, dict) and "kp" in carry:
            return self._attend_paged(p, x, carry, mask=mask)
        q = self._heads(x, p, "Wq", "bq")                 # [b,h,t,d]
        k_new = self._heads(x, p, "Wk", "bk")
        v_new = self._heads(x, p, "Wv", "bv")
        pos = carry["pos"]
        L = carry["k"].shape[2]        # capacity from the carry, not conf
        t = q.shape[2]
        b_ = x.shape[0]
        chunk_valid = (jnp.ones((b_, t), jnp.float32) if mask is None
                       else mask.astype(jnp.float32))
        if getattr(pos, "ndim", 0) == 1:
            if t != 1:
                raise ValueError(
                    "per-row vector pos carries support single-token decode "
                    f"only (t=1), got a {t}-step chunk")
            z = jnp.zeros((), pos.dtype)
            k = jax.vmap(lambda c, n, p_: jax.lax.dynamic_update_slice(
                c, n, (z, p_, z)))(carry["k"],
                                   k_new.astype(carry["k"].dtype), pos)
            v = jax.vmap(lambda c, n, p_: jax.lax.dynamic_update_slice(
                c, n, (z, p_, z)))(carry["v"],
                                   v_new.astype(carry["v"].dtype), pos)
            m = jax.vmap(lambda mm, cv, p_: jax.lax.dynamic_update_slice(
                mm, cv, (p_,)))(carry["m"], chunk_valid, pos)
            written = (jnp.arange(L)[None, :]
                       < (pos + t)[:, None]).astype(jnp.float32)   # [b, L]
            key_mask = m * written
            # t == 1: the single query sits at the newest position, so the
            # written-prefix mask IS the causal mask — no q_offset needed
            o = sdpa_reference(q, k.astype(q.dtype), v.astype(q.dtype),
                               mask=key_mask, causal=False)
        else:
            z = jnp.zeros((), pos.dtype)   # index dtypes must match (x64)
            k = jax.lax.dynamic_update_slice(
                carry["k"], k_new.astype(carry["k"].dtype), (z, z, pos, z))
            v = jax.lax.dynamic_update_slice(
                carry["v"], v_new.astype(carry["v"].dtype), (z, z, pos, z))
            m = jax.lax.dynamic_update_slice(carry["m"], chunk_valid,
                                             (z, pos))
            written = (jnp.arange(L) < pos + t).astype(jnp.float32)   # [L]
            key_mask = m * written[None, :]                            # [b, L]
            o = sdpa_reference(q, k.astype(q.dtype), v.astype(q.dtype),
                               mask=key_mask, causal=self.causal,
                               q_offset=pos)
        o = o.transpose(0, 2, 1, 3).reshape(b_, t, -1)
        y = o @ p["Wo"]
        if self.has_bias:
            y = y + p["bo"]
        if mask is not None:   # zero outputs at padded query steps
            y = y * mask.astype(y.dtype)[:, :, None]
        return y, {"k": k, "v": v, "m": m, "pos": pos + t}

    @staticmethod
    def _gather_pool(pool, scales, table, dtype):
        """Materialize ``[S, h, V, d]`` keys/values by gathering pool
        blocks through an ``[S, NB]`` block table (V = NB * block_size;
        virtual position == token position).  int8 pools dequantize
        against their ``[n_blocks, h, block]`` scales here — quantized
        storage, full-precision math."""
        g = pool[table]                            # [S, NB, h, blk, d]
        if scales is not None:
            g = g.astype(jnp.float32) * scales[table][..., None]
        s_, nb, h, blk, d = g.shape
        return g.transpose(0, 2, 1, 3, 4).reshape(s_, h, nb * blk,
                                                  d).astype(dtype)

    def _attend_paged(self, p, x, carry, *, mask=None):
        """Gather-through-table attention over the paged KV block pool
        (``generation/cache.PagedKV``).  Carry schema: ``kp``/``vp``
        ``[n_blocks, h, block, d]`` pools (int8 pools add ``ksc``/``vsc``
        ``[n_blocks, h, block]`` scales) plus the block ``table`` and
        ``pos`` — ``[S, NB]`` table with vector ``[S]`` positions for the
        fixed-shape decode step, ``[NB]`` row with a scalar suffix start
        for shared-prefix prefill.  Tables and positions are DATA, never
        shapes, so every slot/block mix rides one compiled program.

        Writes land at ``table[pos // block], pos % block``; padded and
        inactive lanes redirect to physical block 0 (the trash block —
        reserved, never allocated, mask-dead).  Reads gather the full
        virtual axis ``V = NB * block`` with virtual position == token
        position, so the written-prefix mask is exactly the dense ring's
        mask and the softmax sees the same finite entries in the same
        order — masked tail entries contribute exact zeros, which is
        what makes paged-vs-dense token streams bit-identical on
        sequential-reduction backends."""
        from ...ops.attention import sdpa_reference
        q = self._heads(x, p, "Wq", "bq")                 # [b,h,t,d]
        k_new = self._heads(x, p, "Wk", "bk")
        v_new = self._heads(x, p, "Wv", "bv")
        kp, vp = carry["kp"], carry["vp"]
        table, pos = carry["table"], carry["pos"]
        quant = kp.dtype == jnp.int8
        blk = kp.shape[2]
        t = q.shape[2]
        b_ = x.shape[0]
        chunk_valid = (jnp.ones((b_, t), jnp.float32) if mask is None
                       else mask.astype(jnp.float32))
        new_carry = dict(carry)
        if getattr(pos, "ndim", 0) == 1:
            # decode: one token per slot, per-slot positions, [S, NB]
            if t != 1:
                raise ValueError(
                    "per-slot vector pos supports single-token decode "
                    f"only (t=1), got a {t}-step chunk")
            nb = table.shape[1]
            bidx = jnp.clip(pos // blk, 0, nb - 1)
            phys = jnp.take_along_axis(table, bidx[:, None], axis=1)[:, 0]
            off = pos % blk
            kw = k_new[:, :, 0, :]                        # [S, h, d]
            vw = v_new[:, :, 0, :]
            tab2 = table
            written = (jnp.arange(nb * blk)[None, :]
                       < (pos + t)[:, None]).astype(jnp.float32)
            causal, q_offset = False, 0
        else:
            # shared-prefix prefill: batch 1, t suffix steps from `pos`
            nb = table.shape[0]
            p_j = pos + jnp.arange(t, dtype=jnp.int32)
            bidx = jnp.clip(p_j // blk, 0, nb - 1)
            phys = jnp.where(chunk_valid[0] > 0, table[bidx], 0)
            off = p_j % blk
            kw = k_new[0].transpose(1, 0, 2)              # [t, h, d]
            vw = v_new[0].transpose(1, 0, 2)
            tab2 = table[None, :]
            v_ax = nb * blk
            prefix = (jnp.arange(v_ax, dtype=jnp.int32)
                      < pos).astype(jnp.float32)
            chunk_m = jax.lax.dynamic_update_slice(
                jnp.zeros((v_ax,), jnp.float32), chunk_valid[0], (pos,))
            written = jnp.clip(prefix + chunk_m, 0.0, 1.0)[None, :]
            causal, q_offset = self.causal, pos
        if quant:
            kq, ks = _kv_quantize(kw)
            vq, vs = _kv_quantize(vw)
            kp = kp.at[phys, :, off, :].set(kq)
            vp = vp.at[phys, :, off, :].set(vq)
            new_carry["ksc"] = carry["ksc"].at[phys, :, off].set(ks)
            new_carry["vsc"] = carry["vsc"].at[phys, :, off].set(vs)
        else:
            kp = kp.at[phys, :, off, :].set(kw.astype(kp.dtype))
            vp = vp.at[phys, :, off, :].set(vw.astype(vp.dtype))
        k = self._gather_pool(kp, new_carry.get("ksc"), tab2, q.dtype)
        v = self._gather_pool(vp, new_carry.get("vsc"), tab2, q.dtype)
        o = sdpa_reference(q, k, v, mask=written, causal=causal,
                           q_offset=q_offset)
        new_carry.update(kp=kp, vp=vp, pos=pos + t)
        o = o.transpose(0, 2, 1, 3).reshape(b_, t, -1)
        y = o @ p["Wo"]
        if self.has_bias:
            y = y + p["bo"]
        if mask is not None:   # zero outputs at padded query steps
            y = y * mask.astype(y.dtype)[:, :, None]
        return y, new_carry

    def apply_with_carry(self, variables, x, carry, *, train=False,
                         key=None, mask=None):
        if carry is None:
            carry = self.init_carry(x.shape[0], x.dtype)
        p = self.maybe_noise_weights(key, variables["params"], train)
        x = self.maybe_dropout_input(key, x, train)
        y, new_carry = self.attend_cached(p, x, carry, mask=mask)
        y = self._maybe_attn_dropout(y, train, key)
        return self.act_fn(y), new_carry


@register_serde
@dataclass
class LatentAttention(BaseLayerConf):
    """Multi-head latent attention (DeepSeek-V2/V3, arXiv:2412.19437
    section 2.1) over RNN-typed input ``[b, t, n_in]``, in the expanded form
    training runs; causal, no bias anywhere.

    ``c_q = N(x Wqa)`` (``q_rank`` wide) and ``q = c_q Wqb`` as ``n_heads``
    heads of ``[head_dim | rope_dim]``; ``[c_kv | k_r] = x Wkva``
    (``kv_rank`` and ``rope_dim`` wide); ``N(c_kv) Wkvb`` as ``n_heads``
    heads of ``[k_nope head_dim | v v_dim]``.  ``N`` is a gain-only RMSNorm
    (``qa_norm``, ``kva_norm``; gains stored as offsets from one).  The
    ``rope_dim`` part of q and the one ``k_r``, shared by every head, are
    turned by rotary positions on adjacent pairs (``_rotary_pairs``, base
    ``rope_theta``); the ``head_dim`` part carries no positions.  A head of
    q and k is ``head_dim + rope_dim`` wide (the softmax scale follows
    that), a head of v ``v_dim``: the flash kernels take both widths as
    they are.  The output is ``concat(o_h) Wo``, ``n_heads * v_dim`` to
    ``n_out``.

    **The keys reach the flash kernels as the projections wrote them**
    (``project``: ``q``, ``kv``, ``k_r``): ``kv`` is the up-projection's
    product, ``[k_nope | v]`` a head, which the kernels read as two column
    blocks, and ``k_r`` the one rotary key, which every head's kernel row
    reads through its index map (``flash_attention(q, kv=, k_shared=)``).
    No ``[b, h, t, head_dim + rope_dim]`` key is assembled, nothing is
    copied to the heads, and ``kv``'s gradient comes back as ``kv`` lies.
    That form needs ``head_dim == v_dim``, a multiple of 128
    (``flash_blocks``); at other widths, and wherever the reference
    attention runs (``attn_impl='reference'``, 'auto' under
    ``flash_min_seq`` or off the TPU, a key mask), ``whole_keys``
    assembles ``k`` and ``v`` from the same three arrays.

    The five products, the two norms, the rotary part and the assembling of
    q (and of k and v where they are assembled) run under the scope
    ``mla_project``; the attention itself under
    ``attn_full``.  Trains and runs forward; there is
    no KV-cache path: a cache of the latent (``kv_rank + rope_dim`` a token)
    with the absorbed products is not written, so ``attend_cached`` raises,
    as ``MultiHeadAttention``'s does for its newer choices."""
    INPUT_KIND = "rnn"
    _BIAS_PARAMS = ("qa_norm", "kva_norm")

    n_in: int = 0
    n_out: int = 0
    n_heads: int = 4
    head_dim: int = 0           # of q and k, the part without positions
    rope_dim: int = 0           # of q and k, the rotary part
    v_dim: int = 0              # of v; default head_dim
    q_rank: int = 0
    kv_rank: int = 0
    attn_impl: str = "auto"     # reference|flash|auto
    flash_min_seq: Optional[int] = None
    rope_theta: float = 10000.0
    eps: float = 1e-6

    def set_n_in(self, itype: InputType, override: bool = False) -> None:
        if self.n_in == 0 or override:
            if itype.kind != "rnn":
                raise ValueError(f"layer '{self.name}': LatentAttention "
                                 f"expects RNN input, got {itype}")
            self.n_in = itype.size
        if self.n_out == 0:
            self.n_out = self.n_in

    def output_type(self, itype: InputType) -> InputType:
        return InputType.recurrent(self.n_out, itype.timesteps)

    def _widths(self):
        """``(heads, nope, rope, v)``."""
        return (self.n_heads, self.head_dim, self.rope_dim,
                self.v_dim or self.head_dim)

    def init(self, key, itype):
        h, dn, dr, dv = self._widths()
        if min(dn, self.q_rank, self.kv_rank) <= 0 or dr <= 0 or dr % 2:
            raise ValueError(
                f"layer '{self.name}': latent attention needs head_dim, "
                f"q_rank, kv_rank and an even rope_dim: {dn}, "
                f"{self.q_rank}, {self.kv_rank}, {dr}")
        if self.attn_impl not in ("auto", "reference", "flash"):
            raise ValueError(f"layer '{self.name}': latent attention runs "
                             "'auto', 'flash' or 'reference', not "
                             f"attn_impl='{self.attn_impl}'")
        ks = jax.random.split(key, 5)
        params = {
            "Wqa": self.make_weight(ks[0], (self.n_in, self.q_rank)),
            "Wqb": self.make_weight(ks[1], (self.q_rank, h * (dn + dr))),
            "Wkva": self.make_weight(ks[2], (self.n_in, self.kv_rank + dr)),
            "Wkvb": self.make_weight(ks[3], (self.kv_rank, h * (dn + dv))),
            "Wo": self.make_weight(ks[4], (h * dv, self.n_out)),
            # gains as offsets from one (_rms_norm)
            "qa_norm": jnp.zeros((self.q_rank,), self._dtype()),
            "kva_norm": jnp.zeros((self.kv_rank,), self._dtype()),
        }
        return {"params": params, "state": {}}

    def project(self, p, x):
        """``(q, kv, k_r)`` as the projections write them: q ``[b, h, t,
        head_dim + rope_dim]``, its rotary part turned; ``kv`` ``[b, h, t,
        head_dim + v_dim]``, each head's ``k_nope`` beside its ``v``, the
        up-projection's product and nothing done to it; ``k_r`` ``[b, 1, t,
        rope_dim]``, the one rotary key a position, turned."""
        h, dn, dr, dv = self._widths()
        b, t, _ = x.shape

        def heads(y, d):
            return y.reshape(b, t, h, d).transpose(0, 2, 1, 3)
        q = heads(_rms_norm(x @ p["Wqa"], p["qa_norm"], self.eps)
                  @ p["Wqb"], dn + dr)
        kva = x @ p["Wkva"]
        kv = heads(_rms_norm(kva[..., :self.kv_rank], p["kva_norm"],
                             self.eps) @ p["Wkvb"], dn + dv)
        # one rotary key a position, for every head
        k_r = _rotary_pairs(kva[:, None, :, self.kv_rank:], self.rope_theta)
        q = jnp.concatenate(
            [q[..., :dn], _rotary_pairs(q[..., dn:], self.rope_theta)],
            axis=-1)
        return q, kv, k_r

    def whole_keys(self, kv, k_r):
        """``(k, v)`` a head, assembled for an attention that takes them
        whole: ``k`` is ``k_nope`` beside the rotary key copied to every
        head, ``[b, h, t, head_dim + rope_dim]``, ``v`` the rest of
        ``kv``."""
        dn = self.head_dim
        k = jnp.concatenate(
            [kv[..., :dn],
             jnp.broadcast_to(k_r, kv.shape[:3] + k_r.shape[3:])], axis=-1)
        return k, kv[..., dn:]

    def _flash_in_parts(self, t: int, mask) -> bool:
        """Whether this call runs the flash kernels on the keys as
        ``project`` leaves them: the kernels are what runs (asked for, or
        what 'auto' resolves to here) and they take these widths in
        parts."""
        from ...ops.flash_attention import flash_blocks
        _, dn, dr, dv = self._widths()
        impl = self.attn_impl
        if impl == "auto":
            impl = auto_attention_impl(
                t, t, dn + dr, masked=mask is not None,
                flash_min_seq=self.flash_min_seq, d_v=dv)
        if impl != "flash" or mask is not None:
            return False
        try:
            flash_blocks(t, t, dn + dr, d_v=dv, d_shared=dr)
        except ValueError:
            return False
        return True

    def attend(self, p, x, *, train=False, key=None, mask=None):
        from ...observability.registry import default_registry
        reg = default_registry()
        if reg.enabled:
            # trace-time, like moe_layers_traced_total
            h, dn, dr, dv = self._widths()
            reg.counter("mla_layers_traced_total",
                        "Latent-attention layers traced into a program, by "
                        "heads and the width of a head of q/k and of v",
                        ("heads", "qk", "v")).labels(
                            str(h), str(dn + dr), str(dv)).inc()
        parts = self._flash_in_parts(x.shape[1], mask)
        with jax.named_scope("mla_project"):
            q, kv, k_r = self.project(p, x)
            # as the attention takes them: the layout a backward reads
            q = checkpoint_name(q, "attn_q")
            if parts:
                kv = checkpoint_name(kv, "attn_kv")
                k_r = checkpoint_name(k_r, "attn_k_shared")
            else:
                k, v = self.whole_keys(kv, k_r)
                k = checkpoint_name(k, "attn_k")
                v = checkpoint_name(v, "attn_v")
        if parts:
            from ...ops.flash_attention import flash_attention
            with jax.named_scope("attn_full"):
                # the kernel names its own output and log-sum-exp
                o = flash_attention(q, kv=kv, k_shared=k_r, causal=True)
        else:
            o = _run_attention(q, k, v, impl=self.attn_impl, causal=True,
                               mask=mask, seq_axis="seq",
                               flash_min_seq=self.flash_min_seq)
        with jax.named_scope("mla_project"):
            b_, h, t, dv = o.shape
            return o.transpose(0, 2, 1, 3).reshape(b_, t, h * dv) @ p["Wo"]

    def apply(self, variables, x, *, train=False, key=None, mask=None):
        p = self.maybe_noise_weights(key, variables["params"], train)
        x = self.maybe_dropout_input(key, x, train)
        y = self.attend(p, x, train=train, key=key, mask=mask)
        return self.act_fn(y), variables.get("state", {})

    def init_carry(self, batch: int, dtype=jnp.float32,
                   max_len: Optional[int] = None):
        return {"pos": jnp.zeros((), jnp.int32)}

    def attend_cached(self, p, x, carry, *, mask=None):
        raise NotImplementedError(
            "latent attention has no KV-cache path (no latent cache, no "
            "absorbed decode form yet): such a layer trains and runs "
            "forward only")


@register_serde
@dataclass
class TransformerBlock(BaseLayerConf):
    """Pre-norm transformer block: norm→MHA→residual, norm→MLP→residual.

    The attention half delegates to ``MultiHeadAttention`` (params carried
    under a ``mha_`` prefix) so the two layers share one projection/head
    implementation; ``ffn_hidden`` (else ``ffn_mult`` times the width)
    sizes the hidden MLP.

    The defaults are GPT-2's block: LayerNorm, no positions of its own,
    heads of ``n_in / n_heads``, a GELU MLP, biases everywhere, full
    attention.  The fields after ``aux_loss_weight`` choose the block of
    today's decoders instead: ``norm='rms'`` (``_rms_norm``: unit offset,
    no shift), ``positions='rotary'``, an explicit ``head_dim``,
    ``gated=True`` (``W2 (silu(Wg x) * (W1 x))``), ``has_bias=False``
    (no bias in any projection), ``attention='eva'`` with ``window`` and
    ``chunk`` or ``attention='sliding'`` with ``window``, ``n_kv_heads``,
    ``qk_norm`` and ``attn_gate`` (``MultiHeadAttention``'s grouped K/V
    heads, per-head q/k norm and output gate), ``attention='latent'`` (the
    attention half is then a ``LatentAttention``, a layer of its own:
    ``latent_q_rank``, ``latent_kv_rank``, ``head_dim`` the part of a q/k
    head without positions, ``rope_dim`` the rotary part, ``v_head_dim``;
    ``positions``, ``n_kv_heads``, ``qk_norm`` and ``attn_gate`` do not
    apply to it), ``post_norm=True`` (a norm
    after each half as well as before it: ``x + N2(Attn(N1 x))``, ``x +
    N4(FFN(N3 x))``; gains ``ln1p_g``, ``ln2p_g``), and with
    ``moe_top_k > 0`` the routed FFN without dropped tokens
    (``nn/layers/moe.RoutedExperts``: ``moe_experts`` routed over,
    ``moe_held=(first, count)`` of them held here, ``moe_hidden`` wide,
    ``moe_shared`` shared experts beside them, gated and biased as the
    block is); ``residual_dtype='float32'`` keeps the residual stream
    (the block's input, its two adds and its output) in float32 under a
    lower compute type, as EvaByte's ``fp32_skip_add`` does: the walk then
    hands the block its input uncast (``PrecisionPolicy.input_dtype``) and
    each norm's output goes to the projections in their own type.  The
    defaults trace the program they always did.

    A block may lie in a looped range of the list (``ListBuilder.loop``:
    ``OuroLM`` walks its blocks four times on one set of weights): it is
    then applied once a pass to the last pass's output, with the same
    positions in every pass, its parameters exist once and their gradient
    is the sum over the passes; ``AUX_LOSS`` blocks (``moe_experts`` on the
    top-1 path) are refused there at build time.

    The KV-cache path (``apply_with_carry`` -> ``attend_cached``) serves the
    GPT-2 block alone: it raises ``NotImplementedError`` for rotary
    positions, sliding and EVA attention, grouped K/V heads, q/k norm, the
    output gate, and for latent attention (no latent cache and no absorbed
    decode form is written).  Such a block trains and runs forward only.
    """
    INPUT_KIND = "rnn"
    HAS_CARRY = True
    _BIAS_PARAMS = ("mha_bq", "mha_bk", "mha_bv", "mha_bo", "b1", "b2",
                    "ln1_g", "ln1_b", "ln2_g", "ln2_b", "mha_phi", "mha_mu",
                    "mha_q_norm", "mha_k_norm", "ln1p_g", "ln1p_b",
                    "ln2p_g", "ln2p_b", "mha_qa_norm", "mha_kva_norm")

    n_in: int = 0
    n_heads: int = 4
    ffn_mult: int = 4
    causal: bool = True
    attn_impl: str = "auto"
    flash_min_seq: Optional[int] = None   # 'auto' crossover override
    seq_axis: str = "seq"
    eps: float = 1e-5
    max_cache_len: int = 512
    # Switch-transformer style sparse FFN: >0 replaces the dense MLP with
    # a top-1 routed expert stack (aux loss threads through state)
    moe_experts: int = 0
    moe_capacity_factor: float = 1.25
    aux_loss_weight: float = 0.01
    norm: str = "layer"         # layer|rms
    positions: str = "none"     # none|rotary
    rope_theta: float = 10000.0
    head_dim: int = 0           # default n_in // n_heads
    ffn_hidden: int = 0         # default ffn_mult * n_in
    gated: bool = False         # silu(Wg x) * (W1 x) in place of gelu(W1 x)
    has_bias: bool = True
    attention: str = "full"     # full|sliding|eva|latent
    window: int = 0
    chunk: int = 0
    residual_dtype: Optional[str] = None   # None: the compute type
    n_kv_heads: int = 0         # default n_heads
    qk_norm: bool = False
    attn_gate: bool = False
    post_norm: bool = False
    # moe_top_k > 0: moe_experts are routed over top-k with no capacity
    # and no dropped token (RoutedExperts) in place of the top-1 path
    moe_top_k: int = 0
    moe_scoring: str = "softmax"    # softmax|sigmoid
    moe_route_norm: bool = False
    moe_route_scale: float = 1.0
    moe_shared: int = 0
    moe_hidden: int = 0             # default ffn_hidden
    moe_held: Optional[Tuple[int, int]] = None   # (first, count); None: all
    # attention='latent' (LatentAttention); head_dim is then the part of a
    # q/k head that carries no positions
    latent_q_rank: int = 0
    latent_kv_rank: int = 0
    rope_dim: int = 0
    v_head_dim: int = 0             # default head_dim

    @property
    def AUX_LOSS(self):
        return self.moe_experts > 0

    def _routed(self):
        from .moe import RoutedExperts
        f = self.moe_hidden or self.ffn_hidden or self.ffn_mult * self.n_in
        return RoutedExperts(
            n_in=self.n_in, hidden=f, experts_total=self.moe_experts,
            top_k=self.moe_top_k, scoring=self.moe_scoring,
            route_norm=self.moe_route_norm,
            route_scale=self.moe_route_scale,
            shared_experts=self.moe_shared,
            experts_held=tuple(self.moe_held) if self.moe_held else None,
            gated=self.gated, has_bias=self.has_bias)

    @property
    def SAVED_NAMES(self):
        """What a backward pass cannot cheaply rebuild from the block's
        input, each tagged with ``checkpoint_name`` where it is computed:
        q, k, v after the head split, the attention's output and (from the
        flash kernel) its log-sum-exp, the stream after the first add, the
        MLP's pre-activation and its gate's; of EVA attention the pooled
        keys and values and the pooling weights.  A scanned run saves these
        and recomputes the rest: the norms, the activation, the head merge
        (``nn/scan_layers.run_scan``).  **In order of worth**, what the
        backward would repeat per byte stacked, for a run under
        ``cache_mode="remat"`` that has room for some of them only: EVA's
        three (a sixteenth of k and v, and the pool's middle pass), the
        matmul outputs (a product's FLOPs per byte of output is its
        contraction width, the same for all five: q and k first, which
        carry their rotation too, then v, then the MLP's two, each as large
        as q, k and v together, so what is left of the room rarely holds
        one), the kernel's log-sum-exp and output, then the float32 stream
        after the first add, which saves the output projection alone.  The
        order means nothing where every name is kept.
        Sequence-parallel attention is a loop of collectives, which a
        backward must not replay: such a block declares nothing."""
        if self.attn_impl in ("ring", "ulysses"):
            return ()
        eva = ("eva_ks", "eva_vs", "eva_a") if self.attention == "eva" \
            else ()
        # the output gate's product, as large as q and as dear
        gate = ("attn_gate",) if self.attn_gate else ()
        # a latent block names its keys as the attention takes them: the
        # [k | v] product and the one rotary key, or k and v assembled
        keys = ("attn_kv", "attn_k_shared") if self.attention == "latent" \
            else ()
        return eva + ("attn_q",) + keys + ("attn_k", "attn_v") + gate + (
            "mlp_up", "mlp_gate", "attn_lse", "attn_out", "block_mid")

    def set_n_in(self, itype: InputType, override: bool = False) -> None:
        if self.n_in == 0 or override:
            if itype.kind != "rnn":
                raise ValueError(f"layer '{self.name}': TransformerBlock "
                                 f"expects RNN input, got {itype}")
            self.n_in = itype.size

    def output_type(self, itype: InputType) -> InputType:
        return InputType.recurrent(self.n_in, itype.timesteps)

    def _mha(self):
        """The attention half's layer: ``MultiHeadAttention``, or for
        ``attention='latent'`` a ``LatentAttention``."""
        if self.attention == "latent":
            if not self.causal:
                raise ValueError(f"layer '{self.name}': latent attention "
                                 "is causal; causal=False is not written")
            return LatentAttention(
                n_in=self.n_in, n_out=self.n_in, n_heads=self.n_heads,
                head_dim=self.head_dim, rope_dim=self.rope_dim,
                v_dim=self.v_head_dim, q_rank=self.latent_q_rank,
                kv_rank=self.latent_kv_rank,
                attn_impl=self.attn_impl, flash_min_seq=self.flash_min_seq,
                rope_theta=self.rope_theta, eps=self.eps,
                activation="identity", weight_init=self.weight_init,
                weight_dist=self.weight_dist, dtype=self.dtype)
        m = MultiHeadAttention(
            n_in=self.n_in, n_out=self.n_in, n_heads=self.n_heads,
            causal=self.causal, attn_impl=self.attn_impl,
            flash_min_seq=self.flash_min_seq,
            seq_axis=self.seq_axis, activation="identity",
            weight_init=self.weight_init, weight_dist=self.weight_dist,
            bias_init=self.bias_init, dtype=self.dtype,
            max_cache_len=self.max_cache_len, head_dim=self.head_dim,
            has_bias=self.has_bias, positions=self.positions,
            rope_theta=self.rope_theta, attention=self.attention,
            window=self.window, chunk=self.chunk,
            n_kv_heads=self.n_kv_heads, qk_norm=self.qk_norm,
            out_gate=self.attn_gate, eps=self.eps)
        return m

    def init(self, key, itype):
        e = self.n_in
        f = self.ffn_hidden or self.ffn_mult * e
        k_mha, k1, k2, kr = jax.random.split(key, 4)
        mha_vars = self._mha().init(k_mha, itype)
        params = {f"mha_{k}": v for k, v in mha_vars["params"].items()}
        if self.norm not in ("layer", "rms"):
            raise ValueError(f"layer '{self.name}': unknown norm "
                             f"'{self.norm}'; expected layer or rms")
        state = {}
        if self.moe_experts > 0 and self.moe_top_k > 0:
            routed_p, state = self._routed().init(
                kr, self.make_weight, self.make_bias)
            params.update(routed_p)
        elif self.moe_experts > 0:
            if self.gated or not self.has_bias:
                raise ValueError(
                    f"layer '{self.name}': the top-1 capacity path "
                    "(moe_top_k=0) has ungated, biased experts; a gated "
                    "or bias-free block routes with moe_top_k > 0")
            E = self.moe_experts
            params.update({
                "router": self.make_weight(kr, (e, E)),
                "w1": self.make_weight(k1, (E, e, f)),
                "b1": self.make_bias((E, 1, f)),
                "w2": self.make_weight(k2, (E, f, e)),
                "b2": self.make_bias((E, 1, e)),
            })
        else:
            params.update({
                "W1": self.make_weight(k1, (e, f)),
                "W2": self.make_weight(k2, (f, e)),
            })
            if self.has_bias:
                params.update(b1=self.make_bias((f,)),
                              b2=self.make_bias((e,)))
            if self.gated:
                params["Wg"] = self.make_weight(jax.random.fold_in(key, 4),
                                                (e, f))
        if self.norm == "rms":
            # the gain is an offset from one
            for which in ("ln1", "ln2") + (("ln1p", "ln2p")
                                           if self.post_norm else ()):
                params[which + "_g"] = jnp.zeros((e,), self._dtype())
        else:
            for which in ("ln1", "ln2") + (("ln1p", "ln2p")
                                           if self.post_norm else ()):
                params[which + "_g"] = jnp.ones((e,), self._dtype())
                params[which + "_b"] = jnp.zeros((e,), self._dtype())
        if self.moe_experts > 0 and not self.moe_top_k:
            state["aux_loss"] = jnp.zeros((), self._dtype())
        return {"params": params, "state": state}

    def _norm(self, p, x, which: str):
        if self.norm == "rms":
            y = _rms_norm(x, p[which + "_g"], self.eps)
        else:
            y = _layer_norm(x, p[which + "_g"], p[which + "_b"], self.eps)
        # a stream wider than the weights: the projections compute in theirs
        return y.astype(p["mha_Wo"].dtype) if self.residual_dtype else y

    def _ffn(self, p, xn, state=None):
        """Dense or routed MLP; returns (out, state_update)."""
        if self.moe_experts > 0 and self.moe_top_k > 0:
            b, t, e = xn.shape
            y, st = self._routed().apply(p, state or {},
                                         xn.reshape(b * t, e), jax.nn.silu
                                         if self.gated else jax.nn.gelu)
            return y.reshape(b, t, e), st
        if self.moe_experts == 0:
            up = xn @ p["W1"]
            if self.has_bias:
                up = up + p["b1"]
            up = checkpoint_name(up, "mlp_up")
            hidden = (jax.nn.silu(checkpoint_name(xn @ p["Wg"], "mlp_gate"))
                      * up if self.gated else jax.nn.gelu(up))
            out = hidden @ p["W2"]
            return (out + p["b2"] if self.has_bias else out), {}
        from ...parallel.expert import moe_ffn
        b, t, e = xn.shape
        x2d = xn.reshape(b * t, e)
        capacity = max(int(self.moe_capacity_factor * b * t
                           / self.moe_experts), 1)
        moe_p = {"router": p["router"], "w1": p["w1"], "b1": p["b1"],
                 "w2": p["w2"], "b2": p["b2"]}
        y, aux = moe_ffn(moe_p, x2d, capacity, act=jax.nn.gelu)
        return y.reshape(b, t, e), {
            "aux_loss": (self.aux_loss_weight * aux).astype(
                jnp.result_type(xn))}

    def _attention_half(self, p, x, *, train, key, mask):
        """The stream after the attention half, and the FFN's input."""
        mha_p = {k[4:]: v for k, v in p.items() if k.startswith("mha_")}
        if self.residual_dtype:
            x = x.astype(self.residual_dtype)

        xn = self._norm(p, x, "ln1")
        att = self._mha().attend(mha_p, xn, train=train, key=key, mask=mask)
        if self.post_norm:
            att = self._norm(p, att, "ln1p")
        x = checkpoint_name(x + att, "block_mid")
        return x, self._norm(p, x, "ln2")

    def apply(self, variables, x, *, train=False, key=None, mask=None):
        p = self.maybe_noise_weights(key, variables["params"], train)
        x = self.maybe_dropout_input(key, x, train)
        x, xn = self._attention_half(p, x, train=train, key=key, mask=mask)
        ff, st = self._ffn(p, xn, variables.get("state"))
        if self.post_norm:
            ff = self._norm(p, ff, "ln2p")
        return x + ff, st if st else variables.get("state", {})

    def routing(self, variables, x, *, mask=None):
        """``(idx [b * t, k], w [b * t, k])``: the experts the routed FFN
        (``moe_top_k > 0``) chooses for each token of the block's input
        ``x``, and their weights — what ``apply`` routes by."""
        if not (self.moe_experts > 0 and self.moe_top_k > 0):
            raise ValueError(f"layer '{self.name}' has no top-k routed FFN")
        p = variables["params"]
        _, xn = self._attention_half(p, x, train=False, key=None, mask=mask)
        return self._routed().route(p, variables.get("state") or {},
                                    xn.reshape(-1, xn.shape[-1]))

    # ---- KV-cache incremental decoding -----------------------------------
    def init_carry(self, batch: int, dtype=jnp.float32,
                   max_len: Optional[int] = None):
        return self._mha().init_carry(batch, dtype, max_len=max_len)

    def apply_with_carry(self, variables, x, carry, *, train=False,
                         key=None, mask=None):
        if carry is None:
            carry = self.init_carry(x.shape[0], x.dtype)
        p = self.maybe_noise_weights(key, variables["params"], train)
        x = self.maybe_dropout_input(key, x, train)
        mha_p = {k[4:]: v for k, v in p.items() if k.startswith("mha_")}
        xn = self._norm(p, x, "ln1")
        attn, new_carry = self._mha().attend_cached(mha_p, xn, carry,
                                                    mask=mask)
        if self.post_norm:
            attn = self._norm(p, attn, "ln1p")
        x = x + attn
        xn = self._norm(p, x, "ln2")
        ff, st = self._ffn(p, xn, variables.get("state"))
        if self.post_norm:
            ff = self._norm(p, ff, "ln2p")
        if st:
            # thread the MoE aux loss out through the caller's mutable
            # variables dict (the MLN carry path reads state after the call)
            variables["state"] = st
        return x + ff, new_carry


@register_serde
@dataclass
class NextTokenMerge(BaseLayerConf):
    """The merge that opens a multi-token-prediction module (DeepSeek-V3,
    arXiv:2412.19437 section 2.2): input ``[b, t, 2 * n_out]``, the
    embedding of the token after next-to-predict laid beside the trunk's
    hidden state of the same position (a ``MergeVertex`` of the two, the
    embedding first); each half gets a gain-only RMSNorm of its own
    (``enorm``, ``hnorm``; gains as offsets from one) and the pair one
    projection without bias, ``[N_e e ; N_h h] W``, to ``n_out``."""
    INPUT_KIND = "rnn"
    _BIAS_PARAMS = ("enorm", "hnorm")

    n_in: int = 0
    n_out: int = 0
    eps: float = 1e-6

    def set_n_in(self, itype: InputType, override: bool = False) -> None:
        if self.n_in == 0 or override:
            self.n_in = itype.size
        if self.n_out == 0:
            self.n_out = self.n_in // 2

    def output_type(self, itype: InputType) -> InputType:
        return InputType.recurrent(self.n_out, itype.timesteps)

    def init(self, key, itype):
        if self.n_in != 2 * self.n_out:
            raise ValueError(
                f"layer '{self.name}': the merge takes an embedding and a "
                f"hidden state of its output's width side by side, got "
                f"{self.n_in} for {self.n_out}")
        return {"params": {
            "W": self.make_weight(key, (self.n_in, self.n_out)),
            "enorm": jnp.zeros((self.n_out,), self._dtype()),
            "hnorm": jnp.zeros((self.n_out,), self._dtype())},
            "state": {}}

    def apply(self, variables, x, *, train=False, key=None, mask=None):
        from ...observability.registry import default_registry
        reg = default_registry()
        if reg.enabled:
            # trace-time, like moe_layers_traced_total
            reg.counter("mtp_modules_traced_total",
                        "Multi-token-prediction modules traced into a "
                        "program, by width", ("width",)).labels(
                            str(self.n_out)).inc()
        p, e = variables["params"], self.n_out
        both = jnp.concatenate(
            [_rms_norm(x[..., :e], p["enorm"], self.eps),
             _rms_norm(x[..., e:], p["hnorm"], self.eps)], axis=-1)
        return both @ p["W"], variables.get("state", {})


@register_serde
@dataclass
class PositionalEncodingLayer(LayerConf):
    """Sinusoidal positional encoding added to RNN-typed input (no params).
    Carry = stream position, so incremental decode keeps absolute
    positions."""
    HAS_CARRY = True

    def output_type(self, itype: InputType) -> InputType:
        return itype

    @staticmethod
    def _pe(t, e, offset, dtype):
        """Sinusoidal table for ``t`` steps starting at ``offset`` —
        a scalar (one shared stream position: [t, e]) or a ``[b]`` vector
        (per-row positions, the slot-batched decode step: [b, t, e])."""
        offset = jnp.asarray(offset, jnp.float32)
        pos = offset[..., None] + jnp.arange(t, dtype=jnp.float32)
        i = jnp.arange(e, dtype=jnp.float32)
        angle = pos[..., None] / jnp.power(10000.0, (2 * (i // 2)) / e)
        return jnp.where(i % 2 == 0, jnp.sin(angle),
                         jnp.cos(angle)).astype(dtype)

    def apply(self, variables, x, *, train=False, key=None, mask=None):
        b, t, e = x.shape
        return x + self._pe(t, e, 0.0, x.dtype), variables.get("state", {})

    def init_carry(self, batch: int, dtype=jnp.float32,
                   max_len: Optional[int] = None):
        return {"pos": jnp.zeros((), jnp.int32)}

    def apply_with_carry(self, variables, x, carry, *, train=False,
                         key=None, mask=None):
        if carry is None:
            carry = self.init_carry(x.shape[0], x.dtype)
        b, t, e = x.shape
        y = x + self._pe(t, e, carry["pos"].astype(jnp.float32), x.dtype)
        return y, {"pos": carry["pos"] + t}
