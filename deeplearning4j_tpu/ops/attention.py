"""Scaled-dot-product attention ops — the compute core of the attention
layer family and of sequence parallelism.

The reference (pre-transformer, 0.9.2) has no attention; this module is the
long-context capability the TPU build adds as first-class (driver brief +
SURVEY.md §5 "Long-context / sequence parallelism: Absent").

Three tiers, mirroring the reference's cuDNN-helper plug-in pattern
(``nn/layers/convolution/ConvolutionLayer.java:74-84`` — optional fast path,
numerics-validated against the fallback):

  1. ``sdpa_reference``   — plain jnp einsum + softmax; XLA fuses well, the
                            always-correct oracle.
  2. pallas flash kernel  — ``ops.flash_attention.flash_attention``; tiled
                            online-softmax, O(t) memory, MXU-shaped blocks.
  3. ring / Ulysses SP    — ``parallel.sequence``; the same online-softmax
                            combine across sequence shards over ICI.

All functions take [batch, heads, time, head_dim] ("bhtd") tensors and an
optional additive bias/mask; softmax statistics are computed in at least
float32 (bfloat16-safe; float64 inputs keep float64 so the gradient-check
oracle sees full precision).
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

NEG_INF = -1e30  # large-negative instead of -inf: keeps masked softmax NaN-free


def causal_mask(t_q: int, t_k: int, q_offset: int = 0, k_offset: int = 0):
    """Boolean [t_q, t_k] mask, True = attend. Offsets position the blocks
    inside the full sequence (used by blockwise/ring attention)."""
    qi = jnp.arange(t_q)[:, None] + q_offset
    ki = jnp.arange(t_k)[None, :] + k_offset
    return qi >= ki


def _apply_masks(scores, mask, causal, q_offset, k_offset, window=None):
    t_q, t_k = scores.shape[-2], scores.shape[-1]
    if causal:
        scores = jnp.where(causal_mask(t_q, t_k, q_offset, k_offset),
                           scores, NEG_INF)
    if window is not None:
        # the band that moves with the query: the last `window` keys
        scores = jnp.where(causal_mask(t_k, t_q, k_offset + window - 1,
                                       q_offset).T, scores, NEG_INF)
    if mask is not None:
        # mask: [b, t_k] key-padding (1=valid) or [b, 1, t_q, t_k] full.
        if mask.ndim == 2:
            mask = mask[:, None, None, :]
        scores = jnp.where(mask.astype(bool), scores, NEG_INF)
    return scores


def sdpa_reference(q, k, v, *, mask=None, causal: bool = False,
                   scale: Optional[float] = None,
                   q_offset: int = 0, k_offset: int = 0,
                   window: Optional[int] = None):
    """Reference scaled-dot-product attention.  q,k,v: [b, h, t, d]; ``v``
    may be of another last dimension than q and k (the output is as wide as
    ``v``, the default scale follows q's width).
    ``window`` keeps of each query's keys those less than ``window``
    positions before it (with ``causal``: the last ``window``)."""
    if scale is None:
        scale = q.shape[-1] ** -0.5
    acc_dt = jnp.promote_types(q.dtype, jnp.float32)
    scores = jnp.einsum("bhqd,bhkd->bhqk", q, k).astype(acc_dt) * scale
    scores = _apply_masks(scores, mask, causal, q_offset, k_offset, window)
    probs = jax.nn.softmax(scores, axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", probs.astype(v.dtype), v)


# ---------------------------------------------------------------------------
# Online-softmax block combine — the shared math of flash + ring attention.
# ---------------------------------------------------------------------------

def attn_block(q, k, v, *, mask=None, causal=False, scale=None,
               q_offset: int = 0, k_offset: int = 0
               ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Attend q to ONE block of (k, v); return (acc, m, l) partial stats:
    acc = sum_j exp(s_j - m) v_j  (unnormalized, f32), m = row max (f32),
    l = sum_j exp(s_j - m) (f32).  Combine partials with ``combine_blocks``."""
    if scale is None:
        scale = q.shape[-1] ** -0.5
    acc_dt = jnp.promote_types(q.dtype, jnp.float32)
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k).astype(acc_dt) * scale
    s = _apply_masks(s, mask, causal, q_offset, k_offset)
    m = jnp.max(s, axis=-1)                                  # [b,h,q]
    # Guard fully-masked rows: exp(NEG_INF - NEG_INF)=1 would pollute l.
    p = jnp.exp(s - m[..., None]) * (s > NEG_INF / 2)
    l = jnp.sum(p, axis=-1)                                  # [b,h,q]
    acc = jnp.einsum("bhqk,bhkd->bhqd", p, v.astype(acc_dt))
    return acc, m, l


def combine_blocks(acc1, m1, l1, acc2, m2, l2):
    """Merge two online-softmax partials over disjoint key blocks."""
    m = jnp.maximum(m1, m2)
    a1 = jnp.exp(m1 - m)
    a2 = jnp.exp(m2 - m)
    acc = acc1 * a1[..., None] + acc2 * a2[..., None]
    l = l1 * a1 + l2 * a2
    return acc, m, l


def finalize_blocks(acc, m, l, dtype):
    """Normalize accumulated partials into the attention output."""
    l = jnp.where(l == 0.0, 1.0, l)  # fully-masked rows -> zeros, not NaN
    return (acc / l[..., None]).astype(dtype)


def init_blocks(b, h, t_q, d, dtype=jnp.float32):
    """Identity element for ``combine_blocks``."""
    acc_dt = jnp.promote_types(dtype, jnp.float32)
    acc = jnp.zeros((b, h, t_q, d), acc_dt)
    m = jnp.full((b, h, t_q), NEG_INF, acc_dt)
    l = jnp.zeros((b, h, t_q), acc_dt)
    return acc, m, l
