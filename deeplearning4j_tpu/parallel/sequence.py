"""Sequence/context parallelism: ring attention + Ulysses (all-to-all).

The reference framework predates transformers and has NO long-context story
beyond truncated BPTT (SURVEY.md §5).  This module is the TPU build's
first-class replacement: shard the time axis of q/k/v over the mesh 'seq'
axis and compute exact attention with either

  * **ring attention** — k/v shards rotate around the ring via
    ``lax.ppermute`` (ICI neighbor exchange); each step attends the local q
    block to the visiting k/v block and merges with the running online-softmax
    partials (``ops.attention.combine_blocks``).  Memory per device: O(t/n);
    comms: n-1 neighbor hops fully overlappable with compute by XLA.
  * **Ulysses** — one ``lax.all_to_all`` reswizzles [seq-shard, all heads] ->
    [all seq, head-shard], runs ordinary (flash) attention per head group,
    and a second all-to-all restores the layout.  Cheaper comms for
    head-rich models; requires n_heads % axis_size == 0.

Both are designed to run INSIDE ``shard_map`` over a mesh with a 'seq' axis —
``MultiHeadAttention`` picks them up via ``attn_impl='ring'|'ulysses'`` when
the training step is sequence-sharded (see ``parallel.dryrun``).
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax

from ..ops.attention import (attn_block, combine_blocks, finalize_blocks,
                             init_blocks)


def ring_self_attention(q, k, v, *, axis_name: str, causal: bool = False,
                        scale: Optional[float] = None):
    """Exact attention with q/k/v sharded [b, h, t/n, d] over ``axis_name``.

    Shard i holds global positions [i*t_blk, (i+1)*t_blk).  k/v blocks rotate
    ring-wise; online-softmax partials make the result exactly equal to full
    attention (up to float32 reduction order).
    """
    n = lax.psum(1, axis_name)
    idx = lax.axis_index(axis_name)
    b, h, t_blk, d = q.shape
    # Initial partials must be marked as device-varying over the seq axis for
    # shard_map's carry typing (they combine with axis-varying blocks).
    acc, m, l = jax.tree.map(
        lambda a: lax.pcast(a, (axis_name,), to="varying"),
        init_blocks(b, h, t_blk, d, q.dtype))
    q_off = idx * t_blk
    perm = [(j, (j + 1) % n) for j in range(n)]

    # n is the static mesh-axis size, so unroll in Python: XLA sees a straight
    # compute/ppermute chain it can overlap, and the final (useless) rotation
    # is simply not emitted — n-1 neighbor hops total.
    k_cur, v_cur = k, v
    for i in range(n):
        # Block currently visiting came from shard (idx - i) mod n.
        src = (idx - i) % n
        a2, m2, l2 = attn_block(q, k_cur, v_cur, causal=causal, scale=scale,
                                q_offset=q_off, k_offset=src * t_blk)
        acc, m, l = combine_blocks(acc, m, l, a2, m2, l2)
        if i < n - 1:
            k_cur = lax.ppermute(k_cur, axis_name, perm)
            v_cur = lax.ppermute(v_cur, axis_name, perm)
    return finalize_blocks(acc, m, l, q.dtype)


def ulysses_attention(q, k, v, *, axis_name: str, causal: bool = False,
                      scale: Optional[float] = None, attn_fn=None):
    """All-to-all sequence parallelism (DeepSpeed-Ulysses style).

    In: [b, h, t/n, d] seq-sharded.  all_to_all -> [b, h/n, t, d]
    head-sharded, full attention locally (``attn_fn``, default reference
    SDPA), all_to_all back.  Requires h % axis_size == 0.
    """
    from ..ops.attention import sdpa_reference
    if attn_fn is None:
        attn_fn = sdpa_reference
    n = lax.psum(1, axis_name)  # static axis size
    if q.shape[1] % n:
        raise ValueError(f"ulysses_attention needs n_heads ({q.shape[1]}) "
                         f"divisible by the '{axis_name}' axis size ({n})")
    # [b, h, t_blk, d] -> split heads across devices, gather time:
    # all_to_all(split_axis=heads, concat_axis=time)
    qg = lax.all_to_all(q, axis_name, split_axis=1, concat_axis=2, tiled=True)
    kg = lax.all_to_all(k, axis_name, split_axis=1, concat_axis=2, tiled=True)
    vg = lax.all_to_all(v, axis_name, split_axis=1, concat_axis=2, tiled=True)
    o = attn_fn(qg, kg, vg, causal=causal, scale=scale)
    # [b, h/n, t, d] -> back to [b, h, t_blk, d]
    return lax.all_to_all(o, axis_name, split_axis=2, concat_axis=1, tiled=True)
