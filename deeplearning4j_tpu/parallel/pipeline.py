"""Pipeline parallelism (GPipe schedule) over a mesh 'pipe' axis.

The reference has NO model/pipeline parallelism (SURVEY.md §2.4: "Model
parallelism: Not implemented") — this is a first-class addition, built the
TPU way: every pipe-axis device runs the SAME program on its own stage's
parameter shard; activations hop stage-to-stage with ``lax.ppermute`` over
ICI.  ``jax.grad`` through the unrolled schedule transposes the ppermutes,
yielding the backward pipeline for free — no hand-written 1F1B machinery.

Contract: stages are structurally identical (same param shapes, same
activation shape), the transformer-stack case.  Stage params are stacked on a
leading axis of size n_stages and sharded over 'pipe'; inputs are split into
microbatches on a leading axis.

    ys = gpipe(stage_fn, stacked_params, xs, axis_name='pipe')

runs inside ``shard_map`` where ``stacked_params`` has specs
``P('pipe', ...)`` and ``xs`` ([n_micro, mb, ...]) is replicated on 'pipe'.
"""
from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp
from jax import lax

def gpipe(stage_fn: Callable, stage_params, xs, *, axis_name: str = "pipe"):
    """Run microbatches [n_micro, mb, ...] through the stage pipeline.

    ``stage_params`` here is the LOCAL shard: [1, ...] leading stage axis
    (shard_map gives each device its own stage slice); ``stage_fn(params, x)``
    maps one microbatch through one stage.  Returns [n_micro, mb, ...] stage-N
    outputs, valid on every device (broadcast from the last stage).
    """
    n = lax.psum(1, axis_name)           # static pipe-axis size
    idx = lax.axis_index(axis_name)
    local = jax.tree.map(lambda p: p[0], stage_params)
    n_micro = xs.shape[0]
    if n_micro < n:
        raise ValueError(f"gpipe needs >= {n} microbatches to fill the "
                         f"pipeline, got {n_micro}")

    # The loop carry must be typed as device-varying over every mesh axis the
    # stage computation touches (e.g. 'seq' when the stage runs ring
    # attention), not just 'pipe' — collect them from the inputs.
    vma = {axis_name} | set(jax.typeof(xs).vma)
    for leaf in jax.tree.leaves(local):
        vma |= set(jax.typeof(leaf).vma)

    def vary(a):
        missing = tuple(vma - set(jax.typeof(a).vma))
        return lax.pcast(a, missing, to="varying") if missing else a

    # Probe the stage output shape (stages are shape-uniform by contract).
    out_shape = jax.eval_shape(stage_fn, local, xs[0])
    buf = vary(jnp.zeros(out_shape.shape, out_shape.dtype))
    outs = vary(jnp.zeros((n_micro,) + tuple(out_shape.shape),
                          out_shape.dtype))

    fwd_perm = [(j, j + 1) for j in range(n - 1)]
    total = n_micro + n - 1

    def tick(t, carry):
        buf, outs = carry
        # Stage 0 consumes microbatch t (clamped; masked out when t >= n_micro),
        # other stages consume the activation that just arrived.
        x0 = vary(xs[jnp.minimum(t, n_micro - 1)])
        inp = jnp.where(idx == 0, x0.astype(buf.dtype), buf)
        y = vary(stage_fn(local, inp))
        # Last stage finished microbatch (t - idx) at this tick — record it.
        mb_idx = t - idx
        valid = jnp.logical_and(mb_idx >= 0, mb_idx < n_micro)
        write = jnp.logical_and(valid, idx == n - 1)
        slot = jnp.clip(mb_idx, 0, n_micro - 1)
        cur = lax.dynamic_index_in_dim(outs, slot, 0, keepdims=False)
        newval = jnp.where(write, y.astype(outs.dtype), cur)
        outs = lax.dynamic_update_index_in_dim(outs, newval, slot, 0)
        # Hand activations to the next stage.
        buf = lax.ppermute(y, axis_name, fwd_perm)
        return buf, outs

    _, outs = lax.fori_loop(0, total, tick, (buf, outs))
    # Broadcast stage-N results to every pipe device (callers typically take
    # the loss psum over 'data' afterwards; replicating keeps specs simple).
    return lax.psum(jnp.where(idx == n - 1, outs, jnp.zeros_like(outs)),
                    axis_name)


def stack_stage_params(param_list):
    """Stack per-stage pytrees (identical structure) on a new leading axis —
    the layout ``gpipe`` shards over 'pipe'."""
    return jax.tree.map(lambda *ps: jnp.stack(ps, axis=0), *param_list)
