"""Pallas TPU flash-attention kernel — the "accelerator helper" tier.

Role-parity with the reference's cuDNN helpers (``deeplearning4j-cuda/.../
CudnnConvolutionHelper.java:54`` pattern: optional per-layer fast path,
numerics-validated against the builtin fallback, cf. ``ValidateCudnnLSTM``).
Here the builtin is ``ops.attention.sdpa_reference`` (chosen by the caller,
never fallen back to from here) and the fast path is a tiled online-softmax
kernel: O(t) memory instead of the O(t^2) score matrix,
with [block_q × d] @ [d × block_k] matmuls shaped for the MXU and softmax
statistics kept in VMEM scratch across the key-block grid dimension.

Grid: (batch*heads, q_blocks, k_blocks) — the last dimension iterates
innermost and sequentially on TPU, so scratch (m, l, acc) carries the running
softmax state across k-blocks of one q-block.  float32 accumulation
regardless of input dtype (bfloat16 inputs stay bfloat16 in HBM/VMEM).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import AxisType, PartitionSpec as P

from .attention import NEG_INF

DEFAULT_BLOCK_Q = 128
DEFAULT_BLOCK_K = 128
# Block caps from a sweep on a TPU v5e at d=64 that predates today's code
# and compiler (bigger q-blocks amortize DMA and feed the MXU
# [block_q,d]@[d,block_k] matmuls at useful sizes; 2048x1024 at d=64 did
# not compile then).  Caps scale down with head_dim to stay inside VMEM.
# tests/test_chip_compile.py compiles the largest auto blocks (t=8192)
# for the v5e on every run; the speed of these blocks on today's stack is
# not measured.
_BLOCK_Q_CAP = 2048 * 64
_BLOCK_K_CAP = 512 * 64


def _auto_blocks(t_q: int, t_k: int, d: int):
    """Largest power-of-two divisors of the sequence lengths under the
    VMEM-scaled caps — the cuDNN algo-search role
    (``ConvolutionLayer.java:349``) resolved by sweep instead of per-call
    search."""
    def pick(t, cap):
        if t <= 128:
            return t          # sub-tile sequences run as one block
        b = max(128, min(t, cap // max(d, 1)))
        # round down to a power of two, then to a divisor of t
        b = 1 << (b.bit_length() - 1)
        while b > 128 and t % b:
            b //= 2
        return b
    return pick(t_q, _BLOCK_Q_CAP), pick(t_k, _BLOCK_K_CAP)


def _block_live(causal: bool, qi, ki, block_q: int, block_k: int):
    """False only for key blocks entirely above the causal diagonal —
    shared by the forward and both backward kernels so the skip predicate
    cannot drift between them."""
    if not causal:
        return True
    return qi * block_q + block_q - 1 >= ki * block_k


def _masked_scores(q, k, qi, ki, *, scale, causal, block_q, block_k):
    """scale·q@kᵀ with the causal mask applied — the one definition of the
    score block used by forward and backward (replay must match exactly)."""
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    if causal:
        qpos = qi * block_q + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
        kpos = ki * block_k + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(qpos >= kpos, s, NEG_INF)
    return s


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, acc_ref, m_ref, l_ref,
                  *, scale: float, causal: bool, block_q: int, block_k: int):
    qi = pl.program_id(1)
    ki = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(ki == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)
        m_ref[:] = jnp.full_like(m_ref, NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)

    @pl.when(_block_live(causal, qi, ki, block_q, block_k))
    def _step():
        q = q_ref[0].astype(jnp.float32)            # [block_q, d]
        k = k_ref[0].astype(jnp.float32)            # [block_k, d]
        v = v_ref[0].astype(jnp.float32)            # [block_k, d]
        s = _masked_scores(q, k, qi, ki, scale=scale, causal=causal,
                           block_q=block_q, block_k=block_k)

        m_prev = m_ref[:]                            # [block_q, 1]
        m_cur = jnp.max(s, axis=-1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new) * (s > NEG_INF / 2)
        alpha = jnp.exp(m_prev - m_new)              # [block_q, 1]
        l_ref[:] = l_ref[:] * alpha + jnp.sum(p, axis=-1, keepdims=True)
        acc_ref[:] = acc_ref[:] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
        m_ref[:] = m_new

    @pl.when(ki == nk - 1)
    def _finalize():
        l = l_ref[:]
        l = jnp.where(l == 0.0, 1.0, l)
        o_ref[0] = (acc_ref[:] / l).astype(o_ref.dtype)
        # logsumexp per row — the backward's softmax replay key.  The lse
        # block spans the whole row (Mosaic tiling: a (1, block_q) slice
        # block is not expressible), so write this q-block's slice in place.
        lse_ref[0, 0, pl.ds(qi * block_q, block_q)] = (
            m_ref[:] + jnp.log(l))[:, 0]


def _like(x, shape=None, dtype=None):
    """Output type for a kernel launched on ``x``: under ``shard_map`` it
    varies over the same mesh axes as the operand."""
    return jax.ShapeDtypeStruct(x.shape if shape is None else shape,
                                dtype or x.dtype, vma=jax.typeof(x).vma)


def _launch_fwd(qr, kr, vr, scale, causal, block_q, block_k, interpret):
    bh, t_q, d = qr.shape
    t_k = kr.shape[1]
    grid = (bh, t_q // block_q, t_k // block_k)
    kernel = functools.partial(_flash_kernel, scale=scale, causal=causal,
                               block_q=block_q, block_k=block_k)
    out, lse = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda bh, qi, ki: (bh, qi, 0)),
            pl.BlockSpec((1, block_k, d), lambda bh, qi, ki: (bh, ki, 0)),
            pl.BlockSpec((1, block_k, d), lambda bh, qi, ki: (bh, ki, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_q, d), lambda bh, qi, ki: (bh, qi, 0)),
            pl.BlockSpec((1, 1, t_q), lambda bh, qi, ki: (bh, 0, 0)),
        ],
        out_shape=[_like(qr, (bh, t_q, d)),
                   _like(qr, (bh, 1, t_q), jnp.float32)],
        scratch_shapes=[
            pltpu.VMEM((block_q, d), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
        ],
        interpret=interpret,
        name="flash_fwd",
    )(qr, kr, vr)
    return out, lse


def _replay_p_ds(q_ref, k_ref, v_ref, do_ref, lse_ref, dd_ref, qi, ki, *,
                 scale, causal, block_q, block_k):
    """Shared backward-step math: recompute the softmax block P from the
    saved logsumexp and form dS = P∘(dP − D)·scale (FlashAttention-2 bwd).
    lse/dd refs span the whole row; this q-block's slice is loaded here."""
    q = q_ref[0].astype(jnp.float32)                # [block_q, d]
    k = k_ref[0].astype(jnp.float32)                # [block_k, d]
    v = v_ref[0].astype(jnp.float32)                # [block_k, d]
    do = do_ref[0].astype(jnp.float32)              # [block_q, d]
    lse = lse_ref[0, 0, pl.ds(qi * block_q, block_q)]
    dd = dd_ref[0, 0, pl.ds(qi * block_q, block_q)]
    s = _masked_scores(q, k, qi, ki, scale=scale, causal=causal,
                       block_q=block_q, block_k=block_k)
    p = jnp.exp(s - lse[:, None]) * (s > NEG_INF / 2)
    dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)
    ds = p * (dp - dd[:, None]) * scale
    return q, k, do, p, ds


def _flash_bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, dd_ref,
                         dq_ref, dq_acc, *, scale, causal,
                         block_q, block_k):
    qi, ki = pl.program_id(1), pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(ki == 0)
    def _init():
        dq_acc[:] = jnp.zeros_like(dq_acc)

    @pl.when(_block_live(causal, qi, ki, block_q, block_k))
    def _step():
        _, k, _, _, ds = _replay_p_ds(
            q_ref, k_ref, v_ref, do_ref, lse_ref, dd_ref, qi, ki,
            scale=scale, causal=causal, block_q=block_q, block_k=block_k)
        dq_acc[:] += jax.lax.dot_general(
            ds, k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(ki == nk - 1)
    def _done():
        dq_ref[0] = dq_acc[:].astype(dq_ref.dtype)


def _flash_bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, dd_ref,
                          dk_ref, dv_ref, dk_acc, dv_acc, *, scale, causal,
                          block_q, block_k):
    # grid: (bh, k_blocks, q_blocks) — q innermost so dk/dv accumulate
    ki, qi = pl.program_id(1), pl.program_id(2)
    nq = pl.num_programs(2)

    @pl.when(qi == 0)
    def _init():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)

    @pl.when(_block_live(causal, qi, ki, block_q, block_k))
    def _step():
        q, _, do, p, ds = _replay_p_ds(
            q_ref, k_ref, v_ref, do_ref, lse_ref, dd_ref, qi, ki,
            scale=scale, causal=causal, block_q=block_q, block_k=block_k)
        dv_acc[:] += jax.lax.dot_general(
            p, do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dk_acc[:] += jax.lax.dot_general(
            ds, q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(qi == nq - 1)
    def _done():
        dk_ref[0] = dk_acc[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[:].astype(dv_ref.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _flash(qr, kr, vr, scale, causal, block_q, block_k, interpret):
    out, _ = _launch_fwd(qr, kr, vr, scale, causal, block_q, block_k,
                             interpret)
    return out


def _flash_fwd(qr, kr, vr, scale, causal, block_q, block_k, interpret):
    out, lse = _launch_fwd(qr, kr, vr, scale, causal, block_q, block_k,
                               interpret)
    return out, (qr, kr, vr, out, lse)


def _launch_bwd(qr, kr, vr, do, lse, dd, scale, causal, block_q,
                       block_k, interpret):
    bh, t_q, d = qr.shape
    t_k = kr.shape[1]
    q_spec = pl.BlockSpec((1, block_q, d), lambda bh, qi, ki: (bh, qi, 0))
    k_spec = pl.BlockSpec((1, block_k, d), lambda bh, qi, ki: (bh, ki, 0))
    row_spec = pl.BlockSpec((1, 1, t_q), lambda bh, qi, ki: (bh, 0, 0))
    dq = pl.pallas_call(
        functools.partial(_flash_bwd_dq_kernel, scale=scale, causal=causal,
                          block_q=block_q, block_k=block_k),
        grid=(bh, t_q // block_q, t_k // block_k),
        in_specs=[q_spec, k_spec, k_spec, q_spec, row_spec, row_spec],
        out_specs=q_spec,
        out_shape=_like(qr),
        scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32)],
        interpret=interpret,
        name="flash_bwd_dq",
    )(qr, kr, vr, do, lse, dd)

    # swapped grid: k outer, q inner (sequential) so dk/dv carry in scratch
    q_spec2 = pl.BlockSpec((1, block_q, d), lambda bh, ki, qi: (bh, qi, 0))
    k_spec2 = pl.BlockSpec((1, block_k, d), lambda bh, ki, qi: (bh, ki, 0))
    row_spec2 = pl.BlockSpec((1, 1, t_q), lambda bh, ki, qi: (bh, 0, 0))
    dk, dv = pl.pallas_call(
        functools.partial(_flash_bwd_dkv_kernel, scale=scale, causal=causal,
                          block_q=block_q, block_k=block_k),
        grid=(bh, t_k // block_k, t_q // block_q),
        in_specs=[q_spec2, k_spec2, k_spec2, q_spec2, row_spec2, row_spec2],
        out_specs=[k_spec2, k_spec2],
        out_shape=[_like(kr), _like(vr)],
        scratch_shapes=[pltpu.VMEM((block_k, d), jnp.float32),
                        pltpu.VMEM((block_k, d), jnp.float32)],
        interpret=interpret,
        name="flash_bwd_dkv",
    )(qr, kr, vr, do, lse, dd)
    return dq, dk, dv


def _flash_bwd(scale, causal, block_q, block_k, interpret, res, do):
    qr, kr, vr, out, lse = res
    # D = rowsum(dO ∘ O): one elementwise+reduce pass, XLA-fused
    dd = jnp.sum(do.astype(jnp.float32) * out.astype(jnp.float32),
                 axis=-1)[:, None, :]               # (bh, 1, t_q) row form
    return _launch_bwd(qr, kr, vr, do, lse, dd, scale, causal, block_q,
                       block_k, interpret)


_flash.defvjp(_flash_fwd, _flash_bwd)


#: kernel names as they appear in the lowered program's ``tpu_custom_call``
#: ops — what a caller greps ``as_text()`` for to prove the kernels are in
KERNEL_NAMES = ("flash_fwd", "flash_bwd_dq", "flash_bwd_dkv")


def flash_blocks(t_q: int, t_k: int, d: int,
                 block_q: Optional[int] = None,
                 block_k: Optional[int] = None):
    """``(block_q, block_k)`` the kernel runs these shapes with.  Raises
    ``ValueError`` naming the reason when it cannot tile them — the one
    support check, shared by ``flash_attention`` (which raises) and
    ``attn_impl='auto'`` (which then chooses the reference path)."""
    auto_q, auto_k = _auto_blocks(t_q, t_k, d)
    block_q = min(block_q, t_q) if block_q else auto_q
    block_k = min(block_k, t_k) if block_k else auto_k
    if d % 64:
        # head_dim must fill whole MXU lanes for the kernel's tiling
        raise ValueError(f"flash attention needs head_dim % 64 == 0, "
                         f"got {d}")
    if t_q % block_q or t_k % block_k:
        raise ValueError(
            f"flash attention needs sequence lengths divisible by its "
            f"blocks: t_q={t_q} % {block_q}, t_k={t_k} % {block_k}")
    return block_q, block_k


def _kernel_partitioning(q):
    """``(mesh, spec)`` to run the kernels under ``shard_map`` with, or
    ``(None, None)`` on one device.  Mosaic kernels cannot be partitioned
    automatically: a jit over arguments sharded on a mesh
    (``ShardedTrainer``, ``ParallelWrapper``) refuses to lower them bare.
    The mesh is read off the operand's type — an array placed with a
    ``NamedSharding`` carries its abstract mesh into the trace — so no
    caller has to pass or set one.  The batch rides the mesh's ``data``
    axis (where ``parallel/mesh.batch_spec`` puts it) when it divides;
    every other axis, and a batch that does not divide, runs replicated."""
    mesh = jax.typeof(q).sharding.mesh
    auto = {name: size for name, size, kind
            in zip(mesh.axis_names, mesh.axis_sizes, mesh.axis_types)
            if kind == AxisType.Auto and size > 1}
    if not auto:
        return None, None
    data = auto.get("data")
    return mesh, (P("data") if data and q.shape[0] % data == 0 else P())


def flash_attention(q, k, v, *, causal: bool = False,
                    scale: Optional[float] = None,
                    block_q: Optional[int] = None,
                    block_k: Optional[int] = None,
                    interpret: bool = False):
    """Flash attention over [b, h, t, d] tensors — differentiable: the
    FlashAttention-2 style backward (saved logsumexp, softmax replayed per
    block, separate dq and dk/dv kernels) keeps training memory O(t).

    Never falls back: shapes the kernel cannot tile raise ``ValueError``
    (``flash_blocks``), and off a TPU backend the Pallas lowering itself
    refuses unless ``interpret=True``.  Key-padding masks are not
    supported here.  ``attn_impl='auto'`` is the caller that chooses
    between this and ``sdpa_reference``.
    """
    _, h, t_q, d = q.shape
    t_k = k.shape[2]
    block_q, block_k = flash_blocks(t_q, t_k, d, block_q, block_k)
    if scale is None:
        scale = d ** -0.5

    def run(q, k, v):
        rows = q.shape[0] * h
        out = _flash(q.reshape(rows, t_q, d), k.reshape(rows, t_k, d),
                     v.reshape(rows, t_k, d), scale, causal, block_q,
                     block_k, interpret)
        return out.reshape(q.shape)

    mesh, spec = _kernel_partitioning(q)
    if mesh is not None:
        run = jax.shard_map(run, mesh=mesh, in_specs=(spec, spec, spec),
                            out_specs=spec)
    return run(q, k, v)
