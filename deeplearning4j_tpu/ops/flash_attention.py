"""Pallas TPU flash-attention kernel — the "accelerator helper" tier.

Role-parity with the reference's cuDNN helpers (``deeplearning4j-cuda/.../
CudnnConvolutionHelper.java:54`` pattern: optional per-layer fast path,
numerics-validated against the builtin fallback, cf. ``ValidateCudnnLSTM``).
Here the builtin is ``ops.attention.sdpa_reference`` (chosen by the caller,
never fallen back to from here) and the fast path is a tiled online-softmax
kernel: O(t) memory instead of the O(t^2) score matrix.

Grid: (heads, pairs of blocks), a block up to 1024 rows.  A launch walks
a table of ``(held block, walked block)`` pairs (``_walk``), built with
numpy when it is traced and scalar-prefetched, so every index map reads
its block from the table and the kernels read from it whether a pair is
its held block's first (the sums start) or last (they are written).  The
table holds the live pairs alone: under ``causal`` no block wholly above
the diagonal is in it, so no grid step idles, and a held block's pairs
come in the order a walk of the whole square took them, so the products
and their order, and with them the results, are that walk's.  The last
grid dimension iterates sequentially on TPU, so scratch carries the
running softmax state (forward) or the gradient sums (backward) across
the key blocks of one query block.  The dk/dv kernel swaps the roles — a
block of keys held, queries walked — and works on the transposed score
tile, so the saved row statistics meet it in the lane-major layout they
are stored in.  A block wholly below the diagonal takes no mask (one
step; in the forward one a group of queries, below), and the block the
diagonal enters is cut, when the kernel is traced, into one step a
``block_q`` rows of queries: all their live keys in one product, the mask
on the ``block_k``-wide tiles the diagonal crosses only, the tiles above
it in no step.  Few large steps and not many small ones, in the body and
not in the grid: a product of 256 x 256 scores costs about as much to
start as to run, a grid step more (``PERF.md`` section 6).
``flash_grid_pairs_traced_total{live, square}`` counts the calls traced
by the pairs a head walks and the steps a walk of the whole square (or
band) would take.

The forward scores a full block's queries as independent groups of
``_TILE`` rows (``_query_groups``), each a step over all the block's keys,
written one after another in the body.  In one step of 1024 rows the
softmax waits for each row's maximum over all its keys and the PV product
waits for the softmax, so the MXU waits with them; with groups the
compiler schedules one group's products while the vector unit forms
another's softmax.  A row's arithmetic is the same either way, so ``o``
and ``lse`` are bit for bit those of one group and the backward replays
them exactly.  dq, with a third product a score, and dk/dv, keys down the
rows, take a block whole.

``window=W`` (causal only) is a band that moves with the query: query
``i`` sees the keys ``i - W < j <= i``.  The table then holds, for each
held block, only the blocks the band can reach that lie on the sequence
(``_band_walk``: the diagonal's block first, then the ones before it), so
a call's work follows ``t x W`` and not ``t^2``; inside a block the band
cuts, the steps are again static, chosen by the pair's distance from the
diagonal, one a tile of queries over the key tiles it can see, the mask
on the tiles an edge crosses only (``_band_steps``).  Such calls carry
names of their own (``flash_win_*``), so a trace tells them from full
calls.

The keys come whole, ``k`` and ``v`` a head, or **in parts, as latent
attention's projections write them** (``flash_attention(q, kv=,
k_shared=)``): ``kv`` is one product, ``[k | v]`` a head, of which the
kernels' BlockSpecs read ``k`` and ``v`` as column blocks 0 and 1, and
``k_shared`` the part of the key every head shares (the one rotary key),
one array a batch row that every head's grid rows read through an index
map that leaves the head out.  A step lays ``[k | k_shared]`` side by side
in VMEM and scores them with one product against the whole q
(``_key_tile``); the dk/dv kernel writes ``[dk | dv]`` as ``kv`` lies and
each head's share of ``k_shared``'s gradient, which one reduction outside
sums over the heads.  So no sliced, broadcast or assembled key exists
outside the kernels.  A call with whole keys traces the kernel bodies it
always traced.

Products take their operands in the type they arrive in and accumulate in
float32 (Mosaic at its default precision gives float32 operands one
bfloat16 pass all the same); ``p`` and ``dS`` are cast to the operand type
for their second products; the softmax statistics, ``lse`` and ``D`` are
float32 throughout.  Tiles and block size come from a sweep on a TPU v5e
under jax 0.9.0 / libtpu 0.0.34 on 2026-10-01 (same section).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import AxisType, PartitionSpec as P

from .attention import NEG_INF

_LANES = 128
# The tile that cuts the diagonal's block (and in the forward a full
# block's queries, _query_groups), swept at d=64 from t=128 to
# t=8192, bfloat16 and float32, and at d=128, t=2048: 256 rows of queries
# a step in the forward and dq kernels (128: 12 % slower a forward call;
# 512: 14 % slower a dq call), 128 in the dk/dv kernel, whose tile has the
# queries along the lanes (256: 12 % slower a call).
_TILE = 256
_DKV_TILE = 128
# Rows of queries one grid step holds and of keys it fetches: the block
# (512: 20 % slower at t=1024).  A block of scores at 1024 x 1024 float32
# is 4 MiB; with the operands' own blocks at up to 512 bytes a row
# (d=128 float32, d=256 bfloat16) the body fits the v5e's 16 MiB of scoped
# VMEM, and wider rows take fewer of them (compile-only; not timed).
_BLOCK_ROWS = 1024
_BLOCK_ROW_BYTES = 512


def _query_groups(q_rows: int) -> int:
    """How many groups of query rows the forward scores a full block of
    ``q_rows`` as (module docstring): one ``_TILE`` of rows each, one
    group where tiles do not divide it.  A forward call on a v5e, ms, for
    1, 2 and 4 groups, causal, o and lse the same bits: [16, 8192, 128]
    2.207, 2.059, 2.035; [32, 8192, 128 + 64 | 128], keys in parts, 5.789,
    5.547, 5.473; [32, 8192, 128] under a window of 2048 2.413, 2.364,
    2.331; [128, 2048, 128] 1.631, 1.576, 1.575; [48, 1024, 64], whose one
    block is the diagonal's, 0.214 in all three (PERF.md section 6)."""
    return q_rows // _TILE if q_rows % _TILE == 0 else 1


def _auto_blocks(t_q: int, t_k: int, d: int):
    """The score tile ``(block_q, block_k)`` for these shapes — the cuDNN
    algo-search role (``ConvolutionLayer.java:349``) resolved by a sweep
    instead of per-call search: ``_TILE``, or the largest power of two
    under it that divides the sequence.  One rule for causal and not, for
    bfloat16 and float32 and for every head_dim: the sweep found no shape
    that wants another."""
    def pick(t):
        if t <= 128:
            return t          # sub-tile sequences run as one block
        b = _TILE
        while b > 128 and t % b:
            b //= 2
        return b
    return pick(t_q), pick(t_k)


def _block_rows(t_q: int, t_k: int, block_q: int, block_k: int,
                causal: bool, row_bytes: int):
    """``(rows of queries held, rows of keys fetched)`` a grid step: the
    most whole tiles under ``_BLOCK_ROWS`` that divide the sequence.  Under
    ``causal`` both are one size, so that a block the diagonal enters is
    entered at its corner and the tiles it crosses are known when the
    kernel is traced."""
    cap = _BLOCK_ROWS * _BLOCK_ROW_BYTES // max(row_bytes, _BLOCK_ROW_BYTES)

    def most(t, unit):
        n = max(1, min(cap, t) // unit)
        while (t // unit) % n:
            n -= 1
        return n * unit
    if not causal:
        return most(t_q, block_q), most(t_k, block_k)
    unit = max(block_q, block_k)
    rows = most(min(t_q, t_k), unit)
    while rows > unit and (t_q % rows or t_k % rows):
        rows -= unit
    if t_q % rows or t_k % rows:
        raise ValueError(
            f"causal flash attention needs both sequence lengths divisible "
            f"by its larger block: t_q={t_q}, t_k={t_k}, {unit}")
    return rows, rows


def _block_live(causal: bool, qi, ki, block_q: int, block_k: int):
    """False only for key blocks entirely above the causal diagonal —
    shared by the three kernels' walk of blocks (``_walk``) and by the
    tiles inside a block, so the skip predicate cannot drift.  (Blocks of
    the grid are one size for queries and keys under ``causal``: there it
    reads ``ki <= qi``.)"""
    if not causal:
        return True
    return qi * block_q + block_q - 1 >= ki * block_k


def _block_full(causal: bool, qi, ki, block_q: int, block_k: int):
    """True for blocks entirely on or below the diagonal: every score in
    them is live, so they take no mask."""
    if not causal:
        return True
    return qi * block_q >= ki * block_k + block_k - 1


def _diagonal_steps(rows: int, block_q: int, block_k: int):
    """The work of a block the diagonal enters at its corner, as static
    ``(q_start, q_size, k_start, k_size, cut)`` steps, one a query tile:
    the keys of every live tile in one product, of which the last ``cut``
    keys are the tiles the diagonal crosses and take the mask; key tiles
    wholly above the diagonal are in no step."""
    steps = []
    for r in range(rows // block_q):
        live = [c for c in range(rows // block_k)
                if _block_live(True, r, c, block_q, block_k)]
        full = [c for c in live if _block_full(True, r, c, block_q, block_k)]
        steps.append((r * block_q, block_q, 0, len(live) * block_k,
                      (len(live) - len(full)) * block_k))
    return steps


def _run_block(step, causal: bool, qi, ki, q_rows: int, k_rows: int,
               block_q: int, block_k: int, groups: int = 1):
    """One live block of the walk (``_walk``, which holds no dead one):
    where it is full one unmasked step over all its keys for each of
    ``groups`` groups of its query rows, the diagonal's steps where the
    diagonal enters it.  Key blocks come in rising order and a step's keys
    start at the block's first, so every row's first live step holds key
    0 and its running maximum is finite before a masked score meets it."""
    rows = q_rows // groups

    def whole():
        for g in range(groups):
            step(g * rows, rows, 0, k_rows, 0)
    if not causal:
        return whole()
    full = _block_full(causal, qi, ki, q_rows, k_rows)
    pl.when(full)(whole)

    @pl.when(jnp.logical_not(full))
    def _diagonal():
        for s in _diagonal_steps(q_rows, block_q, block_k):
            step(*s)


def _band_walk(window: int, rows: int, n_blocks: int) -> int:
    """How many blocks of ``rows`` the band reaches from one held block:
    its own and the ``(window + rows - 2) // rows`` next to it on the
    band's side, at most all of them."""
    return min((window + rows - 2) // rows + 1, n_blocks)


def _band_steps(rows: int, block_q: int, block_k: int, shift: int,
                window: int, groups: int = 1):
    """The work of a block whose first query lies ``shift`` positions past
    its first key, under the causal band ``q - window < k <= q``, as
    static ``(q_start, q_size, k_start, k_size, cut, head)`` steps, one a
    query tile: the keys of every tile it can see in one product, of which
    the first ``head`` keys (the tiles the band's lower edge crosses) and
    the last ``cut`` (those the diagonal crosses) take the mask; a block
    the band covers whole is ``groups`` unmasked steps, one a group of its
    query rows."""
    steps, whole = [], True
    for r in range(rows // block_q):
        q_lo = shift + r * block_q
        q_hi = q_lo + block_q - 1
        tiles = []                      # (full?) of the live key tiles
        first = None
        for c in range(rows // block_k):
            k_lo, k_hi = c * block_k, c * block_k + block_k - 1
            if k_lo <= q_hi and k_hi > q_lo - window:
                first = c if first is None else first
                tiles.append(k_hi <= q_lo and k_lo > q_hi - window)
        whole = whole and len(tiles) == rows // block_k and all(tiles)
        if not tiles:
            continue
        head = tiles.index(True) if True in tiles else len(tiles)
        cut = tiles[::-1].index(True) if True in tiles else 0
        if False in tiles[head:len(tiles) - cut]:
            raise AssertionError("a masked tile between two full ones")
        steps.append((r * block_q, block_q, first * block_k,
                      len(tiles) * block_k, cut * block_k, head * block_k))
    n = rows // groups
    return [(g * n, n, 0, rows, 0, 0) for g in range(groups)] if whole \
        else steps


def _run_band(step, window: int, delta, n_walk: int, rows: int,
               block_q: int, block_k: int, groups: int = 1):
    """One live block of a banded walk: the block ``delta`` blocks from the
    held one along the band, whose static steps (``_band_steps``) are
    chosen by that distance.  The walk (``_walk``) takes the held block's
    own first, so a row's first live step holds its own key and its
    running maximum is finite, and holds no block off the sequence."""
    for d in range(n_walk):
        steps = _band_steps(rows, block_q, block_k, d * rows, window, groups)

        def block(steps=steps, shift=d * rows):
            for s in steps:
                step(*s, shift=shift)
        pl.when(delta == d)(block)


def _scaled(x, scale: float):
    """scale·x in x's own type: the scale rides the ``[rows, d]`` operand,
    not every score tile.  Exact where the scale is a power of two (d=64:
    1/8); all three kernels scale the same operand, q, so the backward
    replays the forward's scores bit for bit."""
    return (x.astype(jnp.float32) * scale).astype(x.dtype)


def _masked_scores(q, k, q0: int, k0: int, cut: int, *,
                   transposed: bool = False, head: int = 0, shift: int = 0,
                   window: Optional[int] = None):
    """(scale·q)@kᵀ — q comes in scaled — with the causal mask on the last
    ``cut`` keys, the tiles the diagonal crosses: the one definition of
    the score tile used by forward and backward (replay must match
    exactly).  ``q0``, ``k0`` are the tile's offsets from the corner the
    diagonal enters at.  ``transposed`` gives k@(scale·q)ᵀ, keys down the
    rows, for the dk/dv kernel.  Under a ``window`` the first ``head``
    keys, the tiles the band's lower edge crosses, take the mask too, and
    ``shift`` is how far the block's first query lies past its first key."""
    a, b = (k, q) if transposed else (q, k)
    s = jax.lax.dot_general(a, b, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)
    if not (cut or head):
        return s
    qdim, kdim = (1, 0) if transposed else (0, 1)
    n = s.shape[kdim]
    if head + cut >= n:
        head, cut = 0, n
    below = n - cut

    def keys(lo, hi):
        return s[lo:hi] if transposed else s[:, lo:hi]

    def masked(part, start):
        qpos = shift + q0 + jax.lax.broadcasted_iota(jnp.int32, part.shape,
                                                     qdim)
        kpos = k0 + start + jax.lax.broadcasted_iota(jnp.int32, part.shape,
                                                     kdim)
        seen = qpos >= kpos
        if window is not None:
            seen = jnp.logical_and(seen, kpos > qpos - window)
        return jnp.where(seen, part, NEG_INF)
    parts = ([masked(keys(0, head), 0)] if head else []) + \
        ([keys(head, below)] if below > head else []) + \
        ([masked(keys(below, n), below)] if cut else [])
    return jnp.concatenate(parts, axis=kdim) if len(parts) > 1 else parts[0]


def _lanes(x, n: int):
    """A lane-replicated ``[rows, 128]`` statistic at ``n`` lanes."""
    w = x.shape[1]
    if n <= w:
        return x[:, :n]
    if n % w == 0:
        return jnp.tile(x, (1, n // w))
    return jnp.broadcast_to(x[:, :1], (x.shape[0], n))


def _column(x):
    """A lane-major ``[1, rows]`` statistic (``lse``, ``D``) as
    lane-replicated ``[rows, 128]``."""
    return jnp.broadcast_to(x[0][:, None], (x.shape[1], _LANES))


def _key_tile(k_ref, ks_ref, keys):
    """The keys of a step as the score takes them: the rows of ``k_ref``,
    and where the key came in parts (``ks_ref``: the part every head
    shares) the two laid side by side in VMEM, ``[k | k_shared]``, as wide
    as q: one product then scores them, as it scores a whole key.  (The
    other way, ``q[:, :d_k] kᵀ + q[:, d_k:] k_sharedᵀ`` as two products
    summed, costs the MXU the same passes and the forward a float32 add a
    score tile: 6.04 ms a call against 5.72 at [32, 8192, 128 + 64 | 128]
    on a v5e, dq and dkv the same; PERF.md section 6, PR 39.)  No
    ``ks_ref``: ``k_ref``'s rows and nothing else."""
    k = k_ref[keys, :]
    if ks_ref is None:
        return k
    return jnp.concatenate([k, ks_ref[keys, :]], axis=1)


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, acc_ref, m_ref, l_ref,
                  *, pair, scale: float, causal: bool, block_q: int,
                  block_k: int, window: Optional[int] = None,
                  n_walk: int = 0, ks_ref=None):
    # grid: (heads, pairs of _walk): a block of queries held, one of keys
    # walked (_walker reads the pair)
    qi, ki, first, last = pair
    q_rows, d_v = q_ref.shape[0], v_ref.shape[1]     # v's width, and o's

    @pl.when(first)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)
        m_ref[:] = jnp.full_like(m_ref, NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)

    def step(q0, nq, k0, nkeys, cut, head=0, shift=0):
        rows, keys = pl.ds(q0, nq), pl.ds(k0, nkeys)
        q = _scaled(q_ref[rows, :], scale)           # [nq, d_qk]
        v = v_ref[keys, :]                           # [nkeys, d_v]
        s = _masked_scores(q, _key_tile(k_ref, ks_ref, keys), q0, k0, cut,
                           head=head, shift=shift, window=window)
        # m, l: lane-replicated [rows, 128], so the row statistics meet
        # the score tile vreg for vreg with no lane broadcast
        m_prev = m_ref[rows, :]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - _lanes(m_new, nkeys))
        alpha = jnp.exp(m_prev - m_new)
        l_ref[rows, :] = alpha * l_ref[rows, :] + jnp.sum(
            p, axis=-1, keepdims=True)
        acc_ref[rows, :] = _lanes(alpha, d_v) * acc_ref[rows, :] + (
            jax.lax.dot_general(p.astype(v.dtype), v,
                                (((1,), (0,)), ((), ())),
                                preferred_element_type=jnp.float32))
        m_ref[rows, :] = m_new

    groups = _query_groups(q_rows)
    if window is None:
        _run_block(step, causal, qi, ki, q_rows, k_ref.shape[0],
                   block_q, block_k, groups)
    else:
        _run_band(step, window, qi - ki, n_walk, q_rows, block_q, block_k,
                  groups)

    @pl.when(last)
    def _finalize():
        l = l_ref[:]
        o_ref[...] = (acc_ref[:] / _lanes(l, d_v)).astype(o_ref.dtype)
        # logsumexp per row — the backward's softmax replay key, stored
        # lane-major: [1, rows] of a (heads, 1, t_q) array
        lse_ref[...] = (m_ref[:] + jnp.log(l))[:, 0][None, :]


def _like(x, shape=None, dtype=None):
    """Output type for a kernel launched on ``x``: under ``shard_map`` it
    varies over the same mesh axes as the operand."""
    return jax.ShapeDtypeStruct(x.shape if shape is None else shape,
                                dtype or x.dtype, vma=jax.typeof(x).vma)


def _walk(causal: bool, t_q: int, t_k: int, q_rows: int, k_rows: int,
          keys_walked: bool, n_walk: int = 0):
    """A head's ``(held block, walked block)`` pairs, in the order the grid
    walks them: for each held block the walked ones ``_block_live`` keeps,
    rising, so keys rise to the diagonal where keys are walked and queries
    rise from it where queries are; under a band (``n_walk``,
    ``_band_walk``) the held block's own first, then the next ones on the
    band's side that lie on the sequence; not causal, the whole square.
    No dead pair is in it, so no grid step idles."""
    n_q, n_k = t_q // q_rows, t_k // k_rows
    n_held, n_walked = (n_q, n_k) if keys_walked else (n_k, n_q)
    pairs = []
    for h in range(n_held):
        if n_walk:
            walked = [h - d if keys_walked else h + d for d in range(n_walk)]
        else:
            walked = range(n_walked)
        pairs += [(h, w) for w in walked if 0 <= w < n_walked and _block_live(
            causal, *((h, w) if keys_walked else (w, h)), q_rows, k_rows)]
    return pairs


def _table(pairs):
    """The walk as the columns the grid's index maps and the kernels read,
    scalar-prefetched: held block, walked block, and whether the pair is
    its held block's first (the sums start) and last (they are written)."""
    held, walked = (np.array(c, np.int32) for c in zip(*pairs))
    turn = held[1:] != held[:-1]
    return tuple(jnp.asarray(c, jnp.int32) for c in (
        held, walked, np.r_[True, turn], np.r_[turn, True]))


_HELD, _WALKED = 0, 1           # the table's columns an index map reads


def _spec(shape, column: int, index):
    """A block spec of a launch on the walk's table: ``index(b, n)`` of the
    block ``n`` that the table's ``column`` names at this step."""
    return pl.BlockSpec(shape,
                        lambda b, i, *table: index(b, table[column][i]))


def _specs(d: int, held: int, walked: int):
    """Block specs of a kernel whose grid is (heads, pairs of the walk):
    the held operand, the walked operand and each one's slice of the
    lane-major row statistics, at the blocks the pair names.  ``d`` is the
    operands' width: q's and k's in one call, v's (o's, dO's) in another
    where the two differ."""
    return (_spec((None, held, d), _HELD, lambda b, n: (b, n, 0)),
            _spec((None, walked, d), _WALKED, lambda b, n: (b, n, 0)),
            _spec((None, 1, held), _HELD, lambda b, n: (b, 0, n)),
            _spec((None, 1, walked), _WALKED, lambda b, n: (b, 0, n)))


def _row_bytes(qr, vr) -> int:
    """Bytes of the widest operand row a block holds: q's (and k's) or
    v's (and o's); of keys in parts, where ``vr`` is the ``[k | v]``
    product, q's or that product's."""
    return max(qr.shape[-1], vr.shape[-1]) * qr.dtype.itemsize


def _value_width(qr, vr, shared) -> int:
    """The width of v (o, dO): ``vr``'s, or of keys in parts, where ``vr``
    is the ``[k | v]`` product, q's less the shared part's."""
    return vr.shape[2] if shared is None else qr.shape[2] - shared[0].shape[2]


def _keys(kr, vr, shared, rows: int, column: int, k_spec, v_spec):
    """``(operands, block specs)`` of a launch's keys, ``rows`` of them a
    block, the block the table's ``column`` names.  Whole keys: ``kr`` and
    ``vr`` under the specs given.  Keys in parts (``shared``: ``(ksr,
    heads)``; ``kr`` and ``vr`` are then both the one ``[k | v]`` product,
    its halves equally wide): k and v are that product's column blocks 0
    and 1, no slice outside the kernel, and the shared part is one array a
    batch row whose index map leaves the head out, so ``heads`` kernel rows
    read it and no broadcast exists."""
    if shared is None:
        return (kr, vr), [k_spec, v_spec]
    ksr, heads = shared
    half = kr.shape[2] // 2
    return (kr, vr, ksr), [
        _spec((None, rows, half), column, lambda b, n: (b, n, 0)),
        _spec((None, rows, half), column, lambda b, n: (b, n, 1)),
        _spec((None, rows, ksr.shape[2]), column,
              lambda b, n: (b // heads, n, 0))]


def _walker(kernel, shared):
    """``kernel`` as a launch on the walk's table calls it: the table's
    columns come first, and this step's pair reaches the kernel as
    ``pair=(held block, walked block, first, last)``; of keys in parts the
    shared part of the key is the fourth operand."""
    def body(held, walked, first, last, q_ref, k_ref, v_ref, *refs):
        i = pl.program_id(1)
        pair = held[i], walked[i], first[i] != 0, last[i] != 0
        if shared is None:
            return kernel(q_ref, k_ref, v_ref, *refs, pair=pair)
        return kernel(q_ref, k_ref, v_ref, *refs[1:], pair=pair,
                      ks_ref=refs[0])
    return body


def _pallas(kernel, shared, table, bh: int, *, in_specs, out_specs,
            out_shape, scratch_shapes, interpret, name):
    """A launch of ``kernel`` on the walk's ``table``: grid (heads, pairs),
    the table's columns scalar-prefetched ahead of the operands."""
    return functools.partial(pl.pallas_call(
        _walker(kernel, shared),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(table), grid=(bh, len(table[0])),
            in_specs=in_specs, out_specs=out_specs,
            scratch_shapes=scratch_shapes),
        out_shape=out_shape, interpret=interpret, name=name), *table)


def _band(window, t_k, rows):
    """The kernels' keyword arguments of a windowed launch, of a full one
    none."""
    if window is None:
        return {}
    return {"window": window,
            "n_walk": _band_walk(window, rows, t_k // rows)}


def _launch_fwd(qr, kr, vr, scale, causal, block_q, block_k, interpret,
                window=None, shared=None):
    bh, t_q, d = qr.shape
    t_k, d_v = kr.shape[1], _value_width(qr, vr, shared)
    q_rows, k_rows = _block_rows(t_q, t_k, block_q, block_k, causal,
                                 _row_bytes(qr, vr))
    band = _band(window, t_k, q_rows)
    table = _table(_walk(causal, t_q, t_k, q_rows, k_rows, True,
                         band.get("n_walk", 0)))
    q_spec, k_spec, row_spec, _ = _specs(d, q_rows, k_rows)
    o_spec, v_spec, _, _ = _specs(d_v, q_rows, k_rows)
    keys, key_specs = _keys(kr, vr, shared, k_rows, _WALKED, k_spec, v_spec)
    out, lse = _pallas(
        functools.partial(_flash_kernel, scale=scale, causal=causal,
                          block_q=block_q, block_k=block_k, **band),
        shared, table, bh,
        in_specs=[q_spec] + key_specs,
        out_specs=[o_spec, row_spec],
        out_shape=[_like(qr, (bh, t_q, d_v)),
                   _like(qr, (bh, 1, t_q), jnp.float32)],
        scratch_shapes=[
            pltpu.VMEM((q_rows, d_v), jnp.float32),
            pltpu.VMEM((q_rows, _LANES), jnp.float32),
            pltpu.VMEM((q_rows, _LANES), jnp.float32),
        ],
        interpret=interpret,
        name="flash_win_fwd" if window else "flash_fwd",
    )(qr, *keys)
    return out, lse


def _flash_bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, dd_ref,
                         dq_ref, dq_acc, *, pair, scale, causal,
                         block_q, block_k, window=None, n_walk=0,
                         ks_ref=None):
    """dq of one block of queries: replay P from the saved logsumexp, form
    dS = P∘(dP − D) (FlashAttention-2 bwd) and add dS·k over the keys;
    the scale meets the ``[rows, d]`` sum once at the end."""
    qi, ki, first, last = pair

    @pl.when(first)
    def _init():
        dq_acc[:] = jnp.zeros_like(dq_acc)

    def step(q0, nq, k0, nkeys, cut, head=0, shift=0):
        rows, keys = pl.ds(q0, nq), pl.ds(k0, nkeys)
        q = _scaled(q_ref[rows, :], scale)           # [nq, d]
        k = _key_tile(k_ref, ks_ref, keys)           # [nkeys, d]
        lse = _column(lse_ref[:, rows])              # [nq, 128]
        dd = _column(dd_ref[:, rows])
        s = _masked_scores(q, k, q0, k0, cut, head=head, shift=shift,
                           window=window)
        p = jnp.exp(s - _lanes(lse, nkeys))
        dp = jax.lax.dot_general(do_ref[rows, :], v_ref[keys, :],
                                 (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - _lanes(dd, nkeys))
        dq_acc[rows, :] += jax.lax.dot_general(
            ds.astype(k.dtype), k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    if window is None:
        _run_block(step, causal, qi, ki, q_ref.shape[0], k_ref.shape[0],
                   block_q, block_k)
    else:
        _run_band(step, window, qi - ki, n_walk, q_ref.shape[0], block_q,
                  block_k)

    @pl.when(last)
    def _done():
        dq_ref[...] = (dq_acc[:] * scale).astype(dq_ref.dtype)


def _flash_bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, dd_ref,
                          dk_ref, dv_ref, dk_acc, dv_acc, *, pair, scale,
                          causal, block_q, block_k, window=None, n_walk=0,
                          ks_ref=None):
    """dk, dv of one block of keys, on the transposed score tile (keys
    down the rows, queries along the lanes): lse and D are used as the
    lane-major rows they are stored as, and Pᵀ·dO, dSᵀ·q are plain
    products.  grid: (heads, pairs of _walk), a block of keys held, one
    of queries walked.  Of keys in parts (``ks_ref``) ``dk_ref`` is the
    block of ``[dk | dv]`` that lies as the up-projection's product does,
    and ``dv_ref`` this head's share of the shared part's gradient."""
    ki, qi, first, last = pair

    @pl.when(first)
    def _init():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)

    def step(q0, nqs, k0, nkeys, cut, head=0, shift=0):
        rows, keys = pl.ds(q0, nqs), pl.ds(k0, nkeys)
        q = _scaled(q_ref[rows, :], scale)           # [nqs, d]
        do = do_ref[rows, :]
        st = _masked_scores(q, _key_tile(k_ref, ks_ref, keys), q0, k0, cut,
                            transposed=True, head=head, shift=shift,
                            window=window)           # [nkeys, nqs]
        pt = jnp.exp(st - lse_ref[:, rows])
        dv_acc[keys, :] += jax.lax.dot_general(
            pt.astype(do.dtype), do, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dpt = jax.lax.dot_general(v_ref[keys, :], do,
                                  (((1,), (1,)), ((), ())),
                                  preferred_element_type=jnp.float32)
        dst = pt * (dpt - dd_ref[:, rows])
        dk_acc[keys, :] += jax.lax.dot_general(
            dst.astype(q.dtype), q, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    if window is None:
        _run_block(step, causal, qi, ki, q_ref.shape[0], k_ref.shape[0],
                   block_q, block_k)
    else:
        _run_band(step, window, qi - ki, n_walk, q_ref.shape[0], block_q,
                  block_k)

    @pl.when(last)
    def _done():
        if ks_ref is None:
            dk_ref[...] = dk_acc[:].astype(dk_ref.dtype)
            dv_ref[...] = dv_acc[:].astype(dv_ref.dtype)
            return
        d_k = k_ref.shape[1]
        dk_ref[:, :d_k] = dk_acc[:, :d_k].astype(dk_ref.dtype)
        dk_ref[:, d_k:] = dv_acc[:].astype(dk_ref.dtype)
        dv_ref[...] = dk_acc[:, d_k:].astype(dv_ref.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8, 9))
def _flash(qr, kr, vr, scale, causal, block_q, block_k, interpret,
           with_lse=False, window=None):
    """The output, and with ``with_lse`` the rows' log-sum-exp
    ``(bh, 1, t_q)`` beside it, as a second differentiable result."""
    out, lse = _launch_fwd(qr, kr, vr, scale, causal, block_q, block_k,
                           interpret, window)
    return (out, lse) if with_lse else out


def _flash_fwd(qr, kr, vr, scale, causal, block_q, block_k, interpret,
               with_lse=False, window=None):
    out, lse = _launch_fwd(qr, kr, vr, scale, causal, block_q, block_k,
                           interpret, window)
    # the backward's residuals are results of this rule, not of the
    # caller's code: named here, or a checkpoint policy that saves by name
    # (nn/scan_layers) reruns the forward kernel in the backward
    out = checkpoint_name(out, "attn_out")
    lse = checkpoint_name(lse, "attn_lse")
    return ((out, lse) if with_lse else out), (qr, kr, vr, out, lse)


def _launch_bwd(qr, kr, vr, do, lse, dd, scale, causal, block_q,
                       block_k, interpret, window=None, shared=None):
    """``(dq, dk, dv)``; of keys in parts (``shared``) ``(dq, the gradient
    of the [k | v] product as that product lies, each head's share of the
    shared part's gradient)``."""
    bh, t_q, d = qr.shape
    t_k, d_v = kr.shape[1], _value_width(qr, vr, shared)
    q_rows, k_rows = _block_rows(t_q, t_k, block_q, block_k, causal,
                                 _row_bytes(qr, vr))
    band = _band(window, t_k, q_rows)
    q_spec, k_spec, row_spec, _ = _specs(d, q_rows, k_rows)
    do_spec, v_spec, _, _ = _specs(d_v, q_rows, k_rows)
    keys, key_specs = _keys(kr, vr, shared, k_rows, _WALKED, k_spec, v_spec)
    dq = _pallas(
        functools.partial(_flash_bwd_dq_kernel, scale=scale, causal=causal,
                          block_q=block_q, block_k=block_k, **band),
        shared, _table(_walk(causal, t_q, t_k, q_rows, k_rows, True,
                             band.get("n_walk", 0))), bh,
        in_specs=[q_spec] + key_specs + [do_spec, row_spec, row_spec],
        out_specs=q_spec,
        out_shape=_like(qr),
        scratch_shapes=[pltpu.VMEM((q_rows, d), jnp.float32)],
        interpret=interpret,
        name="flash_win_bwd_dq" if window else "flash_bwd_dq",
    )(qr, *keys, do, lse, dd)

    # swapped roles: a block of keys held, queries walked, so dk/dv carry
    # in scratch.  Its tile is the finer _DKV_TILE where that divides the
    # caller's.
    block_q, block_k = (_DKV_TILE if b % _DKV_TILE == 0 else b
                        for b in (block_q, block_k))
    k_spec2, q_spec2, _, row_spec2 = _specs(d, k_rows, q_rows)
    v_spec2, do_spec2, _, _ = _specs(d_v, k_rows, q_rows)
    keys, key_specs = _keys(kr, vr, shared, k_rows, _HELD, k_spec2, v_spec2)
    if shared is None:
        out_specs, out_shape = [k_spec2, v_spec2], [_like(kr), _like(vr)]
    else:
        # [dk | dv] a held block, and this head's rows of the shared part's
        out_specs = [_specs(w, k_rows, q_rows)[0]
                     for w in (2 * d_v, d - d_v)]
        out_shape = [_like(kr), _like(kr, (bh, t_k, d - d_v))]
    dk, dv = _pallas(
        functools.partial(_flash_bwd_dkv_kernel, scale=scale, causal=causal,
                          block_q=block_q, block_k=block_k, **band),
        shared, _table(_walk(causal, t_q, t_k, q_rows, k_rows, False,
                             band.get("n_walk", 0))), bh,
        in_specs=[q_spec2] + key_specs + [do_spec2, row_spec2, row_spec2],
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM((k_rows, d), jnp.float32),
                        pltpu.VMEM((k_rows, d_v), jnp.float32)],
        interpret=interpret,
        name="flash_win_bwd_dkv" if window else "flash_bwd_dkv",
    )(qr, *keys, do, lse, dd)
    return dq, dk, dv


def _row_dots(with_lse, out, do):
    """``(dO, D)`` of a backward rule's cotangent: D = rowsum(dO ∘ O), one
    elementwise+reduce pass, XLA-fused, ``(bh, 1, t_q)`` row form."""
    if with_lse:
        do, dlse = do
    dd = jnp.sum(do.astype(jnp.float32) * out.astype(jnp.float32),
                 axis=-1)[:, None, :]
    if with_lse:
        # d lse_i / d s_ij = p_ij, so a cotangent on the log-sum-exp adds
        # dlse_i * p_ij to dS = P∘(dP − D): the kernels take it as D − dlse
        dd = dd - dlse
    return do, dd


def _flash_bwd(scale, causal, block_q, block_k, interpret, with_lse,
               window, res, do):
    qr, kr, vr, out, lse = res
    do, dd = _row_dots(with_lse, out, do)
    return _launch_bwd(qr, kr, vr, do, lse, dd, scale, causal, block_q,
                       block_k, interpret, window)


_flash.defvjp(_flash_fwd, _flash_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8, 9))
def _flash_parts(qr, kvr, ksr, scale, causal, block_q, block_k, interpret,
                 with_lse=False, heads=1):
    """``_flash`` of keys in parts: ``kvr`` ``(b heads, t, d_k + d_v)`` the
    ``[k | v]`` product, ``ksr`` ``(b, t, d_s)`` the part of the key every
    head shares; ``qr``'s last ``d_s`` features meet it."""
    out, lse = _launch_fwd(qr, kvr, kvr, scale, causal, block_q, block_k,
                           interpret, shared=(ksr, heads))
    return (out, lse) if with_lse else out


def _flash_parts_fwd(qr, kvr, ksr, scale, causal, block_q, block_k,
                     interpret, with_lse=False, heads=1):
    out, lse = _launch_fwd(qr, kvr, kvr, scale, causal, block_q, block_k,
                           interpret, shared=(ksr, heads))
    out = checkpoint_name(out, "attn_out")           # as _flash_fwd
    lse = checkpoint_name(lse, "attn_lse")
    return ((out, lse) if with_lse else out), (qr, kvr, ksr, out, lse)


def _flash_parts_bwd(scale, causal, block_q, block_k, interpret, with_lse,
                     heads, res, do):
    qr, kvr, ksr, out, lse = res
    do, dd = _row_dots(with_lse, out, do)
    dq, dkv, dks = _launch_bwd(qr, kvr, kvr, do, lse, dd, scale, causal,
                               block_q, block_k, interpret,
                               shared=(ksr, heads))
    # the heads' shares of the shared part's gradient, summed in float32
    dks = jnp.sum(dks.reshape(-1, heads, *dks.shape[1:]), axis=1,
                  dtype=jnp.float32).astype(ksr.dtype)
    return dq, dkv, dks


_flash_parts.defvjp(_flash_parts_fwd, _flash_parts_bwd)


#: kernel names as they appear in the lowered program's ``tpu_custom_call``
#: ops — what a caller greps ``as_text()`` for to prove the kernels are in:
#: the three of a full call, the three of a windowed one
FULL_KERNEL_NAMES = ("flash_fwd", "flash_bwd_dq", "flash_bwd_dkv")
WINDOW_KERNEL_NAMES = ("flash_win_fwd", "flash_win_bwd_dq",
                       "flash_win_bwd_dkv")
KERNEL_NAMES = FULL_KERNEL_NAMES + WINDOW_KERNEL_NAMES


def flash_blocks(t_q: int, t_k: int, d: int,
                 block_q: Optional[int] = None,
                 block_k: Optional[int] = None, d_v: Optional[int] = None,
                 d_shared: Optional[int] = None):
    """``(block_q, block_k)``, the score tile the kernels run these shapes
    with: ``d`` the width of a head of q and k, ``d_v`` of v (default
    ``d``); ``d_shared``, where the keys come in parts, the width of the
    part every head shares, the last of ``d``.  Raises ``ValueError``
    naming the reason when it cannot tile them — the one support check,
    shared by ``flash_attention`` (which raises) and ``attn_impl='auto'``
    (which then chooses the reference path; a caller whose keys it refuses
    in parts assembles them and asks again)."""
    auto_q, auto_k = _auto_blocks(t_q, t_k, d)
    block_q = min(block_q, t_q) if block_q else auto_q
    block_k = min(block_k, t_k) if block_k else auto_k
    d_v = d_v or d
    if d % 64 or d_v % 64:
        # head_dim must fill whole MXU lanes for the kernel's tiling
        raise ValueError(f"flash attention needs head_dim % 64 == 0, "
                         f"got {d}" + (f" and {d_v}" if d_v != d else ""))
    if d_shared is not None and (d - d_shared != d_v or d_v % _LANES
                                 or d_shared <= 0):
        # k and v are read as lane-tile-wide column blocks of one array
        raise ValueError(
            f"flash attention takes keys in parts where the head's own "
            f"part is as wide as v and a multiple of {_LANES}: got "
            f"{d - d_shared} (of {d}, {d_shared} shared) and {d_v}")
    if t_q % block_q or t_k % block_k:
        raise ValueError(
            f"flash attention needs sequence lengths divisible by its "
            f"blocks: t_q={t_q} % {block_q}, t_k={t_k} % {block_k}")
    if max(block_q, block_k) % min(block_q, block_k):
        raise ValueError(
            f"flash attention needs one of its blocks divisible by the "
            f"other: {block_q}, {block_k}")
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


def flash_attention(q, k=None, v=None, *, kv=None, k_shared=None,
                    causal: bool = False,
                    scale: Optional[float] = None,
                    block_q: Optional[int] = None,
                    block_k: Optional[int] = None,
                    interpret: bool = False, return_lse: bool = False,
                    window: Optional[int] = None):
    """Flash attention over [b, h, t, d] tensors — differentiable: the
    FlashAttention-2 style backward (saved logsumexp, softmax replayed per
    block, separate dq and dk/dv kernels) keeps training memory O(t).
    ``v`` may have a last dimension of its own (latent attention: q and k
    192 wide, v 128): the output is as wide as ``v``, the scale follows
    q's width, and nothing is padded.

    **Keys in parts** (latent attention's, as its projections write them):
    in place of ``k`` and ``v``, ``kv`` ``[b, h, t, d_k + d_v]``, each
    head's own part of the key beside its value as one product left them,
    and ``k_shared`` ``[b, 1, t, d_s]``, the part of the key every head
    shares; ``q`` stays whole, ``[b, h, t, d_k + d_s]``, its last ``d_s``
    features meeting ``k_shared``.  The kernels read ``k`` and ``v`` as
    column blocks of ``kv`` and ``k_shared`` through an index map that
    leaves the head out: no slice, broadcast or assembled key exists
    outside them; ``kv``'s gradient comes back as ``kv`` lies, ``[dk |
    dv]``, and ``k_shared``'s summed over the heads.  Taken where ``d_k ==
    d_v`` is a multiple of 128 and without a window (``flash_blocks``
    refuses the rest: assemble ``k`` and ``v`` then).

    ``return_lse`` gives ``(out, lse)``, ``lse`` the float32 ``[b, h, t]``
    log-sum-exp of each row's scaled scores, differentiable like ``out``
    (the same three kernels; a cotangent on it rides the backward's ``D``):
    what a caller needs to merge this softmax with one over further keys
    (``ops.attention.combine_blocks`` with ``m = lse``, ``l = 1``).

    ``window=W`` (with ``causal``, queries and keys of one length) keeps
    of each query's keys the last ``W``, itself among them: ``i - W < j <=
    i``.  The kernels then skip every block outside that band (their names
    become ``flash_win_*``); a window no shorter than the sequence is the
    plain causal call.

    Never falls back: shapes the kernel cannot tile raise ``ValueError``
    (``flash_blocks``), and off a TPU backend the Pallas lowering itself
    refuses unless ``interpret=True``.  Key-padding masks are not
    supported here.  ``attn_impl='auto'`` is the caller that chooses
    between this and ``sdpa_reference``.
    """
    given = [a is not None for a in (k, v, kv, k_shared)]
    parts = given == [False, False, True, True]
    if not parts and given != [True, True, False, False]:
        raise ValueError("flash attention takes k and v, or kv and "
                         "k_shared in their place")
    _, h, t_q, d = q.shape
    if parts:
        if window is not None:
            raise ValueError("flash attention takes keys in parts without "
                             f"a window, got window={window}")
        d_s = k_shared.shape[3]
        t_k, d_v = kv.shape[2], kv.shape[3] - (d - d_s)
        keys = (kv, k_shared)
    else:
        d_s = None
        t_k, d_v = k.shape[2], v.shape[3]
        keys = (k, v)
    block_q, block_k = flash_blocks(t_q, t_k, d, block_q, block_k, d_v, d_s)
    if scale is None:
        scale = d ** -0.5
    if window is not None:
        if not causal or t_q != t_k or window < 1:
            raise ValueError(
                f"a window ({window}) needs causal attention of queries "
                f"and keys of one length: causal={causal}, t_q={t_q}, "
                f"t_k={t_k}")
        window = int(window) if window < t_k else None
    from ..observability.registry import default_registry
    reg = default_registry()
    if reg.enabled:
        # trace-time, like mla_layers_traced_total
        reg.counter("flash_calls_traced_total",
                    "Calls of flash_attention traced into a program, by "
                    "the form the keys came in: whole (k and v a head) or "
                    "parts (the [k | v] product and the shared part)",
                    ("keys",)).labels("parts" if parts else "whole").inc()
        q_rows, k_rows = _block_rows(t_q, t_k, block_q, block_k, causal,
                                     _row_bytes(q, kv if parts else v))
        reg.counter("flash_fwd_groups_traced_total",
                    "Calls of flash_attention traced into a program, by "
                    "the groups of query rows its forward kernel scores a "
                    "full block as", ("groups",)).labels(
                        str(_query_groups(q_rows))).inc()
        n_walk = _band(window, t_k, q_rows).get("n_walk", 0)
        reg.counter("flash_grid_pairs_traced_total",
                    "Calls of flash_attention traced into a program, by "
                    "the (held, walked) block pairs a head's grid walks "
                    "and the steps a grid over the whole square or band "
                    "would take", ("live", "square")).labels(
                        str(len(_walk(causal, t_q, t_k, q_rows, k_rows,
                                      True, n_walk))),
                        str(t_q // q_rows * (n_walk or t_k // k_rows))).inc()

    def run(q, k, v):
        # keys in parts: k is kv, v the shared part
        rows = q.shape[0] * h
        qr, kr = (a.reshape(rows, *a.shape[2:]) for a in (q, k))
        if parts:
            out = _flash_parts(qr, kr, v.reshape(-1, t_k, d_s), scale,
                               causal, block_q, block_k, interpret,
                               return_lse, h)
        else:
            out = _flash(qr, kr, v.reshape(rows, t_k, d_v), scale, causal,
                         block_q, block_k, interpret, return_lse, window)
        o_shape = q.shape[:3] + (d_v,)
        if return_lse:
            return out[0].reshape(o_shape), out[1].reshape(q.shape[:3])
        return out.reshape(o_shape)

    mesh, spec = _kernel_partitioning(q)
    if mesh is not None:
        # the Pallas interpreter evaluates the kernel body's own equations
        # under the varying-axes check, which refuses a varying tile times
        # a constant; a compiled kernel is one opaque call to that check
        run = jax.shard_map(run, mesh=mesh, in_specs=(spec, spec, spec),
                            out_specs=(spec, spec) if return_lse else spec,
                            check_vma=not interpret)
    return run(q, *keys)
