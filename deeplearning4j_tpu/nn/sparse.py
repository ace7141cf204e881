"""Sparse embedding gradients: densified row exchange for huge tables.

An embedding gradient is a handful of rows of a `[vocab, dim]` table,
yet a dense train step all-reduces (or reduce-scatters) the whole
mostly-zero tensor every step.  This module is the densified
accumulation of assumed-sparse tensors (arXiv:1905.04035, PAPERS.md):
coalesce the rows a batch actually touches and exchange fixed-capacity
index + value blocks instead of the dense table, so per-step comms go
from O(vocab·dim) to O(touched_rows·dim).

The mechanism (wired into ``nn/_common.build_train_step`` when the
stack's first layer, an embedding, declares ``sparse_grad=True``):

1. **Coalesce outside the gradient** — :func:`coalesce` computes the
   sorted unique touched row ids (``jnp.unique`` with a STATIC
   ``size=capacity``, so shapes stay fixed under jit and every
   ``ShapePolicy`` bucket compiles once) plus the position→slot inverse
   map via ``searchsorted``.
2. **Differentiate row space, not table space** — the step gathers
   ``rows = W[uniq]`` *before* ``value_and_grad`` and substitutes the
   table leaf with the gathered rows (and the ids with their slot map),
   so the table's cotangent is ``[capacity, dim]`` — the dense
   ``[vocab, dim]`` cotangent is never materialized.  The lookup itself
   is :func:`embedding_lookup`, a custom-vjp gather whose backward is
   ONE coalesced ``segment_sum`` (deterministic densified
   accumulation of duplicate ids).
3. **SparseRows carrier** — the coalesced gradient travels as
   :class:`SparseRows` (indices + values, pytree-registered), the
   system's first structurally-sparse gradient leaf.
4. **Lazy row-space updater** — the optax transform runs on
   row-space views (:func:`gather_rows_tree` pulls the touched rows of
   every param-shaped mirror leaf — Adam mu/nu, momentum traces — into
   ``[capacity, dim]`` blocks), and :func:`scatter_rows_tree` writes
   only those rows back.  Untouched rows of the table AND its mirrors
   are bit-identical across the step ("lazy" updater semantics: exact
   for stateless updaters like SGD; stateful updaters skip the decay of
   untouched rows, the standard lazy-Adam trade).

Under a ZeRO-3 mesh (``parallel/sharded.py``) the table and its mirrors
are row-sharded over the data axis, and GSPMD derives the whole
exchange from the argument shardings: the touched-row gather becomes a
shard-local gather + an O(capacity·dim) all-reduce returning rows to
requesters, the backward segment-sum becomes per-shard partials + the
same-sized reduction back to the owner shards, and the scatter-update
stays shard-local — no collective in the partitioned HLO carries
O(vocab·dim) bytes (pinned by the ``train_step[embedding_zero3]``
graftaudit card).

Capacity contract: the per-step exchange block is ``capacity`` rows.
``capacity=None`` derives the exact static bound ``min(n_ids, vocab)``
— overflow is impossible by construction.  An explicit
``sparse_grad_capacity`` below that bound is REFUSED at trace time
(:func:`effective_capacity`): silent gradient truncation is the one
behavior this path must never ship.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

__all__ = ["SparseRows", "coalesce", "effective_capacity",
           "embedding_lookup", "RowContext", "gather_rows_tree",
           "scatter_rows_tree", "table_is_unambiguous"]


@jax.tree_util.register_pytree_node_class
@dataclass
class SparseRows:
    """Densified-sparse gradient of a ``[n_rows, dim]`` table.

    ``indices``: ``[capacity]`` int32, sorted unique touched row ids;
    unused slots hold ``n_rows`` (one past the last valid row) so a
    ``mode="drop"`` scatter ignores them.  ``values``: ``[capacity,
    dim]`` coalesced per-row gradient values (duplicate ids already
    segment-summed).  ``n_rows`` is static aux data — it defines the
    dense shape without ever allocating it.
    """

    indices: Any
    values: Any
    n_rows: int

    def tree_flatten(self):
        return (self.indices, self.values), (self.n_rows,)

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(children[0], children[1], aux[0])

    @property
    def capacity(self) -> int:
        return int(self.indices.shape[0])

    @property
    def dim(self) -> int:
        return int(self.values.shape[-1])

    def touched(self):
        """Traced count of real (non-fill) row slots."""
        # explicit accumulator dtype: jnp.sum(int32) widens to i64 under
        # x64, which would put an s64 scalar into the pinned collective
        # census
        return jnp.sum(self.indices < self.n_rows, dtype=jnp.int32)

    def to_dense(self):
        """Materialize the dense ``[n_rows, dim]`` gradient — tests and
        host-side interop ONLY; the train step never calls this (the
        whole point is that the dense tensor does not exist there)."""
        dense = jnp.zeros((self.n_rows, self.dim), self.values.dtype)
        return dense.at[self.indices].add(self.values, mode="drop")  # graftlint: disable=JX027  (documented test/interop escape hatch — the train step itself never densifies)


def effective_capacity(n_ids: int, n_rows: int,
                       configured: Optional[int] = None) -> int:
    """Static row capacity of one step's exchange block.

    The exact bound ``min(n_ids, n_rows)`` can never overflow (a batch
    of ``n_ids`` positions touches at most that many distinct rows).
    ``configured`` may only pad UP to a fixed block size (shape
    stability across ShapePolicy buckets); an undersized capacity is
    refused here, at trace time — the pinned overflow behavior —
    because truncating unique ids would silently drop or misattribute
    gradient mass.
    """
    exact = min(int(n_ids), int(n_rows))
    if configured is None:
        return exact
    configured = int(configured)
    if configured < exact:
        raise ValueError(
            f"sparse_grad_capacity={configured} is below the exact "
            f"touched-row bound min(n_ids={n_ids}, vocab={n_rows}) = "
            f"{exact}: an overflowing capacity would silently truncate "
            "gradient rows — raise the capacity (or leave it None for "
            "the exact bound)")
    return min(configured, int(n_rows))


def coalesce(ids, capacity: int, n_rows: int) -> Tuple[Any, Any]:
    """Coalesce a flat int id vector into ``(uniq, inv)``.

    ``uniq``: ``[capacity]`` sorted unique ids, fill slots = ``n_rows``.
    ``inv``: ``ids``-shaped int32 slot map with ``uniq[inv] == ids`` for
    every position whose id made it into ``uniq`` and ``capacity`` (one
    past the last slot) otherwise — pointing those positions at the
    zero "trash" row of an extended ``[capacity+1, dim]`` row block, so
    their gradient is dropped rather than misattributed.  With
    ``capacity`` from :func:`effective_capacity` every id is always
    found; the guard exists so the contract is positional, not
    assumed.
    """
    capacity = int(capacity)
    flat = ids.reshape(-1).astype(jnp.int32)
    # invalid ids (negative or >= n_rows) collapse onto the fill value
    # FIRST: traced ids bypass the layers' concrete range validation,
    # and an unmasked invalid id would corrupt silently — a negative
    # index wraps in the `.at[...]` scatter (writing the LAST row with
    # a foreign update), and an id > n_rows lands above the fill value,
    # un-sorting `uniq` and breaking the searchsorted slot map.  Masked,
    # an invalid position reads the clamp row forward and sheds its
    # gradient at the dropped fill slot — deterministic, never
    # misattributed.
    flat = jnp.where((flat >= 0) & (flat < n_rows), flat,
                     jnp.int32(n_rows))
    # hand-rolled unique (sort + first-occurrence scatter) instead of
    # jnp.unique: every intermediate stays int32 regardless of
    # jax_enable_x64, so the compiled collective census — which the
    # committed graftaudit card pins — is identical across x64 modes
    # (jnp.unique's internal iota is i64 under x64)
    s = jnp.sort(flat)
    first = jnp.concatenate(
        [jnp.ones((1,), bool), s[1:] != s[:-1]])
    pos = jnp.cumsum(first.astype(jnp.int32)) - 1     # slot per element
    write = jnp.where(first, pos, jnp.int32(capacity))
    uniq = jnp.full((capacity,), jnp.int32(n_rows), jnp.int32) \
        .at[write].set(s, mode="drop")
    slot = jnp.searchsorted(uniq, flat).astype(jnp.int32)
    slot_c = jnp.clip(slot, 0, capacity - 1)
    inv = jnp.where(uniq[slot_c] == flat, slot_c,
                    jnp.int32(capacity))
    return uniq, inv.reshape(ids.shape)


# ---------------------------------------------------------------- lookup
@jax.custom_vjp
def embedding_lookup(table, idx):
    """Gather ``table[idx]`` whose backward is a single coalesced
    ``segment_sum`` — the densified accumulation of arXiv:1905.04035.

    In the sparse train step ``table`` is the substituted
    ``[capacity+1, dim]`` touched-row block, so the cotangent this
    produces IS the :class:`SparseRows` value block (plus the trash
    row); the dense ``[vocab, dim]`` cotangent never exists.  With a
    full table it degrades to the ordinary gather/scatter-add pair.
    Id hygiene lives upstream: `EmbeddingLayer` raises
    ``InvalidInputError`` on concrete out-of-range ids, and the train
    step's :func:`coalesce` masks traced invalid ids onto the dropped
    fill slot (clamp-row forward, no gradient — never a wrapped or
    misattributed row write).
    """
    return table[idx]


def _lookup_fwd(table, idx):
    return table[idx], (idx, table.shape[0])


def _lookup_bwd(res, ct):
    idx, n_rows = res
    dim = ct.shape[-1]
    grad = jax.ops.segment_sum(ct.reshape(-1, dim),
                               idx.reshape(-1).astype(jnp.int32),
                               num_segments=n_rows)
    # integer primal: float0 cotangent (JAX's "no tangent" dtype)
    return grad.astype(ct.dtype), np.zeros(idx.shape, jax.dtypes.float0)


embedding_lookup.defvjp(_lookup_fwd, _lookup_bwd)


# ------------------------------------------------------------ row context
def table_is_unambiguous(params, table_shape) -> bool:
    """The row-space mirror walk identifies the table's optimizer
    mirrors by shape (optax state trees don't carry param paths through
    ``multi_transform`` masking).  That is only sound when exactly ONE
    param leaf has the table's shape — a twin same-shaped parameter
    would alias its mirrors into the row swap."""
    n = sum(1 for leaf in jax.tree_util.tree_leaves(params)
            if getattr(leaf, "shape", None) == tuple(table_shape))
    return n == 1


class RowContext:
    """One step's touched-row workspace: built at trace time from the
    batch ids, consumed by the substitution / update / scatter stages
    of the sparse train step.  Plain object (not a pytree) — it lives
    inside a single trace."""

    __slots__ = ("uniq", "inv", "capacity", "n_rows", "rows", "rows_ext",
                 "x_sub")

    def __init__(self, W, ids, configured_capacity: Optional[int]):
        n_rows, dim = int(W.shape[0]), int(W.shape[1])
        n_ids = int(np.prod(ids.shape, dtype=np.int64))
        cap = effective_capacity(n_ids, n_rows, configured_capacity)
        uniq, inv = coalesce(ids, cap, n_rows)
        self.uniq, self.inv = uniq, inv
        self.capacity, self.n_rows = cap, n_rows
        # fill slots (uniq == n_rows) clamp-gather the last real row;
        # their zero-grad "updates" are dropped at scatter time
        self.rows = W[jnp.clip(uniq, 0, n_rows - 1)]
        # +1 zero trash row: positions whose id missed the block (never,
        # under effective_capacity) read zeros and shed their gradient
        self.rows_ext = jnp.concatenate(
            [self.rows, jnp.zeros((1, dim), W.dtype)], axis=0)
        self.x_sub = inv

    def touched(self):
        """Traced count of real (non-fill) row slots this step touches
        (fixed-i32 accumulator — see :meth:`SparseRows.touched`)."""
        return jnp.sum(self.uniq < self.n_rows, dtype=jnp.int32)

    def scatter_rows(self, table, new_rows):
        """Write the updated touched rows back into the full table;
        fill-slot indices (== n_rows) drop."""
        return table.at[self.uniq].set(new_rows, mode="drop")

    def wrap_grad(self, g_rows_ext) -> SparseRows:
        """[capacity+1, dim] cotangent (from the substituted lookup's
        backward) → the SparseRows carrier; the trash row is dropped
        (zero under the capacity contract)."""
        return SparseRows(self.uniq, g_rows_ext[:self.capacity],
                          self.n_rows)


def gather_rows_tree(tree, ctx: RowContext):
    """Row-space view of an optimizer-state pytree: every leaf shaped
    exactly like the table (its mu/nu/trace mirrors) is gathered down
    to the ``[capacity, dim]`` touched-row block; every other leaf
    (counts, scalars, other params' mirrors) passes through untouched.
    Shape-keyed on purpose — see :func:`table_is_unambiguous`."""
    table_shape = (ctx.n_rows, int(ctx.rows.shape[1]))
    safe = jnp.clip(ctx.uniq, 0, ctx.n_rows - 1)

    def pick(leaf):
        if getattr(leaf, "shape", None) == table_shape and \
                hasattr(leaf, "dtype") and \
                jnp.issubdtype(leaf.dtype, jnp.inexact):
            return leaf[safe]
        return leaf

    return jax.tree_util.tree_map(pick, tree)


def scatter_rows_tree(old_tree, new_row_tree, ctx: RowContext):
    """Inverse of :func:`gather_rows_tree` after the row-space update:
    mirror leaves get their touched rows scattered back (untouched rows
    keep their pre-step bytes — the lazy semantics); everything else
    takes the updated value."""
    table_shape = (ctx.n_rows, int(ctx.rows.shape[1]))
    row_shape = (ctx.capacity, int(ctx.rows.shape[1]))

    def put(old, new):
        # the SAME classification gather_rows_tree used (shape AND
        # inexact dtype): with capacity == vocab the two shapes
        # coincide, and a table-shaped integer state leaf the gather
        # passed through must not be row-permuted here
        if getattr(old, "shape", None) == table_shape and \
                getattr(new, "shape", None) == row_shape and \
                hasattr(old, "dtype") and \
                jnp.issubdtype(old.dtype, jnp.inexact):
            return old.at[ctx.uniq].set(new, mode="drop")
        return new

    return jax.tree_util.tree_map(put, old_tree, new_row_tree)
