"""ZeRO-3 sharded SPMD training: params + optimizer state partitioned
over the data axis.

The replicated scale-out paths (``parallel/master*.py``,
``ParallelWrapper``) hold FULL params and FULL updater state per
worker, so model size is capped by one device and every step ships a
dense all-reduce.  This module is the weight-update sharding transform
of "Automatic Cross-Replica Sharding of Weight Update in Data-Parallel
Training" (arXiv:2004.13336, PAPERS.md) taken to its ZeRO-3 endpoint:

  - every parameter leaf (and its optax mu/nu/trace mirror) is laid out
    with a ``NamedSharding`` row-sharded over ``data``
    (``mesh.zero3_spec``: first axis divisible by dp; sub-threshold
    leaves — biases, norms — replicate, sharding them saves nothing);
  - the train step is the SAME jitted program every network uses
    (``_get_jitted("train_step")`` through the process-global trace
    cache): GSPMD sees sharded param inputs + a data-sharded batch and
    itself inserts the forward all-gather, turns the gradient reduction
    into a reduce-scatter, and keeps the update shard-local — the
    all-reduce → reduce-scatter + all-gather rewrite is derived from
    the shardings, not hand-written collectives;
  - because sharding lives in the ARGUMENTS, not the trace, one Python
    trace serves every mesh size: a dp=2 and a dp=8 run share one
    ``training_compile_total{fn="train_step"}`` tick (each dp still
    gets its own XLA executable — lowering is per-sharding, tracing is
    not).  This is what collapses the thread-pool "replica" abstraction
    into one program.

Mixed precision composes for free: with a bf16 ``PrecisionPolicy`` the
sharded params ARE the f32 masters (``nn/precision``) — the in-step
cast produces bf16 compute values while the updater applies its f32
update to the local shard only ("sharded masters").

Numerics: at a fixed global batch the sharded step is BIT-FOR-BIT the
replicated step on the same mesh whenever GSPMD gathers the sharded
params before the matmul — its choice for every representative shape
(tier-1 pins dp=2/4/8 bitwise); with a *tiny* sharded contracting dim
it may partial-compute + all-reduce instead, which reassociates that
reduction and bounds parity at ~1e-6-relative (f32) — the same noise
class as changing dp in any data-parallel run (also pinned).  Across
dp sizes results always agree to reassociation tolerance.

Checkpoints: ``faulttolerance.checkpoint`` grows ``save_sharded`` /
``restore_sharded`` (portable-collectives resharding, arXiv:2112.01075)
— each process writes only its shard blocks plus a topology manifest,
and a restore reassembles host-side and re-places onto ANY mesh (a
4-way checkpoint resumes 8-way), which is also what lets an elastic
rejoin re-place a sharded model onto the surviving world.  Multi-writer
worlds commit through the two-phase ``ShardBarrier`` staged protocol
(every process's block + generation-fenced marker land before the
primary's manifest+rename), and ``ElasticTrainer`` drives the whole
loop: barrier saves at round boundaries, membership changes rebuilding
the mesh over survivors via ``restore_sharded(mesh=survivors)``, one
train-step trace across topology changes.

Sparse embedding tables ride the same layout: a ``sparse_grad=True``
embedding table is simply the first VERY large parameter this rule
row-shards (``zero3_spec`` puts the vocab axis over ``data``, its
optax mirrors included), so vocabulary size is no longer capped by one
device's HBM.  The train step's densified pre-pass (``nn/sparse``)
then makes the per-step exchange O(touched rows): GSPMD derives, from
these same argument shardings, a ragged touched-row lookup — the
replicated id blocks gather shard-locally and an O(capacity·dim)
all-reduce returns the requested rows to every requester — and the
backward's coalesced index+value blocks reduce back to their owner
shards the same way, while the row scatter-update (params and
mirrors) stays shard-local.  No hand-written collectives, no second
trace: a dp=2 and dp=8 sparse run still share the ONE train-step
trace, and checkpoints reshard through the same
``save_sharded``/``restore_sharded`` per-leaf block format (the table
is just a big leaf; dp=4 → dp=2 restores digest-exact, pinned in
tests/test_sparse_embedding.py).

Gather/compute overlap: the forward all-gather of each layer's shard is
emitted at its USE SITE — the step folds over layers consuming
``params[name]`` one at a time, so GSPMD materializes layer k+1's gather
as a separate collective from layer k's matmul rather than one up-front
blob — which leaves XLA's scheduler free to start layer k+1's all-gather
while layer k computes.  Whether it does is the TPU compiler's default
behaviour: nothing here sets compiler flags (an earlier constructor
appended ``--xla_tpu_*`` flags to ``XLA_FLAGS``, which this jaxlib's flag
parser rejects with a fatal error).  Exposed all-gather time on four
chips: not measured.

The derived collective layout is GUARDED at the IR level: graftaudit
(``tools/graftaudit``, rule AX003) compiles the canonical dp=2/dp=4
sharded train steps from their recorded argument shardings and flags a
dense all-reduce of (near-)param bytes — the pattern that appears when
some op defeats the GSPMD scatter/gather derivation — and
``tests/test_audit.py`` pins both censuses EXACTLY (golden collective
signature), so a layout regression fails tier-1 instead of a profile
review.  The sparse-table program has its own canonical pin:
``train_step[embedding_zero3]``'s committed card must contain no
collective carrying O(vocab·dim) bytes.
"""
from __future__ import annotations

from typing import Optional

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from .mesh import DEFAULT_MIN_SHARD_SIZE, place_sharded, shard_params
from .wrapper import ParallelWrapper

__all__ = ["ShardedTrainer", "per_device_param_bytes", "param_bytes",
           "DEFAULT_MIN_SHARD_SIZE"]

def param_bytes(params) -> int:
    """Global (unsharded) parameter bytes of a pytree."""
    return sum(int(np.prod(getattr(l, "shape", ()), dtype=np.int64))
               * np.dtype(l.dtype).itemsize
               for l in jax.tree_util.tree_leaves(params))


def per_device_param_bytes(params) -> int:
    """Bytes ONE device holds for a pytree: sharded leaves count their
    shard only (``sharding.shard_shape``), replicated/host leaves count
    whole — the ~1/dp memory-win number the bench line reports."""
    total = 0
    for l in jax.tree_util.tree_leaves(params):
        shape = getattr(l, "shape", ())
        sh = getattr(l, "sharding", None)
        if sh is not None and hasattr(sh, "shard_shape"):
            shape = sh.shard_shape(tuple(shape))
        total += int(np.prod(shape, dtype=np.int64)) * \
            np.dtype(l.dtype).itemsize
    return total


class ShardedTrainer(ParallelWrapper):
    """Drop-in ``fit`` with ZeRO-3 param + updater sharding over ``data``.

    Same contract as :class:`ParallelWrapper` (it IS one — the batch
    loop, trimming, listener plumbing, and the shared jitted step are
    inherited); only the placement differs: params, grads and updater
    state live row-sharded over the data axis, so per-device parameter
    memory is ~1/dp of the replicated wrapper's and the gradient
    all-reduce becomes reduce-scatter + (forward) all-gather.

    ``min_shard_size``: leaves with fewer elements replicate (the
    collective latency would exceed the memory saved).
    """

    def __init__(self, model, mesh: Optional[Mesh] = None, *,
                 min_shard_size: int = DEFAULT_MIN_SHARD_SIZE):
        self.min_shard_size = int(min_shard_size)
        if getattr(model.conf, "looped", lambda: None)():
            raise ValueError(
                "ShardedTrainer cannot train a looped range: its passes "
                "over row-sharded weights (a pipeline whose last stage "
                "feeds its first) are not written")
        super().__init__(model, mesh)

    # ------------------------------------------------------------------
    def _place(self):
        m, mesh = self.model, self.mesh
        self.param_shardings = shard_params(mesh, m.params,
                                            min_size=self.min_shard_size)
        m.params = jax.tree_util.tree_map(place_sharded, m.params,
                                          self.param_shardings)
        repl = NamedSharding(mesh, P())
        m.state = jax.tree_util.tree_map(
            lambda a: place_sharded(a, repl), m.state)
        # fused-RNG key: replicate up front so the first step already has
        # the sharding the step's successor-key output carries
        m._rng = place_sharded(m._rng, repl)
        if m.opt_state is not None:
            # leaf-wise, not treedef-matched: optax multi_transform wraps
            # the param-shaped mu/nu subtrees in MaskedNode sentinels, so
            # an exact-structure match never fires.  A mirror leaf has
            # exactly its param's shape, so the per-leaf zero3 rule makes
            # the identical shard/replicate decision the params got.
            opt_sh = shard_params(mesh, m.opt_state,
                                  min_size=self.min_shard_size)
            m.opt_state = jax.tree_util.tree_map(place_sharded,
                                                 m.opt_state, opt_sh)

    # ------------------------------------------------------- memory view
    def per_device_param_bytes(self) -> int:
        return per_device_param_bytes(self.model.params)

    def global_param_bytes(self) -> int:
        return param_bytes(self.model.params)

    # ---------------------------------------------------------- persist
    def save_sharded(self, manager, **kwargs) -> str:
        """Shard-aware checkpoint through a ``CheckpointManager`` — this
        process writes only its shard blocks + the topology manifest
        (``faulttolerance.checkpoint.save_sharded``).  Multi-process
        worlds pass ``barrier=ShardBarrier(...)`` (or run under
        ``ElasticTrainer``, which builds the barrier from the cluster
        view): the primary commits only after every live writer's block
        lands."""
        return manager.save_sharded(self.model, **kwargs)

    def average_params(self):
        """No-op like the parent's, but the returned tree is SHARDED —
        materializing it would defeat the 1/dp layout; callers that need
        host values should go through checkpoint save_sharded."""
        return self.model.params
