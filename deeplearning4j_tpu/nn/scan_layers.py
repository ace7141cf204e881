"""Scan-over-layers: trace one layer body instead of N identical ones.

A 24-block transformer traced layer by layer produces 24 copies of the
same subgraph — trace time, XLA compile time, and compiled-program size
all scale linearly with depth for zero runtime benefit.  When a stack
contains a run of layers with IDENTICAL configuration (same conf values,
names aside, repeated >= ``DL4J_TPU_SCAN_MIN`` times), the forward walk
stacks their params/state on a new leading axis and runs the one layer
body under ``jax.lax.scan`` — the Julia-to-TPU full-compilation paper's
point that structured control flow must reach XLA as control flow, not
as unrolled tape (arxiv 1810.09868).

Exact parity with the unrolled walk is preserved by construction:

  - per-layer RNG keys are precomputed as ``fold_in(key, i)`` — the same
    fold the unrolled loop performs — and scanned over as inputs;
  - the layer body is the layer's own ``apply`` on its own params/state
    slice, so the math per iteration is the unrolled math;
  - the body's mathematics does not depend on what is saved: whatever the
    backward recomputes is the same operations on the same inputs.

What a scanned run saves for the backward pass, stacked over the run
(each saved value is written into a ``[n, ...]`` stack in the forward
and read back in the backward: a copy each way, every step):

  ``all``    the layer declares nothing (``Dense``, ``LSTM``, conv
             stacks, a block of ring or all-to-all attention):
             ``scan``'s partial evaluation stacks every intermediate the
             transposed body reads.  The program is the one a plain
             ``lax.scan`` gives; an inner time scan or a loop of
             collectives is never replayed.
  ``named``  the layer declares ``SAVED_NAMES``, the values of its
             ``apply`` that are dear to recompute, each tagged with
             ``jax.ad_checkpoint.checkpoint_name`` where it is computed
             (``TransformerBlock``: q, k, v after the head split, the
             attention's output and log-sum-exp, the stream after the
             first add, the MLP's pre-activation).  The body runs under
             ``jax.checkpoint`` with ``save_only_these_names``: the run
             saves the block's input and those, and the backward
             recomputes the rest from them, for a GPT-2 block the
             element-wise rest (norms, GELU, head merge): no matmul and
             no kernel runs twice.  (What is neither named nor cheap is
             recomputed all the same: EVA attention's summaries, or the
             scores of ``sdpa_reference``, which the flash kernels
             recompute by design.)  A GPT-2 medium block at 3 x 1024
             tokens saves 8 arrays, 63 MB, where ``all`` stacks 19,
             245 MB: on a TPU v5e the step is 5.5 ms of 89.0 shorter
             (``PERF.md`` sections 5 and 6, PR 30).
  ``input``  ``cache_mode='remat'``: ``jax.checkpoint`` with no policy.
             The run saves each layer's input alone and the backward
             recomputes a layer's forward just before it differentiates
             it: the run's forward FLOPs once more a step, for one
             layer's internals live at a time instead of every layer's.
             On a TPU v5e the benchmark's ``evabyte-4l.train-fit-long``
             trains this way: four blocks 4096 wide on 8192 positions
             fit one chip beside 9.86 GB of state only so, and the
             recomputed forward is 85 ms of a 466 ms step (``PERF.md``
             section 5, PR 29).

The choice follows from ``cache_mode`` and the layer; each scanned run
traced into a training step counts into
``scan_runs_traced_total{layer, saved}``.

Eligibility (anything else falls back to the unrolled walk, which stays
bit-identical): dataclass confs equal ignoring ``name``; no preprocessor
strictly inside the run; no recurrent carry in flight (tBPTT /
rnn_time_step walk unrolled); no AUX_LOSS (MoE) layers; no per-layer
``PrecisionPolicy`` override inside the run; mask propagation must be
the identity (a layer overriding ``feed_forward_mask`` breaks the run
only when a mask is actually present); not an activation-collecting walk
(``feed_forward`` needs every layer's output).

Opt out with ``DL4J_TPU_SCAN_LAYERS=0`` or per-conf via the builder's
``.scan_layers(False)``; ``.scan_layers(k)`` overrides the minimum run
length.
"""
from __future__ import annotations

import copy
import json
import os
from typing import List, Optional, Tuple

__all__ = ["scan_runs", "run_scan", "DEFAULT_MIN_RUN"]

DEFAULT_MIN_RUN = 4


def _min_run(conf) -> int:
    """Configured minimum homogeneous-run length, or 0 when scanning is
    disabled for this conf/process."""
    mode = conf.defaults.get("scan_layers")
    if mode is False or mode == 0:     # 0 mirrors DL4J_TPU_SCAN_LAYERS=0
        return 0
    if os.environ.get("DL4J_TPU_SCAN_LAYERS", "1").lower() in \
            ("0", "off", "false") and mode is None:
        return 0
    if isinstance(mode, bool) or mode is None:
        return int(os.environ.get("DL4J_TPU_SCAN_MIN",
                                  str(DEFAULT_MIN_RUN)))
    return max(2, int(mode))


def _layer_sig(lc, mask_present: bool, carries_present: bool,
               policy) -> Optional[str]:
    """Value signature of one layer for run grouping, or None when the
    layer cannot participate in a scan run."""
    import dataclasses

    from .compile_cache import _encode
    from .layers.base import LayerConf

    if not dataclasses.is_dataclass(lc):
        return None
    if carries_present and getattr(lc, "HAS_CARRY", False):
        return None
    if getattr(lc, "AUX_LOSS", False):
        return None
    if mask_present and type(lc).feed_forward_mask \
            is not LayerConf.feed_forward_mask:
        return None
    if policy is not None and policy.overrides and \
            getattr(lc, "name", None) in policy.overrides:
        return None
    neutral = copy.copy(lc)
    neutral.name = None
    try:
        payload = json.dumps(_encode(neutral, set()), sort_keys=True,
                             separators=(",", ":"), default=repr)
    except Exception:
        return None
    if "@id" in payload:
        # an identity token means the conf has unencodable values — two
        # layers could never compare equal by value, so no run forms
        return None
    return payload


def scan_runs(conf, n: int, *, mask_present: bool, carries_present: bool,
              collect: bool, policy=None) -> List[Tuple[int, int]]:
    """Eligible homogeneous runs ``[(start, stop), ...]`` (half-open)
    within ``conf.layers[:n]``.  Pure trace-time work — called once per
    trace, never per step."""
    min_run = _min_run(conf)
    if collect or min_run <= 0 or n < min_run:
        return []
    sigs = [_layer_sig(conf.layers[i], mask_present, carries_present,
                       policy) for i in range(n)]
    runs: List[Tuple[int, int]] = []
    i = 0
    while i < n:
        if sigs[i] is None:
            i += 1
            continue
        j = i + 1
        # a preprocessor BEFORE layer j would run mid-scan: break the run
        # (one before layer i is fine — it applies ahead of the run)
        while j < n and sigs[j] == sigs[i] and \
                conf.preprocessor(j) is None:
            j += 1
        if j - i >= min_run:
            runs.append((i, j))
        i = j
    return runs


def _count_run(layer: str, saved: str) -> None:
    """One scanned training run traced: trace-time work, like
    ``training_compile_total``."""
    from ..observability.registry import default_registry
    reg = default_registry()
    if reg.enabled:
        reg.counter("scan_runs_traced_total",
                    "Scanned layer runs traced into a training step, by "
                    "what the run saves for the backward pass",
                    ("layer", "saved")).labels(layer, saved).inc()


def run_scan(lc, params_slices, state_slices, h, key, start: int,
             *, train: bool, mask, remat: bool):
    """Execute one homogeneous run under ``jax.lax.scan``.

    ``params_slices``/``state_slices``: the per-layer pytrees in stack
    order.  Returns ``(h, new_state_slices)`` with the same per-layer
    structure the unrolled walk would have produced.
    """
    import jax
    import jax.numpy as jnp

    n_run = len(params_slices)
    stacked_p = jax.tree_util.tree_map(lambda *a: jnp.stack(a),
                                       *params_slices)
    stacked_s = jax.tree_util.tree_map(lambda *a: jnp.stack(a),
                                       *state_slices)
    keys = None
    if key is not None:
        # EXACTLY the unrolled loop's per-layer fold, precomputed and
        # scanned over — parity with the unrolled path is bit-exact
        keys = jnp.stack([jax.random.fold_in(key, start + i)
                          for i in range(n_run)])

    def body(carry, per_layer):
        p, s, k = per_layer
        # the layer's scope, as the unrolled walk names it
        with jax.named_scope(type(lc).__name__):
            y, ns = lc.apply({"params": p, "state": s}, carry, train=train,
                             key=k, mask=mask)
        return y, ns

    names = tuple(getattr(lc, "SAVED_NAMES", ()))
    if remat:
        saved = "input"
        body = jax.checkpoint(body)
    elif train and names:
        saved = "named"
        # in a scan nothing can be shared with the recomputation, so no
        # barrier against common-subexpression elimination is needed
        body = jax.checkpoint(
            body, prevent_cse=False,
            policy=jax.checkpoint_policies.save_only_these_names(*names))
    else:
        saved = "all"
    if train:
        _count_run(type(lc).__name__, saved)
    # explicit length: a paramless/stateless run at inference (no keys)
    # has no xs leaves for scan to infer it from
    h, stacked_ns = jax.lax.scan(body, h, (stacked_p, stacked_s, keys),
                                 length=n_run)
    new_states = [jax.tree_util.tree_map(lambda a, _i=i: a[_i], stacked_ns)
                  for i in range(n_run)]
    return h, new_states
