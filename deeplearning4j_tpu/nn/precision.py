"""First-class mixed-precision policy.

The reference runs CUDA fp32 end to end; the TPU-native fast path is
bf16 compute against f32 master weights (the MXU's native input type),
and fp16 needs loss scaling on top.  Instead of sprinkling ``.astype``
casts through user code (the TensorFlow-paper position: dtype decisions
belong in the SYSTEM — arxiv 1605.08695), the whole dtype story lives in
one conf-level object:

  - ``param_dtype``   master params + updater state (f32: the updater
    accumulates in full precision regardless of compute dtype)
  - ``compute_dtype`` forward/backward math (bf16 / f16)
  - ``keep_f32``      layer classes whose math stays f32 inside a
    low-precision stack (default: BatchNormalization — batch statistics
    are variance-of-mean reductions that cancel catastrophically in
    bf16); loss reductions and the fused softmax/log-softmax inside loss
    functions always run f32 (``nn/losses`` upcasts low-precision
    pre-activations at entry)
  - ``overrides``     per-layer dtype by layer NAME (``{"layer3":
    "float32"}`` pins one layer of an otherwise-bf16 stack)
  - ``loss_scale``    ``None`` | fixed float | ``"dynamic"``: the loss is
    multiplied by the scale inside the jitted step and gradients
    unscaled after ``value_and_grad``; non-finite gradients SKIP the
    update (params/updater/state unchanged) and halve the scale, while
    ``growth_interval`` consecutive finite steps double it — all traced
    into the step, zero extra dispatches.  fp16 defaults to dynamic.

The policy object lives in ``conf.defaults`` and therefore participates
in the compile-cache topology signature: an f32 and a bf16 variant of
the same stack can never false-share a trace, while two nets with equal
policies still share one compiled step.

Dynamic-scale state rides in the network ``state`` pytree under the
reserved ``"__precision__"`` key (a dict of three scalars), so it is
donated through the step, checkpointed, and restored like every other
piece of training state.

**Donation and the fused step** (PR 18): the scale/unscale/skip logic
is traced into the SAME program as the optimizer application and the
fused RNG succession, so the canonical train step's donation set —
params, state, updater state, and the RNG key (argnums ``(0, 1, 2,
3)``, AX007-maximal, floored by ``donation_min`` in
``tools/graftaudit/budgets.json``) — covers every buffer this policy
touches.  Two consequences worth keeping true: the unscaled-gradient
temporaries alias the donated master buffers rather than extending
peak-live, and the skip-update branch must keep returning the donated
params/state/updater values *positionally unchanged* — a skip that
rebuilt them as fresh outputs would silently break the alias match and
cost a full extra copy of the master weights every overflow step.

**Sharded masters** (ZeRO-3, ``parallel/sharded.py``): because the
masters are simply the param pytree, laying params out with a
``NamedSharding`` over the data axis makes them *sharded* masters with
no code here changing — the in-step per-layer cast produces the bf16
compute values (GSPMD may all-gather in bf16, halving the gather
bytes), gradients unscale/accumulate against the f32 shard, and the
updater applies its f32 update to the local shard only.  Tier-1 pins
this composition: a bf16 sharded run is bit-identical to the bf16
replicated run, and the masters never leave full precision
(``tests/test_sharded.py::test_sharded_masters_bf16_matches_replicated``).
"""
from __future__ import annotations

import dataclasses
from dataclasses import dataclass
from typing import Any, Dict, Optional, Tuple

from ..utils.serde import register_serde

#: reserved key in the network ``state`` pytree for loss-scale state
SCALE_STATE_KEY = "__precision__"

_ALIASES = {
    "bf16": "bfloat16", "bfloat16": "bfloat16",
    "mixed_bfloat16": "bfloat16",
    "f16": "float16", "fp16": "float16", "float16": "float16",
    "mixed_float16": "float16",
    "f32": "float32", "fp32": "float32", "float32": "float32",
}


def _canon_dtype(dt: Optional[str]) -> Optional[str]:
    if dt is None:
        return None
    s = str(dt).lower()
    return _ALIASES.get(s, s)


@register_serde
@dataclass
class PrecisionPolicy:
    """Conf-level mixed-precision policy (see module docstring)."""
    compute_dtype: Optional[str] = None      # None/float32 = full precision
    param_dtype: str = "float32"
    loss_scale: Optional[Any] = None         # None | float | "dynamic"
    initial_scale: float = 2.0 ** 15
    growth_factor: float = 2.0
    backoff_factor: float = 0.5
    growth_interval: int = 200
    keep_f32: Tuple[str, ...] = ("BatchNormalization",)
    overrides: Optional[Dict[str, str]] = None   # layer name -> dtype
    # KV-cache storage dtype for the paged generation cache (ROADMAP 2d):
    # None/float32 stores K/V as written; "int8" quantizes blocks at the
    # cache write (per-token, per-head absmax scale) and dequantizes at
    # the attention gather.  Lives on the policy — and therefore in the
    # compile-cache topology signature — so an int8-cache net and an f32
    # one can never false-share a trace.
    kv_dtype: Optional[str] = None

    def __post_init__(self):
        self.compute_dtype = _canon_dtype(self.compute_dtype)
        self.param_dtype = _canon_dtype(self.param_dtype) or "float32"
        if self.kv_dtype is not None:
            kd = str(self.kv_dtype).lower()
            kd = {"i8": "int8", "int8": "int8"}.get(kd, _canon_dtype(kd))
            if kd not in ("int8", "float32"):
                raise ValueError(
                    f"kv_dtype must be None, 'float32' or 'int8', got "
                    f"{self.kv_dtype!r}")
            self.kv_dtype = None if kd == "float32" else kd

    # ----------------------------------------------------------- queries
    @property
    def active(self) -> bool:
        return self.compute_dtype not in (None, "float32")

    @property
    def dynamic(self) -> bool:
        return self.loss_scale == "dynamic"

    @property
    def scaled(self) -> bool:
        return self.loss_scale is not None

    def layer_dtype(self, lc) -> Optional[str]:
        """Compute dtype for one layer conf: per-name override, else f32
        for keep_f32 classes (wrappers resolved through
        ``hyperparam_conf``), else the stack compute dtype.  ``None`` when
        the policy is inactive."""
        if not self.active:
            return None
        name = getattr(lc, "name", None)
        if self.overrides and name in self.overrides:
            return _canon_dtype(self.overrides[name])
        from ._common import hyperparam_conf
        hc = hyperparam_conf(lc) or lc
        kinds = {type(hc).__name__, type(lc).__name__}
        if kinds & set(self.keep_f32):
            return "float32"
        return self.compute_dtype

    def input_dtype(self, lc) -> Optional[str]:
        """The type the walk hands a layer its input in: the layer's
        compute type, but a layer that carries a residual stream in a type
        of its own (``TransformerBlock.residual_dtype``) takes it in that
        one and rounds only what its projections read."""
        if not self.active:
            return None
        return getattr(lc, "residual_dtype", None) or self.layer_dtype(lc)


def named_policy(name: str) -> PrecisionPolicy:
    """Policy from a shorthand string: ``'bfloat16'``/``'bf16'`` (no
    scaling), ``'float16'``/``'f16'``/``'mixed_float16'`` (dynamic
    scaling), ``'float32'`` (inactive)."""
    dt = _canon_dtype(name)
    if dt not in ("bfloat16", "float16", "float32"):
        raise ValueError(
            f"unknown precision '{name}' — use 'bfloat16', 'float16', "
            "'float32', or a PrecisionPolicy(...)")
    scale = "dynamic" if dt == "float16" else None
    return PrecisionPolicy(compute_dtype=None if dt == "float32" else dt,
                           loss_scale=scale)


def resolve(defaults: Dict[str, Any]) -> Optional[PrecisionPolicy]:
    """Resolved policy for a conf's ``defaults`` dict, or ``None`` for a
    full-precision net.  Back-compat: a bare ``compute_dtype`` string
    (the pre-policy knob) resolves to a plain bf16/f16 policy."""
    p = defaults.get("precision")
    if isinstance(p, str):
        p = named_policy(p)
    if p is None:
        cd = _canon_dtype(defaults.get("compute_dtype"))
        if cd and cd != "float32":
            p = PrecisionPolicy(compute_dtype=cd)
    if p is None or not p.active:
        return None
    if p.compute_dtype == "float16" and p.loss_scale is None:
        # fp16 without scaling underflows small gradients — dynamic is
        # the only safe default
        p = dataclasses.replace(p, loss_scale="dynamic")
    return p


def kv_cache_dtype(defaults: Dict[str, Any]) -> Optional[str]:
    """KV-cache storage dtype for a conf's ``defaults``: ``"int8"`` when
    the precision policy requests a quantized cache, else None (store as
    written).  Unlike :func:`resolve` this reads the policy even when
    compute runs full precision — an f32 net can still carry an int8
    cache (the cache is storage, not math)."""
    p = defaults.get("precision")
    if isinstance(p, str):
        p = named_policy(p)
    return getattr(p, "kv_dtype", None)


# ------------------------------------------------------------- step helpers
def init_scale_state(policy: Optional[PrecisionPolicy]):
    """Loss-scale carry for ``state[SCALE_STATE_KEY]`` (``None`` when the
    policy needs none).  Fixed-scale policies still carry the state so
    skip-step bookkeeping (``overflow_steps``) is observable."""
    if policy is None or not policy.scaled:
        return None
    import jax.numpy as jnp
    init = policy.initial_scale if policy.dynamic else float(policy.loss_scale)
    return {"scale": jnp.asarray(init, jnp.float32),
            "good_steps": jnp.asarray(0, jnp.int32),
            "overflow_steps": jnp.asarray(0, jnp.int32)}


def unscale_and_check(grads, scale):
    """Undo the loss scale on the gradient tree and report whether every
    leaf is finite — traced into the step.  Float leaves only
    (``_common.float_grad_leaves``): a ``SparseRows`` gradient carrier
    (``nn/sparse``) holds int32 row indices that must neither be scaled
    nor finiteness-checked."""
    import jax.numpy as jnp

    from ._common import float_grad_leaves, map_float_grads
    inv = 1.0 / scale
    grads = map_float_grads(lambda g: g * inv, grads)
    checks = [jnp.all(jnp.isfinite(g)) for g in float_grad_leaves(grads)]
    finite = jnp.stack(checks).all() if checks else jnp.asarray(True)
    return grads, finite


def overflow_skip(policy: PrecisionPolicy, ls: Dict[str, Any], finite,
                  params, new_params, opt_state, new_opt, state, new_state,
                  gstats):
    """Non-finite grads SKIP the step wholesale: params, updater state and
    layer state all keep their pre-step values, the scale backs off, the
    overflow counter ticks — all where-selected inside the one traced
    program (zero extra dispatches).  Returns the selected
    ``(new_params, new_opt, new_state, sel)``; callers with extra
    per-step outputs (tBPTT carries) reuse ``sel`` on them."""
    import jax
    import jax.numpy as jnp

    def sel(new, old):
        return jax.tree_util.tree_map(
            lambda a, b: jnp.where(finite, a, b), new, old)

    new_params = sel(new_params, params)
    new_opt = sel(new_opt, opt_state)
    old_layers = {k: v for k, v in state.items() if k != SCALE_STATE_KEY}
    new_layers = {k: v for k, v in new_state.items()
                  if k != SCALE_STATE_KEY}
    new_state = sel(new_layers, old_layers)
    new_state[SCALE_STATE_KEY] = next_scale_state(policy, ls, finite)
    gstats["loss_scale"] = ls["scale"]
    # pin the counter dtype: a weak-int where() is i64 under x64, i32
    # without — listeners should see one output signature everywhere
    gstats["overflow"] = jnp.where(finite, 0, 1).astype(jnp.int32)
    return new_params, new_opt, new_state, sel


def next_scale_state(policy: PrecisionPolicy, ls: Dict[str, Any], finite):
    """Traced update of the loss-scale carry after one step whose
    gradients were ``finite`` (a traced bool scalar)."""
    import jax.numpy as jnp
    scale, good = ls["scale"], ls["good_steps"]
    overflow = ls["overflow_steps"] + jnp.where(finite, 0, 1).astype(
        jnp.int32)
    if not policy.dynamic:
        return {"scale": scale, "good_steps": good,
                "overflow_steps": overflow}
    good = jnp.where(finite, good + 1, 0).astype(jnp.int32)
    grow = finite & (good >= policy.growth_interval)
    scale = jnp.where(
        grow, scale * policy.growth_factor,
        jnp.where(finite, scale, scale * policy.backoff_factor))
    # never scale below 1 (pointless) or above f32 range
    scale = jnp.clip(scale, 1.0, 2.0 ** 60)
    good = jnp.where(grow, 0, good).astype(jnp.int32)
    return {"scale": scale, "good_steps": good, "overflow_steps": overflow}
