"""graftaudit rule pack AX001–AX010.

Each rule is ``rule(ir: ProgramIR) -> list[Finding]`` over the analyzed
IR of ONE compiled program (``audit.analyze_program``), registered in
``AUDIT_RULES``.  Findings use the program NAME as their path — the
stable key the baseline and suppression machinery ratchets on — and the
catalog with rationale lives in ``tools/README.md``.

These are the contracts graftlint's AST rules structurally cannot see:
they live in the traced jaxpr / partitioned HLO, not the Python source.
A PR that turns the ZeRO-3 reduce-scatter into a dense all-reduce, leaks
an f32 matmul into a bf16 step, or drops donation on the decode cache
changes NO line any AST rule looks at — only the compiled program set.
"""
from __future__ import annotations

from typing import Callable, Dict, List

from ..graftlint.core import Finding
from . import ir as IR

__all__ = ["AUDIT_RULES", "AUDIT_RULE_DOCS", "DEAD_AFTER_CALL"]

AUDIT_RULES: Dict[str, Callable] = {}
AUDIT_RULE_DOCS: Dict[str, str] = {}

#: which positional args each jit KIND leaves dead after the call —
#: the caller-side contract the builders in ``nn/_common`` /
#: ``nn/multilayer`` / ``generation/programs`` encode in their
#: ``donate_argnums``.  train-family steps return fresh
#: params/state/opt (the old pytrees are garbage the moment the call
#: returns); serve's padded batch is built per dispatch and never
#: reread; the generation cache is threaded through both programs.
DEAD_AFTER_CALL: Dict[str, tuple] = {
    # arg 3 is the RNG key: the fused-RNG step splits it in-program and
    # returns the successor, so the caller's key is dead after the call
    # (the fit loops thread `new_rng` straight back in)
    "train_step": (0, 1, 2, 3),
    "train_step_carry": (0, 1, 2, 3, 8),
    "epoch_scan": (0, 1, 2, 3),
    "epochs_scan": (0, 1, 2, 3),
    "serve": (2,),
    # the paged pair threads the BLOCK POOL (tables/pos ride along as
    # host-mirrored data args and are rebuilt per call, never donated)
    "paged_prefill": (4,),
    "paged_decode": (3,),
}

_LOW_PRECISION = ("bfloat16", "float16")
_DOT_PRIMS = ("dot_general", "conv_general_dilated")
_CALLBACK_PRIMS = ("pure_callback", "io_callback", "debug_callback")


def rule(code: str, doc: str):
    def deco(fn):
        AUDIT_RULES[code] = fn
        AUDIT_RULE_DOCS[code] = doc
        return fn
    return deco


def _finding(ir_prog, code: str, msg: str) -> Finding:
    return Finding(path=ir_prog.name, line=0, col=0, rule=code, message=msg)


# --------------------------------------------------------------------- AX001
@rule("AX001", "f64/weak-type promotion introduced inside a steady-state "
               "program whose inputs are all <=32-bit")
def ax001(ir_prog) -> List[Finding]:
    """Under x64 a dtype-defaulted constant (``jnp.zeros(())``) or a weak
    Python scalar silently promotes everything downstream of it to f64 —
    double the bytes through every fused loop of the hottest program,
    with no Python line changed.  Flagged at the ORIGIN equations (output
    f64/c128, no f64/c128 input), one finding per primitive, and only
    when no program INPUT is 64-bit (a gradient-check feeding f64 data
    wants f64 math).  Contained scalar f64 that never reaches an output
    or an array (optax's weak-typed bias-correction arithmetic) is
    byte-free and stays silent — each origin is judged by what ITS value
    reaches (``escaping_promotion_origins``), so a real escape elsewhere
    never drags the benign scalar math into the report."""
    out: List[Finding] = []
    if not ir_prog.steady:
        return out
    if any(dt in ("float64", "complex128") for dt in ir_prog.input_dtypes):
        return out
    by_prim: Dict[str, int] = {}
    wide_by_prim: Dict[str, str] = {}
    for eqn, wide in IR.escaping_promotion_origins(ir_prog.jaxpr):
        name = eqn.primitive.name
        by_prim[name] = by_prim.get(name, 0) + 1
        wide_by_prim.setdefault(name, wide)
    for name in sorted(by_prim):
        out.append(_finding(
            ir_prog, "AX001",
            f"{by_prim[name]} `{name}` eqn(s) introduce "
            f"{wide_by_prim[name]} into a steady-state program whose "
            "inputs are all <=32-bit: a dtype-defaulted constant or weak "
            "Python scalar is promoting the math under x64 — give the "
            "constant the dtype of the value it joins"))
    return out


# --------------------------------------------------------------------- AX002
@rule("AX002", "precision-policy violation: f32 contraction inside a "
               "low-precision program, or convert_element_type churn")
def ax002(ir_prog) -> List[Finding]:
    """Two arms.  (a) In a program whose manifest DECLARES a bf16/f16
    policy, any ``dot_general``/conv with all-f32 floating operands
    bypassed the policy: the MXU runs it at 1/2 (or worse) throughput
    and the activation memory doubles.  The default keep_f32 classes
    and loss reductions are elementwise/reduce ops (no contractions),
    but a per-name ``overrides={'layer': 'float32'}`` pinning a dense
    layer IS a supported deliberate f32 contraction — so this arm only
    runs on explicitly declared policies, where the declarer also knows
    the overrides: declare ``policy=None`` for such a program, or
    suppress with the override as the justification.  (b) Cast–uncast
    ping-pong (``f32 -> bf16 -> f32`` on one value), any program: two
    wasted element-wise passes and a silent mantissa truncation; either
    stay in the narrow dtype or never leave the wide one."""
    out: List[Finding] = []
    dots = [e for e in IR.iter_eqns(ir_prog.jaxpr)
            if e.primitive.name in _DOT_PRIMS]

    def op_dtypes(eqn):
        return [str(IR.aval_dtype(v)) for v in eqn.invars[:2]
                if IR.aval_dtype(v) is not None]

    if ir_prog.policy in _LOW_PRECISION:
        f32_dots: Dict[str, int] = {}
        for e in dots:
            dts = op_dtypes(e)
            if dts and all(dt == "float32" for dt in dts):
                f32_dots[e.primitive.name] = \
                    f32_dots.get(e.primitive.name, 0) + 1
        for name in sorted(f32_dots):
            out.append(_finding(
                ir_prog, "AX002",
                f"{f32_dots[name]} f32 `{name}` eqn(s) inside a "
                f"declared-{ir_prog.policy} program: the contraction "
                "bypassed the precision policy — cast its operands to "
                "the compute dtype (default keep_f32 classes and loss "
                "reductions have no contractions; a deliberate per-name "
                "f32 override is the suppression justification)"))
    for src, mid, count in IR.convert_churn_chains(ir_prog.jaxpr):
        out.append(_finding(
            ir_prog, "AX002",
            f"convert_element_type churn: {count} value(s) round-trip "
            f"{src} -> {mid} -> {src} — two wasted element-wise passes "
            f"(and mantissa truncation when {mid} is narrower); keep the "
            "value in one dtype across the chain"))
    return out


# --------------------------------------------------------------------- AX003
@rule("AX003", "collective layout guard: dense all-reduce where the "
               "ZeRO-3 layout implies reduce-scatter, or duplicate "
               "per-operand all-gathers")
def ax003(ir_prog) -> List[Finding]:
    """The census itself (count + byte estimate per collective op) lands
    in the program card; this rule guards the two layout regressions
    that cost real HBM/interconnect.  (a) A ZeRO-3 program (sharded
    param args) containing an ``all-reduce`` of (near-)full-model
    gradient bytes: GSPMD was supposed to derive reduce-scatter + shard
    -local update from the shardings (arxiv 2004.13336); a dense
    all-reduce there means some op defeated the derivation and every
    step now ships dp x the gradient bytes.  (b) The same operand
    all-gathered twice with the same result shape — a missed CSE that
    doubles the gather traffic for one leaf."""
    out: List[Finding] = []
    if ir_prog.zero3 and ir_prog.param_bytes > 0:
        for c in ir_prog.collective_ops:
            if c.op != "all-reduce":
                continue
            if c.result_bytes >= 0.5 * ir_prog.param_bytes:
                out.append(_finding(
                    ir_prog, "AX003",
                    f"dense all-reduce of {c.result_bytes} bytes "
                    f"(>= 50% of the {ir_prog.param_bytes}-byte param "
                    "set) in a ZeRO-3 sharded program: the layout "
                    "implies reduce-scatter grads + shard-local update; "
                    "something (an unsharded constraint, a host-shaped "
                    "op) defeated the GSPMD derivation"))
    seen: Dict[tuple, int] = {}
    for c in ir_prog.collective_ops:
        if c.op != "all-gather" or not c.operands:
            continue
        if c.result_bytes < ir_prog.config.dup_gather_bytes:
            # tiny re-gathered index blocks (XLA skips cross-fusion CSE
            # on them) are not the duplicated-param-gather regression
            continue
        key = (c.operands, tuple(c.shapes))
        seen[key] = seen.get(key, 0) + 1
    for (operands, shapes), n in sorted(seen.items()):
        if n > 1:
            out.append(_finding(
                ir_prog, "AX003",
                f"operand {operands[0]} is all-gathered {n}x with "
                f"identical result {shapes}: duplicate per-leaf forward "
                "gather — reuse the gathered value"))
    return out


# --------------------------------------------------------------------- AX004
@rule("AX004", "host callback (pure_callback/io_callback/debug.print) "
               "inside a steady-state program")
def ax004(ir_prog) -> List[Finding]:
    """A callback primitive stalls the device at every execution of the
    program: the runtime must round-trip the host before the next
    fused region can run — the
    zero-steady-state-host-sync contract is void while one of these is
    in a train/serve/decode program.  ``jax.debug.print`` lowers to
    ``debug_callback``, so a leftover debug line is caught here even
    though the AST-side complement (JX026) already flags the source."""
    out: List[Finding] = []
    if not ir_prog.steady:
        return out
    counts: Dict[str, int] = {}
    for eqn in IR.iter_eqns(ir_prog.jaxpr):
        if eqn.primitive.name in _CALLBACK_PRIMS:
            counts[eqn.primitive.name] = \
                counts.get(eqn.primitive.name, 0) + 1
    for name in sorted(counts):
        out.append(_finding(
            ir_prog, "AX004",
            f"{counts[name]} `{name}` eqn(s) in a steady-state program: "
            "every execution stalls the device on a host round-trip — "
            "move the callback out of the hot program (or pragma a "
            "deliberate one with its justification)"))
    return out


# --------------------------------------------------------------------- AX005
@rule("AX005", "donation miss: a large dead-after-call argument is not "
               "in donate_argnums")
def ax005(ir_prog) -> List[Finding]:
    """For the arg positions this program's KIND leaves dead after the
    call (``DEAD_AFTER_CALL``: train steps return fresh
    params/state/opt, serve never rereads its padded batch, the decode
    cache is threaded), a leaf tree above the size threshold that is NOT
    donated forces XLA to keep input and output alive simultaneously —
    on the train step that is 2x params + 2x optimizer state of
    avoidable HBM, exactly the headroom large-model configs run out of
    first."""
    out: List[Finding] = []
    dead = DEAD_AFTER_CALL.get(ir_prog.kind)
    if dead is None and ir_prog.kind.startswith("pretrain"):
        dead = (0, 1, 2)    # layer params, opt state, RNG key (fused split)
    if not dead:
        return out
    for argnum in dead:
        if argnum >= len(ir_prog.arg_bytes):
            continue
        size = ir_prog.arg_bytes[argnum]
        if size < ir_prog.config.min_donate_bytes:
            continue
        if argnum not in ir_prog.donate:
            out.append(_finding(
                ir_prog, "AX005",
                f"arg {argnum} ({size} bytes) is dead after the call in "
                f"kind '{ir_prog.kind}' but not in donate_argnums"
                f"{tuple(ir_prog.donate)}: XLA must hold input and "
                "output alive together — donate it (or pragma the "
                "platform that cannot, with justification)"))
    return out


# --------------------------------------------------------------------- AX006
@rule("AX006", "oversized broadcast intermediate materialized inside the "
               "program")
def ax006(ir_prog) -> List[Finding]:
    """A ``broadcast_in_dim`` whose result is both large in absolute
    bytes and a big multiple of its operand usually means a reduction
    was written as materialize-then-reduce (or a mask/one-hot blew up to
    batch x vocab x seq): XLA often fuses these away, but one that
    survives into the jaxpr at this size is peak-memory risk worth a
    look.  Thresholds ride the audit config so toy canonical programs
    don't cry wolf."""
    out: List[Finding] = []
    cfg = ir_prog.config
    hits = 0
    worst = 0
    for eqn in IR.iter_eqns(ir_prog.jaxpr):
        if eqn.primitive.name != "broadcast_in_dim":
            continue
        ob = sum(IR.aval_bytes(ov) for ov in eqn.outvars)
        ib = max([IR.aval_bytes(iv) for iv in eqn.invars] or [0])
        if ob >= cfg.broadcast_bytes and ob >= cfg.broadcast_ratio * \
                max(ib, 1):
            hits += 1
            worst = max(worst, ob)
    if hits:
        out.append(_finding(
            ir_prog, "AX006",
            f"{hits} broadcast_in_dim eqn(s) materialize >= "
            f"{cfg.broadcast_bytes} bytes (largest {worst}) from a "
            f">= {cfg.broadcast_ratio}x smaller operand: likely a "
            "materialize-then-reduce — restructure to reduce without "
            "the full intermediate"))
    return out


# --------------------------------------------------------------------- AX007
@rule("AX007", "declared-donation incompleteness: the lifetime solver's "
               "maximal safe donation set exceeds donate_argnums")
def ax007(ir_prog) -> List[Finding]:
    """The exact form of AX005's threshold heuristic (which stays as the
    cheap pre-filter): the lifetime solver proved these arguments are
    (a) dead after the call — the caller's bindings were observed
    collected/donated, or the kind contract says so and no observation
    contradicts it — and (b) *usefully* donatable: every array leaf has
    a shape/dtype-compatible unclaimed output leaf for XLA to alias
    into.  Each one not in ``donate_argnums`` keeps input AND output
    alive across the execution for no reason — on a train step that is
    a whole extra params+opt-state of HBM.  Unlike AX005 this cannot
    cry wolf on an argument donation would not help (no aliasable
    output) or one the caller actually re-reads (observed live)."""
    out: List[Finding] = []
    lt = ir_prog.lifetime
    if lt is None:
        return out
    for a in lt.args:
        if not a.donatable or a.argnum in ir_prog.donate:
            continue
        if a.bytes < ir_prog.config.min_donate_bytes:
            continue
        out.append(_finding(
            ir_prog, "AX007",
            f"arg {a.argnum} ({a.bytes} bytes, caller {a.caller}"
            f"{', contract-dead' if a.contract_dead else ''}) is in the "
            f"maximal safe donation set but not donate_argnums"
            f"{tuple(ir_prog.donate)}: every leaf has an aliasable "
            "output — donate it (or suppress for the platform that "
            "cannot, with justification)"))
    return out


# --------------------------------------------------------------------- AX008
@rule("AX008", "per-program IR budget exceeded: peak-live-bytes (this "
               "rule) or a collective/temp/dtype/callback ceiling (the "
               "--diff-cards gate, same code)")
def ax008(ir_prog) -> List[Finding]:
    """The lifetime solver's peak-live-bytes estimate (live-range
    intervals over the eqn order, scan carries included) checked
    against a per-program ceiling — the ``peak_live_bytes`` entries of
    ``budgets.json``, threaded through ``AuditConfig``.  An unbudgeted
    program is silent (budgets are opt-in); a budgeted one that grew
    past its ceiling fails, because a silent 2x in live bytes is
    exactly how an OOM ships: no Python line changed, only the compiled
    program's live set."""
    out: List[Finding] = []
    budgets = ir_prog.config.peak_live_budgets
    if not budgets or ir_prog.peak_live_bytes is None:
        return out
    ceiling = budgets.get(ir_prog.name)
    if ceiling is None or ir_prog.peak_live_bytes <= int(ceiling):
        return out
    out.append(_finding(
        ir_prog, "AX008",
        f"peak-live-bytes estimate {ir_prog.peak_live_bytes} exceeds "
        f"the budget ceiling {int(ceiling)}: the program's live set "
        "grew — find the new/longer-lived buffer (lost donation, new "
        "mirror, wider dtype) or raise the ceiling in budgets.json "
        "with a justifying comment"))
    return out


# --------------------------------------------------------------------- AX009
@rule("AX009", "recompile-hazard call variants: captured specs differing "
               "only by Python-scalar value / weak-typed 0-d leaf")
def ax009(ir_prog) -> List[Finding]:
    """Multiple captured call specs of this entry collapse onto ONE
    program once Python-scalar values and weak-typed 0-d leaves are
    erased: the call sites are feeding raw Python scalars (or mixing
    ``1.0`` with ``np.float32(1.0)``) where a committed dtype belongs.
    Each variant is at best a redundant dispatch-cache entry crowding
    the audit spec ring, at worst a full retrace (weak-type flips, int
    vs float) — the classic \"temperature knob retraces the decode
    step\" bug.  Commit the scalar at the call boundary
    (``np.float32(x)``) so every value rides one compiled program."""
    out: List[Finding] = []
    if ir_prog.variant_count <= 1:
        return out
    detail = "; ".join(ir_prog.variant_churn[:3]) or "0-d leaves"
    out.append(_finding(
        ir_prog, "AX009",
        f"{ir_prog.variant_count} captured call specs differ only by "
        f"Python-scalar value / weak-typed 0-d leaves ({detail}): "
        "commit the scalar to a fixed np dtype at the call boundary so "
        "one compiled variant serves every value"))
    return out


# --------------------------------------------------------------------- AX010
@rule("AX010", "committed-card drift: fresh audit disagrees with the "
               "checked-in program card on a stable field")
def ax010(ir_prog) -> List[Finding]:
    """The committed cards under ``tools/graftaudit/cards/`` are the
    reviewed IR record of each canonical program; this rule is the
    enforcement arm: any stable-field disagreement between the FRESH
    audit and the committed card (collective census, donation map,
    kind/policy flags) — or a missing card — is a finding, so an IR
    regression must either be fixed or land as a reviewable card diff
    (``--write-cards``), never as silent drift.  Only runs when
    ``AuditConfig.cards_dir`` is set (the canonical/gate path)."""
    out: List[Finding] = []
    cards_dir = ir_prog.config.cards_dir
    if not cards_dir:
        return out
    import os

    from .cards import STABLE_FIELDS, build_card, card_filename, load_card

    path = os.path.join(cards_dir, card_filename(ir_prog.name))
    if not os.path.exists(path):
        out.append(_finding(
            ir_prog, "AX010",
            f"no committed card at {path}: run --write-cards and commit "
            "the new program's card"))
        return out
    committed = load_card(path)
    fresh = build_card(ir_prog)
    for fld in STABLE_FIELDS:
        if fresh.get(fld) != committed.get(fld):
            out.append(_finding(
                ir_prog, "AX010",
                f"stable field '{fld}' drifted from the committed card: "
                f"card has {committed.get(fld)!r}, fresh audit has "
                f"{fresh.get(fld)!r} — fix the regression or commit the "
                "reviewed card diff (--write-cards)"))
    return out
