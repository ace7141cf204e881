"""graftlint rule implementations.

Module-local rules JX001–JX017 and JX022–JX030 are functions ``rule(info:
ModuleInfo) -> list[Finding]`` registered in ``RULES``; they share the jit-scope + taint
machinery in ``analysis.py`` (memoized per module, so every rule runs off
one parse and one tree walk).  The whole-program concurrency pack
JX018–JX021 is registered in ``PROGRAM_RULES`` and runs once over the
:class:`~tools.graftlint.program.ProgramModel` built from every linted
module.  See ``tools/README.md`` for the catalog with rationale.
"""
from __future__ import annotations

import ast
import re
from typing import Callable, Dict, List, Optional

from .analysis import ModuleInfo, TaintInfo, call_name, dotted_name
from .core import Finding
from .program import ProgramModel, find_lock_cycles, receiver_is_shared

__all__ = ["RULES", "PROGRAM_RULES", "RULE_DOCS"]

RULES: Dict[str, Callable[[ModuleInfo], List[Finding]]] = {}
PROGRAM_RULES: Dict[str, Callable[[ProgramModel], List[Finding]]] = {}
RULE_DOCS: Dict[str, str] = {}

_HOT_FUNC_RE = re.compile(r"(^|_)(fit|train|step|epoch)", re.IGNORECASE)


def rule(code: str, doc: str):
    def deco(fn):
        RULES[code] = fn
        RULE_DOCS[code] = doc
        return fn
    return deco


def program_rule(code: str, doc: str):
    def deco(fn):
        PROGRAM_RULES[code] = fn
        RULE_DOCS[code] = doc
        return fn
    return deco


def _finding(info: ModuleInfo, node: ast.AST, code: str, msg: str) -> Finding:
    return Finding(path=info.path, line=getattr(node, "lineno", 1),
                   col=getattr(node, "col_offset", 0), rule=code, message=msg)


def _finding_at(path: str, node: ast.AST, code: str, msg: str) -> Finding:
    return Finding(path=path, line=getattr(node, "lineno", 1),
                   col=getattr(node, "col_offset", 0), rule=code, message=msg)


def _jit_scope_taints(info: ModuleInfo) -> Dict[ast.AST, TaintInfo]:
    return {f: info.taint(f) for f in info.jit_scopes}


def _in_loop_same_function(info: ModuleInfo, node: ast.AST) -> bool:
    """Is ``node`` inside a for/while loop without crossing a function
    boundary? (A jit() in a loop body recompiles per iteration only if
    the loop actually re-executes the call.)"""
    cur = info.parent(node)
    while cur is not None:
        if isinstance(cur, (ast.FunctionDef, ast.AsyncFunctionDef,
                            ast.Lambda, ast.Module)):
            return False
        if isinstance(cur, (ast.For, ast.AsyncFor, ast.While)):
            return True
        cur = info.parent(cur)
    return False


# --------------------------------------------------------------------- JX001
@rule("JX001", "host numpy call on a traced value inside a jit scope")
def jx001(info: ModuleInfo) -> List[Finding]:
    out: List[Finding] = []
    taints = _jit_scope_taints(info)
    for func, taint in taints.items():
        for node in ast.walk(func):
            if not isinstance(node, ast.Call):
                continue
            if info.enclosing_function(node) not in taints:
                continue
            fname = call_name(node)
            if not fname:
                continue
            root = fname.split(".")[0]
            if root not in info.numpy_aliases or "." not in fname:
                continue
            args = list(node.args) + [k.value for k in node.keywords]
            if any(taints[info.enclosing_function(node)].expr_tainted(a)
                   for a in args):
                out.append(_finding(
                    info, node, "JX001",
                    f"host-numpy call `{fname}` on a traced value inside a "
                    "jit scope: runs at trace time on abstract tracers "
                    "(TracerArrayConversionError) — use jax.numpy"))
    return _dedupe(out)


# --------------------------------------------------------------------- JX002
@rule("JX002", "Python if/while branches on a tracer value in a jit scope")
def jx002(info: ModuleInfo) -> List[Finding]:
    out: List[Finding] = []
    taints = _jit_scope_taints(info)
    for func, _ in taints.items():
        for node in ast.walk(func):
            if not isinstance(node, (ast.If, ast.While, ast.IfExp, ast.Assert)):
                continue
            enc = info.enclosing_function(node)
            if enc not in taints:
                continue
            if taints[enc].expr_tainted(node.test):
                kind = {ast.If: "if", ast.While: "while",
                        ast.IfExp: "conditional expression",
                        ast.Assert: "assert"}[type(node)]
                out.append(_finding(
                    info, node, "JX002",
                    f"Python `{kind}` on a tracer-derived value inside a jit "
                    "scope: raises TracerBoolConversionError at trace time — "
                    "use jax.lax.cond/select or jnp.where"))
    return _dedupe(out)


# --------------------------------------------------------------------- JX003
@rule("JX003", "host sync (.item()/float()/np.asarray) inside a training loop")
def jx003(info: ModuleInfo) -> List[Finding]:
    out: List[Finding] = []
    # pure-host modules have no device arrays to sync on
    if not (info.jax_aliases or info.jnp_aliases):
        return out
    for func in info.nodes(ast.FunctionDef, ast.AsyncFunctionDef):
        if not _HOT_FUNC_RE.search(func.name):
            continue
        loops = [n for n in ast.walk(func)
                 if isinstance(n, (ast.For, ast.AsyncFor, ast.While))
                 and info.enclosing_function(n) is func]
        for loop in loops:
            for node in ast.walk(loop):
                if not isinstance(node, ast.Call):
                    continue
                sync = _host_sync_kind(info, node)
                if sync:
                    out.append(_finding(
                        info, node, "JX003",
                        f"`{sync}` inside the loop of `{func.name}`: "
                        "host-syncs every iteration, serializing the loop "
                        "against dispatch RTT — keep values on device and "
                        "materialize once after the loop"))
    return _dedupe(out)


def _contains_static_access(node: ast.AST) -> bool:
    """Does the expression read a trace-static property (shape/ndim/…)?
    ``int(x.shape[0])`` and ``int(getattr(x, "shape", ...)[0])`` are host
    math on static metadata, not device syncs."""
    from .analysis import STATIC_ATTRS
    for n in ast.walk(node):
        if isinstance(n, ast.Attribute) and n.attr in STATIC_ATTRS:
            return True
        if isinstance(n, ast.Call):
            cn = call_name(n)
            if (cn == "getattr" and len(n.args) >= 2
                    and isinstance(n.args[1], ast.Constant)
                    and n.args[1].value in STATIC_ATTRS):
                return True
            if cn == "len":
                return True
    return False


def _host_sync_kind(info: ModuleInfo, node: ast.Call) -> Optional[str]:
    # x.item() — unconditional device->host sync on jax/numpy arrays
    if (isinstance(node.func, ast.Attribute) and node.func.attr == "item"
            and not node.args and not node.keywords):
        return ".item()"
    fname = call_name(node)
    if not fname:
        return None
    if fname in ("float", "int") and len(node.args) == 1:
        a = node.args[0]
        # flag only direct materialization of a stored value by bare name
        # (float(loss), int(far)); subscripts/attributes are overwhelmingly
        # host containers (dicts, metadata), and static-shape reads never
        # sync at all
        if isinstance(a, ast.Name) and not _contains_static_access(a):
            return f"{fname}(...)"
        return None
    parts = fname.split(".")
    if (parts[0] in info.numpy_aliases and len(parts) == 2
            and parts[1] in ("asarray", "array", "asanyarray")):
        # building an array FROM Python lists/comprehensions is host ETL,
        # not a device fetch
        if (node.args
                and not isinstance(node.args[0],
                                   (ast.Constant, ast.List, ast.Tuple,
                                    ast.ListComp, ast.GeneratorExp))
                and not _contains_static_access(node.args[0])):
            return f"{fname}(...)"
        return None
    if parts[-1] == "device_get" and parts[0] in info.jax_aliases:
        return f"{fname}(...)"
    return None


# --------------------------------------------------------------------- JX004
@rule("JX004", "jax.jit called in a loop or invoked immediately (recompiles)")
def jx004(info: ModuleInfo) -> List[Finding]:
    out: List[Finding] = []
    for node in info.nodes(ast.Call):
        # jax.jit(f)(args): a fresh compile-cache entry per outer call when
        # f is rebuilt each time; even when cached it re-hashes — hoist it.
        if isinstance(node.func, ast.Call) and info.is_jit_call(node.func):
            out.append(_finding(
                info, node, "JX004",
                "`jax.jit(f)(...)` invoked immediately: wrapping per call "
                "defeats the compile cache when f is a fresh closure — "
                "hoist the jitted callable out of the call site"))
            continue
        if info.is_jit_call(node) and _in_loop_same_function(info, node):
            out.append(_finding(
                info, node, "JX004",
                "`jax.jit` called inside a loop: every iteration builds a "
                "new wrapper (and recompiles when the function object is "
                "fresh) — create the jitted function once outside the loop"))
    return _dedupe(out)


# --------------------------------------------------------------------- JX005
@rule("JX005", "non-hashable static_argnums/static_argnames value")
def jx005(info: ModuleInfo) -> List[Finding]:
    out: List[Finding] = []
    for node in info.nodes(ast.Call):
        if not info.is_jit_call(node):
            continue
        for kw in node.keywords:
            if kw.arg not in ("static_argnums", "static_argnames"):
                continue
            bad = None
            if isinstance(kw.value, (ast.List, ast.Set, ast.Dict,
                                     ast.ListComp, ast.SetComp, ast.DictComp)):
                bad = "a non-hashable literal"
            elif isinstance(kw.value, ast.Call):
                cn = call_name(kw.value) or ""
                parts = cn.split(".")
                if (parts[0] in (info.numpy_aliases | info.jnp_aliases)
                        and parts[-1] in ("array", "asarray", "arange")):
                    bad = "an array value"
                elif parts[-1] in ("list", "dict", "set"):
                    bad = "a non-hashable value"
            if bad:
                out.append(_finding(
                    info, kw.value, "JX005",
                    f"`{kw.arg}` is {bad}: jit hashes static args for its "
                    "compile cache — pass a tuple of ints/strings"))
    return _dedupe(out)


# --------------------------------------------------------------------- JX006
@rule("JX006", "mutation of self/global state inside a jit scope (impurity)")
def jx006(info: ModuleInfo) -> List[Finding]:
    out: List[Finding] = []
    for func in info.jit_scopes:
        if isinstance(func, ast.Lambda):
            continue
        global_names = set()
        for n in ast.walk(func):
            if isinstance(n, ast.Global):
                global_names.update(n.names)
        for node in ast.walk(func):
            if info.enclosing_function(node) is not func:
                continue
            targets: List[ast.AST] = []
            if isinstance(node, ast.Assign):
                targets = node.targets
            elif isinstance(node, (ast.AugAssign, ast.AnnAssign)):
                targets = [node.target]
            for t in targets:
                base = t
                while isinstance(base, (ast.Subscript, ast.Attribute)):
                    base = base.value
                is_self_attr = (isinstance(t, (ast.Attribute, ast.Subscript))
                                and isinstance(base, ast.Name)
                                and base.id == "self")
                is_global = isinstance(t, ast.Name) and t.id in global_names
                if is_self_attr or is_global:
                    what = ("self attribute" if is_self_attr
                            else f"global `{t.id}`")
                    out.append(_finding(
                        info, node, "JX006",
                        f"mutating {what} inside a jit scope: the write "
                        "happens once at trace time, then never again on "
                        "cached executions — return the new value instead"))
    return _dedupe(out)


# --------------------------------------------------------------------- JX007
@rule("JX007", "bare `except:` swallows KeyboardInterrupt/SystemExit")
def jx007(info: ModuleInfo) -> List[Finding]:
    out: List[Finding] = []
    for node in info.nodes(ast.ExceptHandler):
        if node.type is None:
            out.append(_finding(
                info, node, "JX007",
                "bare `except:` catches KeyboardInterrupt and SystemExit, "
                "making training loops unkillable — catch `Exception` (or "
                "narrower) instead"))
    return _dedupe(out)


# --------------------------------------------------------------------- JX008
@rule("JX008", "mutable default argument")
def jx008(info: ModuleInfo) -> List[Finding]:
    out: List[Finding] = []
    for node in info.nodes(ast.FunctionDef, ast.AsyncFunctionDef,
                           ast.Lambda):
        for d in list(node.args.defaults) + [
                d for d in node.args.kw_defaults if d is not None]:
            bad = None
            if isinstance(d, (ast.List, ast.Dict, ast.Set,
                              ast.ListComp, ast.DictComp, ast.SetComp)):
                bad = "mutable literal"
            elif isinstance(d, ast.Call):
                cn = call_name(d) or ""
                if cn in ("list", "dict", "set", "bytearray"):
                    bad = f"`{cn}()`"
            if bad:
                out.append(_finding(
                    info, d, "JX008",
                    f"mutable default argument ({bad}): shared across every "
                    "call — default to None and construct inside"))
    return _dedupe(out)


# --------------------------------------------------------------------- JX009
@rule("JX009", "timing around jax work without block_until_ready")
def jx009(info: ModuleInfo) -> List[Finding]:
    out: List[Finding] = []
    if not (info.jax_aliases or info.jnp_aliases):
        return out
    for func in info.nodes(ast.FunctionDef, ast.AsyncFunctionDef):
        timers: List[ast.Call] = []
        uses_jax = False
        synced = False
        for n in ast.walk(func):
            if isinstance(n, ast.Call):
                fname = call_name(n) or ""
                parts = fname.split(".")
                # only the benchmark clocks: time.time() is the deadline/
                # timeout idiom, not a measurement
                if ((parts[0] in info.time_names and len(parts) == 2
                     and parts[1] in ("perf_counter", "monotonic"))
                        or (len(parts) == 1
                            and parts[0] in info.timer_names)):
                    timers.append(n)
                # fetching values (np.asarray/device_get) closes the async
                # gap just as well as block_until_ready
                if (len(parts) >= 2 and parts[0] in info.numpy_aliases
                        and parts[-1] in ("asarray", "array")):
                    synced = True
                if parts[-1] == "device_get":
                    synced = True
            if isinstance(n, ast.Attribute):
                if n.attr == "block_until_ready":
                    synced = True
                root = dotted_name(n)
                if root:
                    r = root.split(".")[0]
                    if r in (info.jnp_aliases | info.jax_aliases
                             | info.lax_aliases):
                        uses_jax = True
            if isinstance(n, ast.Name) and n.id in (info.jnp_aliases
                                                    | info.jax_aliases):
                uses_jax = True
        if len(timers) >= 2 and uses_jax and not synced:
            out.append(_finding(
                info, timers[-1], "JX009",
                f"`{func.name}` times jax work with no "
                "`block_until_ready()`: async dispatch returns before the "
                "device finishes, so this measures dispatch latency, not "
                "compute — sync the result before reading the clock"))
    return _dedupe(out)


# --------------------------------------------------------------------- JX010
@rule("JX010", "float64 literal/dtype in jitted code (x64 promotion hazard)")
def jx010(info: ModuleInfo) -> List[Finding]:
    out: List[Finding] = []
    for func in info.jit_scopes:
        for node in ast.walk(func):
            if not info.in_jit_scope(node) and info.enclosing_function(
                    node) is not func:
                continue
            bad = None
            if isinstance(node, ast.Attribute) and node.attr in (
                    "float64", "complex128"):
                root = dotted_name(node)
                if root and root.split(".")[0] in (
                        info.numpy_aliases | info.jnp_aliases
                        | info.jax_aliases):
                    bad = root
            elif (isinstance(node, ast.Constant)
                  and isinstance(node.value, str)
                  and node.value in ("float64", "complex128")):
                par = info.parent(node)
                # only flag dtype-ish positions: dtype= kwarg or astype arg
                if isinstance(par, ast.keyword) and par.arg == "dtype":
                    bad = f'"{node.value}"'
                elif (isinstance(par, ast.Call)
                      and isinstance(par.func, ast.Attribute)
                      and par.func.attr in ("astype", "view")):
                    bad = f'"{node.value}"'
            if bad:
                out.append(_finding(
                    info, node, "JX010",
                    f"{bad} inside a jit scope: without jax_enable_x64 this "
                    "silently becomes float32; with it, it doubles HBM "
                    "traffic and forbids TPU vector math — thread the "
                    "model dtype through instead of hardcoding"))
    return _dedupe(out)


# --------------------------------------------------------------------- JX011
@rule("JX011", "time.time() used for interval measurement (wall clock steps)")
def jx011(info: ModuleInfo) -> List[Finding]:
    """Flag the elapsed-interval idiom on the wall clock: ``t0 =
    time.time()`` later subtracted as ``time.time() - t0`` (or ``now -
    t0`` where both derive from ``time.time()``).  Wall clocks step under
    NTP slew/DST, so intervals must come from ``time.perf_counter()`` —
    in-package code uses the ``observability.clock`` helpers.  The
    deadline/timeout idiom (``deadline = time.time() + t``; ``time.time()
    > deadline``; ``deadline - time.time()``) never subtracts a stored
    wall-clock sample FROM a later one and stays legal, as do bare
    timestamps (no arithmetic)."""
    out: List[Finding] = []

    def is_walltime_call(n: ast.AST) -> bool:
        if not isinstance(n, ast.Call):
            return False
        fname = call_name(n) or ""
        parts = fname.split(".")
        if len(parts) == 2 and parts[0] in info.time_names \
                and parts[1] == "time":
            return True
        return len(parts) == 1 and parts[0] in info.walltime_names

    # module-wide fixpoint: names (and self.attrs) holding a bare
    # time.time() sample, including one-hop copies (now = time.time();
    # self._last = now)
    assigns: List = []
    for node in info.nodes(ast.Assign, ast.AnnAssign):
        targets = []
        if isinstance(node, ast.Assign):
            targets = node.targets
        elif isinstance(node, ast.AnnAssign) and node.value is not None:
            targets = [node.target]
        for t in targets:
            key = dotted_name(t)
            if key:
                assigns.append((key, node.value))
    tracked: set = set()
    changed = True
    while changed:
        changed = False
        for key, value in assigns:
            if key in tracked:
                continue
            src = dotted_name(value)
            if is_walltime_call(value) or (src and src in tracked):
                tracked.add(key)
                changed = True

    def holds_sample(n: ast.AST) -> bool:
        if is_walltime_call(n):
            return True
        name = dotted_name(n)
        return name is not None and name in tracked

    for node in info.nodes(ast.BinOp):
        if isinstance(node.op, ast.Sub):
            # later-sample MINUS stored-sample = elapsed interval; the
            # right side must be a stored name (deadline math subtracts
            # a fresh call from a derived bound, which stays legal)
            right = dotted_name(node.right)
            if right is not None and right in tracked \
                    and holds_sample(node.left):
                out.append(_finding(
                    info, node, "JX011",
                    "interval measured with `time.time()`: the wall clock "
                    "steps under NTP/DST, skewing the measurement — use "
                    "`time.perf_counter()` (observability.clock helpers) "
                    "for durations; keep `time.time()` for timestamps and "
                    "deadlines"))
    return _dedupe(out)


def _expr_is_device_value(info: ModuleInfo, node: ast.AST,
                          tracked: set) -> bool:
    """Does this expression produce a device array? jnp./jax. dotted
    calls, bare device_put, or a tracked name / subscript of one.
    (Shared by JX012/JX015.)"""
    if isinstance(node, ast.Call):
        fname = call_name(node) or ""
        parts = fname.split(".")
        if len(parts) >= 2 and parts[0] in (info.jnp_aliases
                                            | info.jax_aliases):
            return True
        return len(parts) == 1 and parts[0] in info.deviceput_names
    name = dotted_name(node)
    return name is not None and name in tracked


def _device_names(info: ModuleInfo, cache: Dict[Optional[ast.AST], set],
                  func: Optional[ast.AST]) -> set:
    """Names in ``func`` (or module scope) assigned from device-valued
    expressions, with one-hop copies, fixpointed.  (Shared by
    JX012/JX015.)"""
    if func in cache:
        return cache[func]
    scope = func if func is not None else info.tree
    assigns = []
    for n in ast.walk(scope):
        if info.enclosing_function(n) is not func:
            continue    # nested functions track their own names
        targets = []
        if isinstance(n, ast.Assign):
            targets = n.targets
        elif isinstance(n, ast.AnnAssign) and n.value is not None:
            targets = [n.target]
        for t in targets:
            key = dotted_name(t)
            if key:
                assigns.append((key, n.value))
    tracked: set = set()
    changed = True
    while changed:
        changed = False
        for key, value in assigns:
            if key not in tracked and \
                    _expr_is_device_value(info, value, tracked):
                tracked.add(key)
                changed = True
    cache[func] = tracked
    return tracked


# --------------------------------------------------------------------- JX012
@rule("JX012", "per-iteration host<->device transfer inside a loop")
def jx012(info: ModuleInfo) -> List[Finding]:
    """Flag host↔device copies paid once per loop iteration: (a) any
    ``jax.device_put`` call inside a ``for``/``while`` body, and (b)
    ``np.asarray``/``np.array`` on a *device-derived* name (one assigned
    from a ``jnp.*``/``jax.*`` call in the same function) inside a loop.
    Each such call serializes the loop against transfer+dispatch RTT — the
    copy belongs in a prefetch stage (``data/pipeline.py``:
    ``DevicePrefetchIterator`` overlaps H2D with the in-flight step) or
    hoisted out of the loop.  Inside jit scopes the same spellings mean
    different things (sharding constraints / trace-time errors already
    covered by JX001), so jitted code is excluded."""
    out: List[Finding] = []
    if not (info.jax_aliases or info.jnp_aliases or info.deviceput_names):
        return out

    device_names_cache: Dict[Optional[ast.AST], set] = {}

    def device_names(func: Optional[ast.AST]) -> set:
        return _device_names(info, device_names_cache, func)

    for node in info.nodes(ast.Call):
        if info.in_jit_scope(node):
            continue
        if not _in_loop_same_function(info, node):
            continue
        fname = call_name(node) or ""
        parts = fname.split(".")
        is_dput = ((parts[-1] == "device_put" and parts[0] in info.jax_aliases)
                   or (len(parts) == 1 and parts[0] in info.deviceput_names))
        if is_dput:
            out.append(_finding(
                info, node, "JX012",
                "`jax.device_put` inside a loop: one host->device transfer "
                "per iteration, serialized against the step instead of "
                "overlapping it — move placement into a prefetch stage "
                "(data/pipeline.DevicePrefetchIterator) or hoist it out of "
                "the loop"))
            continue
        if (parts[0] in info.numpy_aliases and len(parts) == 2
                and parts[1] in ("asarray", "array", "asanyarray")
                and node.args and isinstance(node.args[0], ast.Name)):
            if node.args[0].id in device_names(info.enclosing_function(node)):
                out.append(_finding(
                    info, node, "JX012",
                    f"`{fname}` on a device array inside a loop: "
                    "device->host fetch every iteration, serializing the "
                    "loop against transfer RTT — keep the value on device "
                    "and materialize once after the loop"))
    return _dedupe(out)


# --------------------------------------------------------------------- JX013
@rule("JX013", "jax.jit inside an instance method over a function closing "
               "over self (per-instance retrace hazard)")
def jx013(info: ModuleInfo) -> List[Finding]:
    """Flag ``jax.jit(...)`` constructed inside an instance method when the
    traced function closes over ``self``: the jitted callable (and its
    compile cache) is then rebuilt per instance — every ``clone()`` /
    master replica re-traces an identical program, and per-call closures
    defeat jit's cache entirely.  Key the step by structural config in a
    process-global cache instead (``nn/compile_cache.shared_jit``) and pass
    params/state as arguments.  Functions that only take ``self``-free
    closures (module-level builders over a conf) stay legal, as does jit
    outside methods."""
    out: List[Finding] = []

    def enclosing_self_method(node: ast.AST) -> Optional[ast.AST]:
        """Innermost-to-outermost: any enclosing FunctionDef that is a
        class method with a ``self`` first parameter."""
        cur = info.enclosing_function(node)
        while cur is not None:
            if isinstance(cur, (ast.FunctionDef, ast.AsyncFunctionDef)):
                args = [a.arg for a in (list(cur.args.posonlyargs)
                                        + list(cur.args.args))]
                if args[:1] == ["self"] and isinstance(info.parent(cur),
                                                       ast.ClassDef):
                    return cur
            cur = info.enclosing_function(cur)
        return None

    def closes_over_self(func: ast.AST) -> bool:
        """Does this function reference ``self`` as a FREE variable
        (not one of its own / a nested function's parameters)?"""
        own = {a.arg for a in (list(func.args.posonlyargs)
                               + list(func.args.args)
                               + list(func.args.kwonlyargs))}
        if "self" in own:
            return False
        body = func.body if not isinstance(func, ast.Lambda) \
            else [func.body]
        stack = list(body)
        while stack:
            n = stack.pop()
            if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef,
                              ast.Lambda)):
                params = {a.arg for a in (list(n.args.posonlyargs)
                                          + list(n.args.args)
                                          + list(n.args.kwonlyargs))}
                if "self" not in params:
                    stack.extend(ast.iter_child_nodes(n))
                continue
            if isinstance(n, ast.Name) and n.id == "self":
                return True
            stack.extend(ast.iter_child_nodes(n))
        return False

    def local_def(name: str, at: ast.AST) -> Optional[ast.AST]:
        """Resolve ``name`` to a FunctionDef in the enclosing function
        scopes of ``at``, innermost first."""
        cur = info.enclosing_function(at)
        while cur is not None:
            for n in ast.walk(cur):
                if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef)) \
                        and n.name == name \
                        and info.enclosing_function(n) is cur:
                    return n
            cur = info.enclosing_function(cur)
        return None

    msg = ("`jax.jit` over a function closing over `self` inside an "
           "instance method: the jitted callable is per-instance, so every "
           "clone/replica re-traces an identical program — build the traced "
           "function from structural config (conf/tx) and cache it in the "
           "process-global trace cache (nn/compile_cache.shared_jit)")

    # call form: jax.jit(f, ...) / jit(f) / partial(jax.jit, ...)
    for node in info.nodes(ast.Call):
        if not info.is_jit_call(node):
            continue
        if enclosing_self_method(node) is None:
            continue
        cands: List[ast.AST] = list(node.args[:1])
        for kw in node.keywords:
            if kw.arg in ("fun", "f"):
                cands.append(kw.value)
        for cand in cands:
            target = None
            if isinstance(cand, ast.Lambda):
                target = cand
            elif isinstance(cand, ast.Name):
                target = local_def(cand.id, node)
            if target is not None and closes_over_self(target):
                out.append(_finding(info, node, "JX013", msg))
                break

    # decorator form: @jax.jit on a def nested inside a self-method
    for node in info.nodes(ast.FunctionDef, ast.AsyncFunctionDef):
        if not any(info.is_jit_ref(d) or info.is_jit_call(d)
                   for d in node.decorator_list):
            continue
        if enclosing_self_method(node) is None:
            continue
        if closes_over_self(node):
            out.append(_finding(info, node, "JX013", msg))
    return _dedupe(out)


# --------------------------------------------------------------------- JX014
_CKPT_STR_RE = re.compile(
    r"(checkpoint|ckpt|model\w*\.zip|\.ckpt)", re.IGNORECASE)
_CKPT_NAME_RE = re.compile(r"(checkpoint|ckpt)", re.IGNORECASE)


@rule("JX014", "raw write to a checkpoint-like path bypassing the "
               "atomic-commit helper")
def jx014(info: ModuleInfo) -> List[Finding]:
    """Flag direct ``open(.., "wb")`` / ``np.savez``/``np.save`` /
    ``zipfile.ZipFile(.., "w")`` writes whose target is a checkpoint-like
    path (a string mentioning checkpoint/ckpt/``...model*.zip``, a name
    spelled like one, or a name assigned from such a string): a crash
    mid-write leaves a truncated artifact that restore explodes on.
    Durable artifacts must commit through the atomic temp-then-rename
    helpers (``faulttolerance/atomic.py``: ``atomic_file`` /
    ``atomic_write_bytes`` / staged checkpoint dirs).  Reads, writes to
    non-checkpoint paths, and in-memory buffers stay legal — as do the
    helpers themselves, whose temp targets are runtime-derived names."""
    out: List[Finding] = []

    def expr_is_ckptish(node: ast.AST, tracked: set) -> bool:
        """Does this expression denote a checkpoint-like path? String
        constants / f-string parts matching the pattern, names spelled
        like checkpoints, or names assigned from matching expressions."""
        for n in ast.walk(node):
            if isinstance(n, ast.Constant) and isinstance(n.value, str) \
                    and _CKPT_STR_RE.search(n.value):
                return True
        name = dotted_name(node)
        if name is not None:
            return bool(_CKPT_NAME_RE.search(name)) or name in tracked
        return False

    # per-SCOPE fixpoint: names/attrs assigned from checkpoint-like
    # expressions, including one-hop copies (path = join(d, "ckpt.zip");
    # dst = path).  Scoped like JX012's device tracking — a `path`
    # holding a checkpoint name in one function must not taint an
    # unrelated `path` in another; module-level assignments seed every
    # function's set.
    scope_cache: Dict[Optional[ast.AST], set] = {}

    def tracked_names(func: Optional[ast.AST]) -> set:
        if func in scope_cache:
            return scope_cache[func]
        scope = func if func is not None else info.tree
        assigns = []
        for node in ast.walk(scope):
            if info.enclosing_function(node) is not func:
                continue    # nested functions track their own names
            targets = []
            if isinstance(node, ast.Assign):
                targets = node.targets
            elif isinstance(node, (ast.AnnAssign, ast.AugAssign)) and \
                    getattr(node, "value", None) is not None:
                targets = [node.target]
            for t in targets:
                key = dotted_name(t)
                if key:
                    assigns.append((key, node.value))
        tracked = set() if func is None else set(tracked_names(None))
        changed = True
        while changed:
            changed = False
            for key, value in assigns:
                if key not in tracked and expr_is_ckptish(value, tracked):
                    tracked.add(key)
                    changed = True
        scope_cache[func] = tracked
        return tracked

    def _mode_of(node: ast.Call, default: str = "r") -> Optional[str]:
        if len(node.args) >= 2 and isinstance(node.args[1], ast.Constant) \
                and isinstance(node.args[1].value, str):
            return node.args[1].value
        for kw in node.keywords:
            if kw.arg == "mode" and isinstance(kw.value, ast.Constant) \
                    and isinstance(kw.value.value, str):
                return kw.value.value
        return default

    for node in info.nodes(ast.Call):
        fname = call_name(node) or ""
        parts = fname.split(".")
        target = node.args[0] if node.args else None
        if target is None or not expr_is_ckptish(
                target, tracked_names(info.enclosing_function(node))):
            continue
        bad = None
        if fname == "open":
            mode = _mode_of(node) or ""
            if ("w" in mode or "x" in mode) and "b" in mode:
                bad = f'open(.., "{mode}")'
        elif parts[-1] == "ZipFile" and len(parts) <= 2:
            mode = _mode_of(node) or "r"
            if mode in ("w", "x", "a"):
                bad = f'zipfile.ZipFile(.., "{mode}")'
        elif parts[0] in info.numpy_aliases and len(parts) == 2 and \
                parts[1] in ("save", "savez", "savez_compressed"):
            bad = f"{fname}(..)"
        if bad:
            out.append(_finding(
                info, node, "JX014",
                f"{bad} writes a checkpoint-like path in place: a crash "
                "mid-write leaves a truncated artifact restore explodes "
                "on — commit through the atomic temp-then-rename helper "
                "(faulttolerance/atomic.py: atomic_file / "
                "atomic_write_bytes)"))
    return _dedupe(out)


# --------------------------------------------------------------------- JX015
_JX015_DTYPE_CTORS = frozenset((
    "float32", "float16", "bfloat16", "float64", "int32", "int64",
    "int16", "int8", "uint8", "uint32", "complex64"))


@rule("JX015", "per-iteration dtype cast inside a Python training loop "
               "(host-side cast churn)")
def jx015(info: ModuleInfo) -> List[Finding]:
    """Flag dtype casts paid once per loop iteration: (a)
    ``x.astype(...)`` on a *device-derived* name (assigned from a
    ``jnp.*``/``jax.*`` call in the same function) inside a ``for``/
    ``while`` body, and (b) ``jnp.float32(x)``-style dtype-constructor
    calls inside a loop.  Each such cast is a separate XLA dispatch (or
    an H2D copy) serialized against the step, and its output is a fresh
    buffer the jitted step then re-reads — dtype decisions belong to the
    conf-level ``PrecisionPolicy`` (``builder.precision(...)``), which
    casts inputs/params INSIDE the compiled step, or hoisted out of the
    loop.  Host numpy casts (ETL workers massaging ``np`` arrays) stay
    legal, as does jitted code (a cast there is traced, not dispatched).
    """
    out: List[Finding] = []
    if not (info.jax_aliases or info.jnp_aliases or info.deviceput_names):
        return out
    device_names_cache: Dict[Optional[ast.AST], set] = {}
    for node in info.nodes(ast.Call):
        if info.in_jit_scope(node):
            continue
        if not _in_loop_same_function(info, node):
            continue
        fname = call_name(node) or ""
        parts = fname.split(".")
        if len(parts) == 2 and parts[0] in info.jnp_aliases and \
                parts[1] in _JX015_DTYPE_CTORS and node.args:
            out.append(_finding(
                info, node, "JX015",
                f"`{fname}(..)` inside a loop: one cast dispatch (or H2D "
                "copy) per iteration — move the dtype decision into the "
                "jitted step via the conf-level PrecisionPolicy "
                "(builder.precision(...)) or hoist the cast out of the "
                "loop"))
            continue
        if parts[-1] == "astype" and len(parts) >= 2 and \
                isinstance(node.func, ast.Attribute):
            recv = dotted_name(node.func.value)
            if recv and recv in _device_names(
                    info, device_names_cache,
                    info.enclosing_function(node)):
                out.append(_finding(
                    info, node, "JX015",
                    f"`{recv}.astype(..)` on a device array inside a "
                    "loop: per-iteration cast churn serialized against "
                    "the step — the compute dtype belongs inside the "
                    "jitted step (conf-level PrecisionPolicy, "
                    "builder.precision(...)), or cast once before the "
                    "loop"))
    return _dedupe(out)


# --------------------------------------------------------------------- JX016
_JX016_BACKOFF_CALLS = ("sleep", "backoff", "wait")
_JX016_BUDGET_NAME_RE = re.compile(
    r"attempt|retr|tries|budget|deadline|remaining", re.IGNORECASE)


def _jx016_names_in(node: ast.AST):
    for sub in ast.walk(node):
        if isinstance(sub, ast.Name):
            yield sub.id
        elif isinstance(sub, ast.Attribute):
            yield sub.attr


@rule("JX016", "unbounded retry loop: while True + except + continue with "
               "no backoff and no attempt budget")
def jx016(info: ModuleInfo) -> List[Finding]:
    """Flag ``while True`` loops that retry on exception — an ``except``
    handler ending the iteration with ``continue`` — with neither a
    backoff call (``sleep``/``backoff``/``wait``) nor an attempt budget
    (a comparison on an attempt/retry/deadline-style name) anywhere in
    the loop body.  Such a loop hammers a dead dependency at full tilt
    forever: a hub restart becomes a busy-wait stampede, and the caller
    can never distinguish "still retrying" from "never coming back".
    Bound it with ``faulttolerance.RetryPolicy`` (budgeted, seeded
    exponential backoff) or an explicit deadline."""
    out: List[Finding] = []
    for loop in info.nodes(ast.While):
        test = loop.test
        if not (isinstance(test, ast.Constant) and test.value is True
                or isinstance(test, ast.Constant) and test.value == 1):
            continue
        # retry shape: a Continue inside an except handler whose nearest
        # enclosing loop is THIS while (a continue bound to an inner
        # for/while retries that loop, not this one)
        retry_node = None
        for sub in ast.walk(loop):
            if not isinstance(sub, ast.ExceptHandler):
                continue
            for stmt in ast.walk(sub):
                if isinstance(stmt, ast.Continue) and \
                        _nearest_loop(info, stmt) is loop:
                    retry_node = sub
                    break
            if retry_node is not None:
                break
        if retry_node is None:
            continue
        has_backoff = any(
            isinstance(sub, ast.Call) and (call_name(sub) or "").split(
                ".")[-1] in _JX016_BACKOFF_CALLS
            for sub in ast.walk(loop))
        has_budget = any(
            isinstance(sub, ast.Compare) and any(
                _JX016_BUDGET_NAME_RE.search(n)
                for n in _jx016_names_in(sub))
            for sub in ast.walk(loop))
        if has_backoff or has_budget:
            continue
        out.append(_finding(
            info, retry_node, "JX016",
            "unbounded retry: `while True` re-enters on exception with no "
            "backoff call and no attempt budget in the loop — a dead "
            "dependency is hammered forever at full tilt; bound it with "
            "faulttolerance.RetryPolicy (budgeted seeded backoff) or an "
            "explicit deadline/attempt counter"))
    return _dedupe(out)


# --------------------------------------------------------------------- JX017
# scope: the request-path modules where an unbounded producer queue is a
# memory blowup under load (serving front-ends, streaming brokers,
# parallel dispatchers) — ETL/data modules size queues to their own
# prefetch depth and stay out of scope
_JX017_PATH_RE = re.compile(r"(^|[/\\])(serving|streaming|parallel)[/\\]")
_JX017_QUEUE_CLASSES = frozenset(("Queue", "LifoQueue", "PriorityQueue",
                                  "JoinableQueue"))
_JX017_QUEUE_MODULES = frozenset(("queue", "multiprocessing", "mp"))


@rule("JX017", "queue constructed without an explicit maxsize in a "
               "serving/streaming/parallel module")
def jx017(info: ModuleInfo) -> List[Finding]:
    """Flag ``queue.Queue()`` / ``multiprocessing.Queue()`` (and
    Lifo/Priority/Joinable variants) constructed with neither a
    positional size nor a ``maxsize=`` keyword, in modules under
    ``serving/``, ``streaming/``, or ``parallel/``.  Those modules sit on
    the request path: an unbounded queue there lets any
    producer-faster-than-consumer imbalance (slow device, dead consumer,
    request flood) grow host memory without limit until the process
    OOMs — the failure surfaces far from the queue that caused it.
    Bound the queue and shed/block at the bound (what admission control
    exists for).  An explicit ``maxsize=0`` stays legal — it spells the
    same unboundedness, but *deliberately*."""
    out: List[Finding] = []
    path = info.path.replace("\\", "/")
    if not _JX017_PATH_RE.search(path):
        return out
    # alias map for `import queue as q` / `import multiprocessing as mp`
    # plus names bound by `from queue import Queue [as Q]`
    mod_aliases = set(_JX017_QUEUE_MODULES)
    bare_names = set()
    for node in info.nodes(ast.Import):
        for a in node.names:
            if a.name in ("queue", "multiprocessing"):
                mod_aliases.add(a.asname or a.name)
    for node in info.nodes(ast.ImportFrom):
        if node.module in ("queue", "multiprocessing"):
            for a in node.names:
                if a.name in _JX017_QUEUE_CLASSES:
                    bare_names.add(a.asname or a.name)
    for node in info.nodes(ast.Call):
        fname = call_name(node) or ""
        parts = fname.split(".")
        is_queue_ctor = (
            (len(parts) == 2 and parts[0] in mod_aliases
             and parts[1] in _JX017_QUEUE_CLASSES)
            or (len(parts) == 1 and parts[0] in bare_names))
        if not is_queue_ctor:
            continue
        if node.args or any(kw.arg == "maxsize" for kw in node.keywords):
            continue
        out.append(_finding(
            info, node, "JX017",
            f"`{fname}()` without an explicit maxsize in a "
            "serving/streaming/parallel module: an unbounded producer "
            "queue turns any producer/consumer imbalance into unbounded "
            "host-memory growth under load — pass maxsize and shed or "
            "block at the bound (maxsize=0 spells deliberate "
            "unboundedness)"))
    return _dedupe(out)


# --------------------------------------------------------------------- JX022
_JX022_FACTORIES = frozenset(("counter", "gauge", "histogram"))


@rule("JX022", "registry child lookup inside a per-iteration loop "
               "(cache the child before the loop)")
def jx022(info: ModuleInfo) -> List[Finding]:
    """Flag metric-child resolution paid once per loop iteration:
    ``reg.counter(name, ...)`` / ``.gauge(...)`` / ``.histogram(...)``
    (recognized by the string-literal series name every registry lookup
    passes) and constant-argument ``.labels(...)`` calls inside a
    ``for``/``while`` body.  Each lookup is a dict probe + lock + (first
    time) child construction on the hot path; the observability
    registry's whole cost model rests on resolving children ONCE and
    paying only ``inc()/set()/observe()`` per event — the cached-child
    idiom PR 2 applied by hand.  ``.labels(...)`` with a *varying*
    argument (a per-worker id, a shard name computed in the loop) is the
    reason ``.labels`` exists and stays legal; only fully-constant label
    sets are hoistable and flagged."""
    out: List[Finding] = []
    for node in info.nodes(ast.Call):
        func = node.func
        if not isinstance(func, ast.Attribute):
            continue
        if not _in_loop_same_function(info, node):
            continue
        if func.attr in _JX022_FACTORIES:
            first = node.args[0] if node.args else None
            if isinstance(first, ast.Constant) and \
                    isinstance(first.value, str):
                out.append(_finding(
                    info, node, "JX022",
                    f"`.{func.attr}({first.value!r}, ...)` inside a loop: "
                    "the name->series lookup (dict probe + lock) runs "
                    "every iteration — resolve the child once before the "
                    "loop and call only inc()/set()/observe() per event"))
        elif func.attr == "labels":
            args = list(node.args) + [kw.value for kw in node.keywords]
            if args and all(isinstance(a, ast.Constant) for a in args):
                out.append(_finding(
                    info, node, "JX022",
                    "`.labels(...)` with constant labels inside a loop: "
                    "the labelset->child lookup repeats every iteration "
                    "for the same child — hoist the `.labels(...)` result "
                    "out of the loop (varying label values are the legal "
                    "use and stay in)"))
    return _dedupe(out)


# --------------------------------------------------------------------- JX023
# scope: the request-path modules where a repeated device->host sync
# multiplies by tokens generated, not by requests served — the decode
# tier (generation/) and the serving front-ends that drive it (serving/)
_JX023_PATH_RE = re.compile(r"(^|[/\\])(generation|serving)[/\\]")


@rule("JX023", "host sync (.item()/float()/np.asarray) inside a per-token "
               "loop in a generation/serving module")
def jx023(info: ModuleInfo) -> List[Finding]:
    """Flag ``float()`` / ``int()`` / ``.item()`` / ``np.asarray()`` on
    device-derived values inside a ``for``/``while`` body in modules
    under ``generation/`` or ``serving/``.  The decode loop is the
    tightest loop in the whole serving stack — one iteration per
    GENERATED TOKEN, for every active sequence — so a sync there pays
    the full dispatch round-trip per token instead of overlapping the
    next step's dispatch, which caps the tier's tokens/s no matter how
    fast the chip is.  The engine's contract is ONE materialization
    per step boundary for the whole slot batch (``_decode_step``'s
    batched ``np.asarray``); anything per-token inside a loop is the
    naive re-forward pattern this subsystem exists to replace.  JX003
    is the same defect class for training loops; this rule covers the
    request path, where the loop is bounded by a user's token budget,
    not an epoch count.  Deliberate syncs (a warmup loop blocking on
    each bucket's compile) carry a pragma with justification."""
    out: List[Finding] = []
    path = info.path.replace("\\", "/")
    if not _JX023_PATH_RE.search(path):
        return out
    # pure-host modules (HTTP plumbing with no jax/numpy) can't sync
    if not (info.jax_aliases or info.jnp_aliases or info.numpy_aliases):
        return out
    for node in info.nodes(ast.Call):
        if not _in_loop_same_function(info, node):
            continue
        sync = _host_sync_kind(info, node)
        if sync:
            out.append(_finding(
                info, node, "JX023",
                f"`{sync}` inside a per-token loop in a "
                "generation/serving module: pays a device->host "
                "round-trip every iteration of the request path's "
                "hottest loop — batch the materialization once per "
                "decode-step boundary (or pragma a deliberate "
                "warmup-blocking sync)"))
    return _dedupe(out)


# --------------------------------------------------------------------- JX024
# scope: the sharded-training modules, where a params-sized pytree is
# deliberately laid out at 1/dp per device and one stray materialization
# silently reassembles the WHOLE model on one host, every iteration
_JX024_PATH_RE = re.compile(r"(^|[/\\])(parallel|nn)[/\\]")
_JX024_NAME_RE = re.compile(r"(^|_)(params?|opt_state|grads?)($|_)")
_JX024_NP_FNS = frozenset(("asarray", "array"))


def _jx024_params_typed(node: ast.AST) -> bool:
    """A params-typed expression: a (possibly subscripted) plain or
    dotted name whose final component spells params/grads/opt_state
    (``params``, ``new_params``, ``self.model.params``,
    ``params["layer_0"]``)."""
    while isinstance(node, ast.Subscript):
        node = node.value
    name = dotted_name(node)
    if not name:
        return False
    return bool(_JX024_NAME_RE.search(name.split(".")[-1]))


@rule("JX024", "full-pytree materialization (device_get / np.asarray / "
               "all_gather of params) inside a sharded step loop")
def jx024(info: ModuleInfo) -> List[Finding]:
    """Flag ``jax.device_get(...)``, ``np.asarray(...)``/``np.array(...)``
    and unconstrained ``all_gather(...)`` applied to a params-typed name
    inside a ``for``/``while`` body in a ``parallel/`` or ``nn/`` module.
    The ZeRO-3 layout (``parallel/sharded.py``) holds params, grads and
    updater state at ~1/dp bytes per device; any of these calls on a
    params pytree in a step loop quietly reassembles the FULL model —
    host-side for device_get/np.asarray (a device→host copy of every
    shard plus peak global-params memory, once per iteration), on-device
    for a hand-written ``all_gather`` (resident global params, exactly
    what the sharding exists to avoid — the forward's gather is XLA's
    job, inserted from the sharding constraints and freed within the
    step).  Whole-model materializations belong at checkpoint/serialize
    boundaries (``save_sharded`` writes per-shard blocks and never one
    global array); a deliberate loop materialization carries a pragma
    with its justification."""
    out: List[Finding] = []
    path = info.path.replace("\\", "/")
    if not _JX024_PATH_RE.search(path):
        return out
    if not (info.jax_aliases or info.jnp_aliases or info.numpy_aliases):
        return out
    for node in info.nodes(ast.Call):
        if not node.args or not _jx024_params_typed(node.args[0]):
            continue
        if not _in_loop_same_function(info, node):
            continue
        fname = call_name(node) or ""
        parts = fname.split(".")
        kind = None
        if parts[-1] == "device_get" and (
                len(parts) == 1 or parts[0] in info.jax_aliases):
            kind = f"{fname}(...)"
        elif len(parts) == 2 and parts[0] in info.numpy_aliases and \
                parts[1] in _JX024_NP_FNS:
            kind = f"{fname}(...)"
        elif parts[-1] == "all_gather":
            kind = f"{fname}(...)"
        if kind:
            out.append(_finding(
                info, node, "JX024",
                f"`{kind}` on a params-typed pytree inside a loop in a "
                "sharded-training module: this reassembles the FULL "
                "model (defeating the 1/dp ZeRO layout) once per "
                "iteration — let XLA insert the forward all-gather from "
                "the shardings, and materialize whole params only at "
                "checkpoint/serialize boundaries (or pragma a "
                "deliberate one)"))
    return _dedupe(out)


def _jx025_bounded_exit(loop: ast.While) -> bool:
    """True when the loop carries a bounded/cancellable exit shape: an
    ``if`` whose test is an ``is None`` comparison (drain-until-empty)
    or contains a ``wait``/``is_set`` call (stop-event), with a
    ``break``/``return``/``raise`` in that branch."""
    for sub in ast.walk(loop):
        if not isinstance(sub, ast.If):
            continue
        test = sub.test
        drains = isinstance(test, ast.Compare) and any(
            isinstance(op, ast.Is) for op in test.ops) and any(
            isinstance(c, ast.Constant) and c.value is None
            for c in test.comparators)
        cancels = any(
            isinstance(c, ast.Call) and (call_name(c) or "").split(
                ".")[-1] in ("wait", "is_set")
            for c in ast.walk(test))
        if not (drains or cancels):
            continue
        if any(isinstance(s, (ast.Break, ast.Return, ast.Raise))
               for n in sub.body for s in ast.walk(n)):
            return True
    return False


# --------------------------------------------------------------------- JX025
# scope: the cluster-runtime modules, where an unbounded barrier /
# rendezvous / lease-poll wait turns one dead peer into a permanently
# wedged survivor (the fleet's liveness rests on every wait being
# budgeted)
_JX025_PATH_RE = re.compile(r"(^|[/\\])(faulttolerance|parallel)[/\\]")
_JX025_SLEEP_CALLS = frozenset(("sleep", "wait", "poll", "backoff"))
_JX025_BUDGET_NAME_RE = re.compile(
    r"attempt|retr|tries|budget|deadline|timeout|remaining|expires",
    re.IGNORECASE)


@rule("JX025", "barrier/rendezvous wait loop with no timeout or "
               "RetryPolicy budget in a cluster-runtime module")
def jx025(info: ModuleInfo) -> List[Finding]:
    """Flag ``while`` loops in ``faulttolerance/`` / ``parallel/``
    modules that poll — a ``sleep``/``wait``/``poll``/``backoff`` call
    in the loop body — with no budget evidence anywhere in the loop: no
    comparison on a deadline/timeout/attempt/budget-style name.  These
    are the barrier and rendezvous waits of the cluster runtime
    (``expect_members``, lease polls, shard-block-marker waits); an
    unbudgeted one waits forever on a peer that died mid-protocol, so
    one SIGKILL wedges every survivor.  Bound the wait with an explicit
    deadline, or pace it with ``faulttolerance.RetryPolicy`` under an
    attempt budget.

    Three WAITING shapes stay legal because they are bounded or
    cancellable by construction: the stop-event loop (the wait IS the
    test, ``while not stop.wait(interval)``, or an ``if stop.wait(..):
    return/break`` in the body), the drain-until-empty loop (``x =
    q.poll(..); if x is None: break/return`` — it exits the moment the
    source is momentarily empty, the inverse of waiting for it), and
    any loop comparing a deadline/attempt-style name."""
    out: List[Finding] = []
    path = info.path.replace("\\", "/")
    if not _JX025_PATH_RE.search(path):
        return out
    for loop in info.nodes(ast.While):
        test_calls = {id(sub) for sub in ast.walk(loop.test)
                      if isinstance(sub, ast.Call)}
        sleeps = [
            sub for sub in ast.walk(loop)
            if isinstance(sub, ast.Call) and id(sub) not in test_calls
            and (call_name(sub) or "").split(".")[-1] in _JX025_SLEEP_CALLS
            and _nearest_loop(info, sub) is loop]
        if not sleeps:
            continue
        # stop-event pattern in the TEST: `while not stop.wait(i)` /
        # `while not shutdown.is_set()` — cancellable per iteration
        if any((call_name(sub) or "").split(".")[-1]
               in ("wait", "is_set", "poll")
               for sub in ast.walk(loop.test)
               if isinstance(sub, ast.Call)):
            continue
        has_budget = any(
            isinstance(sub, ast.Compare) and any(
                _JX025_BUDGET_NAME_RE.search(n)
                for n in _jx016_names_in(sub))
            for sub in ast.walk(loop))
        if has_budget or _jx025_bounded_exit(loop):
            continue
        out.append(_finding(
            info, sleeps[0], "JX025",
            "unbudgeted rendezvous wait: this `while` loop polls "
            "(sleep/wait/poll) with no deadline or attempt-budget "
            "comparison anywhere in the loop — a peer that died "
            "mid-protocol wedges this process forever; bound the wait "
            "with an explicit deadline, or pace it with "
            "faulttolerance.RetryPolicy under an attempt budget"))
    return _dedupe(out)


# --------------------------------------------------------------------- JX026
# scope: every non-test package module — the AST-side complement of
# graftaudit AX004 (the IR rule catches a callback that made it into a
# compiled steady-state program; this one catches the source line the
# moment it is written, wherever it would compile to)
_JX026_TEST_PATH_RE = re.compile(
    r"(^|[/\\])tests?([/\\]|$)|(^|[/\\])test_[^/\\]*\.py$|"
    r"(^|[/\\])conftest\.py$")
_JX026_DEBUG_LEAVES = frozenset(("print", "breakpoint", "callback"))
_JX026_CALLBACKS = frozenset(("pure_callback", "io_callback"))


@rule("JX026", "jax.debug.print/breakpoint or host callback "
               "(pure_callback/io_callback) in a non-test package module")
def jx026(info: ModuleInfo) -> List[Finding]:
    """Flag ``jax.debug.print`` / ``jax.debug.breakpoint`` /
    ``jax.debug.callback`` and ``pure_callback`` / ``io_callback``
    (dotted through a jax alias, or imported bare from
    ``jax``/``jax.experimental``) anywhere in a non-test package
    module.  Inside a jitted program each lowers to a callback primitive
    that stalls the device on a host round-trip EVERY execution — the
    forgotten-debug-line failure mode ships straight into the
    steady-state train/serve/decode programs, where graftaudit AX004
    would flag the compiled result; this rule stops the line at review
    time instead, and also outside jit scopes (a ``jax.debug.print`` in
    eager code is still a stray debug statement).  Test modules and
    conftest are out of scope — printing tracers is what debugging a
    test looks like.  A deliberate callback (a documented
    eval-time-only io_callback) carries a pragma with justification."""
    out: List[Finding] = []
    path = info.path.replace("\\", "/")
    if _JX026_TEST_PATH_RE.search(path):
        return out
    # bare names imported from jax / jax.experimental, and jax.debug
    # module aliases (`from jax import debug`, `import jax.debug as d`)
    bare_callbacks: set = set()
    debug_mods: set = set()
    for node in info.nodes(ast.Import):
        for alias in node.names:
            if alias.name == "jax.debug" and alias.asname:
                debug_mods.add(alias.asname)
    for node in info.nodes(ast.ImportFrom):
        mod = node.module or ""
        if mod not in ("jax", "jax.experimental", "jax.debug"):
            continue
        for alias in node.names:
            name = alias.asname or alias.name
            if alias.name in _JX026_CALLBACKS:
                bare_callbacks.add(name)
            elif mod == "jax" and alias.name == "debug":
                debug_mods.add(name)
            elif mod == "jax.debug" and alias.name in _JX026_DEBUG_LEAVES:
                bare_callbacks.add(name)
    for node in info.nodes(ast.Call):
        fname = call_name(node)
        if not fname:
            continue
        parts = fname.split(".")
        hit = None
        if len(parts) == 1 and parts[0] in bare_callbacks:
            hit = fname
        elif len(parts) >= 2:
            root, leaf = parts[0], parts[-1]
            if root in info.jax_aliases and len(parts) >= 3 and \
                    parts[1] == "debug" and leaf in _JX026_DEBUG_LEAVES:
                hit = fname                      # jax.debug.print(...)
            elif root in info.jax_aliases and leaf in _JX026_CALLBACKS:
                hit = fname                      # jax.pure_callback(...)
            elif root in debug_mods and len(parts) == 2 and \
                    leaf in _JX026_DEBUG_LEAVES:
                hit = fname                      # debug.print(...)
        if hit:
            out.append(_finding(
                info, node, "JX026",
                f"`{hit}` in a non-test package module: inside jit this "
                "lowers to a host-callback primitive that stalls the "
                "device every execution (graftaudit AX004 catches the "
                "compiled form); outside jit it is a stray debug "
                "statement — remove it, or pragma a deliberate "
                "callback with its justification"))
    return _dedupe(out)


# --------------------------------------------------------------------- JX027
# scope: every non-test package module — the AST-side complement of the
# nn/sparse densified embedding-gradient path: both source spellings of
# a dense-materialized embedding gradient.  The IR-side pin is the
# graftaudit `train_step[embedding_zero3]` card (no O(vocab·dim)
# collective); this rule stops the source line at review time.
_JX027_VOCAB_NAME_RE = re.compile(
    r"(^|_)(n_in|vocab|vocab_size|n_rows|num_embeddings|table_size|"
    r"n_tokens)$", re.IGNORECASE)
_JX027_SCATTER_METHS = frozenset(("add", "set"))


def _jx027_is_one_hot_call(info: ModuleInfo, node: ast.AST,
                           bare: set, nn_mods: set) -> bool:
    """Is ``node`` a call to jax's one_hot (dotted through a jax/jnp
    alias or a ``jax.nn`` module alias, or imported bare from jax.nn),
    possibly behind a transpose (``one_hot(...).T``)?"""
    if isinstance(node, ast.Attribute) and node.attr in ("T", "mT"):
        node = node.value
    if not isinstance(node, ast.Call):
        return False
    fname = call_name(node)
    if not fname:
        return False
    parts = fname.split(".")
    if len(parts) == 1:
        return parts[0] in bare
    return parts[-1] == "one_hot" and \
        parts[0] in (info.jax_aliases | info.jnp_aliases | nn_mods)


def _jx027_vocabish_zeros(info: ModuleInfo, node: ast.AST) -> bool:
    """Is ``node`` a ``zeros((vocabish, ...))`` call — a jnp/np zeros
    whose FIRST shape element is a name spelled like a vocabulary size
    (n_in / vocab / num_embeddings / ...)?"""
    if not isinstance(node, ast.Call) or not node.args:
        return False
    fname = call_name(node)
    if not fname:
        return False
    parts = fname.split(".")
    if parts[-1] != "zeros" or len(parts) < 2 or parts[0] not in (
            info.jnp_aliases | info.numpy_aliases | info.jax_aliases):
        return False
    shape = node.args[0]
    first = shape.elts[0] if isinstance(shape, (ast.Tuple, ast.List)) \
        and shape.elts else shape
    name = dotted_name(first)
    if not name:
        return False
    return bool(_JX027_VOCAB_NAME_RE.search(name.split(".")[-1]))


@rule("JX027", "dense-materialized embedding gradient: one_hot(...) @ W "
               "lookup, or a full-vocab zeros scatter target, in a "
               "non-test package module")
def jx027(info: ModuleInfo) -> List[Finding]:
    """Both source spellings that materialize an O(vocab·dim) dense
    tensor for what is a row-sparse lookup/gradient: (a) an embedding
    lookup written as ``jax.nn.one_hot(ids, vocab) @ W`` — the matmul
    is O(batch·vocab·dim) MXU work AND its backward builds the dense
    one-hot cotangent, where a gather is O(batch·dim) and the sparse
    path exchanges only touched rows; (b) a gradient/update accumulated
    by scattering into a full-vocab ``jnp.zeros((n_in, ...))`` buffer
    (direct chain or a one-hop assigned name) — exactly the dense
    cotangent ``nn/sparse`` exists to avoid.  Use the embedding layers'
    gather path (``sparse_grad=True`` for the densified exchange);
    a deliberate dense materialization (a host-side test/interop
    conversion like ``SparseRows.to_dense``) carries a pragma with its
    justification.  Test modules are out of scope."""
    out: List[Finding] = []
    path = info.path.replace("\\", "/")
    if _JX026_TEST_PATH_RE.search(path):
        return out
    bare_one_hot: set = set()
    nn_mods: set = set()
    for node in info.nodes(ast.ImportFrom):
        mod = node.module or ""
        if mod in ("jax.nn", "jax.experimental.nn"):
            for alias in node.names:
                if alias.name == "one_hot":
                    bare_one_hot.add(alias.asname or alias.name)
        elif mod == "jax":
            for alias in node.names:
                if alias.name == "nn":          # from jax import nn
                    nn_mods.add(alias.asname or alias.name)
    # (a) one_hot(...) @ W  /  W @ one_hot(...)  /  one_hot(...).T @ W
    for node in info.nodes(ast.BinOp):
        if not isinstance(node.op, ast.MatMult):
            continue
        if _jx027_is_one_hot_call(info, node.left, bare_one_hot,
                                  nn_mods) or \
                _jx027_is_one_hot_call(info, node.right, bare_one_hot,
                                       nn_mods):
            out.append(_finding(
                info, node, "JX027",
                "one_hot(...) @ table: a dense O(batch*vocab*dim) matmul "
                "(and a dense one-hot cotangent on the backward) for what "
                "is a row gather — index the table (EmbeddingLayer id "
                "path; sparse_grad=True for the densified touched-rows "
                "exchange)"))
    # (b) full-vocab zeros scatter targets, direct or one-hop — TWO
    # module-wide phases (not per-function), so module- and class-level
    # scatters are covered too; the one-hop name map is module-global,
    # a deliberate over-approximation the pragma escape covers
    zeros_names: set = set()
    for node in info.nodes(ast.Assign):
        if len(node.targets) == 1 and \
                isinstance(node.targets[0], ast.Name) and \
                _jx027_vocabish_zeros(info, node.value):
            zeros_names.add(node.targets[0].id)
    for node in info.nodes(ast.Call):
        if not isinstance(node.func, ast.Attribute) or \
                node.func.attr not in _JX027_SCATTER_METHS:
            continue
        sub = node.func.value
        if not isinstance(sub, ast.Subscript) or \
                not isinstance(sub.value, ast.Attribute) or \
                sub.value.attr != "at":
            continue
        target = sub.value.value
        hit = _jx027_vocabish_zeros(info, target) or (
            isinstance(target, ast.Name) and target.id in zeros_names)
        if hit:
            out.append(_finding(
                info, node, "JX027",
                "scatter into a full-vocab zeros buffer materializes "
                "the dense [vocab, dim] gradient every step — carry "
                "coalesced row indices + values instead (nn/sparse "
                "SparseRows; the train step's densified exchange), or "
                "pragma a deliberate host-side densification with its "
                "justification"))
    return _dedupe(out)


# --------------------------------------------------------------------- JX028
# scope: every non-test package module EXCEPT nn/compile_cache.py — the
# one module allowed to touch jax.jit directly, because it is the
# counted/recorded/auditable compile path everything else must route
# through.  A stray jax.jit elsewhere compiles programs graftaudit
# never sees: no compile counters, no captured call specs (so no
# caller-liveness for the AX007 donation solver), no cards.
_JX028_COMPILE_CACHE_RE = re.compile(r"(^|[/\\])nn[/\\]compile_cache\.py$")
_JX028_WRAPPERS = frozenset(("jit", "pmap"))


@rule("JX028", "stray jax.jit/jax.pmap outside nn/compile_cache.py in a "
               "non-test package module")
def jx028(info: ModuleInfo) -> List[Finding]:
    """Flag every reference to ``jax.jit`` / ``jax.pmap`` (dotted
    through a jax alias — covering direct calls, bare ``@jax.jit``
    decorators, and ``functools.partial(jax.jit, ...)`` — and the bare
    ``from jax import jit/pmap`` import) in any non-test package module
    other than ``nn/compile_cache.py``.  All steady-state program
    construction must go through ``InstrumentedJit``/``audit_lower``:
    that is where compiles are counted (AX006 churn), call specs are
    recorded (the AX007 caller-liveness probe), and the trace cache the
    IR audit + cards walk is populated.  A raw ``jax.jit`` is an
    invisible second compile cache — its programs never reach the
    differential gate.  Deliberate exceptions (a one-shot capability
    probe, a static-argnames kernel wrapper InstrumentedJit does not
    support yet) carry a pragma with the justification; test modules
    are out of scope."""
    out: List[Finding] = []
    path = info.path.replace("\\", "/")
    if _JX026_TEST_PATH_RE.search(path) or \
            _JX028_COMPILE_CACHE_RE.search(path):
        return out
    for node in info.nodes(ast.ImportFrom):
        if (node.module or "") != "jax":
            continue
        for alias in node.names:
            if alias.name in _JX028_WRAPPERS:
                out.append(_finding(
                    info, node, "JX028",
                    f"`from jax import {alias.name}`: route program "
                    "construction through nn/compile_cache "
                    "(InstrumentedJit) — a raw jit/pmap is an unaudited "
                    "compile path (no counters, no call specs, no IR "
                    "cards)"))
    for node in info.nodes(ast.Attribute):
        name = dotted_name(node)
        if not name:
            continue
        parts = name.split(".")
        if len(parts) == 2 and parts[0] in info.jax_aliases and \
                parts[1] in _JX028_WRAPPERS:
            out.append(_finding(
                info, node, "JX028",
                f"`{name}` outside nn/compile_cache.py: this compiles a "
                "program graftaudit never sees (no compile counters, no "
                "recorded call specs for the donation solver, no card) "
                "— use InstrumentedJit, or pragma a deliberate "
                "exception with its justification"))
    return _dedupe(out)


# --------------------------------------------------------------------- JX029
# the ONE module licensed to fence inside a loop: the step profiler's
# SAMPLED block_until_ready is the honest-device-slice measurement, paid
# every sample_every-th step by design and counted in stepprof_fences_total
_JX029_PROFILER_RE = re.compile(
    r"(^|[/\\])observability[/\\]profiler\.py$")


@rule("JX029", "block_until_ready inside a for/while loop in a non-test "
               "package module (unsampled fence in a hot path)")
def jx029(info: ModuleInfo) -> List[Finding]:
    """Flag ``jax.block_until_ready(...)`` (dotted through a jax alias),
    the bare ``from jax import block_until_ready`` form, and
    ``.block_until_ready()`` method calls inside a ``for``/``while``
    body in any non-test package module outside
    ``observability/profiler.py``.  A fence in a loop serializes host
    and device every iteration — exactly the per-step sync the fit
    loops' async-dispatch design (and the PR 16 host-sync sweep) removed;
    one such line reintroduces the dispatch round-trip per step and
    pins the profiler's dispatch-depth gauge at 0.  The step profiler's own fence is legal
    because it is SAMPLED (every ``sample_every``-th step, counted in
    ``stepprof_fences_total``) — which is why profiler.py is the one
    path-exempt module.  A deliberate loop fence elsewhere (a benchmark
    timing an aggregation round) carries a pragma with justification."""
    out: List[Finding] = []
    path = info.path.replace("\\", "/")
    if _JX026_TEST_PATH_RE.search(path) or _JX029_PROFILER_RE.search(path):
        return out
    bare: set = set()
    for node in info.nodes(ast.ImportFrom):
        if (node.module or "") == "jax":
            for alias in node.names:
                if alias.name == "block_until_ready":
                    bare.add(alias.asname or alias.name)
    for node in info.nodes(ast.Call):
        if not _in_loop_same_function(info, node):
            continue
        fn = node.func
        name = dotted_name(fn)
        dotted = bool(name) and name.split(".")[0] in info.jax_aliases \
            and name.endswith(".block_until_ready")
        is_bare = isinstance(fn, ast.Name) and fn.id in bare
        method = isinstance(fn, ast.Attribute) \
            and fn.attr == "block_until_ready" and not dotted
        if dotted or is_bare or method:
            out.append(_finding(
                info, node, "JX029",
                f"`{name or 'block_until_ready'}` inside a loop: an "
                "every-iteration fence serializes the async dispatch "
                "pipeline (the host-sync class the fit loops removed) — "
                "sample it like observability/profiler.py's fence, hoist "
                "it past the loop, or pragma a deliberate timing sync "
                "with its justification"))
    return _dedupe(out)


# --------------------------------------------------------------------- JX030
# the per-step host work the dispatch pipeline must fit inside one device
# step: a pytree rebuild in a fit/step loop is O(leaves) of Python per
# iteration, the dominant term on the dispatch-bound arm
_JX030_HOT_PATH_RE = re.compile(r"(^|[/\\])(nn|parallel)[/\\]")
_JX030_TREE_FNS = frozenset((
    "tree_map", "tree_flatten", "tree_unflatten", "tree_leaves",
    "tree_structure", "tree_map_with_path", "tree_all", "tree_reduce"))
_JX030_TREE_SHORT = frozenset((   # the jax.tree.* spellings
    "map", "flatten", "unflatten", "leaves", "structure", "all", "reduce"))
_JX030_PYTREE_NAME_RE = re.compile(
    r"param|grad|state|opt|update|mu\b|nu\b", re.IGNORECASE)


def _jx030_in_loop_body(info: ModuleInfo, node: ast.AST) -> bool:
    """Like ``_in_loop_same_function`` but a call in a loop HEADER
    (``for x in tree_leaves(p):`` / ``while tree_all(p):``... the
    ``for`` form runs once, and header position marks intent either
    way) does not count that loop — only code the loop body re-executes
    per iteration is a per-step rebuild."""
    prev: ast.AST = node
    cur = info.parent(node)
    while cur is not None:
        if isinstance(cur, (ast.FunctionDef, ast.AsyncFunctionDef,
                            ast.Lambda, ast.Module)):
            return False
        if isinstance(cur, (ast.For, ast.AsyncFor)):
            if prev is not cur.iter:
                return True
        elif isinstance(cur, ast.While):
            if prev is not cur.test:
                return True
        prev = cur
        cur = info.parent(cur)
    return False


@rule("JX030", "pytree rebuild (tree_map/tree_flatten/... or a dict/list "
               "comprehension over a params-like tree) inside a for/while "
               "loop in an nn// or parallel/ hot path")
def jx030(info: ModuleInfo) -> List[Finding]:
    """Flag per-iteration pytree traversal in the packages that own the
    train loops: ``jax.tree_util.tree_map``/``tree_flatten``/... (any
    jax alias, ``jax.tree.*`` short forms, and bare ``from jax.tree_util
    import tree_map`` included) inside a ``for``/``while`` body in a
    non-test ``nn/`` or ``parallel/`` module, plus dict/list
    comprehensions rebuilding a params-like tree (an iterable named
    param*/grad*/state/opt*/update*) in the same position.  The bounded
    dispatch pipeline only overlaps host work with device execution
    while the host's per-step cost stays under the device step time —
    an O(n_leaves) Python traversal per iteration is exactly the term
    that breaks that on real models (thousands of leaves, every step).
    Hoist the traversal out of the loop (trace it into the step program,
    or restructure so placement/flattening happens once per fit), or
    pragma a deliberate per-iteration rebuild with its justification."""
    out: List[Finding] = []
    path = info.path.replace("\\", "/")
    if _JX026_TEST_PATH_RE.search(path) or \
            not _JX030_HOT_PATH_RE.search(path):
        return out
    bare: set = set()
    for node in info.nodes(ast.ImportFrom):
        if (node.module or "") in ("jax.tree_util", "jax.tree"):
            for alias in node.names:
                if alias.name in _JX030_TREE_FNS | _JX030_TREE_SHORT:
                    bare.add(alias.asname or alias.name)
    for node in info.nodes(ast.Call):
        if not _jx030_in_loop_body(info, node):
            continue
        fn = node.func
        name = dotted_name(fn)
        dotted = False
        if name:
            parts = name.split(".")
            if parts[0] in info.jax_aliases:
                dotted = parts[-1] in _JX030_TREE_FNS or (
                    len(parts) >= 2 and parts[-2] == "tree"
                    and parts[-1] in _JX030_TREE_SHORT)
        is_bare = isinstance(fn, ast.Name) and fn.id in bare
        if dotted or is_bare:
            out.append(_finding(
                info, node, "JX030",
                f"`{name or fn.id}` inside a loop in a train-loop "
                "package: an O(n_leaves) pytree traversal per iteration "
                "is host work the bounded dispatch pipeline cannot hide "
                "— hoist it out of the loop (or into the jitted step), "
                "or pragma a deliberate per-iteration rebuild with its "
                "justification"))
    for node in list(info.nodes(ast.DictComp)) + list(info.nodes(ast.ListComp)):
        if not _jx030_in_loop_body(info, node):
            continue
        for gen in node.generators:
            it = gen.iter
            base = it
            if isinstance(it, ast.Call) and \
                    isinstance(it.func, ast.Attribute) and \
                    it.func.attr in ("items", "values", "keys"):
                base = it.func.value
            name = dotted_name(base)
            if name and _JX030_PYTREE_NAME_RE.search(name.split(".")[-1]):
                out.append(_finding(
                    info, node, "JX030",
                    f"dict/list comprehension over `{name}` inside a "
                    "loop in a train-loop package: a per-iteration "
                    "rebuild of a params-like tree is O(n_leaves) host "
                    "work the dispatch pipeline cannot hide — hoist it, "
                    "or pragma a deliberate rebuild with its "
                    "justification"))
                break
    return _dedupe(out)


# --------------------------------------------------------------------- JX031
# scope: the paged-KV request path — block tables are fixed-shape int32
# DATA passed whole to the two steady programs; per-block Python on the
# host side is the O(blocks)-dispatches pattern paging must not reintroduce
_JX031_PATH_RE = re.compile(r"(^|[/\\])generation[/\\]")
_JX031_TABLE_RE = re.compile(
    r"(^|_)(block_)?(tables?|table_rows?)($|_)|(^|_)block_ids($|_)")
_JX031_XFER = frozenset(("device_put", "device_get"))


def _jx031_table_named(node: ast.AST) -> bool:
    """A block-table-typed expression: a (possibly subscripted) plain or
    dotted name whose final component spells a table (``tables``,
    ``table_row``, ``self.ring.tables[slot]``, ``block_ids``)."""
    while isinstance(node, ast.Subscript):
        node = node.value
    name = dotted_name(node)
    if not name:
        return False
    return bool(_JX031_TABLE_RE.search(name.split(".")[-1]))


def _jx031_subscripts_table(node: ast.AST) -> bool:
    """True when the expression subscripts (or IS) a block-table-named
    value — ``tables[slot, i]``, ``row[i]`` where row spells a table."""
    for sub in ast.walk(node):
        if isinstance(sub, ast.Subscript) and _jx031_table_named(sub):
            return True
    return False


def _jx031_xfer_kind(info: ModuleInfo, node: ast.Call) -> Optional[str]:
    """Classify a per-block transfer/sync call: ``jax.device_put`` /
    ``jax.device_get`` (any jax alias or bare import) or ``.item()``."""
    if isinstance(node.func, ast.Attribute) and node.func.attr == "item" \
            and not node.args:
        return ".item()"
    name = call_name(node) or ""
    parts = name.split(".")
    if parts[-1] in _JX031_XFER and (
            len(parts) == 1 or parts[0] in info.jax_aliases):
        return f"{name}(...)"
    return None


@rule("JX031", "per-block host iteration over a KV block table "
               "(device_put/device_get/.item() per block) in a "
               "generation/ loop body")
def jx031(info: ModuleInfo) -> List[Finding]:
    """Flag per-block device traffic on the paged-KV request path: a
    ``jax.device_put``/``jax.device_get``/``.item()`` call inside a
    ``for`` loop iterating over a block-table-named value, or such a
    call subscripting a table-named value inside any loop body, in a
    non-test ``generation/`` module.  The paged cache's contract is
    that block tables are fixed-shape int32 DATA shipped whole once per
    program call (``paged_prefill`` takes the slot's full table row,
    ``paged_decode`` the whole ``[slots, blocks]`` matrix) and every
    gather happens inside the traced program; Python iterating the
    table and touching the device per BLOCK turns one dispatch into
    O(blocks_per_slot) round-trips per step — at 16-token blocks and
    2k-token sequences that is 128 dispatches where the design pays
    one, and it grows with sequence length exactly the way paging
    exists to prevent.  Host-side bookkeeping loops over tables
    (allocator refcounts, numpy mirror updates) are fine — only the
    per-block device transfer is the defect.  JX023 catches generic
    per-token syncs; this rule catches the per-BLOCK shape specific to
    the paged layout.  A deliberate per-block transfer (a debug dump
    tool) carries a pragma with its justification."""
    out: List[Finding] = []
    path = info.path.replace("\\", "/")
    if not _JX031_PATH_RE.search(path) or _JX026_TEST_PATH_RE.search(path):
        return out
    if not (info.jax_aliases or info.jnp_aliases or info.numpy_aliases):
        return out
    table_loops: List[ast.AST] = [
        loop for loop in list(info.nodes(ast.For)) +
        list(info.nodes(ast.AsyncFor))
        if _jx031_table_named(loop.iter) or (
            isinstance(loop.iter, ast.Call) and
            isinstance(loop.iter.func, ast.Attribute) and
            loop.iter.func.attr in ("tolist", "items", "values") and
            _jx031_table_named(loop.iter.func.value))]
    for node in info.nodes(ast.Call):
        kind = _jx031_xfer_kind(info, node)
        if kind is None:
            continue
        in_table_loop = any(
            node in ast.walk(loop) and node is not loop.iter
            for loop in table_loops)
        per_block_arg = _in_loop_same_function(info, node) and (
            _jx031_subscripts_table(node.func) or
            any(_jx031_subscripts_table(a) for a in node.args))
        if in_table_loop or per_block_arg:
            out.append(_finding(
                info, node, "JX031",
                f"`{kind}` per block of a KV block table inside a loop "
                "in a generation/ module: the table is fixed-shape "
                "int32 data the steady programs take WHOLE — per-block "
                "host transfers turn one dispatch into O(blocks) "
                "round-trips per step and scale with sequence length; "
                "ship the full table as a program argument and gather "
                "inside the trace (or pragma a deliberate debug dump)"))
    return _dedupe(out)


# --------------------------------------------------------------------- JX032
# scope: the serving tier — admission/routing locks are metadata locks;
# holding one across an engine dispatch or HTTP client call serializes
# the whole replica fleet behind a single request
_JX032_PATH_RE = re.compile(r"(^|[/\\])serving[/\\]")
_JX032_LOCK_RE = re.compile(r"(lock|mutex)\d*$")
# blocking dispatch surfaces: engine request entry points, fleet-wide
# swaps, and the JSON/HTTP client verbs (import_session/put_nowait-style
# enqueues are O(1) bookkeeping and stay legal under a lock)
_JX032_DISPATCH = frozenset((
    "submit", "generate", "predict", "predict_versioned", "stream",
    "hot_swap", "promote_latest", "warmup", "post", "get_text",
    "stream_lines"))


def _jx032_lock_item(item: ast.withitem) -> bool:
    """A ``with`` item whose context expression spells a lock: a plain
    or dotted name ending in lock/mutex (``self._lock``,
    ``sess.lock``, ``self._fleet_lock``)."""
    expr = item.context_expr
    if isinstance(expr, ast.Call):      # with self._lock.acquire_timeout(...)
        expr = expr.func
        if isinstance(expr, ast.Attribute):
            expr = expr.value
    name = dotted_name(expr)
    if not name:
        return False
    return bool(_JX032_LOCK_RE.search(name.split(".")[-1].lower()))


@rule("JX032", "engine dispatch or HTTP client call while holding a "
               "lock in a serving/ module")
def jx032(info: ModuleInfo) -> List[Finding]:
    """Flag a blocking dispatch — an engine request entry point
    (``submit``/``generate``/``predict``/``predict_versioned``/
    ``stream``), a fleet-wide swap (``hot_swap``/``promote_latest``/
    ``warmup``), or a JSON client verb (``post``/``get_text``/
    ``stream_lines``) — made INSIDE a ``with <lock>:`` body in a
    non-test ``serving/`` module.  Serving-tier locks (router state,
    session tables, slot pointers) are metadata locks: they exist to
    make a handful of pointer reads/writes atomic and are taken on
    EVERY request.  A dispatch held under one turns the lock's
    nanosecond critical section into the full engine round-trip (queue
    wait + device step + possibly an HTTP hop), so every other request
    — including requests bound for perfectly idle replicas — convoys
    behind it, and a wedged replica holding the dispatch wedges the
    entire admission front with it.  The fleet pattern is
    snapshot-then-dispatch: copy the routing decision out under the
    lock, release it, dispatch outside.  O(1) bookkeeping
    (``import_session`` enqueue, queue puts, counter bumps) stays legal
    under a lock; a deliberate lock-held dispatch carries a pragma with
    its justification."""
    out: List[Finding] = []
    path = info.path.replace("\\", "/")
    if not _JX032_PATH_RE.search(path) or _JX026_TEST_PATH_RE.search(path):
        return out
    lock_withs = [
        w for w in list(info.nodes(ast.With)) +
        list(info.nodes(ast.AsyncWith))
        if any(_jx032_lock_item(item) for item in w.items)]
    if not lock_withs:
        return out
    for node in info.nodes(ast.Call):
        if not (isinstance(node.func, ast.Attribute)
                and node.func.attr in _JX032_DISPATCH):
            continue
        held = any(
            any(node in ast.walk(stmt) for stmt in w.body)
            for w in lock_withs)
        if not held:
            continue
        recv = dotted_name(node.func.value) or "?"
        out.append(_finding(
            info, node, "JX032",
            f"`{recv}.{node.func.attr}(...)` while holding a lock in a "
            "serving/ module: routing/session locks are metadata locks "
            "taken on every request — a dispatch held under one convoys "
            "the whole fleet behind a single engine round-trip (and a "
            "wedged replica wedges the admission front); snapshot the "
            "routing decision under the lock, release it, dispatch "
            "outside (or pragma a deliberate O(1)-bounded call)"))
    return _dedupe(out)


# ===================================================================== #
# Whole-program concurrency pack (JX018-JX021): these run ONCE over the  #
# ProgramModel built from every linted module — see program.py for the   #
# thread-entry / guarded-by / lock-order machinery they share.           #
# ===================================================================== #


# --------------------------------------------------------------------- JX018
@program_rule("JX018", "shared attribute written from a background thread "
                       "with inconsistent lock guarding")
def jx018(program: ProgramModel) -> List[Finding]:
    """For every class that spawns threads: an instance attribute written
    from a thread-entry function and also accessed from the caller side
    must be *consistently* guarded.  Fires at each unguarded mutation
    (outside ``__init__``) when either (a) some other access of the same
    attribute IS lock-guarded — the discipline exists, the mutation skips
    it — or (b) the unguarded mutation is a read-modify-write
    (``self.x += 1``), which loses updates under any interleaving
    regardless of discipline.  Lock/queue/event-typed attributes are
    internally synchronized and exempt; plain single assignments with no
    guard evidence anywhere stay legal (flag-style publication).

    HTTP-handler classes get a second arm: the framework runs one
    handler instance per connection, so ``self`` is private but the
    server reference every request shares is not — an unguarded
    ``srv.counter += 1`` there loses updates across concurrent
    requests.  Receivers built fresh in the function (parsers, local
    accumulators) are single-threaded and stay legal."""
    out: List[Finding] = []
    for cls in program.classes:
        if cls.is_handler:
            for target, held, func in cls.foreign_augs:
                if held or not receiver_is_shared(func, target):
                    continue
                recv = dotted_name(target.value) or "?"
                out.append(_finding_at(
                    cls.path, target, "JX018",
                    f"unguarded read-modify-write to "
                    f"`{recv}.{target.attr}` in handler `{cls.name}`: "
                    "request handlers run one thread per connection, and "
                    f"`{recv}` is shared server state — concurrent "
                    "requests lose updates; guard the counter with a "
                    "lock on the server object"))
        if not cls.entry_funcs:
            continue
        for attr in sorted(cls.attrs()):
            if attr in cls.lock_attrs or attr in cls.safe_attrs:
                continue
            acc = [a for a in cls.accesses if a.attr == attr]
            writes = [a for a in acc if a.write and not a.in_init]
            entry_writes = [w for w in writes if w.func in cls.entry_funcs]
            if not entry_writes:
                continue
            outside = [a for a in acc
                       if a.func not in cls.entry_funcs and not a.in_init]
            if not outside:
                continue           # thread-private state
            guarded = [a for a in acc if a.held]
            unguarded_muts = [w for w in writes if not w.held]
            if not guarded:
                # no discipline to be inconsistent WITH: only the
                # always-unsafe read-modify-writes fire
                unguarded_muts = [w for w in unguarded_muts if w.aug]
            if not unguarded_muts:
                continue
            guards = sorted({lk for a in guarded for lk in a.held})
            for w in unguarded_muts:
                how = ("read-modify-write" if w.aug else
                       "item write" if w.subscript else "write")
                why = (f"other accesses hold self.{guards[0]}"
                       if guards else
                       "a concurrent increment loses updates")
                out.append(_finding_at(
                    cls.path, w.node, "JX018",
                    f"unguarded {how} to `self.{attr}` in "
                    f"`{cls.name}`: the attribute is written from a "
                    f"thread-entry function and read from other threads, "
                    f"but this mutation holds no lock ({why}) — guard "
                    "every access with one lock, or make the attribute a "
                    "thread-safe primitive / registry metric"))
    return _dedupe(out)


# --------------------------------------------------------------------- JX019
@program_rule("JX019", "non-daemon background thread started but never "
                       "joined on any shutdown/close/__exit__ path")
def jx019(program: ProgramModel) -> List[Finding]:
    """A non-daemon thread (``daemon=`` unset or False) that is
    ``start()``-ed but has no ``join()`` (or Timer ``cancel()``) anywhere
    on the owning class — or, for a function-local thread, in the
    creating function — keeps the interpreter alive after main exits and
    leaks a runner that can keep mutating shared state after its owner
    is logically gone.  Threads handed to the caller (returned, passed
    on, stored in containers) are the caller's to join and stay legal,
    as do ``executor.submit`` tasks (the executor owns their
    lifecycle)."""
    out: List[Finding] = []
    spawns = [(cls.path, cls, s)
              for cls in program.classes for s in cls.spawns]
    spawns += [(info.path, None, s) for info, s in program.module_spawns]
    for path, cls, s in spawns:
        if s.kind == "submit":
            continue
        if s.daemon:
            continue
        if not s.started or s.joined:
            continue
        if s.self_attr is None and s.escapes:
            continue
        where = (f"self.{s.self_attr}" if s.self_attr is not None
                 else s.binding or "an unbound handle")
        cleanup = "join()" if s.kind != "timer" else "cancel()/join()"
        out.append(_finding_at(
            path, s.node, "JX019",
            f"non-daemon {s.kind} ({where}) started but never joined: "
            "no shutdown/close/__exit__ path calls "
            f"{cleanup}, so process exit hangs on it and the runner can "
            "outlive its owner — join it on the teardown path, or mark "
            "it daemon=True if it owns no in-flight state"))
    return _dedupe(out)


# --------------------------------------------------------------------- JX020
@program_rule("JX020", "lock-order cycle across nested acquisitions "
                       "(potential deadlock)")
def jx020(program: ProgramModel) -> List[Finding]:
    """Acquiring lock B while holding lock A orders A before B.  If the
    program's lock-order graph — nested ``with`` scopes plus one-hop
    calls into methods that acquire locks (same-class and
    constructor-typed attributes) — contains a cycle, two threads
    entering the cycle from different sides deadlock.  One finding per
    cycle, anchored at one participating acquisition."""
    out: List[Finding] = []
    for nodes, site, path in find_lock_cycles(program.lock_edges()):
        labels = [n.label() for n in nodes]
        out.append(_finding_at(
            path, site, "JX020",
            "lock-order cycle: " + " -> ".join(labels + [labels[0]])
            + " — two threads taking these locks in opposite orders "
            "deadlock; impose one global acquisition order (or collapse "
            "to a single lock)"))
    return _dedupe(out)


# --------------------------------------------------------------------- JX021
@program_rule("JX021", "check-then-act on a shared container outside its "
                       "inferred guard")
def jx021(program: ProgramModel) -> List[Finding]:
    """``if k in self._d: ... self._d[k]`` is two operations; between
    them another thread can remove the key (KeyError) or replace the
    value.  Fires when the container attribute HAS an inferred lock
    guard (so the class does practice locking around it) but the
    check-then-act sequence runs without it.  Also fires on
    ``qsize()``/``empty()``-gated ``get`` in thread-spawning classes:
    the queue's internal lock makes each call atomic but not the pair —
    a sibling consumer wins the race and the gated ``get`` blocks
    forever.  Use ``with lock:`` around the pair, ``dict.get``/``pop``
    with a default, or ``get_nowait`` + ``except Empty``."""
    out: List[Finding] = []
    for cls in program.classes:
        for node, kind, target, key, held in cls.check_then_act:
            if kind == "membership":
                guards = cls.guards(target)
                if not guards or held & guards:
                    continue
                out.append(_finding_at(
                    cls.path, node, "JX021",
                    f"check-then-act on `self.{target}` outside its "
                    f"inferred guard (self.{sorted(guards)[0]}): the key "
                    "can vanish between the membership test and the "
                    "access — hold the guard across the pair, or use "
                    ".get()/.pop() with a default"))
            else:
                if not cls.entry_funcs:
                    continue
                out.append(_finding_at(
                    cls.path, node, "JX021",
                    f"`{target}.qsize()/.empty()`-gated get: the check "
                    "and the get are two operations, and a sibling "
                    "consumer can drain the queue between them, blocking "
                    "this get forever — use get_nowait() and handle "
                    "queue.Empty"))
    return _dedupe(out)


def _nearest_loop(info: ModuleInfo, node: ast.AST) -> Optional[ast.AST]:
    """Nearest enclosing for/while of ``node`` without crossing a
    function boundary."""
    cur = info.parent(node)
    while cur is not None:
        if isinstance(cur, (ast.FunctionDef, ast.AsyncFunctionDef,
                            ast.Lambda, ast.Module)):
            return None
        if isinstance(cur, (ast.For, ast.AsyncFor, ast.While)):
            return cur
        cur = info.parent(cur)
    return None


def _dedupe(findings: List[Finding]) -> List[Finding]:
    seen = set()
    out = []
    for f in sorted(findings, key=lambda f: (f.path, f.line, f.col, f.rule)):
        key = (f.path, f.line, f.col, f.rule)
        if key not in seen:
            seen.add(key)
            out.append(f)
    return out
