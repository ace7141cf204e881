"""Share of the device's busy time under the routed FFN's five name scopes
(``moe_route``: router, scores, top-k, weights; ``moe_dispatch``: the sort
by expert and the gather; ``moe_experts``: the grouped products;
``moe_shared``; ``moe_combine``), forward, backward and what the backward
rebuilds together, by self time.

As ``eva_attention_device_pct.train`` does for its scopes: the operations
of the traced stretch, clipped to it, by self time, each put down to the
first of the five scopes its own scope names.  A program without them, as
the parent of the PR that added them, gives ``None``."""
import functools

from benchmark import common, program_spans
from benchmark import trace_reduce as tr

SCOPES = ("moe_route", "moe_dispatch", "moe_experts", "moe_shared",
          "moe_combine")


@functools.lru_cache(maxsize=2)
def scope_shares(path: str):
    """``({scope: self ns}, busy ns)`` of the stretch in one ``.xplane.pb``,
    or ``None`` where the stretch or the scopes cannot be found."""
    host, scoped, any_scope = program_spans.load(path)
    outer = [e for e in host if e.name == program_spans.OUTER]
    planes = tr.device_planes(scoped)
    if not any_scope or not outer or not planes:
        return None
    lo = min(e.start for e in outer)
    hi = max(e.start + e.dur for e in outer)

    def within(plane):
        return [tr.Event(e.plane, e.line, e.name, max(e.start, lo),
                         min(e.start + e.dur, hi) - max(e.start, lo))
                for e in tr.device_ops(scoped, plane)
                if e.start + e.dur > lo and e.start < hi]
    selfs = tr.self_times(max((within(p) for p in planes),
                              key=lambda ops: sum(e.dur for e in ops)))
    by_scope, busy = dict.fromkeys(SCOPES, 0.0), sum(selfs.values())
    for scope, ns in selfs.items():
        found = [part for part in scope.split("/") if part in by_scope]
        if found:
            by_scope[found[0]] += ns
    if busy and any(by_scope.values()):
        common.say("moe scopes: " + ", ".join(
            f"{s} {ns / 1e6:.3f} ms ({100 * ns / busy:.2f} %)"
            for s, ns in by_scope.items()))
    return by_scope, busy


def pct(ctx, scopes):
    """Self time under ``scopes`` over busy time, in per cent."""
    if ctx.get("trace") is None:
        return None
    path = ctx.get("xplane") or tr.newest_xplane(
        program_spans.trace_dir_of(ctx["cell"]))
    shares = scope_shares(path)
    if shares is None or not shares[1]:
        return None
    by_scope, busy = shares
    ns = sum(by_scope[s] for s in scopes)
    return 100.0 * ns / busy if ns else None


def read(ctx):
    return pct(ctx, SCOPES)
