"""Share of the device's busy time in latent attention: under its name
scope ``mla_project`` (the five projections, the two latent norms, the
rotary turn of part of a head, the assembling of q and k) and in the
attention between them (``attn_full``, the flash kernels at heads 192 wide
in q and k and 128 in v), forward and backward together, by self time.

As ``moe_device_pct.train`` reduces the file for its scopes: the operations
of the traced stretch, clipped to it, by self time, here kept by their
whole scope so that the module's reader (``mtp_device_pct.train``) asks the
same table another question.  A program without ``mla_project``, as the
parent of the PR that added it, gives ``None``: ``attn_full`` alone is some
other attention's."""
import functools

from benchmark import common, program_spans
from benchmark import trace_reduce as tr

SCOPES = ("mla_project", "attn_full")


@functools.lru_cache(maxsize=2)
def scope_self_times(path: str):
    """``({whole scope: self ns}, busy ns)`` of the stretch in one
    ``.xplane.pb``, or ``None`` where the stretch or any scope cannot be
    found."""
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
    return selfs, sum(selfs.values())


def under(ctx, scopes):
    """``({scope: self ns}, busy ns)``: each operation put down to the
    first of ``scopes`` that its own scope names; ``None`` without a
    trace."""
    if ctx.get("trace") is None:
        return None
    path = ctx.get("xplane") or tr.newest_xplane(
        program_spans.trace_dir_of(ctx["cell"]))
    table = scope_self_times(path)
    if table is None or not table[1]:
        return None
    selfs, busy = table
    by_scope = dict.fromkeys(scopes, 0.0)
    for scope, ns in selfs.items():
        found = [part for part in scope.split("/") if part in by_scope]
        if found:
            by_scope[found[0]] += ns
    return by_scope, busy


def read(ctx):
    shares = under(ctx, SCOPES)
    if shares is None or not shares[0]["mla_project"]:
        return None
    by_scope, busy = shares
    common.say("mla scopes: " + ", ".join(
        f"{s} {ns / 1e6:.3f} ms ({100 * ns / busy:.2f} %)"
        for s, ns in by_scope.items()))
    return 100.0 * sum(by_scope.values()) / busy
