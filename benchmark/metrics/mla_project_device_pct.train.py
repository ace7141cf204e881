"""Share of the device's busy time under latent attention's name scope
``mla_project`` alone: its five projections, the two latent norms, the
rotary turn of part of a head, whatever assembles or takes apart what the
attention kernels are handed, and the turns of their output, forward and
backward together, by self time; the kernels themselves (``attn_full``) lie
outside it.  ``mla_device_pct.train`` blends the two, and its share hardly
moves when numerator and denominator fall together: this is the part that
a change to how q, k and v reach the kernels moves.  Read through
``mla_device_pct.train``'s table, as ``mtp_device_pct.train`` reads it.  A
program without the scope gives ``None``.

The log also says, where the program counts them, how the flash kernels'
calls traced so far took their keys (``flash_calls_traced_total``: whole,
or in parts as the projections wrote them)."""
from benchmark import common

mla = common.load_module("metrics", "mla_device_pct.train")


def key_forms():
    """``{form: calls traced}`` from the program's registry, ``{}`` where
    the program has no such counter."""
    from deeplearning4j_tpu.observability.registry import default_registry
    counter = default_registry().get("flash_calls_traced_total")
    if counter is None:                 # as at the parent of the PR's change
        return {}
    return {labels[0]: child.value for labels, child in counter.samples()}


def read(ctx):
    shares = mla.under(ctx, ("mla_project",))
    if shares is None or not shares[0]["mla_project"]:
        return None
    by_scope, busy = shares
    forms = key_forms()
    common.say(f"mla_project scope: {by_scope['mla_project'] / 1e6:.3f} ms "
               f"of {busy / 1e6:.3f} busy"
               + (f"; flash calls traced by their keys: {forms}"
                  if forms else ""))
    return 100.0 * by_scope["mla_project"] / busy
