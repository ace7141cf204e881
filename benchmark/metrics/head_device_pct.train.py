"""Share of the device's busy time under the output layer's class scope
(``RnnOutputLayer``, ``OutputLayer``, ``CenterLossOutputLayer``: the head's
product, its softmax loss and its three gradients), forward and backward
together, by self time.

``program_spans`` already puts every operation of the traced stretch down
to the layer class in its scope; this reader asks that table for the
classes whose name ends in ``OutputLayer``.  Where the head walks chunks of
rows (``nn/losses.chunked_softmax_xent``) every chunk's operations keep the
class scope, the gradient products among them (they then run under
``jvp(forward)/RnnOutputLayer/``), so the whole-array head of a parent and
the walked one are read alike.  A trace without scopes, or a program
without such a layer, gives ``None``."""
from benchmark import common, program_spans

SUFFIX = "OutputLayer"


def read(ctx):
    t = program_spans.tables(ctx)
    if t is None or t["device_by_layer"] is None or not t["device_self_ns"]:
        return None
    heads = {layer: ns for layer, ns in t["device_by_layer"].items()
             if layer.endswith(SUFFIX)}
    if not heads:
        return None
    busy = t["device_self_ns"]
    common.say("head: " + ", ".join(
        f"{layer} {ns / 1e6:.3f} ms ({100 * ns / busy:.2f} %)"
        for layer, ns in heads.items()))
    return 100.0 * sum(heads.values()) / busy
