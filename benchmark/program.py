"""What the traffic kinds share in handling the program's objects: the
updater that the configuration states, and, after the first steps, norms of
the leaves of the program's trees and a field of its optimizer's state.
The trees are the program's own, ``{layer: {leaf: array}}``."""
from __future__ import annotations


def leaf_norms(tree, minus=None) -> dict:
    """``(layer, leaf)`` -> norm of that leaf of ``tree`` (of ``tree -
    minus`` where given), as host floats, in one jitted call."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def norms(t, m):
        if m is not None:
            t = jax.tree_util.tree_map(lambda a, b: a - b, t, m)
        return {k: {kk: jnp.sqrt(jnp.sum(jnp.square(a.astype(jnp.float32))))
                    for kk, a in v.items()} for k, v in t.items() if v}
    host = jax.device_get(norms(tree, minus))
    return {(k, kk): float(a) for k, v in host.items() for kk, a in v.items()}


def optimizer_field(opt_state, field: str) -> dict:
    """The leaves that the optimizer keeps under ``field`` (optax's ``mu``
    of Adam, ``trace`` of momentum), as a tree like the parameters: the
    program keeps one masked state per group of layers."""
    import jax
    states = [s for s in jax.tree_util.tree_leaves(
        opt_state, is_leaf=lambda s: hasattr(s, field)) if hasattr(s, field)]
    out = {}
    for state in states:
        for path, leaf in jax.tree_util.tree_flatten_with_path(
                getattr(state, field))[0]:
            layer, name = (k.key for k in path)
            out.setdefault(layer, {})[name] = leaf
    if not out:
        raise RuntimeError(f"no '{field}' in the optimizer's state")
    return out


def updater(cfg: dict):
    """The program's updater with the numbers of the configuration's
    ``optimizer``."""
    from deeplearning4j_tpu.nn.conf import updaters
    numbers = dict(cfg["optimizer"])
    kind = {"adam": updaters.Adam,
            "nesterov": updaters.Nesterovs}[numbers.pop("kind")]
    return kind(**numbers)


def free(net) -> None:
    """Let go of everything the program's network keeps on the device."""
    if net is not None:
        net.params = net.opt_state = net.state = None
