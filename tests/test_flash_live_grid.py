"""The three flash kernels walk a table of live (held block, walked block)
pairs (``ops/flash_attention._walk``), scalar-prefetched, and no other grid
steps: the pairs ``_block_live`` keeps, in the order a grid over the whole
square walked them, so a launch does the same products in the same order
with the dead steps gone.  The table itself, the grids the launches take,
the results against ``sdpa_reference`` in the Pallas interpreter with the
grid's block cut to 256 rows (4 and 8 blocks a sequence), and the counter
that says how far the walk is shorter than the square."""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu.observability.registry import default_registry
from deeplearning4j_tpu.ops import flash_attention as F
from deeplearning4j_tpu.ops.attention import sdpa_reference

ROWS = 256


def _columns(pairs):
    return [np.asarray(c).tolist() for c in F._table(pairs)]


# ------------------------------------------------------------- the table
@pytest.mark.parametrize("n", [1, 4, 8])
def test_a_causal_square_walks_its_live_pairs_in_the_grid_order(n):
    """Queries held (fwd, dq): each row's keys rising to the diagonal, so
    its first pair holds key 0; keys held (dk/dv): each key block's
    queries rising from the diagonal.  Exactly the pairs ``_block_live``
    keeps, n(n+1)/2 of them."""
    t = n * ROWS
    live = {(qi, ki) for qi in range(n) for ki in range(n)
            if F._block_live(True, qi, ki, ROWS, ROWS)}
    by_query = F._walk(True, t, t, ROWS, ROWS, True)
    by_key = F._walk(True, t, t, ROWS, ROWS, False)
    assert len(by_query) == len(by_key) == n * (n + 1) // 2 == len(live)
    assert by_query == [(qi, ki) for qi in range(n) for ki in range(qi + 1)]
    assert by_key == [(ki, qi) for ki in range(n) for qi in range(ki, n)]
    assert set(by_query) == live == {(q, k) for k, q in by_key}


@pytest.mark.parametrize("window,n", [(300, 4), (300, 8), (600, 8),
                                      (1, 8), (2048, 8)])
def test_a_band_walks_the_blocks_it_reaches_on_the_sequence(window, n):
    """The held block's own first, then the ones ``_band_walk`` reaches
    before it (keys walked) or after it (queries walked), none off the
    sequence."""
    t, reach = n * ROWS, F._band_walk(window, ROWS, n)
    by_query = F._walk(True, t, t, ROWS, ROWS, True, reach)
    by_key = F._walk(True, t, t, ROWS, ROWS, False, reach)
    assert by_query == [(h, h - d) for h in range(n) for d in range(reach)
                        if h - d >= 0]
    assert by_key == [(h, h + d) for h in range(n) for d in range(reach)
                      if h + d < n]
    assert len(by_query) == len(by_key) == sum(min(h + 1, reach)
                                               for h in range(n))


def test_a_launch_that_is_not_causal_walks_the_whole_square():
    pairs = F._walk(False, 4 * ROWS, 2 * ROWS, ROWS, ROWS, True)
    assert pairs == [(h, w) for h in range(4) for w in range(2)]
    assert F._walk(False, 4 * ROWS, 2 * ROWS, ROWS, ROWS, False) == [
        (h, w) for h in range(2) for w in range(4)]


def test_the_table_marks_each_held_blocks_first_and_last_pair():
    held, walked, first, last = _columns(
        F._walk(True, 3 * ROWS, 3 * ROWS, ROWS, ROWS, True))
    assert held == [0, 1, 1, 2, 2, 2] and walked == [0, 0, 1, 0, 1, 2]
    assert first == [1, 1, 0, 1, 0, 0] and last == [1, 0, 1, 0, 0, 1]
    assert _columns([(0, 0)]) == [[0], [0], [1], [1]]


# ------------------------------------------------------------- the grids
def _grids(closed):
    """``{kernel name: grid}`` of the ``pallas_call`` equations anywhere in
    a closed jaxpr."""
    found = {}

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                found[eqn.params["name"]] = eqn.params["grid_mapping"].grid
            for v in eqn.params.values():
                inner = getattr(v, "jaxpr", v)
                if hasattr(inner, "eqns"):
                    walk(inner)
    walk(closed.jaxpr)
    return found


@pytest.mark.parametrize("t,window,pairs", [(1024, None, 10),
                                            (2048, None, 36),
                                            (2048, 300, 21),
                                            (256, None, 1)])
def test_the_launches_grids_are_heads_by_live_pairs(monkeypatch, t, window,
                                                    pairs):
    monkeypatch.setattr(F, "_BLOCK_ROWS", ROWS)
    q = jnp.zeros((1, 2, t, 64), jnp.float32)

    def attend(q, k, v):
        return F.flash_attention(q, k, v, causal=True, window=window,
                                 interpret=True)
    names = F.WINDOW_KERNEL_NAMES if window else F.FULL_KERNEL_NAMES
    forward = _grids(jax.make_jaxpr(attend)(q, q, q))
    backward = _grids(jax.make_jaxpr(
        lambda q, k, v: jax.vjp(attend, q, k, v)[1](q))(q, q, q))
    assert forward == {names[0]: (2, pairs)}
    assert backward == {name: (2, pairs) for name in names}


# ------------------------------------------------------------- the results
def _whole(t, seed=0):
    keys = jax.random.split(jax.random.PRNGKey(seed + t), 4)
    return tuple(jax.random.normal(k, (1, 2, t, 64), jnp.float32)
                 for k in keys)


def _parts(t, seed=3):
    keys = jax.random.split(jax.random.PRNGKey(seed + t), 4)
    shapes = [(1, 2, t, 192), (1, 2, t, 256), (1, 1, t, 64), (1, 2, t, 128)]
    return tuple(jax.random.normal(k, s, jnp.float32)
                 for k, s in zip(keys, shapes))


def _assembled(q, kv, k_shared, **kw):
    k = jnp.concatenate(
        [kv[..., :128], jnp.broadcast_to(k_shared, kv.shape[:3] + (64,))],
        axis=-1)
    return sdpa_reference(q, k, kv[..., 128:], causal=True, **kw)


@pytest.mark.parametrize("blocks", [4, 8])
@pytest.mark.parametrize("form", ["causal", "windowed", "parts"])
def test_forward_and_three_gradients_match_the_reference(monkeypatch, form,
                                                         blocks):
    """Float32 on both sides, at the tolerance of the kernels' other
    reference checks: they differ by the order of their sums."""
    monkeypatch.setattr(F, "_BLOCK_ROWS", ROWS)
    t = blocks * ROWS
    window = 300 if form == "windowed" else None
    if form == "parts":
        q, kv, ks, do = _parts(t)
        args = (q, kv, ks)

        def flash(q, kv, ks):
            return F.flash_attention(q, kv=kv, k_shared=ks, causal=True,
                                     block_q=128, block_k=128,
                                     interpret=True)
        reference = _assembled
    else:
        q, k, v, do = _whole(t)
        args = (q, k, v)
        flash = functools.partial(F.flash_attention, causal=True,
                                  window=window, block_q=128, block_k=128,
                                  interpret=True)
        reference = functools.partial(sdpa_reference, causal=True,
                                      window=window)
    out, vjp = jax.vjp(flash, *args)
    want, vjp_want = jax.vjp(reference, *args)
    np.testing.assert_allclose(out, want, atol=2e-5)
    for g, w, like in zip(vjp(do), vjp_want(do), args):
        assert g.shape == like.shape
        np.testing.assert_allclose(g, w, atol=2e-5)


# ------------------------------------------------------------- the counter
def _pairs_traced():
    c = default_registry().get("flash_grid_pairs_traced_total")
    return {} if c is None else {labels: child.value
                                 for labels, child in c.samples()}


@pytest.mark.parametrize("t,window,labels", [
    (2048, None, ("36", "64")),         # a square of 8 blocks
    (2048, 300, ("21", "24")),          # a band reaching 3 of 8 blocks
    (256, None, ("1", "1"))])           # one block: nothing to skip
def test_the_counter_reads_the_pairs_walked_and_the_square(monkeypatch, t,
                                                           window, labels):
    monkeypatch.setattr(F, "_BLOCK_ROWS", ROWS)
    q = jax.ShapeDtypeStruct((1, 2, t, 64), jnp.float32)
    before = _pairs_traced()
    jax.eval_shape(lambda q: F.flash_attention(
        q, q, q, causal=True, window=window, interpret=True), q)
    moved = {k: n - before.get(k, 0) for k, n in _pairs_traced().items()
             if n != before.get(k, 0)}
    assert moved == {labels: 1}
