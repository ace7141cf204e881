"""``flash_attention(..., window=W)``: the band that moves with the query,
in the Pallas interpreter against a dense mask, forward and backward, for
sequences that are and are not multiples of the grid's block and of the
window; the static steps a block is cut into (``_band_steps``) against a
count of the visible pairs by brute force; the windowed kernels' own
names."""
import itertools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu.ops import flash_attention as F
from deeplearning4j_tpu.ops.attention import sdpa_reference


def dense(q, k, v, window):
    """Causal attention of the last ``window`` keys, by a dense mask."""
    t, d = q.shape[2], q.shape[3]
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k) * d ** -0.5
    i, j = jnp.arange(t)[:, None], jnp.arange(t)[None, :]
    s = jnp.where((j <= i) & (j > i - window), s, -jnp.inf)
    return jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(s, axis=-1), v)


def qkv(t, d=64, heads=2, seed=0):
    keys = jax.random.split(jax.random.PRNGKey(seed + t), 3)
    return tuple(jax.random.normal(k, (1, heads, t, d), jnp.float32)
                 for k in keys)


# (sequence, window, tile, rows a grid block may hold): the window a
# multiple of the block and not, the sequence a multiple of the window and
# not, one block and several, a window under one tile, a sequence under one
# tile; float32 so that the comparison is tight
SHAPES = [(32, 8, None, 1024),        # one sub-tile block, both edges in it
          (256, 128, 128, 128),       # window = block: two blocks a query
          (512, 256, 128, 128),       # window two blocks: three a query
          (512, 300, 128, 256),       # window no multiple of tile or block
          (384, 200, 128, 128),       # sequence no multiple of the window
          (768, 100, 128, 256),       # blocks of 256 (768 = 3 x 256)
          (512, 128, 128, 1024)]      # one block of 512, the band inside it


@pytest.mark.parametrize("t,window,tile,rows", SHAPES)
def test_windowed_forward_and_backward_match_a_dense_mask(monkeypatch, t,
                                                          window, tile,
                                                          rows):
    """Tolerance 2e-5 absolute on outputs of order one and on gradients:
    both sides are float32 and differ by the order of their sums."""
    monkeypatch.setattr(F, "_BLOCK_ROWS", rows)
    q, k, v = qkv(t)

    def flash(q, k, v):
        return F.flash_attention(q, k, v, causal=True, window=window,
                                 block_q=tile, block_k=tile, interpret=True)
    np.testing.assert_allclose(flash(q, k, v), dense(q, k, v, window),
                               atol=2e-5)
    got = jax.grad(lambda *a: jnp.sum(jnp.sin(flash(*a))),
                   argnums=(0, 1, 2))(q, k, v)
    want = jax.grad(lambda *a: jnp.sum(jnp.sin(dense(*a, window))),
                    argnums=(0, 1, 2))(q, k, v)
    for g, w in zip(got, want):
        np.testing.assert_allclose(g, w, atol=2e-5)


def test_the_log_sum_exp_of_a_windowed_call(monkeypatch):
    monkeypatch.setattr(F, "_BLOCK_ROWS", 128)
    q, k, v = qkv(256)
    _, lse = F.flash_attention(q, k, v, causal=True, window=100,
                               interpret=True, return_lse=True)
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k) * 64 ** -0.5
    i, j = jnp.arange(256)[:, None], jnp.arange(256)[None, :]
    s = jnp.where((j <= i) & (j > i - 100), s, -jnp.inf)
    np.testing.assert_allclose(lse, jax.nn.logsumexp(s, axis=-1), atol=2e-5)


def test_sdpa_reference_takes_the_same_window():
    q, k, v = qkv(64, d=16)
    np.testing.assert_allclose(
        sdpa_reference(q, k, v, causal=True, window=10),
        dense(q, k, v, 10), atol=2e-6)


def test_a_window_no_shorter_than_the_sequence_is_the_causal_call():
    q, k, v = qkv(128)
    text = jax.jit(lambda q, k, v: F.flash_attention(
        q, k, v, causal=True, window=128, interpret=True)).lower(
            q, k, v).as_text()
    plain = jax.jit(lambda q, k, v: F.flash_attention(
        q, k, v, causal=True, interpret=True)).lower(q, k, v).as_text()
    assert text == plain


@pytest.mark.parametrize("kwargs", [
    {"causal": False, "window": 8},        # a band needs the diagonal
    {"causal": True, "window": 0}])
def test_a_window_that_makes_no_band_is_refused(kwargs):
    q, k, v = qkv(32)
    with pytest.raises(ValueError, match="window"):
        F.flash_attention(q, k, v, interpret=True, **kwargs)


def test_queries_and_keys_of_two_lengths_take_no_window():
    q, _, _ = qkv(32)
    k, v, _ = qkv(64)
    with pytest.raises(ValueError, match="one length"):
        F.flash_attention(q, k, v, causal=True, window=8, interpret=True)


# ------------------------------------------------------- the static steps
@pytest.mark.parametrize("rows,bq,bk,window", [
    (1024, 256, 256, 2048), (1024, 256, 256, 1000), (1024, 128, 128, 2048),
    (512, 256, 128, 300), (512, 128, 256, 300), (256, 128, 128, 100),
    (32, 32, 32, 8), (768, 256, 256, 256), (1024, 256, 256, 1)])
def test_band_steps_cover_every_visible_pair_once(rows, bq, bk, window):
    """Over all the blocks the band walks from a held block: every visible
    (query, key) pair lies in exactly one step, the unmasked part of a step
    holds visible pairs only, and a block past the walk holds none."""
    n_walk = F._band_walk(window, rows, 10 ** 6)
    for delta in range(n_walk + 1):
        shift = delta * rows
        i = shift + np.arange(rows)[:, None]
        j = np.arange(rows)[None, :]
        visible = (j <= i) & (j > i - window)
        if delta == n_walk:
            assert not visible.any()
            continue
        covered = np.zeros((rows, rows), int)
        for q0, nq, k0, nk, cut, head in F._band_steps(rows, bq, bk, shift,
                                                       window):
            covered[q0:q0 + nq, k0:k0 + nk] += 1
            assert visible[q0:q0 + nq, k0 + head:k0 + nk - cut].all() or \
                head + cut >= nk
        assert (covered[visible] == 1).all()
        assert covered.max() <= 1


def test_the_walk_follows_the_band_not_the_square():
    # the cell's shape: 8 blocks of 1024, a window of 2048: 3 blocks a
    # query block where the causal square walks up to 8
    assert F._band_walk(2048, 1024, 8) == 3
    assert F._band_walk(2048, 1024, 2) == 2
    assert F._band_walk(1, 1024, 8) == 1
    assert F._band_walk(1025, 1024, 8) == 2
    assert F._band_walk(1026, 1024, 8) == 3


def test_windowed_calls_carry_names_of_their_own():
    q, k, v = qkv(256)

    def loss(window):
        return lambda q, k, v: jnp.sum(F.flash_attention(
            q, k, v, causal=True, window=window, interpret=True))
    windowed = jax.make_jaxpr(jax.grad(loss(100), argnums=(0, 1, 2)))(
        q, k, v)
    full = jax.make_jaxpr(jax.grad(loss(None), argnums=(0, 1, 2)))(q, k, v)
    for name in F.WINDOW_KERNEL_NAMES:
        assert name in str(windowed) and name not in str(full)
    for name in F.FULL_KERNEL_NAMES:
        assert name in str(full) and name not in str(windowed)
    assert F.KERNEL_NAMES == F.FULL_KERNEL_NAMES + F.WINDOW_KERNEL_NAMES
    assert not any(a in b for a, b in itertools.permutations(
        F.KERNEL_NAMES, 2))
