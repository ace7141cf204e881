"""``flash_attention`` with a value head of another width than the query's
and key's (latent attention: q and k 192 wide, v 128): the three kernels in
the Pallas interpreter against ``sdpa_reference``, the forward and the
three backward outputs, full causal and windowed, beside the equal widths
the kernels always took; and what ``flash_blocks`` refuses.  (That equal widths
lower to the kernels they always did is shown on the benchmark's cells,
against the parent commit: PERF.md section 6, PR 35.)  Below them the form
that takes the keys in parts, the ``[k | v]`` product and the part of the
key every head shares (PR 39): against the call on assembled keys, against
the reference, on poisoned dead blocks, and what is refused."""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu.ops import flash_attention as F
from deeplearning4j_tpu.ops.attention import sdpa_reference

WIDTHS = [(64, 64), (128, 128), (192, 128)]


def qkv(t, d_qk, d_v, heads=2, seed=0):
    keys = jax.random.split(jax.random.PRNGKey(seed + t + d_qk), 3)
    return tuple(jax.random.normal(k, (1, heads, t, d), jnp.float32)
                 for k, d in zip(keys, (d_qk, d_qk, d_v)))


@pytest.mark.parametrize("window", [None, 100], ids=["full", "windowed"])
@pytest.mark.parametrize("d_qk,d_v", WIDTHS)
def test_forward_and_three_gradients_match_the_reference(monkeypatch, d_qk,
                                                         d_v, window):
    """Several blocks of the grid (256 rows a block over 512 positions),
    float32 on both sides: they differ by the order of their sums."""
    monkeypatch.setattr(F, "_BLOCK_ROWS", 256)
    q, k, v = qkv(512, d_qk, d_v)

    def flash(q, k, v):
        return F.flash_attention(q, k, v, causal=True, window=window,
                                 block_q=128, block_k=128, interpret=True)

    def reference(q, k, v):
        return sdpa_reference(q, k, v, causal=True, window=window)
    out = flash(q, k, v)
    assert out.shape == (1, 2, 512, d_v)
    np.testing.assert_allclose(out, reference(q, k, v), atol=2e-5)
    got = jax.grad(lambda *a: jnp.sum(jnp.sin(flash(*a))),
                   argnums=(0, 1, 2))(q, k, v)
    want = jax.grad(lambda *a: jnp.sum(jnp.sin(reference(*a))),
                    argnums=(0, 1, 2))(q, k, v)
    for g, w, like in zip(got, want, (q, k, v)):
        assert g.shape == like.shape
        np.testing.assert_allclose(g, w, atol=2e-5)


def test_the_log_sum_exp_beside_a_narrower_value():
    q, k, v = qkv(256, 192, 128)
    out, lse = F.flash_attention(q, k, v, causal=True, interpret=True,
                                 return_lse=True)
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k) * 192 ** -0.5
    s = jnp.where(jnp.tril(jnp.ones((256, 256), bool)), s, -jnp.inf)
    assert out.shape == (1, 2, 256, 128) and lse.shape == (1, 2, 256)
    np.testing.assert_allclose(lse, jax.nn.logsumexp(s, axis=-1), atol=2e-5)


def test_the_scale_follows_the_query_width():
    """``1 / sqrt(192)``, not ``1 / sqrt(128)``: against a softmax written
    out here."""
    q, k, v = qkv(128, 192, 128)
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k) / np.sqrt(192.0)
    s = jnp.where(jnp.tril(jnp.ones((128, 128), bool)), s, -jnp.inf)
    want = jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(s, axis=-1), v)
    got = F.flash_attention(q, k, v, causal=True, interpret=True)
    np.testing.assert_allclose(got, want, atol=2e-5)
    np.testing.assert_allclose(sdpa_reference(q, k, v, causal=True), want,
                               atol=2e-5)


@pytest.mark.parametrize("d,d_v", [(192, 100), (100, 128), (96, 64)])
def test_flash_blocks_refuses_a_width_no_multiple_of_64(d, d_v):
    with pytest.raises(ValueError, match="head_dim % 64"):
        F.flash_blocks(256, 256, d, d_v=d_v)


def test_flash_blocks_takes_both_widths():
    assert F.flash_blocks(8192, 8192, 192, d_v=128) == \
        F.flash_blocks(8192, 8192, 128)


# ---- keys in parts: the [k | v] product and the part every head shares ----
# (PR 39: latent attention's keys as its projections write them)
def parts_inputs(t, dtype, heads=4, seed=3):
    keys = jax.random.split(jax.random.PRNGKey(seed + t), 4)
    shapes = [(1, heads, t, 192), (1, heads, t, 256), (1, 1, t, 64),
              (1, heads, t, 128)]
    return tuple(jax.random.normal(k, s, jnp.float32).astype(dtype)
                 for k, s in zip(keys, shapes))


def assembled(kv, k_shared):
    """``(k, v)`` a head, as a caller without the parts form hands them."""
    k = jnp.concatenate(
        [kv[..., :128],
         jnp.broadcast_to(k_shared, kv.shape[:3] + (64,))], axis=-1)
    return k, kv[..., 128:]


def in_parts(q, kv, k_shared, **kw):
    return F.flash_attention(q, kv=kv, k_shared=k_shared, causal=True,
                             interpret=True, **kw)


def as_whole(q, kv, k_shared, attention=None, **kw):
    k, v = assembled(kv, k_shared)
    if attention is not None:
        return attention(q, k, v, causal=True)
    return F.flash_attention(q, k, v, causal=True, interpret=True, **kw)


def outputs_and_gradients(fn, q, kv, k_shared, do):
    out, vjp = jax.vjp(fn, q, kv, k_shared)
    return (out,) + vjp(do.astype(out.dtype))


@pytest.mark.parametrize("t", [512, 1024])
def test_keys_in_parts_equal_the_assembled_call_in_bfloat16(monkeypatch, t):
    """4 heads at 128 + 64 | 128, bfloat16, blocks of 256 rows over 512
    and 1024 positions (full, diagonal and dead blocks of the grid all
    occur): the output, ``dq``, ``dkv`` as ``[dk | dv]`` and the shared
    part's gradient summed over the heads, against the call on ``k`` and
    ``v`` assembled from the same inputs.  The kernels form the same
    products from the same operands, so all but the last are the same
    numbers; the sum over heads is in float32 here and XLA's own there."""
    monkeypatch.setattr(F, "_BLOCK_ROWS", 256)
    q, kv, ks, do = parts_inputs(t, jnp.bfloat16)
    got = outputs_and_gradients(in_parts, q, kv, ks, do)
    want = outputs_and_gradients(as_whole, q, kv, ks, do)
    assert [g.shape for g in got] == [do.shape, q.shape, kv.shape, ks.shape]
    assert all(g.dtype == jnp.bfloat16 for g in got)
    for g, w in zip(got[:3], want[:3]):
        np.testing.assert_array_equal(np.asarray(g.astype(jnp.float32)),
                                      np.asarray(w.astype(jnp.float32)))
    # one rounding to bfloat16 of a sum of 4 bfloat16 shares
    g, w = (np.asarray(a.astype(jnp.float32)) for a in (got[3], want[3]))
    np.testing.assert_allclose(g, w, rtol=2 ** -7, atol=2 ** -7)


@pytest.mark.parametrize("lse", [False, True], ids=["out", "out-and-lse"])
@pytest.mark.parametrize("t", [512, 1024])
def test_keys_in_parts_match_the_reference_in_float32(monkeypatch, t, lse):
    """The same four against ``sdpa_reference`` on assembled keys, float32
    on both sides, at this file's tolerance; with ``return_lse`` the
    log-sum-exp too, a cotangent on it riding the backward's ``D``."""
    monkeypatch.setattr(F, "_BLOCK_ROWS", 256)
    q, kv, ks, _ = parts_inputs(t, jnp.float32)
    reference = functools.partial(as_whole, attention=sdpa_reference)

    def flash(q, kv, ks):
        if not lse:
            return in_parts(q, kv, ks, block_q=128, block_k=128)
        out, rows = in_parts(q, kv, ks, block_q=128, block_k=128,
                             return_lse=True)
        return out + jnp.cos(rows)[..., None]

    def plain(q, kv, ks):
        out = reference(q, kv, ks)
        if not lse:
            return out
        k, _ = assembled(kv, ks)
        s = jnp.einsum("bhqd,bhkd->bhqk", q, k) * 192 ** -0.5
        s = jnp.where(jnp.tril(jnp.ones((t, t), bool)), s, -jnp.inf)
        return out + jnp.cos(jax.nn.logsumexp(s, axis=-1))[..., None]
    np.testing.assert_allclose(flash(q, kv, ks), plain(q, kv, ks), atol=2e-5)
    got = jax.grad(lambda *a: jnp.sum(jnp.sin(flash(*a))),
                   argnums=(0, 1, 2))(q, kv, ks)
    want = jax.grad(lambda *a: jnp.sum(jnp.sin(plain(*a))),
                    argnums=(0, 1, 2))(q, kv, ks)
    for g, w in zip(got, want):
        assert g.shape == w.shape
        # the first keys' gradients, sums over every query, reach 10
        np.testing.assert_allclose(g, w, atol=2e-5, rtol=1e-5)


def test_dead_keys_in_parts_are_neither_masked_nor_read(monkeypatch):
    """As ``test_causal_skip_is_real_and_shared_by_the_three_kernels`` for
    whole keys: with every row of ``kv`` and of the shared part above a
    block of queries set to NaN the output and ``dq`` of that block, and
    with every query and ``dO`` row before a block of keys poisoned that
    block's ``[dk | dv]`` and its shares of the shared part's gradient,
    are finite and equal to the unpoisoned run's."""
    monkeypatch.setattr(F, "_BLOCK_ROWS", 256)
    t, rows = 1024, 256
    q, kv, ks, do = (a[0] for a in parts_inputs(t, jnp.bfloat16, heads=2))
    scale, bq, bk = 192 ** -0.5, *F.flash_blocks(t, t, 192, d_v=128,
                                                 d_shared=64)

    def forward(q, kv, ks):
        return F._launch_fwd(q, kv, kv, scale, True, bq, bk, True,
                             shared=(ks, 2))

    def backward(q, kv, ks, do):
        return F._launch_bwd(q, kv, kv, do, lse, dd, scale, True, bq, bk,
                             True, shared=(ks, 2))
    out, lse = forward(q, kv, ks)
    dd = jnp.sum(do.astype(jnp.float32) * out.astype(jnp.float32),
                 axis=-1)[:, None, :]
    dq, dkv, dks = backward(q, kv, ks, do)
    assert dkv.shape == kv.shape and dks.shape == (2, t, 64)

    def same(got, want, block):
        at = slice(block * rows, (block + 1) * rows)
        got, want = (np.asarray(a[:, at].astype(jnp.float32))
                     for a in (got, want))
        assert np.isfinite(got).all()
        np.testing.assert_array_equal(got, want)
    for block in (0, 2):
        dead = (block + 1) * rows           # keys from here on: never seen
        kvp, ksp = (a.at[:, dead:].set(jnp.nan) for a in (kv, ks))
        same(forward(q, kvp, ksp)[0], out, block)
        same(backward(q, kvp, ksp, do)[0], dq, block)
    for block in (1, 3):
        live = block * rows                 # queries before here: never seen
        qp, dop = (a.at[:, :live].set(jnp.nan) for a in (q, do))
        _, dkv_p, dks_p = backward(qp, kv, ks, dop)
        same(dkv_p, dkv, block)
        same(dks_p, dks, block)


@pytest.mark.parametrize("d,d_v,d_shared,why", [
    (192, 64, 64, "as wide as v"),             # d_k 128 != d_v 64
    (128, 64, 64, "multiple of 128"),          # d_k == d_v == 64
    (256, 192, 64, "multiple of 128"),         # d_k == d_v == 192
    (128, 128, 0, "in parts")])                # nothing shared
def test_flash_blocks_refuses_parts_it_cannot_read_as_column_blocks(
        d, d_v, d_shared, why):
    with pytest.raises(ValueError, match=why):
        F.flash_blocks(512, 512, d, d_v=d_v, d_shared=d_shared)
    # the same widths with whole keys are taken
    assert F.flash_blocks(512, 512, d, d_v=d_v)


def test_flash_blocks_takes_the_latent_widths_in_parts():
    assert F.flash_blocks(8192, 8192, 192, d_v=128, d_shared=64) == \
        F.flash_blocks(8192, 8192, 192, d_v=128)


@pytest.mark.parametrize("given,why", [
    (dict(window=100), "without a window"),
    (dict(k=True), "k and v, or kv and k_shared"),
    (dict(k_shared=None), "k and v, or kv and k_shared")])
def test_flash_attention_refuses_a_window_or_a_mixed_form_with_parts(given,
                                                                    why):
    q, kv, ks, _ = parts_inputs(256, jnp.float32)
    kw = dict(kv=kv, k_shared=ks, causal=True, interpret=True)
    if given.pop("k", None):
        kw.update(k=kv[..., :192], v=kv[..., :128])
    kw.update(given)
    with pytest.raises(ValueError, match=why):
        F.flash_attention(q, **kw)


def test_the_counter_tells_the_two_forms_apart():
    from deeplearning4j_tpu.observability.registry import default_registry

    def count():
        c = default_registry().get("flash_calls_traced_total")
        return {} if c is None else {labels[0]: child.value
                                     for labels, child in c.samples()}
    before = count()
    q, kv, ks, _ = parts_inputs(256, jnp.float32)
    in_parts(q, kv, ks)
    as_whole(q, kv, ks)
    as_whole(q, kv, ks)
    after = count()
    assert after["parts"] - before.get("parts", 0) == 1
    assert after["whole"] - before.get("whole", 0) == 2
