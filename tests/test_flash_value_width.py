"""``flash_attention`` with a value head of another width than the query's
and key's (latent attention: q and k 192 wide, v 128): the three kernels in
the Pallas interpreter against ``sdpa_reference``, the forward and the
three backward outputs, full causal and windowed, beside the equal widths
the kernels always took; and what ``flash_blocks`` refuses.  (That equal widths
lower to the kernels they always did is shown on the benchmark's cells,
against the parent commit: PERF.md section 6, PR 35.)"""
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
