"""The forward kernel scores a full block as independent groups of query
rows, a ``_TILE`` of rows each (``ops/flash_attention._query_groups``):
the same keys in the same order into the same rows of the running
softmax, so ``o`` and ``lse`` are a single-group launch's bit for bit.  In
the Pallas interpreter, with the grid's block cut to 512 rows, so that a
call of 1024 positions has full blocks of two groups; causal, windowed
and keys in parts, at a length whose blocks hold two groups and at one
whose block holds one."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu.observability.registry import default_registry
from deeplearning4j_tpu.ops import flash_attention as F


def _inputs(t, d, d_v, parts, heads=2):
    keys = jax.random.split(jax.random.PRNGKey(t + d), 3)
    q = jax.random.normal(keys[0], (1, heads, t, d), jnp.bfloat16)
    if parts:
        d_s = d - d_v
        return q, dict(
            kv=jax.random.normal(keys[1], (1, heads, t, 2 * d_v),
                                 jnp.bfloat16),
            k_shared=jax.random.normal(keys[2], (1, 1, t, d_s),
                                       jnp.bfloat16))
    return q, dict(k=jax.random.normal(keys[1], (1, heads, t, d),
                                       jnp.bfloat16),
                   v=jax.random.normal(keys[2], (1, heads, t, d_v),
                                       jnp.bfloat16))


def _groups_traced():
    c = default_registry().get("flash_fwd_groups_traced_total")
    return {} if c is None else {labels[0]: child.value
                                 for labels, child in c.samples()}


# (t, d, d_v, keys in parts, window, groups a full block): 1024 and 2048
# positions hold full blocks of 512 rows, two groups each (a window of
# 1024 over 2048 covers the block behind the held one whole); 256 is one
# block of one group
CASES = [(1024, 64, 64, False, None, 2),
         (256, 64, 64, False, None, 1),
         (2048, 64, 64, False, 1024, 2),
         (256, 64, 64, False, 128, 1),
         (1024, 192, 128, True, None, 2),
         (256, 192, 128, True, None, 1)]


@pytest.mark.parametrize("t,d,d_v,parts,window,groups", CASES)
def test_grouped_forward_equals_one_group_bit_for_bit(
        monkeypatch, t, d, d_v, parts, window, groups):
    monkeypatch.setattr(F, "_BLOCK_ROWS", 512)
    q, keys = _inputs(t, d, d_v, parts)

    def run():
        return F.flash_attention(q, **keys, causal=True, window=window,
                                 return_lse=True, interpret=True)
    before = _groups_traced()
    with monkeypatch.context() as one_group:
        one_group.setattr(F, "_query_groups", lambda q_rows: 1)
        o1, lse1 = run()
    o, lse = run()
    after = _groups_traced()
    np.testing.assert_array_equal(np.asarray(o, np.float32),
                                  np.asarray(o1, np.float32))
    np.testing.assert_array_equal(np.asarray(lse), np.asarray(lse1))
    # one call traced with one group, one with this shape's groups
    moved = {g: n - before.get(g, 0) for g, n in after.items()
             if n != before.get(g, 0)}
    assert moved == ({"1": 2} if groups == 1 else {"1": 1, str(groups): 1})


@pytest.mark.parametrize("q_rows,groups", [(1024, 4), (512, 2), (768, 3),
                                           (256, 1), (384, 1), (128, 1)])
def test_groups_follow_the_rows_a_block_holds(q_rows, groups):
    assert F._query_groups(q_rows) == groups


def test_a_grouped_full_block_covers_each_row_once():
    """The steps of a full block: ``groups`` steps over all its keys whose
    rows tile the block; a band block's cut steps are not split."""
    calls = []
    F._run_block(lambda *s: calls.append(s), False, 0, 0, 1024, 1024,
                 256, 256, groups=2)
    assert calls == [(0, 512, 0, 1024, 0), (512, 512, 0, 1024, 0)]
    assert F._band_steps(1024, 256, 256, 1024, 2048, 2) == [
        (0, 512, 0, 1024, 0, 0), (512, 512, 0, 1024, 0, 0)]
    assert F._band_steps(1024, 256, 256, 0, 2048, 2) == F._band_steps(
        1024, 256, 256, 0, 2048)
