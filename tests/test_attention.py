"""Attention stack tests: reference SDPA semantics, pallas flash kernel
numerics vs fallback (the reference's cuDNN-vs-builtin validation pattern,
``ValidateCudnnLSTM``), ring/Ulysses sequence parallelism on an 8-device CPU
mesh, and end-to-end transformer training through MultiLayerNetwork."""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax import shard_map

from deeplearning4j_tpu import (InputType, MultiLayerNetwork,
                                NeuralNetConfiguration)
from deeplearning4j_tpu.nn.layers import (LayerNormLayer, MultiHeadAttention,
                                          OutputLayer, PositionalEncodingLayer,
                                          RnnOutputLayer, TransformerBlock)
from deeplearning4j_tpu.ops.attention import sdpa_reference
from deeplearning4j_tpu.ops.flash_attention import flash_attention
from deeplearning4j_tpu.parallel.sequence import (ring_self_attention,
                                                  ulysses_attention)
from deeplearning4j_tpu.utils.gradient_check import check_gradients


def _qkv(b=2, h=4, t=16, d=8, seed=0, dtype=np.float32):
    rng = np.random.default_rng(seed)
    return tuple(jnp.asarray(rng.standard_normal((b, h, t, d)), dtype)
                 for _ in range(3))


# ------------------------------------------------------------- reference SDPA

def test_sdpa_matches_numpy():
    q, k, v = _qkv(t=5, d=3)
    out = sdpa_reference(q, k, v)
    qn, kn, vn = map(np.asarray, (q, k, v))
    s = np.einsum("bhqd,bhkd->bhqk", qn, kn) / np.sqrt(3)
    p = np.exp(s - s.max(-1, keepdims=True))
    p /= p.sum(-1, keepdims=True)
    expect = np.einsum("bhqk,bhkd->bhqd", p, vn)
    np.testing.assert_allclose(np.asarray(out), expect, atol=1e-5)


def test_sdpa_causal_ignores_future():
    q, k, v = _qkv(t=6)
    out1 = sdpa_reference(q, k, v, causal=True)
    v2 = v.at[:, :, 3:, :].set(99.0)  # perturb future values
    k2 = k.at[:, :, 3:, :].set(-7.0)
    out2 = sdpa_reference(q, k2, v2, causal=True)
    np.testing.assert_allclose(np.asarray(out1[:, :, :3]),
                               np.asarray(out2[:, :, :3]), atol=1e-5)


def test_sdpa_key_padding_mask():
    q, k, v = _qkv(t=8)
    mask = jnp.ones((2, 8)).at[:, 6:].set(0)
    out = sdpa_reference(q, k, v, mask=mask)
    expect = sdpa_reference(q[:, :, :, :], k[:, :, :6], v[:, :, :6])
    np.testing.assert_allclose(np.asarray(out), np.asarray(expect), atol=1e-5)


# ------------------------------------------------------- flash kernel parity

@pytest.mark.parametrize("causal", [False, True])
def test_flash_attention_matches_reference(causal):
    q, k, v = _qkv(b=2, h=2, t=256, d=64, seed=3)
    ref = sdpa_reference(q, k, v, causal=causal)
    out = flash_attention(q, k, v, causal=causal, block_q=64, block_k=64,
                          interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("causal", [False, True])
def test_flash_attention_gradients_match_reference(causal):
    """The FlashAttention-2 style backward (saved logsumexp, per-block
    softmax replay, separate dq and dk/dv kernels) must produce the
    reference VJP — the contract that makes attn_impl='flash' trainable."""
    import jax
    q, k, v = _qkv(b=2, h=2, t=256, d=64, seed=5)
    do = jnp.asarray(
        np.random.default_rng(1).standard_normal(q.shape), jnp.float32)

    def f(q, k, v):
        return jnp.sum(flash_attention(q, k, v, causal=causal,
                                       block_q=64, block_k=64,
                                       interpret=True) * do)

    def r(q, k, v):
        return jnp.sum(sdpa_reference(q, k, v, causal=causal) * do)

    gf = jax.grad(f, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(r, argnums=(0, 1, 2))(q, k, v)
    for name, a, b in zip("qkv", gf, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=3e-4,
                                   err_msg=f"d{name}")


def _rel_err(got, want):
    want = np.asarray(want, np.float32)
    return float(np.max(np.abs(np.asarray(got, np.float32) - want))
                 / np.max(np.abs(want)))


# tilings the 64 x 64 cases above do not reach: a q tile spanning several
# key tiles (one block sees skipped, unmasked and diagonal tiles), key
# tiles wider than the q tile, t_q != t_k, and blocks smaller than the
# sequence (the grid's own skip, and the index maps that keep dead blocks
# from being copied)
_BF16_TILINGS = [
    (256, 256, 128, 64, None, "q-spans-k"),
    (256, 256, 64, 128, None, "k-wider"),
    (256, 256, 256, 64, None, "one-q-tile"),
    (128, 256, 64, 128, None, "tq-ne-tk"),
    (256, 256, 64, 64, 64, "tile-a-block"),
    (512, 512, 128, 64, 256, "four-blocks"),
]


@pytest.mark.parametrize(
    "causal,t_q,t_k,block_q,block_k,block_rows",
    [pytest.param(causal, *case[:5],
                  id=f"{case[5]}-{'causal' if causal else 'full'}")
     for case in _BF16_TILINGS for causal in (False, True)
     if not causal or case[0] == case[1]])   # no caller: causal, t_q != t_k
def test_flash_attention_bf16_matches_float32_reference(
        causal, t_q, t_k, block_q, block_k, block_rows, monkeypatch):
    """bf16 operands go to the MXU as they arrive, ``p`` and ``dS`` are
    rounded to bf16 for their second products: forward and all three
    gradients stay within bf16 rounding of ``sdpa_reference`` evaluated in
    float32 on the same bf16 inputs."""
    from deeplearning4j_tpu.ops import flash_attention as F
    if block_rows:
        monkeypatch.setattr(F, "_BLOCK_ROWS", block_rows)
    rng = np.random.default_rng(11)
    q = jnp.asarray(rng.standard_normal((1, 2, t_q, 64)), jnp.bfloat16)
    k, v = (jnp.asarray(rng.standard_normal((1, 2, t_k, 64)), jnp.bfloat16)
            for _ in range(2))
    do = jnp.asarray(rng.standard_normal(q.shape), jnp.float32)

    def f(q, k, v):
        out = flash_attention(q, k, v, causal=causal, block_q=block_q,
                              block_k=block_k, interpret=True)
        return jnp.sum(out.astype(jnp.float32) * do), out

    def r(q, k, v):
        out = sdpa_reference(*(a.astype(jnp.float32) for a in (q, k, v)),
                             causal=causal)
        return jnp.sum(out * do), out

    gf, out = jax.grad(f, argnums=(0, 1, 2), has_aux=True)(q, k, v)
    gr, ref = jax.grad(r, argnums=(0, 1, 2), has_aux=True)(q, k, v)
    assert out.dtype == jnp.bfloat16
    assert _rel_err(out, ref) < 1e-2
    for name, a, b in zip("qkv", gf, gr):
        assert a.dtype == jnp.bfloat16
        assert _rel_err(a, b) < 1.5e-2, f"d{name}"


def test_causal_skip_is_real_and_shared_by_the_three_kernels():
    """For the tiles the cell's shape runs under ``causal``: fewer tiles
    are live than the square holds (the parent's 1024 x 512 read exactly
    1), and with every key/value row of the tiles wholly above a query
    tile's diagonal set to NaN the forward and dq of that query tile, and
    with every query/dO row of the tiles wholly above a key tile's
    diagonal poisoned the dk/dv of that key tile, are finite and equal to
    the unpoisoned run: dead tiles are neither masked to zero nor read."""
    from deeplearning4j_tpu.ops import flash_attention as F
    t, d = 1024, 64
    bq, bk = F.flash_blocks(t, t, d)
    live = [bool(F._block_live(True, qi, ki, bq, bk))
            for qi in range(t // bq) for ki in range(t // bk)]
    assert sum(live) / len(live) < 1
    assert all(F._block_live(True, qi, ki, 1024, 512)
               for qi in range(1) for ki in range(2))

    rng = np.random.default_rng(2)
    q, k, v, do = (jnp.asarray(rng.standard_normal((1, t, d)), jnp.bfloat16)
                   for _ in range(4))
    scale = d ** -0.5
    out, lse = F._launch_fwd(q, k, v, scale, True, bq, bk, True)
    dd = jnp.sum(do.astype(jnp.float32) * out.astype(jnp.float32),
                 axis=-1)[:, None, :]
    dq, dk, dv = F._launch_bwd(q, k, v, do, lse, dd, scale, True, bq, bk,
                               True)

    def same(got, want, rows):
        got, want = (np.asarray(a[:, rows].astype(jnp.float32))
                     for a in (got, want))
        assert np.isfinite(got).all()
        np.testing.assert_array_equal(got, want)

    def first_dead_key(qi):      # keys from here on: wholly above qi's rows
        dead = [c for c in range(t // bk)
                if not F._block_live(True, qi, c, bq, bk)]
        return min(dead or [t // bk]) * bk

    def first_live_query(ki):    # queries before here: wholly above ki's keys
        return min(r for r in range(t // bq)
                   if F._block_live(True, r, ki, bq, bk)) * bq

    q_tiles = [qi for qi in range(t // bq) if first_dead_key(qi) < t]
    k_tiles = [ki for ki in range(t // bk) if first_live_query(ki) > 0]
    assert q_tiles and k_tiles
    for qi in q_tiles[::2]:
        dead = first_dead_key(qi)
        kp, vp = (a.at[:, dead:].set(jnp.nan) for a in (k, v))
        rows = slice(qi * bq, (qi + 1) * bq)
        same(F._launch_fwd(q, kp, vp, scale, True, bq, bk, True)[0], out,
             rows)
        same(F._launch_bwd(q, kp, vp, do, lse, dd, scale, True, bq, bk,
                           True)[0], dq, rows)
    for ki in k_tiles[::-2]:
        dead = first_live_query(ki)
        qp, dop = (a.at[:, :dead].set(jnp.nan) for a in (q, do))
        lsep, ddp = (a.at[:, :, :dead].set(jnp.nan) for a in (lse, dd))
        got = F._launch_bwd(qp, k, v, dop, lsep, ddp, scale, True, bq, bk,
                            True)
        rows = slice(ki * bk, (ki + 1) * bk)
        same(got[1], dk, rows)
        same(got[2], dv, rows)


@pytest.mark.parametrize("t,d,why", [(7, 5, "head_dim"),
                                     (192, 64, "divisible")])
def test_flash_attention_refuses_shapes_it_cannot_tile(t, d, why):
    """No silent fall to the reference path: the kernel entry point names
    why it cannot run, and only attn_impl='auto' may choose."""
    q, k, v = _qkv(t=t, d=d)
    with pytest.raises(ValueError, match=why):
        flash_attention(q, k, v, block_q=128, block_k=128, interpret=True)


@pytest.mark.parametrize("rows,spec", [(4, P("data")), (3, P())])
def test_flash_attention_under_a_mesh_matches_one_device(rows, spec):
    """Mosaic kernels cannot be partitioned automatically, so under a jit
    over mesh-sharded arguments the kernels run inside a shard_map over
    the mesh read off the operand: batch over ``data`` when it divides
    (no all-gather), replicated when it does not.  Gradients equal the
    one-device run bit for bit (the loss sum reassociates across shards)."""
    mesh = Mesh(np.array(jax.devices()[:4]), ("data",))
    q, k, v = _qkv(b=rows, h=2, t=256, d=64, seed=9)

    def loss(q, k, v):
        return jnp.sum(flash_attention(q, k, v, causal=True, block_q=128,
                                       block_k=128, interpret=True) ** 2)
    f = jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2)))
    want_l, want_g = f(q, k, v)
    placed = [jax.device_put(a, NamedSharding(mesh, spec))
              for a in (q, k, v)]
    got_l, got_g = f(*placed)
    assert float(got_l) == pytest.approx(float(want_l), rel=1e-5)
    for a, b in zip(got_g, want_g):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    if spec == P("data"):
        assert "all-gather" not in f.lower(*placed).compile().as_text()


def test_explicit_flash_impl_raises_off_tpu():
    """attn_impl='flash' on a backend that cannot run the kernel raises
    (the Pallas lowering refuses) instead of quietly running SDPA."""
    from deeplearning4j_tpu.nn.layers import attention as L
    q, k, v = _qkv(b=1, h=1, t=128, d=64)
    with pytest.raises(Exception, match="(?i)interpret|tpu|backend"):
        jax.block_until_ready(L._run_attention(
            q, k, v, impl="flash", causal=True, mask=None, seq_axis="seq"))


# ----------------------------------------------------- sequence parallelism

def _mesh_seq(n=8):
    return Mesh(np.array(jax.devices()[:n]), ("seq",))


@pytest.mark.parametrize("causal", [False, True])
def test_ring_attention_exact(causal):
    q, k, v = _qkv(b=2, h=2, t=32, d=4, seed=5)
    mesh = _mesh_seq()
    spec = P(None, None, "seq", None)
    fn = shard_map(
        functools.partial(ring_self_attention, axis_name="seq", causal=causal),
        mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec)
    out = fn(q, k, v)
    ref = sdpa_reference(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=1e-5, rtol=1e-5)


@pytest.mark.parametrize("causal", [False, True])
def test_ulysses_attention_exact(causal):
    q, k, v = _qkv(b=2, h=8, t=32, d=4, seed=6)
    mesh = _mesh_seq()
    spec = P(None, None, "seq", None)
    fn = shard_map(
        functools.partial(ulysses_attention, axis_name="seq", causal=causal),
        mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec)
    out = fn(q, k, v)
    ref = sdpa_reference(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=1e-5, rtol=1e-5)


# ------------------------------------------------------------- layer + model

def _build(layers, itype, seed=7):
    lb = (NeuralNetConfiguration.builder().seed(seed)
          .activation("identity").weight_init("xavier").list())
    for l in layers:
        lb.layer(l)
    return MultiLayerNetwork(lb.set_input_type(itype).build()).init()


def test_mha_gradient_check():
    net = _build([MultiHeadAttention(n_out=4, n_heads=2, attn_impl="reference"),
                  RnnOutputLayer(n_out=2, activation="softmax", loss="mcxent")],
                 InputType.recurrent(3, 5))
    rng = np.random.default_rng(0)
    x = rng.standard_normal((2, 5, 3))
    y = np.eye(2)[rng.integers(0, 2, (2, 5))]
    assert check_gradients(net, x, y)


def test_transformer_block_gradient_check():
    net = _build([TransformerBlock(n_heads=2, ffn_mult=2,
                                   attn_impl="reference"),
                  RnnOutputLayer(n_out=2, activation="softmax", loss="mcxent")],
                 InputType.recurrent(4, 6))
    rng = np.random.default_rng(1)
    x = rng.standard_normal((2, 6, 4))
    y = np.eye(2)[rng.integers(0, 2, (2, 6))]
    assert check_gradients(net, x, y)


def test_layernorm_and_posenc_shapes():
    net = _build([PositionalEncodingLayer(), LayerNormLayer(),
                  MultiHeadAttention(n_out=8, n_heads=4, causal=True,
                                     attn_impl="reference"),
                  RnnOutputLayer(n_out=3, activation="softmax", loss="mcxent")],
                 InputType.recurrent(8, 10))
    x = np.random.default_rng(2).standard_normal((4, 10, 8))
    out = net.output(x)
    assert out.shape == (4, 10, 3)
    np.testing.assert_allclose(np.asarray(out.sum(-1)), 1.0, atol=1e-5)


def test_transformer_lm_trains():
    """Tiny causal LM: loss must drop over a few steps."""
    net = _build([TransformerBlock(n_heads=2, ffn_mult=2, causal=True,
                                   attn_impl="reference"),
                  RnnOutputLayer(n_out=5, activation="softmax", loss="mcxent")],
                 InputType.recurrent(5, 8))
    rng = np.random.default_rng(3)
    ids = rng.integers(0, 5, (8, 9))
    x = np.eye(5)[ids[:, :-1]]
    y = np.eye(5)[ids[:, 1:]]
    first = float(net.score((x, y)))
    for _ in range(30):
        net.fit(x, y)
    assert float(net.score((x, y))) < first


def test_transformer_incremental_decode_matches_full_forward():
    """KV-cached rnn_time_step == full forward at each position (the
    attention-era stateful-inference contract; reference rnnTimeStep)."""
    from deeplearning4j_tpu.models import TransformerLM
    net = TransformerLM(vocab_size=13, seq_len=9, embed=16, n_layers=2,
                        n_heads=2).init()
    rng = np.random.default_rng(0)
    x = rng.integers(0, 13, (3, 9))
    full = np.asarray(net.output(x))                      # [3, 9, 13]
    net.rnn_clear_previous_state()
    steps = []
    for t in range(9):
        y = np.asarray(net.rnn_time_step(x[:, t:t + 1]))  # [3, 1, 13]
        steps.append(y[:, 0])
    inc = np.stack(steps, axis=1)
    np.testing.assert_allclose(inc, full, rtol=2e-3, atol=2e-4)
    # chunked streaming matches too (prefix then remainder)
    net.rnn_clear_previous_state()
    a = np.asarray(net.rnn_time_step(x[:, :4]))
    b = np.asarray(net.rnn_time_step(x[:, 4:]))
    np.testing.assert_allclose(np.concatenate([a, b], 1), full, rtol=2e-3,
                               atol=2e-4)


def test_cached_attention_honors_mask_and_causal_flag():
    """Carry-path parity with apply(): padding mask respected, causal flag
    honored (non-causal MHA must not become causal in the cache path)."""
    from deeplearning4j_tpu.nn.layers.attention import MultiHeadAttention
    rng = np.random.default_rng(0)
    for causal in (False, True):
        lc = MultiHeadAttention(n_in=8, n_out=8, n_heads=2, causal=causal,
                                attn_impl="reference", activation="identity",
                                max_cache_len=16)
        v = lc.init(jax.random.PRNGKey(0), None)
        x = jnp.asarray(rng.standard_normal((3, 6, 8)), jnp.float32)
        mask = jnp.asarray(np.array([[1, 1, 1, 1, 0, 0],
                                     [1, 1, 1, 1, 1, 1],
                                     [1, 1, 0, 0, 0, 0]], np.float32))
        full, _ = lc.apply(v, x, mask=mask)
        carry = lc.init_carry(3, jnp.float32)
        cached, carry = lc.apply_with_carry(v, x, carry, mask=mask)
        # parity at VALID positions; the carry path additionally zeroes
        # padded query steps (the recurrent _mask_step convention)
        m = np.asarray(mask)[:, :, None]
        np.testing.assert_allclose(np.asarray(cached) * m,
                                   np.asarray(full) * m,
                                   rtol=2e-3, atol=2e-4,
                                   err_msg=f"causal={causal}")
        np.testing.assert_allclose(np.asarray(cached) * (1 - m), 0.0)
        assert int(carry["pos"]) == 6


def test_auto_dispatch_follows_measured_crossover(monkeypatch):
    """attn_impl='auto' selects by the crossover (the CudnnAlgoMode role,
    ConvolutionLayer.java:349) — reference SDPA below flash_min_seq,
    flash at/above, reference always when masked, when the kernel cannot
    tile the shapes, and off a TPU.  The threshold is overridable per
    layer and by env."""
    import deeplearning4j_tpu.ops.attention as A
    import deeplearning4j_tpu.ops.flash_attention as F
    from deeplearning4j_tpu.nn.layers import attention as L

    calls = []
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(F, "flash_attention",
                        lambda q, k, v, **kw: calls.append("flash") or q)
    monkeypatch.setattr(A, "sdpa_reference",
                        lambda q, k, v, **kw: calls.append("ref") or q)
    short = jnp.zeros((1, 2, 64, 64), jnp.float32)   # below the min tile
    long = jnp.zeros((1, 2, max(L.DEFAULT_FLASH_MIN_SEQ, 128), 64),
                     jnp.float32)
    run = lambda q, **kw: L._run_attention(q, q, q, impl="auto", causal=True,
                                           seq_axis="seq", **kw)
    run(short, mask=None)                      # below crossover -> reference
    run(long, mask=None)                       # at crossover -> flash
    run(short, mask=None, flash_min_seq=32)    # per-layer override -> flash
    run(long, mask=None, flash_min_seq=1 << 20)  # raised override -> ref
    run(long, mask=jnp.ones((1, long.shape[2])))  # masked -> always ref
    run(jnp.zeros((1, 2, 192, 64)), mask=None)  # 192 % 128: cannot tile
    run(jnp.zeros((1, 2, 256, 32)), mask=None)  # head_dim 32: cannot tile
    monkeypatch.setattr(jax, "default_backend", lambda: "cpu")
    run(long, mask=None)                       # not a TPU -> reference
    assert calls == ["ref", "flash", "flash", "ref", "ref", "ref", "ref",
                     "ref"]
    assert L.auto_attention_impl(1024, 1024, 64, masked=False) == "reference"


# ------------------------------------------- carry-primitive parity (ISSUE 11)
# The generation subsystem's correctness rests on apply_with_carry being
# EXACTLY the causal forward evaluated incrementally.  These pin the
# contract per layer, token by token, including the positional-encoding
# offset off-by-one class and bf16 under PrecisionPolicy.

def _token_by_token(lc, v, x, mask=None):
    """Run x through apply_with_carry one token at a time; concat outputs."""
    carry = lc.init_carry(x.shape[0], jnp.float32)
    steps = []
    for i in range(x.shape[1]):
        m = None if mask is None else mask[:, i:i + 1]
        y, carry = lc.apply_with_carry(v, x[:, i:i + 1], carry, mask=m)
        steps.append(np.asarray(y))
    return np.concatenate(steps, axis=1), carry


def test_mha_carry_token_by_token_matches_full_causal():
    lc = MultiHeadAttention(n_in=8, n_out=8, n_heads=2, causal=True,
                            attn_impl="reference", activation="identity",
                            max_cache_len=16)
    v = lc.init(jax.random.PRNGKey(1), None)
    x = jnp.asarray(np.random.default_rng(1).standard_normal((2, 7, 8)),
                    jnp.float32)
    full, _ = lc.apply(v, x)
    inc, carry = _token_by_token(lc, v, x)
    np.testing.assert_allclose(inc, np.asarray(full), rtol=2e-3, atol=2e-4)
    assert int(carry["pos"]) == 7


def test_transformer_block_carry_token_by_token_matches_full():
    lc = TransformerBlock(n_in=8, n_heads=2, ffn_mult=2, causal=True,
                          attn_impl="reference")
    v = lc.init(jax.random.PRNGKey(2), None)
    x = jnp.asarray(np.random.default_rng(2).standard_normal((3, 6, 8)),
                    jnp.float32)
    full, _ = lc.apply(v, x)
    inc, carry = _token_by_token(lc, v, x)
    np.testing.assert_allclose(inc, np.asarray(full), rtol=2e-3, atol=2e-4)
    assert int(carry["pos"]) == 6


def test_positional_encoding_carry_offset_off_by_one_class():
    """The classic generation bug: after consuming T tokens, the NEXT
    token must read sinusoid table row T — not T-1 (repeats a position)
    nor T+1 (skips one).  Pinned directly against the full-sequence
    table, plus chunked-stream parity."""
    lc = PositionalEncodingLayer()
    x = jnp.asarray(np.random.default_rng(3).standard_normal((2, 9, 6)),
                    jnp.float32)
    full, _ = lc.apply({}, x)
    # chunked: 4 tokens then 5 — concatenation must equal the full pass
    carry = lc.init_carry(2)
    y1, carry = lc.apply_with_carry({}, x[:, :4], carry)
    assert int(carry["pos"]) == 4
    y2, carry = lc.apply_with_carry({}, x[:, 4:], carry)
    assert int(carry["pos"]) == 9
    np.testing.assert_allclose(
        np.concatenate([np.asarray(y1), np.asarray(y2)], axis=1),
        np.asarray(full), rtol=1e-6, atol=1e-6)
    # the off-by-one pin: a single token at offset t reads exactly row t
    for t in (0, 4, 8):
        one, _ = lc.apply_with_carry({}, x[:, t:t + 1],
                                     {"pos": jnp.asarray(t, jnp.int32)})
        np.testing.assert_allclose(np.asarray(one),
                                   np.asarray(full[:, t:t + 1]),
                                   rtol=1e-6, atol=1e-6,
                                   err_msg=f"offset {t}")


def test_positional_encoding_vector_offsets_per_row():
    """The slot-batched decode form: a [b] position vector addresses each
    row's own table offset in one call."""
    lc = PositionalEncodingLayer()
    x = jnp.asarray(np.random.default_rng(4).standard_normal((3, 12, 6)),
                    jnp.float32)
    full, _ = lc.apply({}, x)
    offs = [0, 5, 11]
    xt = jnp.stack([x[i, o][None] for i, o in enumerate(offs)])  # [3,1,6]
    y, carry = lc.apply_with_carry(
        {}, xt, {"pos": jnp.asarray(offs, jnp.int32)})
    for i, o in enumerate(offs):
        np.testing.assert_allclose(np.asarray(y[i]),
                                   np.asarray(full[i, o:o + 1]),
                                   rtol=1e-6, atol=1e-6, err_msg=f"row {i}")
    np.testing.assert_array_equal(np.asarray(carry["pos"]),
                                  np.asarray(offs) + 1)


def test_mha_vector_pos_decode_matches_per_row_scalar_carries():
    """The fixed-shape decode step's core primitive: one single-token
    apply_with_carry over a slot batch whose rows sit at DIFFERENT
    positions must equal each row decoded alone with a scalar-pos carry."""
    lc = MultiHeadAttention(n_in=8, n_out=8, n_heads=2, causal=True,
                            attn_impl="reference", activation="identity",
                            max_cache_len=16)
    v = lc.init(jax.random.PRNGKey(5), None)
    rng = np.random.default_rng(5)
    lens = [3, 6, 1]
    hist = jnp.asarray(rng.standard_normal((3, 6, 8)), jnp.float32)
    xt = jnp.asarray(rng.standard_normal((3, 1, 8)), jnp.float32)
    refs, rows = [], {"k": [], "v": [], "m": []}
    for i, L in enumerate(lens):
        c = lc.init_carry(1, jnp.float32)
        _, c = lc.apply_with_carry(v, hist[i:i + 1, :L], c)   # prefill
        y_ref, c_ref = lc.apply_with_carry(v, xt[i:i + 1], c)  # ref decode
        refs.append(np.asarray(y_ref))
        for key in rows:
            rows[key].append(c[key][0])
        assert int(c_ref["pos"]) == L + 1
    batch_carry = {key: jnp.stack(rows[key]) for key in rows}
    batch_carry["pos"] = jnp.asarray(lens, jnp.int32)
    y_vec, c_vec = lc.apply_with_carry(v, xt, batch_carry)
    for i in range(3):
        np.testing.assert_allclose(np.asarray(y_vec[i:i + 1]), refs[i],
                                   rtol=2e-3, atol=2e-4, err_msg=f"row {i}")
    np.testing.assert_array_equal(np.asarray(c_vec["pos"]),
                                  np.asarray(lens) + 1)


def test_mha_vector_pos_rejects_multi_token_chunks():
    lc = MultiHeadAttention(n_in=8, n_out=8, n_heads=2, causal=True,
                            attn_impl="reference", activation="identity",
                            max_cache_len=16)
    v = lc.init(jax.random.PRNGKey(6), None)
    x = jnp.zeros((2, 3, 8), jnp.float32)
    carry = lc.init_carry(2, jnp.float32)
    carry = dict(carry, pos=jnp.zeros((2,), jnp.int32))
    with pytest.raises(ValueError, match="single-token"):
        lc.apply_with_carry(v, x, carry)


def test_transformer_carry_parity_bf16_precision_policy():
    """Incremental decode parity must survive mixed precision: a bf16
    PrecisionPolicy stack's rnn_time_step token loop matches its full
    forward within bf16 tolerance (both paths cast identically)."""
    lb = (NeuralNetConfiguration.builder().seed(11)
          .weight_init("xavier").precision("bfloat16").list()
          .layer(PositionalEncodingLayer())
          .layer(TransformerBlock(n_heads=2, ffn_mult=2, causal=True,
                                  attn_impl="reference"))
          .layer(RnnOutputLayer(n_out=5, activation="softmax",
                                loss="mcxent")))
    net = MultiLayerNetwork(
        lb.set_input_type(InputType.recurrent(6, 10)).build()).init()
    x = np.random.default_rng(12).standard_normal((2, 10, 6)).astype(
        np.float32)
    full = np.asarray(net.output(x), np.float32)
    net.rnn_clear_previous_state()
    steps = [np.asarray(net.rnn_time_step(x[:, t:t + 1]), np.float32)[:, 0]
             for t in range(10)]
    inc = np.stack(steps, axis=1)
    # bf16 has ~3 decimal digits; the softmax head keeps rows comparable
    np.testing.assert_allclose(inc, full, rtol=0.06, atol=0.02)
    assert (inc.argmax(-1) == full.argmax(-1)).mean() > 0.9
