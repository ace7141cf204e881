"""EvaByte through the normal path against the benchmark's plain reference
(``benchmark/reference/evabyte.py``), at a small size on the CPU: the
decoder block's new choices (RMSNorm with a unit offset, rotary positions,
gated SiLU, no biases), EVA attention (windows in the flash kernels, run by
the Pallas interpreter here, summaries in XLA, one softmax over both), the
multi-byte head, and ``flash_attention(return_lse=True)``.

Tolerances.  Both sides compute in float32 (64-bit mode is on in the tests,
but weights and activations are float32), in different orders: the program
merges two partial softmaxes where the reference takes one over the joint
key set, and the flash kernels sum by tile.  Log-probabilities and the loss
agree to a few float32 roundings of numbers of order 1-10 (5e-5 absolute,
1e-5 relative); a gradient leaf agrees to 1e-4 of its own largest element
(sums of a few thousand float32 products in two orders).  Each removal
below moves the loss by over ten times its tolerance and some gradient
leaf by a hundred times its own.
"""
import functools
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from benchmark import common  # noqa: E402
from deeplearning4j_tpu.models import EvaByteLM  # noqa: E402
from deeplearning4j_tpu.nn.multilayer import _stack_loss  # noqa: E402
from deeplearning4j_tpu.ops import flash_attention as F  # noqa: E402
from deeplearning4j_tpu.ops.attention import (causal_mask,  # noqa: E402
                                              sdpa_reference)

ref = common.load_module("reference", "evabyte")
traffic = common.load_module("traffic", "byte_fit_stream")

# three windows of 128, the last holding 4 chunks of 16 where the others
# hold 8; heads of 64, the least the kernels tile
CFG = {"hidden_size": 128, "num_attention_heads": 2, "head_dim": 64,
       "intermediate_size": 192, "num_hidden_layers": 4, "vocab_size": 24,
       "num_pred_heads": 3, "window_size": 128, "chunk_size": 16,
       "rms_norm_eps": 1e-5, "rope_theta": 100000, "init_std": 0.08,
       "train_seq_len": 320,
       "optimizer": {"kind": "adam", "learning_rate": 3e-4, "beta1": 0.9,
                     "beta2": 0.999, "epsilon": 1e-8}}
ROWS = 2


def build(**changes):
    cfg = {**CFG, **changes}
    return EvaByteLM(
        vocab_size=cfg["vocab_size"], seq_len=cfg["train_seq_len"],
        embed=cfg["hidden_size"], n_layers=cfg["num_hidden_layers"],
        n_heads=cfg["num_attention_heads"], head_dim=cfg["head_dim"],
        ffn_hidden=cfg["intermediate_size"],
        pred_heads=cfg["num_pred_heads"], window=cfg["window_size"],
        chunk=cfg["chunk_size"], rope_theta=float(cfg["rope_theta"]),
        attn_impl=cfg.get("attn_impl", "auto"), cache_mode="remat",
        compute_dtype=cfg.get("compute_dtype")).init()


@pytest.fixture(scope="module")
def seeded():
    """Weights and one batch from a seed, the reference's logits, loss and
    gradient for them."""
    params = ref.init_params(CFG, jax.random.PRNGKey(3))
    # gains off zero, so that the unit offset and the final norm matter
    params["norm_g"] = params["norm_g"] + 0.3
    params["blocks"]["ln1_g"] = params["blocks"]["ln1_g"] - 0.2
    params["blocks"]["ln2_g"] = params["blocks"]["ln2_g"] + 0.1
    x, y, _, mask = traffic.byte_batches(
        5, 1, ROWS, CFG["train_seq_len"], CFG["vocab_size"],
        CFG["num_pred_heads"])[0]
    logp = np.stack([jax.nn.log_softmax(ref.row_logits(
        ref._static(CFG), "float32", True, params, jnp.asarray(row)))
        for row in x])
    loss, grads = ref.loss_and_grads(CFG, params, x, y)
    return {"params": params, "batch": (x, y, mask), "logp": logp,
            "loss": float(loss), "grads": grads}


def program_side(seeded, net, params=None):
    """(log-probabilities, loss, gradient in the reference's layout) of
    the program on the seeded weights."""
    n = CFG["num_hidden_layers"]
    params = traffic.as_program(n, params or seeded["params"])
    params = {**{k: v for k, v in net.params.items() if not v}, **params}
    x, y, mask = seeded["batch"]
    net.params = params
    logp = np.log(np.asarray(net.output(x))).reshape(seeded["logp"].shape)
    loss, grads = jax.value_and_grad(lambda p: _stack_loss(
        net.conf, p, net.state, jnp.asarray(x), jnp.asarray(y), train=True,
        key=None, label_mask=jnp.asarray(mask))[0])(params)
    return logp, float(loss), grads


def assert_leaves_close(program_grads, reference_grads, rel=1e-4):
    n = CFG["num_hidden_layers"]
    want = traffic.as_program(n, reference_grads)
    for layer, leaves in want.items():
        for leaf, w in leaves.items():
            w = np.asarray(w)
            got = np.asarray(program_grads[layer][leaf])
            assert np.max(np.abs(w)) > 0, (layer, leaf)
            np.testing.assert_allclose(
                got, w, rtol=0, atol=rel * np.max(np.abs(w)),
                err_msg=f"{layer}.{leaf}")


@pytest.mark.parametrize("impl", ["reference", "flash"])
def test_program_matches_reference(seeded, impl, monkeypatch):
    """Logits of all heads, the loss and every gradient leaf, ``phi`` and
    ``mu`` among them, with the windows through ``sdpa``'s arithmetic and
    through the flash kernels (interpreted)."""
    if impl == "flash":
        monkeypatch.setattr(F, "flash_attention", functools.partial(
            F.flash_attention, interpret=True))
    net = build(attn_impl=impl)
    logp, loss, grads = program_side(seeded, net)
    np.testing.assert_allclose(logp, seeded["logp"], rtol=1e-5, atol=5e-5)
    assert loss == pytest.approx(seeded["loss"], rel=1e-5)
    assert_leaves_close(grads, seeded["grads"])


def test_the_blocks_are_one_scanned_run_and_train_through_fit(seeded):
    net = build()
    x, y, mask = seeded["batch"]
    before = []
    for _ in range(3):
        net.fit([(x, y, None, mask)])
        before.append(net.get_score())
    assert before[-1] < before[0]
    text = net._get_jitted("train_step").audit_lower(
        net._get_jitted("train_step").audit_specs()[-1]).as_text(
            debug_info=True)
    assert text.count("stablehlo.while") >= 1
    for scope in ("eva_pool", "eva_window", "eva_summary", "eva_merge"):
        assert f"TransformerBlock/{scope}" in text, scope


def _dots(jaxpr):
    """Every ``dot_general`` of a jaxpr and of the jaxprs inside it."""
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "dot_general":
            yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _dots(sub)


def test_the_residual_stream_is_float32_under_bfloat16(seeded):
    """Under bfloat16 compute the blocks carry the residual stream in
    float32 and the final norm reads it so (the model's ``fp32_skip_add``),
    while every product with a weight still takes bfloat16 operands."""
    from deeplearning4j_tpu.nn import precision
    from deeplearning4j_tpu.nn._common import _cast_floats
    net = build(attn_impl="reference", compute_dtype="bfloat16")
    pol = precision.resolve(net.conf.defaults)
    assert pol.layer_dtype(net.conf.layers[-2]) == "float32"   # final norm
    x, y, mask = seeded["batch"]
    params = {k: (v if k == "layer_5" else _cast_floats(v, "bfloat16"))
              for k, v in net.params.items()}
    jaxpr = jax.make_jaxpr(lambda p: _stack_loss(
        net.conf, p, net.state, jnp.asarray(x), jnp.asarray(y), train=True,
        key=None, label_mask=jnp.asarray(mask), precision=pol)[0])(params)
    scan, = [e for e in jaxpr.jaxpr.eqns if e.primitive.name == "scan"]
    consts, carry = scan.params["num_consts"], scan.params["num_carry"]
    stream = [v.aval for v in scan.invars[consts:consts + carry]
              if v.aval.shape == (ROWS, CFG["train_seq_len"],
                                  CFG["hidden_size"])]
    assert [str(a.dtype) for a in stream] == ["float32"]
    # the seven projections of the block and the head
    dots = [e for e in _dots(jaxpr.jaxpr) if e.invars[1].aval.ndim == 2]
    assert len(dots) == 8
    for eqn in dots:
        assert {str(v.aval.dtype) for v in eqn.invars} == {"bfloat16"}, eqn
    net.fit([(x, y, None, mask)])
    assert np.isfinite(net.get_score())


# what the reference computes with one piece of the mathematics removed,
# against the program with it in: each must break the agreement above
def without_summaries(params, x, y):
    return ref.loss_and_grads(CFG, params, x, y, fault="no_summaries")


def without_mu(params, x, y):
    blocks = dict(params["blocks"], mu=jnp.zeros_like(params["blocks"]["mu"]))
    return ref.loss_and_grads(CFG, dict(params, blocks=blocks), x, y)


def without_unit_offset(params, x, y):
    shifted = dict(params, norm_g=params["norm_g"] - 1.0, blocks=dict(
        params["blocks"], ln1_g=params["blocks"]["ln1_g"] - 1.0,
        ln2_g=params["blocks"]["ln2_g"] - 1.0))
    return ref.loss_and_grads(CFG, shifted, x, y)


@pytest.mark.parametrize("removed", [without_summaries, without_mu,
                                     without_unit_offset])
def test_a_removed_piece_breaks_the_agreement(seeded, removed):
    x, y, _ = seeded["batch"]
    loss, grads = removed(seeded["params"], x, y)
    assert abs(float(loss) - seeded["loss"]) > 1e-4 * seeded["loss"]
    gap = max(float(jnp.max(jnp.abs(a - b)) / jnp.max(jnp.abs(b)))
              for a, b in zip(jax.tree_util.tree_leaves(grads),
                              jax.tree_util.tree_leaves(seeded["grads"])))
    assert gap > 1e-2


@pytest.mark.parametrize("head", range(CFG["num_pred_heads"]))
def test_every_prediction_head_is_in_the_loss(seeded, head):
    """Head ``n`` at position ``t`` is scored against byte ``t + 1 + n``:
    other targets for that head alone move the program's loss as they move
    the reference's, and its columns of the head's matrix get a
    gradient."""
    net = build()
    x, y, mask = seeded["batch"]
    y2 = y.copy()
    y2[:, :, head] = (y2[:, :, head] + 1) % CFG["vocab_size"]
    want, _ = ref.loss_and_grads(CFG, seeded["params"], x, y2)
    assert abs(float(want) - seeded["loss"]) > 1e-4
    moved = dict(seeded, batch=(x, y2, mask))
    _, loss, grads = program_side(moved, net)
    assert loss == pytest.approx(float(want), rel=1e-5)
    v = CFG["vocab_size"]
    columns = np.asarray(grads[f"layer_{CFG['num_hidden_layers'] + 2}"]["W"])
    assert np.abs(columns[:, head * v:(head + 1) * v]).max() > 0
    assert np.array_equal(y[:, :-1 - head, head], x[:, 1 + head:])
    assert mask[:, -1 - head:, head].sum() == 0 and \
        mask[:, :-1 - head, head].all()


# ------------------------------------------- flash_attention(return_lse=True)
@pytest.mark.parametrize("causal", [False, True])
def test_flash_lse_value_and_gradient(causal):
    rng = np.random.default_rng(11)
    q, k, v = (jnp.asarray(rng.standard_normal((2, 2, 256, 64)), jnp.float32)
               for _ in range(3))
    do = jnp.asarray(rng.standard_normal(q.shape), jnp.float32)
    dlse = jnp.asarray(rng.standard_normal(q.shape[:3]), jnp.float32)

    def plain(q, k, v):
        s = jnp.einsum("bhqd,bhkd->bhqk", q, k) * 64 ** -0.5
        if causal:
            s = jnp.where(causal_mask(256, 256), s, -jnp.inf)
        return (sdpa_reference(q, k, v, causal=causal),
                jax.nn.logsumexp(s, axis=-1))

    def flash(q, k, v):
        return F.flash_attention(q, k, v, causal=causal, block_q=64,
                                 block_k=64, interpret=True, return_lse=True)

    def scalar(fn):
        def f(q, k, v):
            o, lse = fn(q, k, v)
            return jnp.sum(o * do) + jnp.sum(lse * dlse)
        return f
    (o, lse), (o_ref, lse_ref) = flash(q, k, v), plain(q, k, v)
    assert lse.shape == q.shape[:3] and lse.dtype == jnp.float32
    np.testing.assert_allclose(o, o_ref, atol=2e-5, rtol=2e-5)
    np.testing.assert_allclose(lse, lse_ref, atol=2e-5, rtol=2e-5)
    got = jax.grad(scalar(flash), argnums=(0, 1, 2))(q, k, v)
    want = jax.grad(scalar(plain), argnums=(0, 1, 2))(q, k, v)
    for name, a, b in zip("qkv", got, want):
        np.testing.assert_allclose(a, b, atol=3e-4, err_msg=f"d{name}")
    # without the cotangent on lse the backward is the one it always was
    only_o = jax.grad(lambda q: jnp.sum(flash(q, k, v)[0] * do))(q)
    as_ever = jax.grad(lambda q: jnp.sum(F.flash_attention(
        q, k, v, causal=causal, block_q=64, block_k=64,
        interpret=True) * do))(q)
    np.testing.assert_array_equal(only_o, as_ever)


# ------------------------------------ the GPT-2 block, by the new fields
def test_the_gpt2_block_is_unchanged_by_the_new_fields():
    """A ``TransformerBlock`` with every new field spelt out at its default
    gives the output and the gradients of one that names none, to the bit,
    and traces the same program; so does ``ffn_hidden`` at the 4x it
    defaults to."""
    from deeplearning4j_tpu.nn.conf.input_type import InputType
    from deeplearning4j_tpu.nn.layers.attention import TransformerBlock
    plain = TransformerBlock(n_in=32, n_heads=4, causal=True,
                             attn_impl="reference")
    spelt = TransformerBlock(
        n_in=32, n_heads=4, causal=True, attn_impl="reference", norm="layer",
        positions="none", head_dim=8, ffn_hidden=128, gated=False,
        has_bias=True, attention="full", window=0, chunk=0,
        residual_dtype=None)
    itype = InputType.recurrent(32, 16)
    v_plain = plain.init(jax.random.PRNGKey(0), itype)
    v_spelt = spelt.init(jax.random.PRNGKey(0), itype)
    assert sorted(v_plain["params"]) == sorted(v_spelt["params"]) == sorted(
        ["mha_Wq", "mha_Wk", "mha_Wv", "mha_Wo", "mha_bq", "mha_bk",
         "mha_bv", "mha_bo", "W1", "b1", "W2", "b2", "ln1_g", "ln1_b",
         "ln2_g", "ln2_b"])
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 16, 32), jnp.float32)

    def loss(block, p):
        y, _ = block.apply({"params": p, "state": {}}, x, train=True)
        return jnp.sum(jnp.sin(y))
    for (a, ga), (b, gb) in [(
            jax.value_and_grad(functools.partial(loss, plain))(
                v_plain["params"]),
            jax.value_and_grad(functools.partial(loss, spelt))(
                v_spelt["params"]))]:
        assert float(a) == float(b)
        for name in ga:
            np.testing.assert_array_equal(ga[name], gb[name], err_msg=name)
    assert str(jax.make_jaxpr(functools.partial(loss, plain))(
        v_plain["params"])) == str(jax.make_jaxpr(functools.partial(
            loss, spelt))(v_spelt["params"]))


def test_the_cache_path_refuses_what_it_cannot_do():
    from deeplearning4j_tpu.nn.layers.attention import MultiHeadAttention
    mha = MultiHeadAttention(n_in=16, n_out=16, n_heads=2, causal=True,
                             positions="rotary")
    with pytest.raises(NotImplementedError, match="rotary"):
        mha.attend_cached({}, jnp.zeros((1, 1, 16)), mha.init_carry(1))
