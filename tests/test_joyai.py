"""JoyAI-LLM-Flash's architecture through the normal path at a small size on
the CPU (1 dense + 2 routed layers and the multi-token-prediction module at
tiny widths, 16 experts of which 2 held, top-4, heads 12 wide in q and k
and 8 in v, sequence 32): the latent-attention layer and the whole graph
against the plain reference ``benchmark/reference/joyai.py`` on seeded
weights — forward, both losses, every leaf's gradient through
``ComputationGraph``'s own loss; rotary positions on adjacent pairs of part
of a head against a complex multiplication; the sixteen shares of an
expert-parallel layer against the uncut layer; the graph's new vertices;
what the KV-cache path refuses."""
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
from deeplearning4j_tpu.models import JoyAIFlashLM  # noqa: E402
from deeplearning4j_tpu.nn.computation_graph import (ComputationGraph,  # noqa: E402
                                                     _graph_loss)
from deeplearning4j_tpu.nn.conf.computation_graph import (  # noqa: E402
    ComputationGraphConfiguration, TimeConcatVertex, TimeSliceVertex)
from deeplearning4j_tpu.nn.conf.input_type import InputType  # noqa: E402
from deeplearning4j_tpu.nn.layers import attention as A  # noqa: E402
from deeplearning4j_tpu.nn.layers.attention import (LatentAttention,  # noqa: E402
                                                    NextTokenMerge,
                                                    RMSNormLayer,
                                                    TransformerBlock)
from deeplearning4j_tpu.nn.layers.recurrent import RnnOutputLayer  # noqa: E402
from deeplearning4j_tpu.observability.registry import (MetricsRegistry,  # noqa: E402
                                                       default_registry,
                                                       set_default_registry)
from deeplearning4j_tpu.ops import flash_attention as F  # noqa: E402

ref = common.load_module("reference", "joyai")
traffic = common.load_module("traffic", "mtp_lm_fit_stream")

SMALL = {
    "family": "joyai", "hidden_size": 32, "num_attention_heads": 4,
    "q_lora_rank": 24, "kv_lora_rank": 16, "qk_nope_head_dim": 8,
    "qk_rope_head_dim": 4, "v_head_dim": 8, "intermediate_size": 48,
    "moe_intermediate_size": 16, "n_routed_experts": 2,
    "experts_held": [0, 2], "published": {"n_routed_experts": 16},
    "num_experts_per_tok": 4, "n_shared_experts": 1, "norm_topk_prob": True,
    "routed_scaling_factor": 2.5, "rope_theta": 32000000,
    "rms_norm_eps": 1e-6, "vocab_size": 48, "num_hidden_layers": 3,
    "first_k_dense_replace": 1, "num_nextn_predict_layers": 1,
    "mtp_loss_weight": 0.3, "init_std": 0.2, "train_seq_len": 32,
    "precision": "float32", "cache_mode": "none",
    "optimizer": {"kind": "adam", "learning_rate": 3e-4, "beta1": 0.9,
                  "beta2": 0.999, "epsilon": 1e-8}}
T = SMALL["train_seq_len"]


@pytest.fixture(scope="module")
def seeded():
    """The program's graph on the reference's seeded weights, and rows of
    ``T + 1`` token ids."""
    net = traffic.build(SMALL)
    theirs = ref.init_params(SMALL, jax.random.PRNGKey(3))
    net.params = {**{k: v for k, v in net.params.items() if not v},
                  **traffic.as_program(theirs)}
    ids = np.random.default_rng(0).integers(0, 48, (2, T + 1)).astype(
        np.int32)
    return net, theirs, ids


# ------------------------------------------------------------- the rotation
@pytest.mark.parametrize("d", [4, 64])
def test_rotary_pairs_is_a_complex_multiplication(d):
    """Features ``(2i, 2i + 1)`` as one complex number, times ``exp(i pos
    theta^(-2i/d))``, in numpy's complex128."""
    x = np.random.default_rng(d).normal(size=(2, 3, 16, d))
    got = np.asarray(A._rotary_pairs(jnp.asarray(x, jnp.float32), 32e6))
    angle = np.arange(16)[:, None] * (32e6 ** (-np.arange(0, d, 2) / d))
    z = (x[..., 0::2] + 1j * x[..., 1::2]) * np.exp(1j * angle)
    want = np.stack([z.real, z.imag], axis=-1).reshape(x.shape)
    np.testing.assert_allclose(got, want, atol=2e-5)
    # position 0 is left as it is; the reference's form agrees
    np.testing.assert_array_equal(got[:, :, 0], x[:, :, 0].astype(np.float32))
    np.testing.assert_allclose(ref.rotary_pairs(jnp.asarray(x, jnp.float32),
                                                32e6), want, atol=2e-5)


def test_rotary_pairs_is_not_rotate_half():
    x = jnp.asarray(np.random.default_rng(1).normal(size=(1, 1, 8, 8)),
                    jnp.float32)
    assert float(jnp.max(jnp.abs(A._rotary_pairs(x, 1e4)
                                 - A._rotary(x, 1e4)))) > 1e-2


def test_positions_turn_part_of_a_head_only(seeded):
    """q's and k's first ``nope`` features carry no positions: a layer whose
    rotary part is zeroed gives the same output at every shift of the
    sequence's content."""
    layer = LatentAttention(n_in=32, n_out=32, n_heads=4, head_dim=8,
                            rope_dim=4, v_dim=8, q_rank=24, kv_rank=16,
                            attn_impl="reference", weight_init="xavier")
    p = layer.init(jax.random.PRNGKey(0), InputType.recurrent(32, 16))[
        "params"]
    x = jax.random.normal(jax.random.PRNGKey(1), (1, 16, 32))
    q, kv, k_r = layer.project(p, x)
    assert kv.shape == (1, 4, 16, 16) and k_r.shape == (1, 1, 16, 4)
    k, v = layer.whole_keys(kv, k_r)
    assert q.shape == k.shape == (1, 4, 16, 12) and v.shape == (1, 4, 16, 8)
    # the one rotary key is every head's
    np.testing.assert_array_equal(k[0, 0, :, 8:], k[0, 3, :, 8:])
    # at position 0 nothing is turned; later positions' nope part is what
    # the projections gave
    q_flat = (A._rms_norm(x @ p["Wqa"], p["qa_norm"], 1e-6) @ p["Wqb"]
              ).reshape(1, 16, 4, 12).transpose(0, 2, 1, 3)
    np.testing.assert_allclose(q[..., :8], q_flat[..., :8], atol=1e-6)
    np.testing.assert_allclose(q[:, :, 0], q_flat[:, :, 0], atol=1e-6)
    assert float(jnp.max(jnp.abs(q[:, :, 5:, 8:] - q_flat[:, :, 5:, 8:]))) \
        > 1e-3


# ------------------------------------------- the layer against the reference
def _layer_and_theirs(impl):
    layer = LatentAttention(n_in=32, n_out=32, n_heads=4, head_dim=64,
                            rope_dim=64, v_dim=64, q_rank=24, kv_rank=16,
                            rope_theta=32e6, attn_impl=impl)
    cfg = dict(SMALL, qk_nope_head_dim=64, qk_rope_head_dim=64,
               v_head_dim=64)
    shapes = ref.layer_shapes(cfg, False)
    keys = jax.random.split(jax.random.PRNGKey(5), len(shapes))
    theirs = {name: (1.0 + 0.1 * jax.random.normal(k, s)
                     if name in ref.NORMS
                     else 0.2 * jax.random.normal(k, s))
              for k, (name, s) in zip(keys, sorted(shapes.items()))}
    mine = {k[4:]: v for k, v in traffic._block(theirs).items()
            if k.startswith("mha_")}
    return layer, cfg, mine, theirs


@pytest.mark.parametrize("impl", ["reference", "flash"])
def test_latent_attention_matches_the_reference(monkeypatch, impl):
    """Forward and every leaf's gradient of the layer alone, heads 128 wide
    in q and k and 64 in v over 128 positions, through ``sdpa_reference``
    and through the flash kernels in the Pallas interpreter; float32 on
    both sides: 5e-5 of each array's largest entry."""
    if impl == "flash":
        monkeypatch.setattr(F, "flash_attention", functools.partial(
            F.flash_attention, interpret=True))
    layer, cfg, mine, theirs = _layer_and_theirs(impl)
    x = jax.random.normal(jax.random.PRNGKey(2), (1, 128, 32))

    def program(p, x):
        return jnp.sum(jnp.sin(layer.attend(p, x)))

    def reference(p, x):
        return jnp.sum(jnp.sin(ref.latent_attention(cfg, p, x[0])))
    np.testing.assert_allclose(layer.attend(mine, x)[0],
                               ref.latent_attention(cfg, theirs, x[0]),
                               atol=5e-5)
    got_p, got_x = jax.grad(program, argnums=(0, 1))(mine, x)
    want_p, want_x = jax.grad(reference, argnums=(0, 1))(theirs, x)
    np.testing.assert_allclose(got_x, want_x, atol=5e-5 * float(
        jnp.max(jnp.abs(want_x))))
    assert set(got_p) == set(mine)
    for name, g in got_p.items():
        want = want_p[name]
        assert float(jnp.max(jnp.abs(g - want))) <= 5e-5 * float(
            jnp.max(jnp.abs(want))), name


def test_the_kernels_get_unpadded_widths(monkeypatch):
    """q and k reach ``flash_attention`` 128 wide and v 64 wide here (192
    and 128 at the published sizes): nothing pads v to q's width."""
    seen = {}
    real = F.flash_attention

    def spy(q, k, v, **kw):
        seen["shapes"] = (q.shape, k.shape, v.shape)
        return real(q, k, v, interpret=True, **kw)
    monkeypatch.setattr(F, "flash_attention", spy)
    layer, _, mine, _ = _layer_and_theirs("flash")
    out = layer.attend(mine, jnp.ones((1, 128, 32)))
    assert seen["shapes"] == ((1, 4, 128, 128), (1, 4, 128, 128),
                              (1, 4, 128, 64))
    assert out.shape == (1, 128, 32)


@pytest.mark.parametrize("t", [256, 512])
def test_keys_in_parts_and_assembled_keys_are_one_layer(monkeypatch, t):
    """At widths the kernels take in parts (a head's own part of the key
    and its value both 128 wide, beside a 64-wide rotary part) the flash
    path hands them ``project``'s three arrays as they are and the
    reference path assembles ``k`` and ``v`` from the same three: the same
    output and the same gradient of every parameter and of the input,
    float32 on both sides, so the two ways of reading the keys cannot
    drift.  At 512 positions the grid has blocks below, on and above the
    diagonal."""
    monkeypatch.setattr(F, "_BLOCK_ROWS", 256)
    seen = []
    real = F.flash_attention

    def spy(q, k=None, v=None, **kw):
        seen.append({n: a.shape for n, a in
                     dict(q=q, k=k, v=v, kv=kw.get("kv"),
                          k_shared=kw.get("k_shared")).items()
                     if a is not None})
        return real(q, k, v, interpret=True, **kw)
    monkeypatch.setattr(F, "flash_attention", spy)
    layers = {impl: LatentAttention(
        n_in=32, n_out=32, n_heads=2, head_dim=128, rope_dim=64, v_dim=128,
        q_rank=24, kv_rank=16, rope_theta=32e6, attn_impl=impl,
        weight_init="xavier") for impl in ("flash", "reference")}
    p = layers["flash"].init(jax.random.PRNGKey(0),
                             InputType.recurrent(32, t))["params"]
    x = jax.random.normal(jax.random.PRNGKey(1), (1, t, 32))

    def loss(impl):
        return lambda p, x: jnp.sum(jnp.sin(layers[impl].attend(p, x)))
    out = layers["flash"].attend(p, x)
    assert seen == [{"q": (1, 2, t, 192), "kv": (1, 2, t, 256),
                     "k_shared": (1, 1, t, 64)}]
    want = layers["reference"].attend(p, x)
    np.testing.assert_allclose(out, want, atol=5e-5 * float(
        jnp.max(jnp.abs(want))))
    got_p, got_x = jax.grad(loss("flash"), argnums=(0, 1))(p, x)
    want_p, want_x = jax.grad(loss("reference"), argnums=(0, 1))(p, x)
    assert len(seen) == 2                      # no call with whole keys
    for name, g, w in [("x", got_x, want_x)] + [
            (n, got_p[n], want_p[n]) for n in sorted(p)]:
        assert float(jnp.max(jnp.abs(g - w))) <= 5e-5 * float(
            jnp.max(jnp.abs(w))), name


def test_widths_the_kernels_refuse_in_parts_reach_them_assembled(monkeypatch):
    """A head's own part of the key 64 wide is no column block of a
    128-lane tile: ``flash_blocks`` refuses the parts and the layer
    assembles ``k`` and ``v`` as it always did."""
    with pytest.raises(ValueError, match="in parts"):
        F.flash_blocks(128, 128, 128, d_v=64, d_shared=64)
    layer, _, mine, _ = _layer_and_theirs("flash")
    assert not layer._flash_in_parts(128, None)
    assert LatentAttention(n_heads=2, head_dim=128, rope_dim=64, v_dim=128,
                           attn_impl="flash")._flash_in_parts(256, None)
    # a scanned run of latent blocks keeps the keys in either form
    names = TransformerBlock(attention="latent").SAVED_NAMES
    assert names[:5] == ("attn_q", "attn_kv", "attn_k_shared", "attn_k",
                         "attn_v")
    assert "attn_kv" not in TransformerBlock().SAVED_NAMES
    # a key mask is the reference's; 'auto' off the TPU too
    assert not LatentAttention(
        n_heads=2, head_dim=128, rope_dim=64, v_dim=128,
        attn_impl="auto")._flash_in_parts(256, None)


# ----------------------------------------- the graph against the reference
def test_the_graph_is_the_configuration(seeded):
    net = seeded[0]
    assert isinstance(net, ComputationGraph)
    conf = net.conf
    blocks = [conf.vertices[f"block_{i}"].layer for i in range(3)] + \
        [conf.vertices["mtp_block"].layer]
    assert all(b.attention == "latent" and b.head_dim == 8 and
               b.rope_dim == 4 and b.v_head_dim == 8 for b in blocks)
    assert [b.moe_experts for b in blocks] == [0, 16, 16, 16]
    assert all(b.moe_top_k == 4 and tuple(b.moe_held) == (0, 2)
               for b in blocks[1:])
    assert conf.vertex_inputs["mtp_in"] == ["next_in", "block_2"]
    assert conf.vertex_inputs["streams"] == ["norm", "mtp_norm"]
    assert conf.network_outputs == ["head"]
    assert {v for v, s in conf.vertex_scopes.items() if s == "mtp"} == {
        "mtp_in", "mtp_merge", "mtp_block", "mtp_norm"}
    assert net.num_params() == ref.n_params(SMALL)
    # one embedding and one head, whatever the streams
    assert [n for n, p in net.params.items() if "W" in p and
            p["W"].shape in ((48, 32), (32, 48))] == ["embed", "head"]


def test_both_streams_logits_match_the_reference(seeded):
    """The head's softmax over the two streams laid end to end against the
    reference's two sets of logits; 2e-5 absolute on probabilities."""
    net, theirs, ids = seeded
    out = np.asarray(net.output(ids))
    assert out.shape == (2, 2 * T, 48)
    for r in range(2):
        main, mtp, _ = ref.row_logits(ref._static(SMALL), "float32", None,
                                      theirs, jnp.asarray(ids[r]))
        np.testing.assert_allclose(out[r, :T], jax.nn.softmax(main, -1),
                                   atol=2e-5)
        np.testing.assert_allclose(out[r, T:], jax.nn.softmax(mtp, -1),
                                   atol=2e-5)


def _program_loss_and_grads(net, batch):
    xs, ys, _, lms = batch
    return jax.value_and_grad(
        lambda p: _graph_loss(net.conf, p, net.state, [jnp.asarray(xs[0])],
                              [jnp.asarray(ys[0])], None,
                              [jnp.asarray(lms[0])], train=True, key=None),
        has_aux=True)(net.params)


def test_both_losses_and_every_leafs_gradient_match_the_reference(seeded):
    """``L_main + 0.3 L_mtp`` by the graph's own loss walk on the batch
    ``JoyAIFlashLM.batch`` builds, and with the module's weight at nought
    and at one the two terms alone; gradients relative to each leaf's
    largest entry: 1e-4 (float32 on both sides)."""
    net, theirs, ids = seeded
    (loss, _), grads = _program_loss_and_grads(
        net, JoyAIFlashLM.batch(ids, 0.3))
    their_loss, (l_main, l_mtp), their_grads, _ = ref.loss_and_grads(
        SMALL, theirs, ids)
    assert float(loss) == pytest.approx(float(their_loss), rel=1e-6)
    assert float(their_loss) == pytest.approx(
        float(l_main) + 0.3 * float(l_mtp), rel=1e-6)
    (only_main, _), _ = _program_loss_and_grads(
        net, JoyAIFlashLM.batch(ids, 0.0))
    (both, _), _ = _program_loss_and_grads(net, JoyAIFlashLM.batch(ids, 1.0))
    assert float(only_main) == pytest.approx(float(l_main), rel=1e-6)
    assert float(both) - float(only_main) == pytest.approx(float(l_mtp),
                                                           rel=1e-5)
    flat = ref.flat(their_grads)
    seen = set()
    for vertex, leaves in grads.items():
        for leaf, g in leaves.items():
            name = traffic.reference_name(vertex, leaf)
            seen.add(name)
            scale = float(jnp.max(jnp.abs(flat[name])))
            assert scale > 0, name
            assert float(jnp.max(jnp.abs(g - flat[name]))) <= 1e-4 * scale, \
                name
    assert seen == set(flat)


def test_the_shared_leaves_gradients_are_the_sum_over_both_streams(seeded):
    """The embedding's and the head's gradient under ``L_main + 0.3 L_mtp``
    is the gradient under the trunk's term alone plus 0.3 times what the
    module's term adds (its gradient at weight one less the trunk's), and
    neither part is nought; a leaf of the module gets nothing from the
    trunk's term."""
    net, _, ids = seeded

    def grads(mtp_weight):
        return _program_loss_and_grads(
            net, JoyAIFlashLM.batch(ids, mtp_weight))[1]
    both, main, whole = grads(0.3), grads(0.0), grads(1.0)
    for vertex in ("embed", "head"):
        g, a, w = (t[vertex]["W"] for t in (both, main, whole))
        module = w - a
        assert float(jnp.max(jnp.abs(a))) > 0 and \
            float(jnp.max(jnp.abs(module))) > 0
        np.testing.assert_allclose(g, a + 0.3 * module, atol=1e-5 * float(
            jnp.max(jnp.abs(g))))
    assert float(jnp.max(jnp.abs(main["mtp_merge"]["W"]))) == 0.0
    assert float(jnp.max(jnp.abs(both["mtp_merge"]["W"]))) > 0.0


def test_fit_through_the_graph_follows_the_references_first_step(seeded):
    """``ComputationGraph.fit`` on an iterator of one batch: the score is
    the reference's loss, Adam's first moment the reference's gradient
    (norm by leaf), and the program's routing the reference's."""
    _, theirs, ids = seeded
    net = traffic.build(SMALL)
    # fit donates its weights: copies, as the benchmark's set-up makes them
    net.params = {**{k: v for k, v in net.params.items() if not v},
                  **jax.jit(traffic.as_program)(theirs)}
    choices = traffic.program_choices(net, SMALL, ids)
    net.fit(iter([JoyAIFlashLM.batch(ids, 0.3)]))
    their_loss, _, their_grads, chosen = ref.loss_and_grads(SMALL, theirs,
                                                            ids)
    assert net.get_score() == pytest.approx(float(their_loss), rel=1e-5)
    from benchmark import program
    mine = traffic.Job._named(program.leaf_norms(
        program.optimizer_field(net.opt_state, "mu")), scale=10.0)
    want = {k: float(v) for k, v in ref.leaf_norms(their_grads).items()}
    assert set(mine) == set(want)
    for name, norm in want.items():
        assert mine[name] == pytest.approx(norm, rel=1e-4), name
    assert choices.shape == np.asarray(chosen).shape == (2, 3, T, 4)
    assert traffic.routing_agreement(choices, chosen) == 1.0
    first = net.get_score()
    for _ in range(3):
        net.fit([JoyAIFlashLM.batch(ids, 0.3)])
    assert net.get_score() < first


def test_the_counters_count_the_latent_layers_and_the_module(seeded):
    _, _, ids = seeded
    before = set_default_registry(MetricsRegistry())
    try:
        # a topology of its own, so that the step is traced here and not
        # found in the process's trace cache
        net = traffic.build(dict(SMALL, rms_norm_eps=2e-6))
        start = traffic.traced_counters()
        net.fit([JoyAIFlashLM.batch(ids, 0.3)])
        traced = {k: v - start[k]
                  for k, v in traffic.traced_counters().items()}
        assert traced == {"mla_layers_traced_total": 4,
                          "mtp_modules_traced_total": 1,
                          "moe_layers_traced_total": 3}
        assert default_registry().get("mla_layers_traced_total").labels(
            "4", "12", "8").value == 4
    finally:
        set_default_registry(before)


def test_the_scopes_are_in_the_lowered_step(seeded):
    net, _, ids = seeded
    xs, ys, _, lms = JoyAIFlashLM.batch(ids, 0.3)
    step = net._get_jitted("train_step")
    text = step.lower(net.params, net.state, net.opt_state, net._rng,
                      [jnp.asarray(xs[0])], [jnp.asarray(ys[0])], None,
                      [jnp.asarray(lms[0])]).as_text(debug_info=True)
    for scope in ("TransformerBlock/mla_project", "TransformerBlock/attn_full",
                  "mtp/NextTokenMerge", "mtp/TransformerBlock/mla_project",
                  "mtp/RMSNormLayer", "mtp/TransformerBlock/moe_experts"):
        assert scope in text, scope


# ------------------------------------------------------------ the share test
def test_sixteen_shares_add_up_to_the_uncut_layer():
    """A routed layer of 16 experts over sixteen chips, one expert each:
    the sixteen shares' routed parts (the reference's, shared expert left
    out) and the shared expert counted once add up to the uncut layer; the
    program's share 0 gives what the reference's share 0 gives."""
    cut = dict(SMALL, n_routed_experts=1, experts_held=[0, 1])
    whole = dict(SMALL, n_routed_experts=16, experts_held=[0, 16])
    shapes = ref.layer_shapes(whole, True)
    keys = jax.random.split(jax.random.PRNGKey(9), len(shapes))
    p = {name: 0.3 * jax.random.normal(k, s)
         for k, (name, s) in zip(keys, sorted(shapes.items()))}
    x = jax.random.normal(jax.random.PRNGKey(4), (40, 32))
    uncut, idx = ref.routed_ffn(whole, p, x)
    assert idx.shape == (40, 4)
    # the reference holds experts 0..n-1 of the router's: a share is the
    # layer with its expert moved to the front of the router's columns
    total = ref.mlp(ref._highest, x, p["sg"], p["s1"], p["s2"])
    for e in range(16):
        order = [e] + [i for i in range(16) if i != e]
        share = dict(p, router=p["router"][:, order],
                     **{k: p[k][e:e + 1] for k in ("eg", "e1", "e2")})
        part, _ = ref.routed_ffn(cut, share, x, shared=False)
        total = total + part
    np.testing.assert_allclose(total, uncut, atol=2e-5)
    # the program's layer, told it holds expert 0 of 16
    block = TransformerBlock(
        n_in=32, n_heads=4, attention="latent", head_dim=8, rope_dim=4,
        v_head_dim=8, latent_q_rank=24, latent_kv_rank=16, norm="rms",
        gated=True, has_bias=False, moe_experts=16, moe_top_k=4,
        moe_scoring="sigmoid", moe_route_norm=True, moe_route_scale=2.5,
        moe_shared=1, moe_hidden=16, moe_held=(0, 1))
    mine = {"router": p["router"], "wg": p["eg"][:1], "w1": p["e1"][:1],
            "w2": p["e2"][:1], "sg": p["sg"], "s1": p["s1"], "s2": p["s2"]}
    state = {"route_bias": jnp.zeros((16,)),
             "expert_tokens": jnp.zeros((1,), jnp.int32)}
    got, _ = block._ffn(mine, x[None], state)
    share0 = dict(p, **{k: p[k][:1] for k in ("eg", "e1", "e2")})
    want, _ = ref.routed_ffn(cut, share0, x)
    np.testing.assert_allclose(got[0], want, atol=2e-5)


# ------------------------------------------------------- the graph's vertices
def test_time_slice_and_time_concat_vertices():
    x = jnp.arange(2 * 5 * 3, dtype=jnp.float32).reshape(2, 5, 3)
    ids = jnp.arange(10).reshape(2, 5)
    mask = jnp.asarray([[1, 1, 1, 0, 0], [1, 1, 1, 1, 1]], jnp.float32)
    head, tail = TimeSliceVertex(0, -1), TimeSliceVertex(1, None)
    np.testing.assert_array_equal(head.apply({}, [x])[0], x[:, :-1])
    np.testing.assert_array_equal(tail.apply({}, [ids])[0], ids[:, 1:])
    np.testing.assert_array_equal(tail.feed_forward_mask([mask]),
                                  mask[:, 1:])
    assert head.feed_forward_mask([None]) is None
    it = InputType.recurrent(3, 5)
    assert head.output_type([it]).timesteps == 4
    assert TimeSliceVertex(1, 4).output_type([it]).timesteps == 3
    cat = TimeConcatVertex()
    both = cat.apply({}, [x[:, :-1], x[:, 1:]])[0]
    assert both.shape == (2, 8, 3)
    np.testing.assert_array_equal(both[:, 4:], x[:, 1:])
    assert cat.output_type([head.output_type([it])] * 2).timesteps == 8
    m = cat.feed_forward_mask([mask[:, :-1], None], [x[:, :-1], x[:, 1:]])
    np.testing.assert_array_equal(m[:, 4:], np.ones((2, 4)))
    assert cat.feed_forward_mask([None, None]) is None


def test_a_graph_of_the_new_vertices_round_trips_and_trains():
    """Two streams of one embedded row through one output layer, the
    scopes and vertices through JSON."""
    from deeplearning4j_tpu.nn.conf.multi_layer import NeuralNetConfiguration
    from deeplearning4j_tpu.nn.conf.updaters import Adam
    from deeplearning4j_tpu.nn.layers.feedforward import \
        EmbeddingSequenceLayer
    g = (NeuralNetConfiguration.builder().seed(1)
         .updater(Adam(learning_rate=1e-2)).graph_builder())
    g.add_inputs("ids").set_input_types(InputType.recurrent(11, 9))
    g.add_layer("embed", EmbeddingSequenceLayer(n_out=8), "ids")
    g.add_vertex("a", TimeSliceVertex(0, -1), "embed")
    g.add_vertex("b", TimeSliceVertex(1, None), "embed", scope="second")
    g.add_layer("b_norm", RMSNormLayer(), "b", scope="second")
    g.add_vertex("both", TimeConcatVertex(), "a", "b_norm")
    g.add_layer("head", RnnOutputLayer(n_out=11, activation="softmax",
                                       loss="sparse_mcxent"), "both")
    g.set_outputs("head")
    conf = g.build()
    again = ComputationGraphConfiguration.from_json(conf.to_json())
    assert again.vertex_scopes == {"b": "second", "b_norm": "second"}
    assert isinstance(again.vertices["both"], TimeConcatVertex)
    assert again.vertices["b"].start == 1 and again.vertices["b"].stop is None
    net = ComputationGraph(again).init()
    ids = np.random.default_rng(2).integers(0, 11, (3, 9)).astype(np.int32)
    batch = ([ids], [np.concatenate([ids[:, 1:], ids[:, 1:]], axis=1)],
             None, None)
    net.fit([batch])
    first = net.get_score()
    for _ in range(20):
        net.fit([batch])
    assert net.get_score() < first
    assert net.output(ids).shape == (3, 16, 11)


def test_the_merge_norms_each_half_alone():
    merge = NextTokenMerge(n_in=8, n_out=4, weight_init="xavier")
    p = merge.init(jax.random.PRNGKey(0), InputType.recurrent(8, 5))[
        "params"]
    p = dict(p, enorm=jnp.full((4,), 0.5), hnorm=jnp.full((4,), -0.25))
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 5, 8))
    got, _ = merge.apply({"params": p}, x)

    def norm(v, gain):
        return v * jax.lax.rsqrt(jnp.mean(v * v, -1, keepdims=True)
                                 + 1e-6) * gain
    want = jnp.concatenate([norm(x[..., :4], 1.5), norm(x[..., 4:], 0.75)],
                           axis=-1) @ p["W"]
    np.testing.assert_allclose(got, want, atol=1e-6)
    with pytest.raises(ValueError, match="side by side"):
        NextTokenMerge(n_in=9, n_out=4).init(jax.random.PRNGKey(0),
                                             InputType.recurrent(9, 5))


# --------------------------------------------------- what the cache refuses
def test_the_kv_cache_path_refuses_latent_attention(seeded):
    net = seeded[0]
    block = net.conf.vertices["block_0"].layer
    variables = {"params": net.params["block_0"], "state": {}}
    with pytest.raises(NotImplementedError, match="latent"):
        block.apply_with_carry(variables, jnp.ones((1, 1, 32)), None)
    layer = block._mha()
    assert isinstance(layer, LatentAttention)
    with pytest.raises(NotImplementedError, match="absorbed"):
        layer.attend_cached({}, jnp.ones((1, 1, 32)), layer.init_carry(1))


def test_latent_attention_refuses_what_it_cannot_build():
    it = InputType.recurrent(32, 16)
    with pytest.raises(ValueError, match="even rope_dim"):
        LatentAttention(n_in=32, n_heads=4, head_dim=8, rope_dim=3,
                        q_rank=8, kv_rank=8).init(jax.random.PRNGKey(0), it)
    with pytest.raises(ValueError, match="attn_impl"):
        LatentAttention(n_in=32, n_heads=4, head_dim=8, rope_dim=4,
                        q_rank=8, kv_rank=8, attn_impl="ring").init(
                            jax.random.PRNGKey(0), it)


def test_a_latent_block_is_causal_or_refused():
    block = TransformerBlock(
        n_in=32, n_heads=4, attention="latent", head_dim=8, rope_dim=4,
        latent_q_rank=8, latent_kv_rank=8, causal=False)
    with pytest.raises(ValueError, match="latent attention is causal"):
        block.init(jax.random.PRNGKey(0), InputType.recurrent(32, 16))


def test_the_zoo_lists_the_model_with_its_published_sizes():
    m = JoyAIFlashLM()
    assert (m.embed, m.n_heads, m.q_rank, m.kv_rank, m.nope_dim, m.rope_dim,
            m.v_dim, m.ffn_hidden, m.moe_hidden, m.experts, m.top_k,
            m.n_layers, m.vocab_size, m.route_scale, m.rope_theta) == (
        2048, 32, 1536, 512, 128, 64, 128, 7168, 768, 256, 8, 40, 129280,
        2.5, 32e6)
    xs, ys, ms, lms = JoyAIFlashLM.batch(np.arange(12).reshape(2, 6), 0.3)
    np.testing.assert_array_equal(ys[0][0], [1, 2, 3, 4, 5, 2, 3, 4, 5, 0])
    np.testing.assert_allclose(lms[0][0], [1] * 5 + [0.3] * 4 + [0])
    assert ms is None and xs[0].shape == (2, 6)
