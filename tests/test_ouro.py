"""Ouro's looped decoder through the normal path at a small size on the CPU
(four blocks walked three times at tiny widths, sequence 32): the program
against the plain reference ``benchmark/reference/ouro.py`` on seeded
weights — every exit's logits, the exit distribution, the loss with and
without the entropy term, every leaf's gradient (the gate's among them),
in float32 and under the bfloat16 policy; ``output()`` is the last pass's;
the reference's faults are faults."""
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from benchmark import common  # noqa: E402
from deeplearning4j_tpu.nn import multilayer as ml  # noqa: E402
from deeplearning4j_tpu.nn.layers.recurrent import (  # noqa: E402
    ExitGateOutputLayer, publish_exit_mass)
from deeplearning4j_tpu.observability.registry import (  # noqa: E402
    MetricsRegistry, default_registry, set_default_registry)

ref = common.load_module("reference", "ouro")
traffic = common.load_module("traffic", "loop_lm_fit_stream")

PASSES, LAYERS, T, V = 3, 4, 32, 48
SMALL = {
    "family": "ouro", "hidden_size": 32, "head_dim": 8,
    "num_attention_heads": 4, "num_key_value_heads": 4,
    "intermediate_size": 48, "num_hidden_layers": LAYERS,
    "total_ut_steps": PASSES, "rope_theta": 1000000, "rms_norm_eps": 1e-6,
    "vocab_size": V, "exit_beta": 0.1, "init_std": 0.2,
    "train_seq_len": T, "precision": "float32", "cache_mode": "none",
    "optimizer": {"kind": "adam", "learning_rate": 3e-4, "beta1": 0.9,
                  "beta2": 0.999, "epsilon": 1e-8}}
BF16 = dict(SMALL, precision="bfloat16")


def _seeded(cfg):
    """The program's network on the reference's seeded weights, a batch."""
    net = traffic.build(cfg)
    theirs = ref.init_params(cfg, jax.random.PRNGKey(3))
    # a gate that is not at nought, so that its bias is told from none
    theirs["gate_b"] = theirs["gate_b"] + 0.3
    net.params = {**{k: v for k, v in net.params.items() if not v},
                  **traffic.as_program(theirs)}
    ids = np.random.default_rng(0).integers(0, V, (2, T + 1)).astype(
        np.int32)
    return net, theirs, ids[:, :-1], ids[:, 1:]


@pytest.fixture(scope="module")
def seeded():
    return _seeded(SMALL)


@pytest.fixture(scope="module")
def seeded_bf16():
    return _seeded(BF16)


def test_the_list_is_the_configurations(seeded):
    net = seeded[0]
    conf = net.conf
    assert conf.loop == [1, LAYERS + 2, PASSES]
    assert [type(lc).__name__ for lc in conf.layers] == \
        ["EmbeddingSequenceLayer"] + ["TransformerBlock"] * LAYERS + \
        ["RMSNormLayer", "ExitGateOutputLayer"]
    block = conf.layers[1]
    assert (block.norm, block.post_norm, block.gated, block.has_bias,
            block.positions, block.head_dim, block.rope_theta) == \
        ("rms", True, True, False, "rotary", 8, 1e6)
    head = conf.layers[-1]
    assert (head.exits, head.exit_beta, head.has_bias) == (PASSES, 0.1,
                                                           False)
    assert conf.layer_input_types[-1].timesteps == PASSES * T
    n = sum(int(np.prod(a.shape))
            for a in jax.tree_util.tree_leaves(net.params))
    assert n == ref.n_params(SMALL)


def test_the_published_models_count_is_the_issues():
    full = common.load_json("configs", "ouro-2p6b-8l.json")
    layer = 4 * 2048 * 2048 + 3 * 2048 * 5632 + 4 * 2048
    assert layer == 51_388_416
    assert ref.n_params(full) == 2 * 49152 * 2048 + 8 * layer + 2048 + 2049
    assert ref.n_params(dict(full, num_hidden_layers=48)) == \
        2 * 49152 * 2048 + 48 * layer + 2048 + 2049


def _states_of(net, x):
    n = len(net.conf.layers)
    h, _ = ml._stack_forward(net.conf, net.params, net.state, x,
                             train=False, key=None, to_layer=n - 1)
    return h


def test_every_exits_logits_match_the_reference(seeded):
    """The program's states after each pass through its own head, against
    the reference's logits: 2e-4 of the logits' scale (float32 on both
    sides, sums in another order)."""
    net, theirs, x, _ = seeded
    h = _states_of(net, x)
    assert h.shape == (2, PASSES * T, 32)
    mine = (h @ net.params[f"layer_{LAYERS + 2}"]["W"]).reshape(
        2, PASSES, T, V)
    for r in range(2):
        logits, _ = ref.row_exits(ref._static(SMALL), "float32", None,
                                  theirs, jnp.asarray(x[r]))
        assert logits.shape == (PASSES, T, V)
        scale = float(jnp.max(jnp.abs(logits)))
        np.testing.assert_allclose(mine[r], logits, atol=2e-4 * scale)
        # the passes differ: no pass is a copy of another
        assert float(jnp.max(jnp.abs(logits[0] - logits[-1]))) > 0.05 * scale


def test_the_exit_distribution_matches_and_the_last_pass_takes_the_rest(
        seeded):
    net, theirs, x, _ = seeded
    head = net.conf.layers[-1]
    p, logp = head.exit_distribution(net.params[f"layer_{LAYERS + 2}"],
                                     _states_of(net, x))
    assert p.shape == (2, PASSES, T)
    np.testing.assert_allclose(jnp.sum(p, axis=1), 1.0, atol=1e-6)
    np.testing.assert_allclose(jnp.exp(logp), p, rtol=1e-6)
    for r in range(2):
        _, theirs_p = ref.row_exits(ref._static(SMALL), "float32", None,
                                    theirs, jnp.asarray(x[r]))
        np.testing.assert_allclose(p[r], theirs_p, atol=2e-6)
    lam = jax.nn.sigmoid(jnp.array([[0.2, -1.0], [3.0, 0.5], [9.0, 9.0]]))
    want = ref.exit_distribution(lam)
    np.testing.assert_allclose(want[0], lam[0], rtol=1e-6)
    np.testing.assert_allclose(want[1], lam[1] * (1 - lam[0]), rtol=1e-6)
    np.testing.assert_allclose(want[2], (1 - lam[0]) * (1 - lam[1]),
                               rtol=1e-6)


def test_output_is_the_last_passs_distribution(seeded):
    net, theirs, x, _ = seeded
    out = np.asarray(net.output(x))
    assert out.shape == (2, T, V)
    for r in range(2):
        logits, _ = ref.row_exits(ref._static(SMALL), "float32", None,
                                  theirs, jnp.asarray(x[r]))
        np.testing.assert_allclose(out[r], jax.nn.softmax(logits[-1], -1),
                                   atol=2e-5)


def _program(net, x, y, beta=None):
    conf = net.conf
    if beta is not None:
        import copy
        conf = copy.deepcopy(conf)
        conf.layers[-1].exit_beta = beta
    return jax.value_and_grad(
        lambda p: ml._stack_loss(conf, p, net.state, x, y, train=True,
                                 key=None), has_aux=True)(net.params)


@pytest.mark.parametrize("beta", [0.1, 0.0], ids=["with_entropy",
                                                  "without_entropy"])
def test_loss_and_every_leafs_gradient_match_the_reference(seeded, beta):
    """Relative to each leaf's largest entry: 2e-4 (float32 on both sides,
    three passes' worth of sums in another order)."""
    net, theirs, x, y = seeded
    (loss, state), grads = _program(net, x, y, beta)
    their_loss, their_grads, mass, parts = ref.loss_and_grads(
        SMALL, theirs, x, y, beta=beta)
    assert float(loss) == pytest.approx(float(their_loss), rel=2e-6)
    assert float(their_loss) == pytest.approx(
        float(parts["expected"] - beta * parts["entropy"]), rel=1e-6)
    np.testing.assert_allclose(state[f"layer_{LAYERS + 2}"]["exit_mass"],
                               mass, atol=1e-6)
    flat = ref.flat(their_grads)
    seen = set()
    for layer, leaves in grads.items():
        for leaf, g in leaves.items():
            name = traffic.reference_name(LAYERS, layer, leaf)
            seen.add(name)
            scale = float(jnp.max(jnp.abs(flat[name])))
            assert scale > 0, name
            assert float(jnp.max(jnp.abs(g - flat[name]))) <= 2e-4 * scale, \
                name
    assert seen == set(flat) and {"gate_w", "gate_b"} <= seen


def test_the_entropy_term_is_what_beta_weighs(seeded):
    net, theirs, x, y = seeded
    (with_h, _), _ = _program(net, x, y, 0.1)
    (without, _), _ = _program(net, x, y, 0.0)
    _, _, _, parts = ref.loss_and_grads(SMALL, theirs, x, y)
    assert float(without - with_h) == pytest.approx(
        0.1 * float(parts["entropy"]), rel=1e-4)
    assert float(parts["entropy"]) > 0


def test_under_the_bfloat16_policy_the_step_follows_the_reference(
        seeded_bf16):
    """The program's own train step under the bfloat16 policy (masters
    float32, the loop's sum of the gradients float32) against the float32
    reference by the benchmark's own gaps, beside the reference with its
    operands rounded to bfloat16: the program is no further off than twice
    that, and within the tiny cell's limits."""
    from benchmark.check import train as check_train
    net, theirs, x, y = seeded_bf16
    tx = net._build_tx()
    step = jax.jit(ml._build_train_step(net.conf, tx, False))
    out = step(net.params, net.state, net.opt_state, net._rng, x, y, None,
               None)
    loss = float(out[4])
    their_loss, their_grads, _, _ = ref.loss_and_grads(BF16, theirs, x, y)
    rounded_loss, rounded_grads, _, _ = ref.loss_and_grads(
        BF16, theirs, x, y, precision="bfloat16")
    assert abs(loss - float(their_loss)) <= 2 * abs(
        float(rounded_loss) - float(their_loss)) + 1e-3 * float(their_loss)
    # the optimizer's first moment holds the gradient it was given
    from benchmark import program
    mine = {traffic.reference_name(LAYERS, layer, leaf): norm / (1 - 0.9)
            for (layer, leaf), norm in program.leaf_norms(
                program.optimizer_field(out[2], "mu")).items()}
    want = {k: float(v) for k, v in ref.leaf_norms(their_grads).items()}
    rounded = {k: float(v) for k, v in ref.leaf_norms(rounded_grads).items()}
    ours = check_train._leaf_gaps(mine, want, median_of_all=True)[0]
    theirs_gap = check_train._leaf_gaps(rounded, want,
                                        median_of_all=True)[0]
    assert ours <= max(2 * theirs_gap, 0.03), (ours, theirs_gap)
    # every leaf of the gradient is float32, the gate's too
    assert {str(a.dtype) for a in jax.tree_util.tree_leaves(out[0])} == \
        {"float32"}


@pytest.mark.parametrize("fault,moves", [
    ("passes_3", "losses"), ("last_pass_grad", "layers.0.Wq"),
    ("gate_detached", "gate_w")])
def test_each_fault_of_the_reference_is_a_fault(seeded, fault, moves):
    _, theirs, x, y = seeded
    loss, grads, _, _ = ref.loss_and_grads(SMALL, theirs, x, y)
    f_loss, f_grads, f_mass, _ = ref.loss_and_grads(SMALL, theirs, x, y,
                                                    fault=fault)
    if moves == "losses":
        assert abs(float(f_loss) - float(loss)) > 1e-3 * float(loss)
        assert f_mass.shape == (PASSES - 1,)
        return
    assert float(f_loss) == pytest.approx(float(loss), rel=1e-6)
    a, b = ref.flat(grads)[moves], ref.flat(f_grads)[moves]
    assert float(jnp.linalg.norm(a - b)) > 0.1 * float(jnp.linalg.norm(a))
    if fault == "gate_detached":
        assert float(jnp.max(jnp.abs(b))) == 0.0
    with pytest.raises(ValueError, match="no such fault"):
        ref.loss_and_grads(SMALL, theirs, x, y, fault="no_window")


def test_a_label_mask_weighs_the_positions_of_every_exit(seeded):
    """A masked position counts at no exit: the loss is that of the kept
    positions, and the exits' shares are means over them."""
    net, _, x, y = seeded
    mask = np.ones((2, T), np.float32)
    mask[:, T // 2:] = 0
    head = net.conf.layers[-1]
    h = _states_of(net, x)
    variables = {"params": net.params[f"layer_{LAYERS + 2}"], "state": {}}
    masked, st = head.loss_and_state(variables, h, y, mask=mask)
    half = jnp.concatenate([h[:, r * T:r * T + T // 2]
                            for r in range(PASSES)], axis=1)
    kept, st_half = head.loss_and_state(variables, half, y[:, :T // 2])
    assert float(masked) == pytest.approx(float(kept), rel=1e-5)
    np.testing.assert_allclose(st["exit_mass"], st_half["exit_mass"],
                               atol=1e-6)
    # a feature mask reaches the head repeated a pass, and reads the same
    through, _ = head.loss_and_state(variables, h, y,
                                     mask=np.tile(mask, (1, PASSES)))
    assert float(through) == float(masked)
    with pytest.raises(ValueError, match="integer labels"):
        head.loss_and_state(variables, h, jnp.tile(y, (1, PASSES)))


def test_the_head_refuses_what_it_is_not():
    from deeplearning4j_tpu.nn.conf.input_type import InputType
    for kw in ({"loss": "mcxent"}, {"activation": "identity"},
               {"pred_heads": 2}):
        head = ExitGateOutputLayer(n_in=8, n_out=16, exits=2, name="h",
                                   **kw)
        with pytest.raises(ValueError, match="exit-gated head"):
            head.init(jax.random.PRNGKey(0), InputType.recurrent(8, 4))


def test_fit_trains_and_publishes_the_exits_shares(seeded):
    _, _, x, y = seeded
    before = set_default_registry(MetricsRegistry())
    try:
        twin = traffic.build(SMALL)
        twin.fit([(x, y)])
        first = twin.get_score()
        for _ in range(4):
            twin.fit([(x, y)])
        assert twin.get_score() < first
        gauge = default_registry().get("loop_exit_mass")
        shares = {labels[0]: child.value
                  for labels, child in gauge.samples()}
        assert sorted(shares) == ["1", "2", "3"]
        assert sum(shares.values()) == pytest.approx(1.0, abs=1e-5)
        np.testing.assert_allclose(
            [shares[k] for k in sorted(shares)],
            twin.state[f"layer_{LAYERS + 2}"]["exit_mass"], atol=1e-6)
        # a model without such a head publishes nothing
        set_default_registry(MetricsRegistry())
        publish_exit_mass(type("M", (), {"state": {"layer_0": {}}})())
        assert default_registry().get("loop_exit_mass") is None
    finally:
        set_default_registry(before)
