"""The head that never holds its logits whole (``nn/losses.
chunked_softmax_xent``; ``OutputLayer.compute_loss`` hands it a softmax
head whose float32 logits would pass ``losses.HEAD_CHUNK_BYTES``): loss and
gradients against the whole-array path for 1, 2 and 8 chunks, with and
without a bias, under every kind of label mask; under bfloat16 within the
error the whole-array path has against float32; one optimizer step of a
small LM (the stack) and of a two-stream graph (JoyAI's shape of head)
equal to the whole-array step's; what takes the whole-array path by shape
or by loss kind (no array of the logits' whole shape in a walked step's
lowered text, none of a chunk's in the others'); the counter's labels; one
trace on a four-device mesh.

The networks here reach the walk by a smaller ``HEAD_CHUNK_BYTES``
(``chunking``), the function's own cases by its ``rows_per_chunk``."""
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu.models import TransformerLM
from deeplearning4j_tpu.nn import losses as L
from deeplearning4j_tpu.nn.computation_graph import ComputationGraph
from deeplearning4j_tpu.nn.conf.computation_graph import (TimeConcatVertex,
                                                          TimeSliceVertex)
from deeplearning4j_tpu.nn.conf.input_type import InputType
from deeplearning4j_tpu.nn.conf.multi_layer import NeuralNetConfiguration
from deeplearning4j_tpu.nn.conf.updaters import Sgd
from deeplearning4j_tpu.nn.layers.attention import RMSNormLayer
from deeplearning4j_tpu.nn.layers.feedforward import (EmbeddingSequenceLayer,
                                                      OutputLayer)
from deeplearning4j_tpu.nn.layers.recurrent import RnnOutputLayer
from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
from deeplearning4j_tpu.observability.registry import (MetricsRegistry,
                                                       default_registry,
                                                       set_default_registry)
from deeplearning4j_tpu.parallel import ParallelWrapper, make_mesh

B, T, D, V = 2, 16, 8, 11
COUNTER = "head_chunks_traced_total"


def operands(dtype=jnp.float32, seed=0):
    rng = np.random.default_rng(seed)
    return (jnp.asarray(rng.normal(size=(B, T, D)), dtype),
            jnp.asarray(rng.normal(size=(D, V)) / np.sqrt(D), dtype),
            jnp.asarray(rng.normal(size=(V,)), dtype),
            jnp.asarray(rng.integers(0, V, (B, T)), jnp.int32))


def label_mask(kind):
    rng = np.random.default_rng(5)
    if kind == "none":
        return None
    if kind == "binary":
        return jnp.asarray(rng.integers(0, 2, (B, T)), jnp.float32)
    if kind == "weighted":          # the second stream's 0.3
        m = np.ones((B, T), np.float32)
        m[:, T // 2:] = 0.3
        m[:, -1] = 0.0
        return jnp.asarray(m)
    m = np.ones((B, T), np.float32)    # a row fully masked
    m[1] = 0.0
    return jnp.asarray(m)


def whole(x, W, b, y, mask):
    """The whole-array path: the layer's product, the registry's loss."""
    z = x @ W
    if b is not None:
        z = z + b
    return L.get("sparse_mcxent")(y, z, "softmax", mask)


def chunked(x, W, b, y, mask, rows):
    return L.chunked_softmax_xent(x, W, b, y,
                                  L.position_weights(mask, y.shape), rows)


@pytest.mark.parametrize("mask", ["none", "binary", "weighted", "row_off"])
@pytest.mark.parametrize("bias", [True, False])
@pytest.mark.parametrize("chunks", [1, 2, 8])
def test_loss_and_gradients_equal_the_whole_array_path(chunks, bias, mask):
    x, W, b, y = operands()
    b, mask = b if bias else None, label_mask(mask)
    argnums = (0, 1, 2) if bias else (0, 1)
    want, dwant = jax.value_and_grad(whole, argnums)(x, W, b, y, mask)
    got, dgot = jax.value_and_grad(chunked, argnums)(x, W, b, y, mask,
                                                     T // chunks)
    # float32 with a float64 switch on: nothing was promoted
    assert got.dtype == want.dtype == jnp.float32
    np.testing.assert_allclose(got, want, rtol=1e-6)
    for g, w in zip(dgot, dwant):
        assert g.dtype == w.dtype and g.shape == w.shape
        np.testing.assert_allclose(g, w, atol=1e-6)
    # without differentiation only the loss walk runs
    np.testing.assert_allclose(
        jax.jit(chunked, static_argnums=5)(x, W, b, y, mask, T // chunks),
        want, rtol=1e-6)


@pytest.mark.parametrize("chunks", [1, 2, 8])
def test_bfloat16_operands_within_the_whole_array_paths_own_error(chunks):
    """Under the bfloat16 policy the head gets bfloat16 operands: the
    gradients come back bfloat16, as the whole-array path's, and no
    further from the float32 gradients than those are."""
    x, W, b, y = operands()
    mask = label_mask("weighted")
    exact, dexact = jax.value_and_grad(whole, (0, 1, 2))(x, W, b, y, mask)
    low = [a.astype(jnp.bfloat16) for a in (x, W, b)]
    want, dwant = jax.value_and_grad(whole, (0, 1, 2))(*low, y, mask)
    got, dgot = jax.value_and_grad(chunked, (0, 1, 2))(*low, y, mask,
                                                       T // chunks)
    assert got.dtype == jnp.float32
    assert abs(got - exact) <= 1.5 * abs(want - exact) + 1e-6 * abs(exact)
    for g, w, e in zip(dgot, dwant, dexact):
        assert g.dtype == w.dtype == jnp.bfloat16
        err = float(jnp.max(jnp.abs(g.astype(jnp.float32) - e)))
        allowed = float(jnp.max(jnp.abs(w.astype(jnp.float32) - e)))
        assert err <= 1.5 * allowed + 1e-6


def test_the_cotangent_scales_every_gradient_and_the_weights_get_theirs():
    x, W, b, y = operands()
    w = L.position_weights(label_mask("weighted"), y.shape)
    f = lambda *a: 3.0 * L.chunked_softmax_xent(*a, y, w, 4)  # noqa: E731
    g = lambda *a: 3.0 * jnp.sum(w * -jnp.take_along_axis(     # noqa: E731
        jax.nn.log_softmax(a[0] @ a[1] + a[2]), y[..., None], -1)[..., 0])
    for got, want in zip(jax.grad(f, (0, 1, 2))(x, W, b),
                         jax.grad(g, (0, 1, 2))(x, W, b)):
        np.testing.assert_allclose(got, want, atol=3e-6)
    per = jax.grad(lambda w: L.chunked_softmax_xent(x, W, b, y, w, 4))(w)
    np.testing.assert_allclose(per, -jnp.take_along_axis(
        jax.nn.log_softmax(x @ W + b), y[..., None], -1)[..., 0], rtol=1e-5)


@pytest.mark.parametrize("mask", ["none", "binary", "weighted", "row_off"])
def test_position_weights_is_the_mean_rule_of_every_loss(mask):
    rng = np.random.default_rng(3)
    per = jnp.asarray(rng.normal(size=(B, T)), jnp.float32)
    mask = label_mask(mask)
    np.testing.assert_allclose(
        jnp.sum(per * L.position_weights(mask, per.shape)),
        L._apply_mask_and_mean(per[..., None], mask), atol=1e-6)


@pytest.mark.parametrize("batch,steps,classes,rows", [
    (1, 8192, 25024, 2048),     # Trinity's share: 820 MB of logits
    (1, 16384, 16160, 4096),    # JoyAI's two streams: 1.06 GB
    (3, 1024, 50304, 256),      # GPT-2 medium: 618 MB
    (1, 8192, 320, None),       # under the size
    (256, 1, 1000, None),       # a classifier's batch
    (1, 8191, 25024, None),     # a prime: only single rows divide it
    (1, 6000, 25024, 2000)])    # not a power of two
def test_rows_a_chunk_follow_the_logits_size(batch, steps, classes, rows):
    assert L.head_rows_per_chunk(batch, steps, classes) == rows


# ---------------------------------------------------------------- networks
@pytest.fixture
def chunking(monkeypatch):
    """Heads of this file's sizes walk in chunks of 8 time steps."""
    def on(batch, classes, steps=8, least=1):
        monkeypatch.setattr(L, "HEAD_CHUNK_BYTES", 4 * batch * steps * classes)
        monkeypatch.setattr(L, "_MIN_CHUNK_ROWS", least)
    return on


@pytest.fixture
def registry():
    old = default_registry()
    reg = MetricsRegistry()
    set_default_registry(reg)
    yield reg
    set_default_registry(old)


def traced(reg):
    c = reg.get(COUNTER)
    return {} if c is None else {labels: child.value
                                 for labels, child in c.samples()}


def copied(tree):
    return jax.tree_util.tree_map(jnp.array, tree)


def small_lm(seed, precision=None):
    # the seed is part of the topology: two seeds, two traces
    return TransformerLM(vocab_size=12, seq_len=32, embed=16, n_layers=2,
                         n_heads=2, sparse_labels=True, seed=seed,
                         updater=Sgd(learning_rate=0.1),
                         compute_dtype=precision).init()


def logits_steps(net, batch, classes):
    """The time lengths of every ``[batch, steps, classes]`` array in the
    lowered train step: the whole sequence where the head forms its logits
    whole, the chunk's alone where it walks."""
    step = net._get_jitted("train_step")
    text = step.audit_lower(step.audit_specs()[-1]).as_text()
    return {int(t) for t in re.findall(
        rf"tensor<{batch}x(\d+)x{classes}x(?:f32|bf16)>", text)}


def test_one_step_of_a_small_lm_equals_the_whole_array_step(chunking,
                                                            registry):
    rng = np.random.default_rng(0)
    ids = rng.integers(0, 12, (2, 32))
    plain, walked = small_lm(41), small_lm(42)
    walked.params = copied(plain.params)
    plain.fit([(ids, ids)])
    assert traced(registry) == {} and logits_steps(plain, 2, 12) == {32}
    chunking(2, 12)
    walked.fit([(ids, ids)])
    assert traced(registry) == {("64", "12", "4"): 1.0}
    assert logits_steps(walked, 2, 12) == {8}
    np.testing.assert_allclose(walked.get_score(), plain.get_score(),
                               rtol=1e-6)
    for got, want in zip(jax.tree_util.tree_leaves(walked.params),
                         jax.tree_util.tree_leaves(plain.params)):
        np.testing.assert_allclose(got, want, atol=1e-6)
    # score and evaluate take the loss walk alone
    np.testing.assert_allclose(walked.score((ids, ids)),
                               plain.score((ids, ids)), rtol=1e-6)


def test_one_bfloat16_step_is_as_near_the_float32_step_as_the_whole_array(
        chunking, registry):
    """Under the bfloat16 policy the walked step differs from the
    whole-array step by roundings of the same size (the cotangent's
    bfloat16 rounding falls differently), so both are held against the
    float32 step: the masters stay float32 and move as far."""
    rng = np.random.default_rng(0)
    ids = rng.integers(0, 12, (2, 32))
    exact, plain, walked = (small_lm(43), small_lm(44, "bfloat16"),
                            small_lm(45, "bfloat16"))
    plain.params = copied(exact.params)
    walked.params = copied(exact.params)
    exact.fit([(ids, ids)])
    plain.fit([(ids, ids)])
    chunking(2, 12)
    walked.fit([(ids, ids)])
    assert traced(registry) == {("64", "12", "4"): 1.0}
    assert logits_steps(walked, 2, 12) == {8}
    assert abs(walked.get_score() - exact.get_score()) <= \
        2 * abs(plain.get_score() - exact.get_score()) + 1e-3
    leaves = [jax.tree_util.tree_leaves(n.params)
              for n in (walked, plain, exact)]
    for got, want, true in zip(*leaves):
        assert got.dtype == want.dtype == jnp.float32
        assert float(jnp.max(jnp.abs(got - true))) <= \
            2 * float(jnp.max(jnp.abs(want - true))) + 1e-4


def two_streams(seed, **head):
    g = (NeuralNetConfiguration.builder().seed(seed)
         .updater(Sgd(learning_rate=0.1)).graph_builder())
    g.add_inputs("ids").set_input_types(InputType.recurrent(11, 17))
    g.add_layer("embed", EmbeddingSequenceLayer(n_out=8), "ids")
    g.add_vertex("a", TimeSliceVertex(0, -1), "embed")
    g.add_vertex("b", TimeSliceVertex(1, None), "embed")
    g.add_layer("b_norm", RMSNormLayer(), "b")
    g.add_vertex("both", TimeConcatVertex(), "a", "b_norm")
    g.add_layer("head", RnnOutputLayer(
        n_out=11, activation="softmax",
        **{"loss": "sparse_mcxent", "has_bias": False, **head}), "both")
    g.set_outputs("head")
    return ComputationGraph(g.build()).init()


def two_stream_batch(dense=False):
    rng = np.random.default_rng(1)
    ids = rng.integers(0, 11, (3, 17))
    y = np.concatenate([ids[:, 1:], ids[:, 1:]], axis=1)
    mask = np.concatenate([np.ones((3, 16), np.float32),
                           np.full((3, 15), 0.3, np.float32),
                           np.zeros((3, 1), np.float32)], axis=1)
    if dense:
        y = np.eye(11, dtype=np.float32)[y]
    return [ids], [y], None, [mask]


def test_one_step_of_a_two_stream_graph_equals_the_whole_array_step(
        chunking, registry):
    """One head over two streams laid end to end, the label mask carrying
    the second stream's weight: JoyAI's shape of head, through
    ``ComputationGraph.fit``."""
    plain, walked = two_streams(51), two_streams(52)
    walked.params = copied(plain.params)
    plain.fit([two_stream_batch()])
    assert traced(registry) == {} and logits_steps(plain, 3, 11) == {32}
    chunking(3, 11)
    walked.fit([two_stream_batch()])
    assert traced(registry) == {("96", "11", "4"): 1.0}
    assert logits_steps(walked, 3, 11) == {8}
    np.testing.assert_allclose(walked.get_score(), plain.get_score(),
                               rtol=1e-6)
    for got, want in zip(jax.tree_util.tree_leaves(walked.params),
                         jax.tree_util.tree_leaves(plain.params)):
        np.testing.assert_allclose(got, want, atol=1e-6)


@pytest.mark.parametrize("why,head,dense", [
    ("dense labels", {"loss": "mcxent"}, True),
    ("column weights", {"loss_weights": [1.0] * 10 + [2.0]}, False),
    ("a head of its own on the ids' bias", {"has_bias": True}, None)])
def test_what_the_graphs_head_does_not_walk(chunking, registry, why, head,
                                            dense):
    """Dense ``mcxent`` and ``loss_weights`` take the whole-array path at
    any size; a bias alone changes nothing (the control: it walks)."""
    chunking(3, 11)
    net = two_streams(60 + len(why), **head)
    net.fit([two_stream_batch(dense=bool(dense))])
    walked = dense is None
    assert bool(traced(registry)) == walked
    assert logits_steps(net, 3, 11) == ({8} if walked else {32})


def test_rows_no_chunk_divides_and_a_small_head_take_the_whole_array_path(
        chunking, registry):
    # 31 steps, a prime: only chunks of one step divide them; 32 steps
    # under the size
    chunking(2, 12, least=4)
    rng = np.random.default_rng(0)
    for seq in (31, 32):
        if seq == 32:
            chunking(2, 12, steps=32, least=4)
        net = TransformerLM(vocab_size=12, seq_len=seq, embed=16, n_layers=1,
                            n_heads=2, sparse_labels=True, seed=70).init()
        ids = rng.integers(0, 12, (2, seq))
        net.fit([(ids, ids)])
        assert traced(registry) == {}
        assert logits_steps(net, 2, 12) == {seq}


def test_a_float16_head_takes_the_whole_array_path(chunking, registry):
    """float16's loss scale protects whole logits today: the head under
    that policy is left as it is, at any size."""
    rng = np.random.default_rng(0)
    ids = rng.integers(0, 12, (2, 32))
    chunking(2, 12)
    net = small_lm(75, "float16")
    net.fit([(ids, ids)])
    assert traced(registry) == {}
    assert logits_steps(net, 2, 12) == {32}


def test_several_prediction_heads_take_the_whole_array_path(chunking,
                                                            registry):
    chunking(4, 12)
    b = (NeuralNetConfiguration.builder().seed(80)
         .updater(Sgd(learning_rate=0.1)).list())
    b.layer(RnnOutputLayer(n_out=12, pred_heads=2, activation="softmax",
                           loss="sparse_mcxent"))
    net = MultiLayerNetwork(
        b.set_input_type(InputType.recurrent(5, 32)).build()).init()
    rng = np.random.default_rng(0)
    x = rng.normal(size=(4, 32, 5)).astype(np.float32)
    y = rng.integers(0, 6, (4, 32, 2))
    net.fit([(x, y, None, np.ones((4, 32, 2), np.float32))])
    assert traced(registry) == {} and logits_steps(net, 4, 12) == {32}


def test_a_feed_forward_head_takes_the_whole_array_path(chunking, registry):
    chunking(64, 12, steps=1)
    b = (NeuralNetConfiguration.builder().seed(81)
         .updater(Sgd(learning_rate=0.1)).list())
    b.layer(OutputLayer(n_out=12, activation="softmax",
                        loss="sparse_mcxent"))
    net = MultiLayerNetwork(
        b.set_input_type(InputType.feed_forward(5)).build()).init()
    rng = np.random.default_rng(0)
    net.fit([(rng.normal(size=(64, 5)).astype(np.float32),
              rng.integers(0, 12, (64,)))])
    assert traced(registry) == {}


@pytest.mark.skipif(len(jax.devices()) < 4, reason="needs 4 virtual devices")
def test_one_trace_on_a_four_device_mesh_keeps_the_batch_sharded(
        chunking, registry):
    """The walk cuts the time axis and leaves the batch axis whole: under
    ``ParallelWrapper`` over four devices the step is traced once, walks
    the head, and gives the single device's step."""
    rng = np.random.default_rng(0)
    ids = rng.integers(0, 12, (8, 32))
    chunking(8, 12)
    alone, meshed = small_lm(91), small_lm(92)
    meshed.params = copied(alone.params)
    alone.fit([(ids, ids)])
    ParallelWrapper(meshed, make_mesh(dp=4)).fit([(ids, ids)])
    assert traced(registry) == {("256", "12", "4"): 2.0}
    compiles = registry.get("training_compile_total")
    assert compiles.labels("train_step").value == 2
    for got, want in zip(jax.tree_util.tree_leaves(meshed.params),
                         jax.tree_util.tree_leaves(alone.params)):
        np.testing.assert_allclose(got, want, atol=1e-6)
