"""A looped range of a list (``ListBuilder.loop``): the layers ``first ..
stop - 1`` walked ``passes`` times on their one set of parameters, against
the same layers written out ``passes`` times with the weights tied by hand.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from deeplearning4j_tpu.nn import multilayer as ml
from deeplearning4j_tpu.nn.conf.input_type import InputType
from deeplearning4j_tpu.nn.conf.multi_layer import (MultiLayerConfiguration,
                                                    NeuralNetConfiguration)
from deeplearning4j_tpu.nn.conf.updaters import Adam, Sgd
from deeplearning4j_tpu.nn.layers.attention import (RMSNormLayer,
                                                    TransformerBlock)
from deeplearning4j_tpu.nn.layers.feedforward import EmbeddingSequenceLayer
from deeplearning4j_tpu.nn.layers.recurrent import (LSTM, ExitGateOutputLayer,
                                                    RnnOutputLayer)
from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork

V, T, E, BLOCKS = 24, 8, 16, 4


def _block():
    return TransformerBlock(n_heads=2, head_dim=8, causal=True,
                            attn_impl="reference", norm="rms",
                            post_norm=True, gated=True, has_bias=False,
                            ffn_hidden=24, positions="rotary")


def _conf(passes, *, loop=True, written_out=1, cache_mode="none",
          scan=None, precision=None, head="exit", updater=None):
    """Embedding, ``written_out`` copies of (``BLOCKS`` blocks and a norm),
    a head; with ``loop`` the one copy is walked ``passes`` times."""
    b = NeuralNetConfiguration.builder().seed(3).weight_init("xavier") \
        .updater(updater or Sgd(learning_rate=0.05)).cache_mode(cache_mode)
    if scan is not None:
        b = b.scan_layers(scan)
    if precision:
        b = b.precision(precision)
    lb = b.list().layer(EmbeddingSequenceLayer(n_out=E))
    for _ in range(written_out):
        for _ in range(BLOCKS):
            lb = lb.layer(_block())
        lb = lb.layer(RMSNormLayer(eps=1e-6))
    if loop:
        lb = lb.loop(1, BLOCKS + 2, passes)
    if head == "exit":
        lb = lb.layer(ExitGateOutputLayer(n_out=V, has_bias=False,
                                          exits=passes if loop else 1,
                                          exit_beta=0.1))
    else:
        lb = lb.layer(RnnOutputLayer(n_out=V, has_bias=False,
                                     activation="softmax",
                                     loss="sparse_mcxent"))
    return lb.set_input_type(InputType.recurrent(V, T)).build()


def _ids(rows=2, seed=0):
    ids = np.random.default_rng(seed).integers(0, V, (rows, T + 1))
    return ids[:, :-1].astype(np.int32), ids[:, 1:].astype(np.int32)


def _tied(net, passes):
    """The looped network's parameters laid out for the list written out
    ``passes`` times: copy ``r`` of layer ``i`` is layer ``i + r * (BLOCKS
    + 1)``."""
    per = BLOCKS + 1
    out = {"layer_0": net.params["layer_0"]}
    for r in range(passes):
        for i in range(1, per + 1):
            out[f"layer_{i + r * per}"] = net.params[f"layer_{i}"]
    return out


def _pass_outputs(conf_out, params, x, passes):
    """Each pass's output (its closing norm's), joined in time, by the list
    written out."""
    acts, _ = ml._stack_forward(conf_out, params, {}, x, train=False,
                                key=None, collect=True,
                                to_layer=len(conf_out.layers) - 1)
    per = BLOCKS + 1
    return jnp.concatenate([acts[r * per + per] for r in range(passes)],
                           axis=1)


@pytest.mark.parametrize("passes", [2, 3])
def test_outputs_equal_the_list_written_out(passes):
    net = MultiLayerNetwork(_conf(passes)).init()
    conf_out = _conf(passes, loop=False, written_out=passes)
    x, _ = _ids()
    n = len(net.conf.layers)
    looped, _ = ml._stack_forward(net.conf, net.params, net.state, x,
                                  train=False, key=None, to_layer=n - 1)
    assert looped.shape == (2, passes * T, E)
    want = _pass_outputs(conf_out, _tied(net, passes), x, passes)
    np.testing.assert_allclose(looped, want, rtol=2e-5, atol=2e-6)


@pytest.mark.parametrize("passes", [2, 3])
def test_a_weights_gradient_is_the_sum_over_its_copies(passes):
    net = MultiLayerNetwork(_conf(passes)).init()
    conf_out = _conf(passes, loop=False, written_out=passes)
    x, _ = _ids()
    n = len(net.conf.layers)
    probe = jax.random.normal(jax.random.PRNGKey(1), (2, passes * T, E))

    def looped(p):
        out, _ = ml._stack_forward(net.conf, p, net.state, x, train=True,
                                   key=None, to_layer=n - 1)
        return jnp.sum(out * probe)

    def written_out(p):
        return jnp.sum(_pass_outputs(conf_out, p, x, passes) * probe)
    got = jax.grad(looped)(net.params)
    copies = jax.grad(written_out)(_tied(net, passes))
    per = BLOCKS + 1
    for i in range(1, per + 1):
        for leaf, g in got[f"layer_{i}"].items():
            want = sum(copies[f"layer_{i + r * per}"][leaf]
                       for r in range(passes))
            np.testing.assert_allclose(
                g, want, rtol=2e-4, atol=1e-5 * float(jnp.abs(want).max()),
                err_msg=f"layer_{i}.{leaf}")
    want = copies["layer_0"]["W"]
    np.testing.assert_allclose(got["layer_0"]["W"], want, rtol=2e-4,
                               atol=1e-5 * float(jnp.abs(want).max()))


def test_one_pass_is_the_walk_of_today_bit_for_bit():
    x, y = _ids()
    plain = MultiLayerNetwork(_conf(1, loop=False, head="rnn")).init()
    one = MultiLayerNetwork(_conf(1, head="rnn")).init()
    assert one.conf.loop == [1, BLOCKS + 2, 1] and one.conf.looped() is None
    np.testing.assert_array_equal(plain.output(x), one.output(x))
    for net in (plain, one):
        net.fit(x, y)
        net.fit(x, y)
    assert plain.get_score() == one.get_score()
    for k, v in plain.params.items():
        for kk, a in v.items():
            np.testing.assert_array_equal(a, one.params[k][kk])


def _losses(conf, steps=3):
    net = MultiLayerNetwork(conf).init()
    x, y = _ids()
    out = []
    for _ in range(steps):
        net.fit(x, y)
        out.append(net.get_score())
    return out, net


def test_scanned_passes_equal_the_passes_unrolled(monkeypatch):
    scanned, a = _losses(_conf(3))
    monkeypatch.setenv("DL4J_TPU_SCAN_LAYERS", "0")
    unrolled, b = _losses(_conf(3))
    np.testing.assert_allclose(scanned, unrolled, rtol=1e-5)
    for k, v in a.params.items():
        for kk, w in v.items():
            np.testing.assert_allclose(w, b.params[k][kk], rtol=1e-4,
                                       atol=1e-6)


def test_unrolled_by_the_builder_too():
    scanned, _ = _losses(_conf(2))
    unrolled, _ = _losses(_conf(2, scan=False))
    np.testing.assert_allclose(scanned, unrolled, rtol=1e-5)


def test_remat_gives_the_numbers_of_none():
    plain, a = _losses(_conf(3))
    remat, b = _losses(_conf(3, cache_mode="remat"))
    np.testing.assert_allclose(plain, remat, rtol=1e-5)
    for k, v in a.params.items():
        for kk, w in v.items():
            np.testing.assert_allclose(w, b.params[k][kk], rtol=1e-4,
                                       atol=1e-6)


def test_the_loop_is_counted_with_what_it_saved():
    from deeplearning4j_tpu.observability.registry import (
        MetricsRegistry, default_registry, set_default_registry)
    old = default_registry()
    reg = MetricsRegistry()
    set_default_registry(reg)
    try:
        _losses(_conf(3, cache_mode="remat"), steps=1)
        counter = reg.get("loop_runs_traced_total")
        labels = {lab: child.value for lab, child in counter.samples()}
        assert labels == {(str(BLOCKS), "3", "input"): 1}
        # the CPU reports no limit: each layer-pass keeps its input alone
        assert reg.get("loop_saved_stack_bytes").value == \
            3 * BLOCKS * 2 * T * E * 4
        mass = {lab: child.value
                for lab, child in reg.get("loop_exit_mass").samples()}
        assert sorted(mass) == [("1",), ("2",), ("3",)]
        assert sum(mass.values()) == pytest.approx(1.0, abs=1e-5)
    finally:
        set_default_registry(old)


def _whiles(module):
    """``(whiles around it, traced by _Walk.loop)`` for each
    ``stablehlo.while`` of a lowered module, reached from ``main`` through
    the calls of its private functions."""
    funcs = {op.attributes["sym_name"].value: op
             for op in (o.operation for o in module.body.operations)
             if op.name == "func.func"}
    found = []

    def walk(op, depth):
        if op.name == "func.call":
            walk(funcs[op.attributes["callee"].value], depth)
        if op.name == "stablehlo.while":
            found.append((depth, "_Walk.loop" in str(op.location)))
            depth += 1
        for region in op.regions:
            for block in region.blocks:
                for inner in block.operations:
                    walk(inner.operation, depth)
    walk(funcs["main"], 0)
    return found


@pytest.mark.parametrize("passes", [2, 3])
def test_each_pass_is_one_loop_and_no_loop_holds_another(passes):
    """The scan over the passes is unrolled: the lowered step has no
    ``while`` around the range's runs, each pass's run is one ``while``
    forward and one backward, and one more holds the part of the run that
    no pass changes (taken out of the passes by the differentiation)."""
    net = MultiLayerNetwork(_conf(passes)).init()
    x, y = _ids()
    step = ml._build_train_step(net.conf, net._build_tx(), False)
    module = jax.jit(step).lower(
        net.params, net.state, net.opt_state, net._rng, x, y, None,
        None).compiler_ir("stablehlo")
    whiles = _whiles(module)
    assert [depth for depth, _ in whiles] == [0] * len(whiles)
    assert sum(looped for _, looped in whiles) == 2 * passes + 1


def test_the_range_is_held_once_in_parameters_and_adam_state():
    conf = _conf(3, updater=Adam(learning_rate=1e-3))
    net = MultiLayerNetwork(conf).init()
    assert sorted(net.params) == [f"layer_{i}" for i in range(BLOCKS + 3)]
    n_params = sum(int(np.prod(a.shape))
                   for a in jax.tree_util.tree_leaves(net.params))
    block = 4 * E * E + 3 * E * 24 + 4 * E
    assert n_params == V * E + BLOCKS * block + E + E * V + E + 1
    moments = [a for a in jax.tree_util.tree_leaves(net.opt_state)
               if getattr(a, "ndim", 0) >= 1]
    assert sum(int(np.prod(a.shape)) for a in moments) == 2 * n_params


def test_a_saved_model_holds_the_range_once_and_continues(tmp_path):
    from deeplearning4j_tpu.utils import model_serializer
    conf = _conf(3, updater=Adam(learning_rate=1e-3))
    x, y = _ids()
    net = MultiLayerNetwork(conf).init()
    net.fit(x, y)
    path = tmp_path / "looped.zip"
    model_serializer.write_model(net, str(path))
    back = model_serializer.restore_model(str(path))
    assert back.conf.loop == [1, BLOCKS + 2, 3]
    assert sorted(back.params) == sorted(net.params)
    for k, v in net.params.items():
        for kk, a in v.items():
            np.testing.assert_array_equal(a, back.params[k][kk])
    for both in range(2):
        net.fit(x, y)
        back.fit(x, y)
        assert back.get_score() == pytest.approx(net.get_score(), rel=1e-6)


def test_serde_round_trip():
    conf = _conf(3)
    back = MultiLayerConfiguration.from_json(conf.to_json())
    assert back.loop == [1, BLOCKS + 2, 3] and back.looped() == (1, 6, 3)
    assert back.layer_input_types[-1] == InputType.recurrent(E, 3 * T)
    a, b = MultiLayerNetwork(conf).init(), MultiLayerNetwork(back).init()
    x, _ = _ids()
    np.testing.assert_array_equal(a.output(x), b.output(x))
    # a configuration saved before the field existed has no loop
    import json
    old = json.loads(conf.to_json())
    del old["loop"]
    old["layers"][-1]["exits"] = 1
    assert MultiLayerConfiguration.from_json(json.dumps(old)).loop is None


def test_a_feature_mask_is_repeated_with_the_passes():
    net = MultiLayerNetwork(_conf(2)).init()
    x, _ = _ids()
    mask = np.ones((2, T), np.float32)
    mask[1, T - 2:] = 0
    n = len(net.conf.layers)
    out, _, m = ml._stack_forward(net.conf, net.params, net.state, x,
                                  train=False, key=None, mask=mask,
                                  to_layer=n - 1, return_mask=True)
    assert out.shape == (2, 2 * T, E)
    np.testing.assert_array_equal(m, np.tile(mask, (1, 2)))


def test_the_gradients_sum_over_the_passes_in_float32():
    """Under the bfloat16 policy the looped range's masters reach the loop
    uncast: the transposed scan over the passes carries a float32 sum for
    every weight, and no bfloat16 one."""
    net = MultiLayerNetwork(_conf(3, precision="bfloat16")).init()
    x, y = _ids()
    step = ml._build_train_step(net.conf, net._build_tx(), False)
    jaxpr = jax.make_jaxpr(step)(net.params, net.state, net.opt_state,
                                 net._rng, x, y, None, None)
    weight_shapes = {tuple(a.shape) for a in jax.tree_util.tree_leaves(
        net.params["layer_1"]) if a.ndim == 2}
    sums = []

    def walk(jp):
        for eqn in jp.eqns:
            if eqn.primitive.name == "scan" and eqn.params["reverse"] \
                    and eqn.params["length"] == 3:
                n_consts = eqn.params["num_consts"]
                n_carry = eqn.params["num_carry"]
                sums.extend(v.aval for v in
                            eqn.invars[n_consts:n_consts + n_carry])
            for v in eqn.params.values():
                for sub in (v if isinstance(v, (tuple, list)) else (v,)):
                    sub = getattr(sub, "jaxpr", sub)
                    if hasattr(sub, "eqns"):
                        walk(sub)
    walk(jaxpr.jaxpr)
    carried = [a for a in sums if tuple(a.shape) in weight_shapes]
    assert carried, "no scan over the passes carries a weight's gradient"
    assert {str(a.dtype) for a in carried} == {"float32"}
    # and the step trains
    net.fit(x, y)
    first = net.get_score()
    for _ in range(5):
        net.fit(x, y)
    assert net.get_score() < first


# ---- what cannot take a looped range raises by name ----------------------
def _list(*layers, loop, tbptt=False):
    lb = NeuralNetConfiguration.builder().list()
    for lc in layers:
        lb = lb.layer(lc)
    if tbptt:
        lb = lb.backprop_type("tbptt", 4, 4)
    return lb.loop(*loop).set_input_type(InputType.recurrent(V, T))


def _head():
    return RnnOutputLayer(n_out=V, activation="softmax",
                          loss="sparse_mcxent")


def _emb():
    return EmbeddingSequenceLayer(n_out=E)


@pytest.mark.parametrize("build, words", [
    (lambda: _list(_emb(), _block(), _head(), loop=(1, 3, 2)).build(),
     "before the last"),
    (lambda: _list(_emb(), _block(), _head(), loop=(1, 1, 2)).build(),
     "has to lie inside"),
    (lambda: _list(_emb(), _block(), _head(), loop=(1, 2, 0)).build(),
     "at least one pass"),
    (lambda: _list(_emb(), LSTM(n_out=E), _head(), loop=(1, 2, 2),
                   tbptt=True).build(), "truncated BPTT"),
    (lambda: _list(_emb(), TransformerBlock(n_heads=2, moe_experts=2),
                   _head(), loop=(1, 2, 2)).build(), "AUX_LOSS"),
    (lambda: _list(_emb(), LSTM(n_out=E + 1), _head(),
                   loop=(1, 2, 2)).build(), "hand the next what it took"),
    (lambda: _list(_emb(), _block(), _head(), loop=(0, 2, 2)).build(),
     "hand the next what it took"),
], ids=["no_layer_after", "empty", "no_pass", "tbptt", "aux_loss",
        "width_changes", "embedding_inside"])
def test_a_range_that_cannot_loop_is_refused_at_build_time(build, words):
    with pytest.raises(ValueError, match=words):
        build()


def _looped_net():
    return MultiLayerNetwork(_conf(2)).init()


def _sharded(net):
    from deeplearning4j_tpu.parallel.sharded import ShardedTrainer
    return ShardedTrainer(net)


def _graph(net):
    from deeplearning4j_tpu.nn.computation_graph import ComputationGraph
    return ComputationGraph(net.conf)


def _generate(net):
    from deeplearning4j_tpu.models import generate_tokens
    return generate_tokens(net, _ids()[0][:, :4], 2)


def _engine(net):
    from deeplearning4j_tpu.generation.engine import GenerationEngine
    engine = GenerationEngine(lambda: None, start=False)
    return engine._ensure_ring(net)


def _transfer(net):
    from deeplearning4j_tpu.nn.transfer_learning import TransferLearning
    return TransferLearning.Builder(net)


@pytest.mark.parametrize("use, words", [
    (lambda net: net.feed_forward(_ids()[0]), "feed_forward"),
    (lambda net: net.rnn_time_step(_ids()[0]), "rnn_time_step"),
    (_generate, "rnn_time_step"),
    (_engine, "generation cannot run through a looped range"),
    (_graph, "ComputationGraph cannot walk a looped range"),
    (_sharded, "ShardedTrainer cannot train a looped range"),
    (_transfer, "transfer learning cannot edit"),
    (lambda net: ml._stack_forward(net.conf, net.params, net.state,
                                   _ids()[0], train=False, key=None,
                                   to_layer=3), "inside it"),
], ids=["feed_forward", "rnn_time_step", "generate_tokens", "engine",
        "graph", "sharded_trainer", "transfer_learning", "to_layer"])
def test_each_use_that_cannot_take_a_looped_range_raises_by_name(use, words):
    with pytest.raises(ValueError, match=words):
        use(_looped_net())
