"""What a scanned run of layers saves for the backward pass
(``nn/scan_layers.run_scan``): a layer that names the values dear to
recompute (``TransformerBlock.SAVED_NAMES``) runs under a checkpoint policy
that saves those and the block's input; a layer that names nothing keeps
``lax.scan``'s own program; ``cache_mode='remat'`` saves the input alone.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from deeplearning4j_tpu import (InputType, MultiLayerNetwork,
                                NeuralNetConfiguration)
from deeplearning4j_tpu.nn.conf.updaters import Sgd
from deeplearning4j_tpu.nn.layers.attention import TransformerBlock
from deeplearning4j_tpu.nn.layers.feedforward import DenseLayer, OutputLayer
from deeplearning4j_tpu.nn.layers.recurrent import LSTM, RnnOutputLayer
from deeplearning4j_tpu.nn.multilayer import _build_train_step, _stack_loss
from deeplearning4j_tpu.observability.registry import default_registry

T, E = 8, 16
NAMES = TransformerBlock().SAVED_NAMES
# jax.checkpoint's primitive, as jax has spelt it
CHECKPOINT = ("checkpoint", "remat", "remat2")


def _net(layers, itype, out, **defaults):
    b = NeuralNetConfiguration.builder().seed(5).updater(
        Sgd(learning_rate=0.05))
    for k, v in defaults.items():
        b = getattr(b, k)(v)
    lb = b.list()
    for lc in layers:
        lb = lb.layer(lc)
    return MultiLayerNetwork(
        lb.layer(out).set_input_type(itype).build()).init()


def _blocks(n=6, t=T, e=E, heads=2, **defaults):
    """``n`` identical blocks on ``[b, t, e]`` input under a softmax head;
    ``block_kw`` in ``defaults`` goes to every block."""
    block_kw = defaults.pop("block_kw", {})
    return _net([TransformerBlock(n_heads=heads, **block_kw)
                 for _ in range(n)],
                InputType.recurrent(e, t),
                RnnOutputLayer(n_out=5, activation="softmax", loss="mcxent"),
                **defaults)


def _dense(**defaults):
    return _net([DenseLayer(n_out=E, activation="tanh") for _ in range(7)],
                InputType.feed_forward(E),
                OutputLayer(n_out=5, activation="softmax", loss="mcxent"),
                **defaults)


def _lstm(**defaults):
    return _net([LSTM(n_out=E) for _ in range(6)],
                InputType.recurrent(E, T),
                RnnOutputLayer(n_out=5, activation="softmax", loss="mcxent"),
                **defaults)


def _seq_batch(t=T, e=E, rows=3, seed=0):
    rng = np.random.default_rng(seed)
    x = rng.standard_normal((rows, t, e)).astype(np.float32)
    y = np.eye(5, dtype=np.float32)[rng.integers(0, 5, (rows, t))]
    return jnp.asarray(x), jnp.asarray(y)


def _flat_batch(rows=6, seed=0):
    rng = np.random.default_rng(seed)
    x = rng.standard_normal((rows, E)).astype(np.float32)
    y = np.eye(5, dtype=np.float32)[rng.integers(0, 5, rows)]
    return jnp.asarray(x), jnp.asarray(y)


def _step_jaxpr(net, x, y):
    """The train step's jaxpr, traced from the builder the network jits."""
    step = _build_train_step(net.conf, net._tx, False)
    return jax.make_jaxpr(step)(net.params, net.state, net.opt_state,
                                net._rng, x, y, None, None)


def _sub_jaxprs(eqn):
    for v in eqn.params.values():
        for j in (v if isinstance(v, (list, tuple)) else (v,)):
            j = getattr(j, "jaxpr", j)
            if hasattr(j, "eqns"):
                yield j


def _walk(jaxpr, into_kernels=False):
    """Every equation of ``jaxpr`` and of the jaxprs its equations hold; a
    Pallas kernel's body is the kernel's business."""
    for eqn in jaxpr.eqns:
        yield eqn
        if eqn.primitive.name == "pallas_call" and not into_kernels:
            continue
        for sub in _sub_jaxprs(eqn):
            yield from _walk(sub, into_kernels)


def _scans(closed):
    """(forward scan, backward scan) of a train step over one scanned run:
    the backward runs in reverse."""
    scans = [e for e in _walk(closed.jaxpr) if e.primitive.name == "scan"
             and e.params["length"] >= 4]
    fwd = [e for e in scans if not e.params["reverse"]]
    bwd = [e for e in scans if e.params["reverse"]]
    assert len(fwd) == 1 and len(bwd) == 1, (len(fwd), len(bwd))
    return fwd[0], bwd[0]


def _source(jaxpr, var):
    """The variable ``var`` is a copy of: back through ``reduce_precision``
    (``jax.checkpoint`` puts one on each residual's producer) and through a
    nested ``jit`` that hands an input back as an output (``jnp.var``
    under a policy hands back its operand)."""
    made_by = {v: e for e in jaxpr.eqns for v in e.outvars}
    while var in made_by:
        eqn = made_by[var]
        if eqn.primitive.name == "reduce_precision":
            var = eqn.invars[0]
        elif eqn.primitive.name in ("jit", "pjit"):
            inner = eqn.params["jaxpr"].jaxpr
            out = inner.outvars[eqn.outvars.index(var)]
            if out not in inner.invars:
                break
            var = eqn.invars[inner.invars.index(out)]
        else:
            break
    return var


def _stacked(scan):
    """What a scan stacks over its iterations: its outputs after the
    carry, one for each distinct value (XLA merges two stacks of one
    value: the compiled GPT-2 step holds one, ``PERF.md`` PR 30)."""
    body = scan.params["jaxpr"].jaxpr
    sources = {_source(body, v): None
               for v in body.outvars[scan.params["num_carry"]:]}
    return [v.aval for v in sources]


def _dots(scan) -> int:
    return sum(e.primitive.name == "dot_general"
               for e in _walk(scan.params["jaxpr"].jaxpr))


def _loss_and_grads(net, x, y, key):
    def loss(p):
        return _stack_loss(net.conf, p, net.state, x, y, train=True,
                           key=key)[0]
    return jax.jit(jax.value_and_grad(loss))(net.params)


def test_named_scan_matches_unrolled_walk_with_dropout():
    """Loss and every gradient leaf of six scanned blocks, saved by name,
    against the unrolled walk: float32, dropout on (its keys are scanned),
    to 1e-6 of each leaf's largest element."""
    # (1e-6 of the leaf: the two programs associate float32 sums alike
    # but for what XLA fuses otherwise)
    x, y = _seq_batch()
    kw = dict(block_kw={"dropout": 0.8})
    scanned, unrolled = _blocks(**kw), _blocks(scan_layers=False, **kw)
    key = jax.random.PRNGKey(11)
    before = _runs()
    loss_s, g_s = _loss_and_grads(scanned, x, y, key)
    assert _runs_since(before) == {("TransformerBlock", "named"): 1}
    loss_u, g_u = _loss_and_grads(unrolled, x, y, key)
    # dropout is on: another key gives another loss
    assert float(_loss_and_grads(unrolled, x, y,
                                 jax.random.PRNGKey(12))[0]) != float(loss_u)
    assert float(loss_s) == pytest.approx(float(loss_u), rel=1e-6)
    leaves_s = jax.tree_util.tree_leaves_with_path(g_s)
    leaves_u = jax.tree_util.tree_leaves(g_u)
    assert len(leaves_s) == len(leaves_u) > 6 * 12
    # a key bias has no gradient but round-off (softmax does not see it):
    # such a leaf is held to the median leaf's size
    sizes = [float(jnp.max(jnp.abs(b))) for b in leaves_u]
    floor = float(np.median(sizes))
    for (path, a), b, size in zip(leaves_s, leaves_u, sizes):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=0,
                                   atol=1e-6 * max(size, floor),
                                   err_msg=str(path))


def test_census_of_what_a_named_scan_stacks():
    """Under bfloat16 compute, with the kernels in (traced, not lowered:
    the CPU cannot run them), the forward scan stacks the block's input and
    at most one array a declared name, none of them a float32 ``[t, e]``
    activation, and the backward body multiplies twice a forward matmul,
    no more: nothing dear is recomputed."""
    t, e = 128, 128
    net = _blocks(t=t, e=e, heads=2, precision="bfloat16",
                  block_kw={"attn_impl": "flash"})
    x, y = _seq_batch(t=t, e=e, rows=2)
    fwd, bwd = _scans(_step_jaxpr(net, x, y))
    saved = _stacked(fwd)
    carry_leaves = 1
    # the gate is declared and this block has none
    assert carry_leaves < len(saved) <= \
        len(NAMES) - 1 + carry_leaves
    for a in saved:
        assert not (a.dtype == jnp.float32 and a.shape[-2:] == (t, e)), a
    assert sum(a.dtype == jnp.bfloat16 and a.shape == (2, t, e)
               for a in saved) == 2          # the input and the middle
    kernels = [k.params["name"] for k in _walk(bwd.params["jaxpr"].jaxpr)
               if k.primitive.name == "pallas_call"]
    assert sorted(kernels) == ["flash_bwd_dkv", "flash_bwd_dq"]
    assert _dots(fwd) == 6                   # q, k, v, o, up, down
    assert _dots(bwd) <= 2 * _dots(fwd)


def test_without_the_names_the_scan_stacks_more(monkeypatch):
    """The control of the census: the same step with the block's names
    ignored stacks every intermediate the backward reads, float32 ones
    among them."""
    t, e = 128, 128
    x, y = _seq_batch(t=t, e=e, rows=2)
    kw = dict(t=t, e=e, heads=2, precision="bfloat16",
              block_kw={"attn_impl": "flash"})
    named = _stacked(_scans(_step_jaxpr(_blocks(**kw), x, y))[0])
    monkeypatch.setattr(TransformerBlock, "SAVED_NAMES", ())
    everything = _stacked(_scans(_step_jaxpr(_blocks(**kw), x, y))[0])

    def nbytes(avals):
        return sum(int(np.prod(a.shape)) * a.dtype.itemsize for a in avals)
    assert len(everything) > len(named)
    assert nbytes(everything) > 2 * nbytes(named)
    assert any(a.dtype == jnp.float32 and a.shape[-2:] == (t, e)
               for a in everything)


@pytest.mark.parametrize("build,batch", [(_dense, _flat_batch),
                                         (_lstm, _seq_batch)],
                         ids=["dense", "lstm"])
def test_layers_that_name_nothing_keep_the_plain_scan(build, batch):
    """A scanned Dense stack and a scanned LSTM stack (whose inner scan
    over time must not be replayed) hold no checkpoint, in the jaxpr or in
    the lowered text."""
    net = build()
    x, y = batch()
    before = _runs()
    closed = _step_jaxpr(net, x, y)
    assert _runs_since(before) == {(type(net.conf.layers[0]).__name__,
                                    "all"): 1}
    assert any(e.primitive.name == "scan" and e.params["length"] >= 6
               for e in _walk(closed.jaxpr))
    assert not any(e.primitive.name in CHECKPOINT + ("name",)
                   for e in _walk(closed.jaxpr))
    assert "checkpoint" not in str(closed)
    step = _build_train_step(net.conf, net._tx, False)
    text = jax.jit(step).lower(net.params, net.state, net.opt_state,
                               net._rng, x, y, None, None).as_text()
    assert "checkpoint" not in text


def test_a_named_scan_is_a_checkpoint_in_the_jaxpr():
    """The control of the test above: the transformer's step does hold
    one, and the names it saves by."""
    eqns = list(_walk(_step_jaxpr(_blocks(), *_seq_batch()).jaxpr))
    assert any(e.primitive.name in CHECKPOINT for e in eqns)
    assert {e.params["name"] for e in eqns if e.primitive.name == "name"} \
        <= set(NAMES)


@pytest.mark.parametrize("build,batch", [(_blocks, _seq_batch),
                                         (_dense, _flat_batch)],
                         ids=["transformer", "dense"])
def test_remat_still_saves_the_input_alone(build, batch):
    """``cache_mode='remat'``: the scan stacks the carry's one leaf, names
    or no names."""
    net = build(cache_mode="remat")
    x, y = batch()
    before = _runs()
    fwd, _ = _scans(_step_jaxpr(net, x, y))
    assert _runs_since(before) == {(type(net.conf.layers[0]).__name__,
                                    "input"): 1}
    saved = _stacked(fwd)
    assert len(saved) == 1
    assert saved[0].shape == x.shape


def test_a_block_of_sequence_parallel_attention_declares_nothing():
    """Ring and all-to-all attention are loops of collectives: their
    blocks keep the plain scan, which replays nothing."""
    assert len(NAMES) == 8 and len(set(NAMES)) == 8
    for impl in ("ring", "ulysses"):
        assert TransformerBlock(attn_impl=impl).SAVED_NAMES == ()
    for impl in ("auto", "flash", "reference"):
        assert TransformerBlock(attn_impl=impl).SAVED_NAMES == NAMES


def _runs():
    c = default_registry().get("scan_runs_traced_total")
    if c is None:
        return {}
    return {labels: float(child.value() if callable(child.value)
                          else child.value) for labels, child in c.samples()}


def _runs_since(before):
    return {k: int(v - before.get(k, 0.0)) for k, v in _runs().items()
            if v != before.get(k, 0.0)}


def test_counter_reads_one_run_of_each_kind_through_fit():
    """``scan_runs_traced_total{layer, saved}``: one ``named`` run for the
    transformer, one ``all`` run for the MLP, one ``input`` run under
    remat, each counted when ``fit`` traces its step."""
    before = _runs()
    _blocks().fit(*_seq_batch())
    _dense().fit(*_flat_batch())
    _blocks(cache_mode="remat").fit(*_seq_batch())
    assert _runs_since(before) == {("TransformerBlock", "named"): 1,
                                   ("DenseLayer", "all"): 1,
                                   ("TransformerBlock", "input"): 1}
