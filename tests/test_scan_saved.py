"""What a scanned run of layers saves for the backward pass
(``nn/scan_layers.run_scan``): a layer that names the values dear to
recompute (``TransformerBlock.SAVED_NAMES``) runs under a checkpoint policy
that saves those and the block's input; a layer that names nothing keeps
``lax.scan``'s own program; ``cache_mode='remat'`` saves the input and as
many of the names, in their order of worth, as the device has room for:
none where it reports no limit (the CPU), which is the program remat had.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from deeplearning4j_tpu import (InputType, MultiLayerNetwork,
                                NeuralNetConfiguration)
from deeplearning4j_tpu.nn import scan_layers
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


# ---- cache_mode="remat" keeps what fits ----------------------------------

N_RUN = 6
EVA = dict(attention="eva", window=4, chunk=2, gated=True, has_bias=False,
           norm="rms", positions="rotary")


def _run_and_walk(lc, room, x, key):
    """Loss and gradients (input and every layer's parameters) of
    ``N_RUN`` copies of ``lc`` through ``run_scan`` under remat with
    ``room`` bytes, and through the unrolled walk's loop."""
    itype = InputType.recurrent(x.shape[-1], x.shape[1])
    lc.set_n_in(itype)
    ps = [lc.init(jax.random.fold_in(jax.random.PRNGKey(3), i),
                  itype)["params"] for i in range(N_RUN)]

    def scanned(ps, x):
        h, _ = scan_layers.run_scan(lc, ps, [{}] * N_RUN, x, key, 0,
                                    train=True, mask=None, remat=True,
                                    room=room)
        return jnp.sum(h * h)

    def unrolled(ps, x):
        for i, p in enumerate(ps):
            x, _ = lc.apply({"params": p, "state": {}}, x, train=True,
                            key=jax.random.fold_in(key, i), mask=None)
        return jnp.sum(x * x)
    return (jax.jit(jax.value_and_grad(scanned, (0, 1)))(ps, x),
            jax.jit(jax.value_and_grad(unrolled, (0, 1)))(ps, x), ps)


def _sizes(lc, ps, x, key):
    """``({name: bytes over the run}, own stacks, reserve)`` as
    ``run_scan`` counts them for ``N_RUN`` copies of ``lc``."""
    def body(c, per):
        return lc.apply({"params": per[0], "state": per[1]}, c, train=True,
                        key=per[2], mask=None)
    per_layer, reads = scan_layers.body_census(body, x, (ps[0], {}, key))
    own = 2 * N_RUN * scan_layers.tree_bytes(ps[0]) \
        + N_RUN * scan_layers.tree_bytes(x)
    return ({n: per_layer[n] * N_RUN for n in lc.SAVED_NAMES
             if n in per_layer}, own, int(scan_layers.RESERVE * reads))


@pytest.mark.parametrize("keeps,label", [
    ((), "input"), (("attn_q", "attn_k"), "some"), (None, "named")],
    ids=["empty", "partial", "full"])
def test_remat_run_matches_unrolled_walk_whatever_it_keeps(keeps, label):
    """Loss and every gradient leaf of six scanned blocks under remat,
    keeping nothing, two names and every name, against the unrolled loop:
    float32, dropout on, to 1e-6 of each leaf's largest element."""
    x, _ = _seq_batch()
    key = jax.random.PRNGKey(11)
    lc = TransformerBlock(n_heads=2, dropout=0.8)
    lc.set_n_in(InputType.recurrent(E, T))
    probe = lc.init(jax.random.PRNGKey(0), InputType.recurrent(E, T))
    sizes, own, reserve = _sizes(lc, [probe["params"]], x, key)
    room = own + reserve + (sum(sizes.values()) if keeps is None
                            else sum(sizes[n] for n in keeps))
    before = _runs()
    (loss_s, g_s), (loss_u, g_u), _ = _run_and_walk(lc, room, x, key)
    assert _runs_since(before) == {("TransformerBlock", label): 1}
    gauge = default_registry().get("scan_saved_stack_bytes")
    assert gauge.labels("TransformerBlock").value == (
        sum(sizes.values()) if keeps is None
        else sum(sizes[n] for n in keeps))
    assert float(loss_s) == pytest.approx(float(loss_u), rel=1e-6)
    leaves_s = jax.tree_util.tree_leaves_with_path(g_s)
    leaves_u = jax.tree_util.tree_leaves(g_u)
    assert len(leaves_s) == len(leaves_u) > N_RUN * 12
    sizes_u = [float(jnp.max(jnp.abs(b))) for b in leaves_u]
    floor = float(np.median(sizes_u))
    for (path, a), b, size in zip(leaves_s, leaves_u, sizes_u):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=0,
                                   atol=1e-6 * max(size, floor),
                                   err_msg=str(path))


def test_census_of_a_partial_remat_scan_is_the_input_plus_what_it_keeps(
        monkeypatch):
    """With room for q and k beside the reserve, the forward scan of the
    train step stacks the block's input, those two and nothing else, byte
    for byte what the census said."""
    net = _blocks(cache_mode="remat")
    x, y = _seq_batch()
    lc = net.conf.layers[0]
    sizes, own, reserve = _sizes(lc, [net.params["layer_0"]], x,
                                 jax.random.PRNGKey(0))
    room = own + reserve + sizes["attn_q"] + sizes["attn_k"]
    monkeypatch.setattr(scan_layers, "free_bytes", lambda: room)
    before = _runs()
    fwd, _ = _scans(_step_jaxpr(net, x, y))
    assert _runs_since(before) == {("TransformerBlock", "some"): 1}
    saved = _stacked(fwd)

    def nbytes(a):
        return int(np.prod(a.shape)) * a.dtype.itemsize
    assert sorted(nbytes(a) * N_RUN for a in saved) == sorted(
        [scan_layers.tree_bytes(x) * N_RUN, sizes["attn_q"],
         sizes["attn_k"]])


@pytest.mark.parametrize("room,kept", [
    (0, ()), (-5, ()), (3, ()), (9, ("attn_q", "attn_k")),
    (10, ("mlp_up",)),
    # the gate does not fit beside its twin: passed over, q taken
    (15, ("mlp_up", "attn_q")), (19, ("mlp_up", "attn_q", "attn_k")),
    (20, ("mlp_up", "mlp_gate")), (10 ** 6, ("mlp_up", "mlp_gate",
                                              "attn_q", "attn_k"))])
def test_greedy_choice_passes_over_what_does_not_fit(room, kept):
    sizes = {"mlp_up": 10, "mlp_gate": 10, "attn_q": 4, "attn_k": 4}
    assert scan_layers.fitting(sizes, room) == kept


def test_an_eva_block_lists_the_pooled_three_first():
    """The pooled keys, values and pooling weights are a sixteenth (here
    1 / chunk) of k and v: cheapest to keep, so first in the order; a block
    of full attention does not list them, and its trace does not hold
    them."""
    lc = TransformerBlock(n_heads=2, **EVA)
    assert lc.SAVED_NAMES[:3] == ("eva_ks", "eva_vs", "eva_a")
    assert lc.SAVED_NAMES[3:] == NAMES
    assert NAMES == ("attn_q", "attn_k", "attn_v", "mlp_up", "mlp_gate",
                     "attn_lse", "attn_out", "block_mid")
    x, _ = _seq_batch()
    itype = InputType.recurrent(E, T)
    lc.set_n_in(itype)
    p = lc.init(jax.random.PRNGKey(0), itype)["params"]
    sizes, _, _ = _sizes(lc, [p], x, None)
    assert sizes["eva_ks"] == sizes["eva_vs"] == sizes["attn_k"] // 2
    assert sizes["eva_a"] * (E // 2) == sizes["attn_k"]
    assert sizes["mlp_gate"] == sizes["mlp_up"]
    assert list(sizes)[:3] == ["eva_ks", "eva_vs", "eva_a"]


def test_a_layer_without_names_under_remat_keeps_the_bare_checkpoint(
        monkeypatch):
    """However much room there is, a Dense run under remat is one
    ``jax.checkpoint`` with no policy: the input alone is stacked."""
    monkeypatch.setattr(scan_layers, "free_bytes", lambda: 10 ** 12)
    net = _dense(cache_mode="remat")
    before = _runs()
    closed = _step_jaxpr(net, *_flat_batch())
    assert _runs_since(before) == {("DenseLayer", "input"): 1}
    assert len(_stacked(_scans(closed)[0])) == 1
    remats = [e for e in _walk(closed.jaxpr)
              if e.primitive.name in CHECKPOINT]
    assert remats and all(e.params["policy"] is None for e in remats)
    assert all(e.params["prevent_cse"] for e in remats)


def test_fit_counts_a_partial_run_and_gauges_its_bytes(monkeypatch):
    """Through ``fit`` on a device that reports a limit:
    ``scan_runs_traced_total{saved="some"}`` ticks once and
    ``scan_saved_stack_bytes`` reads the bytes of the kept names; what the
    step holds (parameters, updater state, batch) has come off the limit."""
    net = _blocks(cache_mode="remat")
    x, y = _seq_batch()
    lc = net.conf.layers[0]
    sizes, own, reserve = _sizes(lc, [net.params["layer_0"]], x,
                                 jax.random.PRNGKey(0))
    held = scan_layers.tree_bytes((net.params, net.state, net.opt_state,
                                   net._rng, x, y))
    want = sizes["attn_q"] + sizes["attn_k"]
    monkeypatch.setattr(scan_layers, "_device_limit",
                        lambda: held + own + reserve + want)
    before = _runs()
    net.fit(x, y)
    assert _runs_since(before) == {("TransformerBlock", "some"): 1}
    gauge = default_registry().get("scan_saved_stack_bytes")
    assert gauge.labels("TransformerBlock").value == want
    assert np.isfinite(net.score())


def test_what_a_step_holds_comes_off_the_limit_and_nests(monkeypatch):
    """``free_bytes`` is nought outside a step, the limit less the declared
    arguments inside one, less again inside a nested program and after a
    run has claimed its stacks; nought where the device reports none."""
    a = jnp.zeros((4, 8), jnp.float32)                 # 128 bytes
    assert scan_layers.free_bytes() == 0
    with scan_layers.holding(a):
        assert scan_layers.free_bytes() == 0           # the CPU: no limit
    monkeypatch.setattr(scan_layers, "_device_limit", lambda: 1000)
    assert scan_layers.free_bytes() == 0               # no step declared
    with scan_layers.holding(a, {"b": a, "n": None, "k": 3}):
        assert scan_layers.free_bytes() == 1000 - 256
        with scan_layers.holding([a]):
            assert scan_layers.free_bytes() == 1000 - 384
            scan_layers._claim(500)
            assert scan_layers.free_bytes() == 116
            scan_layers._claim(500)
            assert scan_layers.free_bytes() == 0
        assert scan_layers.free_bytes() == 1000 - 256
    assert scan_layers.free_bytes() == 0


def test_with_no_limit_the_remat_step_is_the_bare_checkpoint_to_the_letter(
        monkeypatch):
    """Where the device reports no limit the lowered remat step is the text
    of one bare ``jax.checkpoint`` around the body (what a block that names
    nothing gets, and what remat was before it kept anything); with room it
    is another program."""
    net = _blocks(cache_mode="remat")
    x, y = _seq_batch()

    def text():
        step = _build_train_step(net.conf, net._tx, False)
        return jax.jit(step).lower(net.params, net.state, net.opt_state,
                                   net._rng, x, y, None, None).as_text()
    ours = text()
    monkeypatch.setattr(scan_layers, "_device_limit", lambda: 10 ** 12)
    roomy = text()
    monkeypatch.setattr(TransformerBlock, "SAVED_NAMES", ())
    bare = text()
    assert ours == bare
    assert roomy != bare


def test_an_epoch_scan_declares_its_dataset_beside_the_step(monkeypatch):
    """``fit_on_device`` keeps the whole dataset on the device: what a
    remat run may spend is the limit less the step's arguments through
    ``fit``, and less the dataset too inside the epoch's program."""
    limit = 10 ** 9
    monkeypatch.setattr(scan_layers, "_device_limit", lambda: limit)
    free, real = [], scan_layers.free_bytes

    def spy():
        free.append(real())
        return free[-1]
    monkeypatch.setattr(scan_layers, "free_bytes", spy)
    net = _blocks(cache_mode="remat")
    x, y = _seq_batch(rows=12)
    state = scan_layers.tree_bytes((net.params, net.state, net.opt_state,
                                    net._rng))
    net.fit(x[:3], y[:3])
    assert free == [limit - state - scan_layers.tree_bytes((x[:3], y[:3]))]
    net.fit_on_device(x, y, batch_size=4, epochs=1)
    assert free[1:] == [limit - state
                        - scan_layers.tree_bytes((x[:4], y[:4]))
                        - scan_layers.tree_bytes((x, y))]


def test_a_step_the_compiler_refuses_is_traced_again_with_inputs_alone(
        monkeypatch, caplog):
    """The room is arithmetic; whether a program fits is the compiler's
    to say.  A train step that it refuses for memory after a remat run
    kept names is traced once more with the run's input alone, logged and
    counted, and stays so at the next batch shape; any other failure, and
    a refusal of a step that kept nothing, pass through."""
    monkeypatch.setattr(scan_layers, "_device_limit", lambda: 10 ** 12)
    count = scan_layers._count_run
    refusal = ("RESOURCE_EXHAUSTED: XLA:TPU compile permanent error. Ran "
               "out of memory in memory space hbm. Used 15.89G of 15.75G")

    def refusing(layer, saved, stack_bytes):
        count(layer, saved, stack_bytes)
        if saved != "input":
            raise RuntimeError(refusal)
    monkeypatch.setattr(scan_layers, "_count_run", refusing)
    net = _blocks(cache_mode="remat")
    x, y = _seq_batch()
    before = _runs()
    with caplog.at_level("WARNING", logger="deeplearning4j_tpu.nn"):
        net.fit(x, y)
    assert _runs_since(before) == {("TransformerBlock", "named"): 1,
                                   ("TransformerBlock", "input"): 1}
    assert "traced again with their inputs alone" in caplog.text
    fallbacks = default_registry().get("scan_fallbacks_total")
    assert fallbacks.labels("train_step").value == 1
    assert np.isfinite(net.score())
    # another batch shape: another trace of the same step, no new refusal
    before = _runs()
    net.fit(*_seq_batch(rows=2))
    assert _runs_since(before) == {("TransformerBlock", "input"): 1}
    assert fallbacks.labels("train_step").value == 1

    # a step that kept nothing and is refused all the same: not ours
    def always(layer, saved, stack_bytes):
        raise RuntimeError(refusal)
    monkeypatch.setattr(scan_layers, "_count_run", always)
    monkeypatch.setattr(scan_layers, "_device_limit", lambda: None)
    with pytest.raises(RuntimeError, match="RESOURCE_EXHAUSTED"):
        _blocks(cache_mode="remat", l2=1e-4).fit(x, y)
    assert fallbacks.labels("train_step").value == 1
    assert not scan_layers.refused_for_memory(RuntimeError(
        "RESOURCE_EXHAUSTED: Error allocating device buffer"))
