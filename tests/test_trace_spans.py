"""The program names its own time: host spans (``jax.profiler.
TraceAnnotation``, prefix ``dl4j.``) at the places every training entry
passes through, the collector's callback, and the name scopes of the step
program.  Spans are always written, so the tests swap the annotation for
a stub that records; scopes are metadata, so the tests read the lowered
step's text and compare the jaxpr with the scopes taken out.
"""
import contextlib
import gc
import json
import os
import re

import jax
import numpy as np
import pytest

from deeplearning4j_tpu.nn import computation_graph as graph_mod
from deeplearning4j_tpu.nn import multilayer as stack_mod
from deeplearning4j_tpu.nn.computation_graph import ComputationGraph
from deeplearning4j_tpu.nn.conf.input_type import InputType
from deeplearning4j_tpu.nn.conf.multi_layer import NeuralNetConfiguration
from deeplearning4j_tpu.nn.conf.updaters import Adam
from deeplearning4j_tpu.nn.layers.feedforward import DenseLayer, OutputLayer
from deeplearning4j_tpu.nn.layers.recurrent import LSTM, RnnOutputLayer
from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
from deeplearning4j_tpu.observability.registry import default_registry
from deeplearning4j_tpu.parallel import (ParallelWrapper, ShardedTrainer,
                                         make_mesh)
from deeplearning4j_tpu.train.listeners import TrainingListener

STEPS = 18          # the step profiler fences every 16th step of a fit


def stack_net(seed=42, depth=1):
    lb = (NeuralNetConfiguration.builder().seed(seed)
          .updater(Adam(learning_rate=0.02)).list())
    for _ in range(depth):
        lb = lb.layer(DenseLayer(n_out=8, activation="tanh"))
    conf = (lb.layer(OutputLayer(n_out=3, activation="softmax",
                                 loss="mcxent"))
            .set_input_type(InputType.feed_forward(4)).build())
    return MultiLayerNetwork(conf).init()


def graph_net(seed=42):
    conf = (NeuralNetConfiguration.builder().seed(seed)
            .updater(Adam(learning_rate=0.02)).graph_builder()
            .add_inputs("in")
            .add_layer("d0", DenseLayer(n_out=8, activation="tanh"), "in")
            .add_layer("out", OutputLayer(n_out=3, activation="softmax",
                                          loss="mcxent"), "d0")
            .set_outputs("out")
            .set_input_types(InputType.feed_forward(4)).build())
    return ComputationGraph(conf).init()


NETS = {"stack": stack_net, "graph": graph_net}


def wrapper_net(seed=42):
    return ParallelWrapper(stack_net(seed), make_mesh(dp=2))


def sharded_net(seed=42):
    return ShardedTrainer(stack_net(seed), make_mesh(dp=2),
                          min_shard_size=0)


# everything with a ``fit``: the two containers and the two mesh wrappers
# (two of the rig's eight host devices); one loop runs them all
FITS = {**NETS, "wrapper": wrapper_net, "sharded": sharded_net}


def batches(n=STEPS, batch=8, seed=0):
    rng = np.random.default_rng(seed)
    return [(rng.standard_normal((batch, 4), dtype=np.float32),
             np.eye(3, dtype=np.float32)[rng.integers(0, 3, batch)])
            for _ in range(n)]


def leaves(net):
    return [np.asarray(a) for a in jax.tree_util.tree_leaves(net.params)]


class Recorded:
    """What the stub saw: ``(name, parent's name)`` in opening order."""

    def __init__(self):
        self.spans, self._open = [], []

    def names(self):
        return [name for name, _ in self.spans]

    def parents(self, name):
        return {parent for n, parent in self.spans if n == name}


@pytest.fixture
def recorded(monkeypatch):
    rec = Recorded()

    class Annotation:
        def __init__(self, name, **_attributes):
            self.name = name

        def __enter__(self):
            rec.spans.append((self.name,
                              rec._open[-1] if rec._open else None))
            rec._open.append(self.name)
            return self

        def __exit__(self, *_exc):
            assert rec._open.pop() == self.name, "spans must nest"
            return False

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", Annotation)
    return rec


def collector_callbacks():
    return [c for c in gc.callbacks
            if getattr(c, "__name__", "") == "_on_collection"]


# ------------------------------------------------------------------ fit
@pytest.mark.parametrize("kind", sorted(FITS))
def test_fit_writes_every_span_under_the_entry(kind, recorded):
    net = FITS[kind]()
    net.fit(batches())
    names = recorded.names()
    # the container's init() came first, a span of its own outside the entry
    assert names[:2] == ["dl4j.init", "dl4j.fit"]
    assert recorded.parents("dl4j.init") == {None}
    assert recorded.parents("dl4j.fit") == {None}
    assert names.count("dl4j.fit") == 1 and names.count("dl4j.init") == 1
    # one look at the iterator a step, and the one that finds it empty
    assert names.count("dl4j.input_wait") == STEPS + 1
    assert names.count("dl4j.h2d") == STEPS
    assert names.count("dl4j.call.train_step") == STEPS
    for name in ("dl4j.input_wait", "dl4j.h2d", "dl4j.call.train_step",
                 "dl4j.profiler_fence", "dl4j.sync"):
        assert recorded.parents(name) == {"dl4j.fit"}, name
    # the end of the epoch and the end of the call
    assert names.count("dl4j.sync") == 2
    assert not collector_callbacks()


def test_a_steady_step_is_one_call_and_one_wait_and_the_16th_fences(
        recorded):
    stack_net().fit(batches())
    per_step, step = [], None
    for name, _parent in recorded.spans:
        if name == "dl4j.input_wait":
            step = []
            per_step.append(step)
        elif step is not None and name != "dl4j.gc":
            step.append(name)
    steady = ["dl4j.h2d", "dl4j.call.train_step", "dl4j.window_wait"]
    # step 1 only fills the window; step 16 is fenced, and the fence
    # drains the window itself, so its own push waits for nothing
    assert per_step[0] == steady[:2]
    assert per_step[1:15] == [steady] * 14
    assert per_step[15] == ["dl4j.h2d", "dl4j.call.train_step",
                            "dl4j.profiler_fence", "dl4j.window_wait"]
    assert recorded.parents("dl4j.window_wait") == {"dl4j.fit",
                                                    "dl4j.profiler_fence"}
    assert per_step[16] == steady
    assert recorded.names().count("dl4j.profiler_fence") == 1


@pytest.mark.parametrize("kind", sorted(NETS))
def test_fit_on_device_writes_its_spans(kind, recorded):
    net = NETS[kind]()
    x, y = batches(1, batch=32)[0]
    net.fit_on_device(x, y, batch_size=8)
    names = recorded.names()
    assert names[:2] == ["dl4j.init", "dl4j.fit_on_device"]
    assert names.count("dl4j.call.epoch_scan") == 1
    assert names.count("dl4j.sync") == 1
    assert "dl4j.fit" not in names and "dl4j.window_wait" not in names
    for name in ("dl4j.call.epoch_scan", "dl4j.sync"):
        assert recorded.parents(name) == {"dl4j.fit_on_device"}
    assert not collector_callbacks()


def test_a_compile_falls_inside_the_call_span(recorded):
    """The first call of a program traces and compiles inside
    ``dl4j.call.<name>``; nothing zero-length is written after it."""
    net = stack_net(seed=7, depth=2)
    net.fit(batches(2))
    assert "xla.compile" not in recorded.names()
    assert recorded.names().count("dl4j.call.train_step") == 2


# ------------------------------------------------------------ collector
class CollectOnce(TrainingListener):
    def __init__(self):
        self.seen = []

    def iteration_done(self, model, iteration, epoch):
        if iteration == 2:
            self.seen = collector_callbacks()
            gc.collect()


def test_a_collection_is_a_span_and_adds_to_the_pause_counter(recorded):
    reg = default_registry()
    net = stack_net()
    listener = CollectOnce()
    net.add_listeners(listener)

    def pause():
        inst = reg.get("host_gc_pause_seconds_total")
        return 0.0 if inst is None else inst.value

    before = pause()
    net.fit(batches(4))
    assert len(listener.seen) == 1
    assert "dl4j.gc" in recorded.names()
    assert pause() > before
    assert not collector_callbacks()


@pytest.mark.parametrize("kind", sorted(FITS))
def test_the_collectors_callback_goes_when_fit_raises(kind):
    net = FITS[kind]()

    def broken():
        yield batches(1)[0]
        raise RuntimeError("the iterator broke")

    with pytest.raises(RuntimeError, match="the iterator broke"):
        net.fit(broken())
    assert not collector_callbacks()
    if kind in NETS:
        with pytest.raises(ValueError):
            x, y = batches(1, batch=4)[0]
            net.fit_on_device(x, y, batch_size=8)  # larger than the data
        assert not collector_callbacks()


def test_nested_entries_share_one_callback():
    from deeplearning4j_tpu.observability.tracer import training_entry
    seen = []

    @training_entry("dl4j.outer")
    def outer():
        seen.append(len(collector_callbacks()))
        inner()
        seen.append(len(collector_callbacks()))

    @training_entry("dl4j.inner")
    def inner():
        seen.append(len(collector_callbacks()))

    outer()
    assert seen == [1, 1, 1] and not collector_callbacks()


# ------------------------------------------- one loop, one step builder
def test_a_step_that_raises_in_the_wrappers_fit_leaves_nothing_behind(
        monkeypatch):
    """The wrapper's ``fit`` is the one loop: a step that raises abandons
    the window's tokens without waiting for them and flushes the profiler
    on the way out."""
    from deeplearning4j_tpu.nn import _common
    from deeplearning4j_tpu.observability import profiler as profiler_mod
    windows, profilers = [], []

    class Window(_common.DispatchWindow):
        def __init__(self, *args, **kwargs):
            super().__init__(*args, **kwargs)
            windows.append(self)

    def profiler_for(program, **kwargs):
        profilers.append(profiler_mod.StepProfiler(program, **kwargs))
        return profilers[-1]

    monkeypatch.setattr(_common, "DispatchWindow", Window)
    monkeypatch.setattr(profiler_mod, "step_profiler_for", profiler_for)
    wrapper = wrapper_net()
    step, calls = wrapper._get_step(), []

    def third_call_raises(*args):
        calls.append(1)
        if len(calls) == 3:
            raise RuntimeError("the step broke")
        return step(*args)

    wrapper._step = third_call_raises
    with pytest.raises(RuntimeError, match="the step broke"):
        wrapper.fit(batches(6))
    (window,), (prof,) = windows, profilers
    assert wrapper.model.iteration == 2
    assert len(window) == 0
    assert prof.steps == 2 and prof._buf == []
    assert wrapper.model._stepprof is None
    assert not collector_callbacks()


@pytest.mark.parametrize("kind", sorted(FITS))
def test_every_fit_counts_its_steps_and_clocks_the_feed(kind):
    from deeplearning4j_tpu.observability.registry import (
        MetricsRegistry, set_default_registry)
    previous = set_default_registry(MetricsRegistry())
    try:
        net = FITS[kind]()
        net.fit(batches(5, batch=8))
        reg = default_registry()
        assert reg.get("training_steps_total").value == 5
        assert reg.get("training_examples_total").value == 40
        steps = reg.get("training_step_seconds")
        assert steps.labels("compile").count \
            + steps.labels("steady").count == 5
        assert reg.get("training_etl_seconds").labels("fetch").count == 5
    finally:
        set_default_registry(previous)
    model = getattr(net, "model", net)
    assert model.iteration == 5 and model.last_batch_size == 8
    assert model.last_etl_ms >= 0.0


def test_a_stack_and_a_graph_of_the_same_layers_train_alike():
    """One builder: the same dense net written as a stack and as a graph,
    from one seed over the same batches, holds the same parameters bit for
    bit after three steps."""
    stack, graph = stack_net(seed=23), graph_net(seed=23)
    names = {"layer_0": "d0", "layer_1": "out"}
    for layer, vertex in names.items():
        for leaf, a in stack.params[layer].items():
            np.testing.assert_array_equal(
                np.asarray(a), np.asarray(graph.params[vertex][leaf]))
    feed = batches(3)
    stack.fit(feed)
    graph.fit(feed)
    assert stack.iteration == graph.iteration == 3
    assert stack.get_score() == graph.get_score()
    for layer, vertex in names.items():
        for leaf, a in stack.params[layer].items():
            np.testing.assert_array_equal(
                np.asarray(a), np.asarray(graph.params[vertex][leaf]))


# ------------------------- what the one loop walks: tBPTT, checkpoints
CHUNKS = 3          # a tBPTT batch of 12 steps walked in chunks of 4


def tbptt_net(seed=7, tbptt=True):
    lb = (NeuralNetConfiguration.builder().seed(seed)
          .updater(Adam(learning_rate=0.02)).list()
          .layer(LSTM(n_out=4))
          .layer(RnnOutputLayer(n_out=2, activation="softmax",
                                loss="mcxent")))
    if tbptt:
        lb.backprop_type("tbptt", fwd=4, back=4)
    conf = lb.set_input_type(InputType.recurrent(3, 4 * CHUNKS)).build()
    return MultiLayerNetwork(conf).init()


def sequences(n, batch=8, seed=0):
    rng = np.random.default_rng(seed)
    return [(rng.standard_normal((batch, 4 * CHUNKS, 3), dtype=np.float32),
             np.eye(2, dtype=np.float32)[
                 rng.integers(0, 2, (batch, 4 * CHUNKS))])
            for _ in range(n)]


TBPTT_FITS = {
    "stack": tbptt_net,
    "wrapper": lambda: ParallelWrapper(tbptt_net(), make_mesh(dp=2)),
    "sharded": lambda: ShardedTrainer(tbptt_net(), make_mesh(dp=2),
                                      min_shard_size=0),
}


@pytest.mark.parametrize("kind", sorted(TBPTT_FITS))
def test_a_tbptt_stack_through_each_loop(kind, recorded):
    """The stack hands the loop its tBPTT walk: a batch longer than a
    chunk trains chunk by chunk, one optimizer step a chunk.  The
    wrappers hand it no walk, so there the same stack trains each batch
    as one whole-sequence step, as a stack without tBPTT would.  Either
    way the loop counts one step and one batch of examples a batch, and
    writes one ``dl4j.input_wait`` a batch under ``dl4j.fit``."""
    from deeplearning4j_tpu.observability.registry import (
        MetricsRegistry, set_default_registry)
    feed = sequences(3)
    previous = set_default_registry(MetricsRegistry())
    try:
        net = TBPTT_FITS[kind]()
        net.fit(feed)
        reg = default_registry()
        assert reg.get("training_steps_total").value == 3
        assert reg.get("training_examples_total").value == 24
    finally:
        set_default_registry(previous)
    assert recorded.names().count("dl4j.input_wait") == 4   # + the end
    assert recorded.parents("dl4j.input_wait") == {"dl4j.fit"}
    model = getattr(net, "model", net)
    if kind == "stack":
        assert model.iteration == 3 * CHUNKS
        return
    assert model.iteration == 3
    whole = tbptt_net(tbptt=False)
    whole.fit(feed)
    np.testing.assert_allclose(model.params_flat(), whole.params_flat(),
                               rtol=1e-5, atol=1e-6)


CKPT_NETS = {"stack": (stack_net, lambda: batches(7), 3),
             "graph": (graph_net, lambda: batches(7), 3),
             # every CHUNKS-th iteration ends a batch: saved after two
             "tbptt": (tbptt_net, lambda: sequences(5), 2 * CHUNKS)}


@pytest.mark.parametrize("kind", sorted(CKPT_NETS))
def test_a_checkpoint_boundary_resumes_at_its_cursor(kind, tmp_path):
    """A save at a checkpoint boundary drains the dispatch window first,
    so it holds finished steps and a cursor in batches: a run resumed
    from it skips what was consumed and ends on the uninterrupted run's
    parameters bit for bit, for the stack, the graph and a tBPTT walk."""
    from deeplearning4j_tpu.faulttolerance import CheckpointConfig
    build, feed_of, every = CKPT_NETS[kind]
    feed = feed_of()
    whole = build()
    whole.fit(feed)
    saved = build()
    cfg = CheckpointConfig(directory=str(tmp_path),
                           save_every_n_iterations=every, keep_last=10,
                           background=False)
    saved.fit(feed, checkpoint=cfg)
    step, path, _ = cfg.resolve().checkpoints()[0]
    assert step == every
    with open(os.path.join(path, "training_state.json")) as f:
        cursor = json.load(f)["cursor"]
    assert cursor == {"fit_epoch": 0,
                      "batch_seq": 2 if kind == "tbptt" else every}
    resumed = build()
    resumed.fit(feed, resume_from=path)
    assert resumed.iteration == whole.iteration
    for a, b in zip(leaves(whole), leaves(resumed)):
        np.testing.assert_array_equal(a, b)


# ------------------------------------------------- nothing else changes
@pytest.mark.parametrize("kind", sorted(FITS))
def test_spans_change_no_number(kind, recorded, monkeypatch):
    stubbed = FITS[kind]()
    stubbed.fit(batches())
    assert recorded.spans
    monkeypatch.undo()                  # the profiler's own annotation
    plain = FITS[kind]()
    plain.fit(batches())
    assert plain.get_score() == stubbed.get_score()
    for a, b in zip(leaves(plain), leaves(stubbed)):
        np.testing.assert_array_equal(a, b)


def lowered_text(net):
    step = net._get_jitted("train_step")
    return step.audit_lower(step.audit_specs()[-1]).as_text(debug_info=True)


@pytest.mark.parametrize("kind,layers", [
    ("stack", ("DenseLayer", "OutputLayer")),
    ("graph", ("DenseLayer", "OutputLayer"))])
def test_the_lowered_step_names_its_parts(kind, layers):
    net = NETS[kind](seed=11)
    net.fit(batches(1))
    text = lowered_text(net)
    for scope in ("jvp(forward)/", "transpose(jvp(forward))/", "grad_post/",
                  "optimizer/") + tuple(
                      f"jvp(forward)/{name}/" for name in layers):
        assert scope in text, scope


def test_a_scanned_run_names_its_layer_inside_the_loop():
    """The scanned body is lowered as a function of its own, whose
    operations carry the layer's scope alone; the compiler's inliner puts
    the caller's scopes in front, so read the compiled program."""
    net = stack_net(seed=13, depth=6)          # six equal layers: a scan
    net.fit(batches(1))
    step = net._get_jitted("train_step")
    text = step.audit_lower(step.audit_specs()[-1]).compile().as_text()
    inside = r"/while/body/(closed_call/)?DenseLayer/dot_general"
    assert re.search(r"/jvp\(forward\)" + inside, text)
    assert re.search(r"/transpose\(jvp\(forward\)\)" + inside, text)


@contextlib.contextmanager
def no_scope(_name):
    yield


@pytest.mark.parametrize("kind", sorted(NETS))
def test_scopes_are_metadata_the_jaxpr_is_the_same(kind, monkeypatch):
    """The step with its scopes and the step traced with
    ``jax.named_scope`` doing nothing have the same equations, and the
    builder donates what it donated (checked against the parent commit's
    builder by hand, PERF.md section 6)."""
    net = NETS[kind](seed=17)
    net.fit(batches(1))
    entry = net._get_jitted("train_step")
    args, kwargs = entry.audit_specs()[-1]
    assert entry.donate_argnums == (0, 1, 2, 3)

    def jaxpr():
        if kind == "stack":
            step = stack_mod._build_train_step(net.conf, net._tx, False)
        else:
            step = graph_mod._build_graph_train_step(net.conf, net._tx)
        return str(jax.make_jaxpr(step)(*args, **kwargs))

    scoped = jaxpr()
    monkeypatch.setattr(jax, "named_scope", no_scope)
    assert jaxpr() == scoped
