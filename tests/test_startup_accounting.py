"""Start-up accounted for from inside the program (ISSUE 38): the six
``jit_*`` counters that the listeners of ``nn/compile_cache`` keep from
JAX's own duration events, the ``fn`` they are counted under, the span
``dl4j.init`` and ``model_init_seconds_total``, the two gauges of the
import, and ``observability.startup_report()``.
"""
import logging
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax._src import monitoring

import deeplearning4j_tpu
from deeplearning4j_tpu import (ComputationGraph, InputType,
                                MultiLayerNetwork, NeuralNetConfiguration,
                                observability)
from deeplearning4j_tpu.nn import compile_cache as cc
from deeplearning4j_tpu.nn.conf.updaters import Sgd
from deeplearning4j_tpu.nn.layers.feedforward import (DenseLayer,
                                                      OutputLayer)
from deeplearning4j_tpu.observability import startup
from deeplearning4j_tpu.observability.registry import default_registry
from deeplearning4j_tpu.observability.tracer import (Tracer, open_entry,
                                                     set_default_tracer)

JIT = tuple(name for name, _text in startup.JIT_COUNTERS.values())
SECONDS = JIT[:4]


def stack_conf(seed, hidden=11):
    lb = (NeuralNetConfiguration.builder().seed(seed)
          .updater(Sgd(learning_rate=0.1)).list())
    lb.layer(DenseLayer(n_out=hidden, activation="tanh"))
    lb.layer(OutputLayer(n_out=3, activation="softmax", loss="mcxent"))
    return lb.set_input_type(InputType.feed_forward(5)).build()


def graph_conf(seed, hidden=11):
    gb = (NeuralNetConfiguration.builder().seed(seed)
          .updater(Sgd(learning_rate=0.1)).graph_builder()
          .add_inputs("in")
          .add_layer("d", DenseLayer(n_out=hidden, activation="tanh"), "in")
          .add_layer("out", OutputLayer(n_out=3, activation="softmax",
                                        loss="mcxent"), "d")
          .set_outputs("out")
          .set_input_types(InputType.feed_forward(5)))
    return gb.build()


# a topology of its own for every test that counts traces: the seed is in
# the signature, and the process-global trace cache serves an equal one
CONTAINERS = [
    pytest.param(lambda seed: MultiLayerNetwork(stack_conf(seed)),
                 id="MultiLayerNetwork"),
    pytest.param(lambda seed: ComputationGraph(graph_conf(seed)),
                 id="ComputationGraph")]


def batches(n, rows=16):
    rng = np.random.default_rng(0)
    x = rng.standard_normal((rows, 5)).astype(np.float32)
    y = np.eye(3, dtype=np.float32)[rng.integers(0, 3, rows)]
    return [(x, y)] * n


def by_fn(name):
    counter = default_registry().get(name)
    return {} if counter is None else {
        fn: child.value for (fn,), child in counter.samples()}


def total(name):
    return sum(by_fn(name).values())


def everything():
    return {name: by_fn(name) for name in JIT + ("training_compile_total",)}


# ------------------------------------------------------------- the label
@pytest.mark.parametrize("build", CONTAINERS)
def test_fn_is_init_inside_init_the_programs_name_in_its_call_eager_outside(
        build):
    before = {name: by_fn(name) for name in JIT}
    net = build(seed=3801)
    seen = []
    layer = net.conf.layers[0] if hasattr(net.conf, "layers") \
        else net.conf.vertices["d"]
    init = type(layer).init

    def watched(self, *a, **k):
        seen.append(open_entry.fn)
        return init(self, *a, **k)
    type(layer).init = watched
    try:
        net.init()
    finally:
        type(layer).init = init
    assert seen and set(seen) == {"init"}
    assert open_entry.fn is None
    grew = {name: by_fn(name).get("init", 0.0)
            - before[name].get("init", 0.0) for name in JIT}
    # an init draws its weights with jitted samplers: traced here, or
    # served by jax's in-memory cache when another test drew the shapes
    assert all(v >= 0.0 for v in grew.values())

    # a program nothing else in the suite makes, outside both: eager
    ones = jnp.ones((7,)).block_until_ready()  # its own program, made first
    eager_before = by_fn("jit_trace_seconds_total").get("eager", 0.0)
    compiled_before = by_fn("jit_programs_compiled_total").get("eager", 0.0)
    jax.jit(lambda a: a * 38.0 + 3801.0)(ones).block_until_ready()
    assert by_fn("jit_trace_seconds_total")["eager"] > eager_before
    assert by_fn("jit_programs_compiled_total")["eager"] == \
        compiled_before + 1

    # inside the call of an InstrumentedJit: its name
    step_before = {name: by_fn(name).get("train_step", 0.0) for name in JIT}
    net.fit(batches(1))
    for name in ("jit_trace_seconds_total", "jit_lower_seconds_total",
                 "jit_compile_seconds_total",
                 "jit_programs_compiled_total"):
        assert by_fn(name)["train_step"] > step_before[name], name
    assert open_entry.fn is None


def test_fn_is_restored_when_the_call_raises():
    def boom(x):
        raise ValueError("no program")
    wrapped = cc.InstrumentedJit(boom, name="boom")
    open_entry.fn = "outer"
    try:
        with pytest.raises(ValueError):
            wrapped(jnp.ones((2,)))
        assert open_entry.fn == "outer"
    finally:
        open_entry.fn = None


# ------------------------------------------------------ each second once
@pytest.mark.parametrize("build", CONTAINERS)
def test_a_step_that_calls_jitted_functions_adds_its_trace_seconds_once(
        build):
    net = build(seed=3802).init()
    before = {name: by_fn(name).get("train_step", 0.0) for name in SECONDS}
    seen = []
    listener = lambda event, seconds, **kw: seen.append(  # noqa: E731
        (event, kw.get("fun_name")))
    jax.monitoring.register_event_duration_secs_listener(listener)
    try:
        t0 = time.perf_counter()
        net.fit(batches(1))
        wall = time.perf_counter() - t0
    finally:
        jax.monitoring.unregister_event_duration_listener(
            listener)
    traces = [name for event, name in seen if event.endswith("trace_duration")]
    # the step's own trace came after those of the jitted functions it
    # calls (tanh, matmul, ...), each inside its interval
    assert traces.count("train_step") == 1 and traces.index("train_step") > 0
    grew = {name: by_fn(name).get("train_step", 0.0) - before[name]
            for name in SECONDS}
    assert 0.0 < grew["jit_trace_seconds_total"] <= wall
    assert sum(grew.values()) <= wall


def test_an_interval_is_counted_less_what_it_held():
    """The listeners by themselves, on made-up events as JAX sends them:
    a scalar under the event's name when an interval begins, its duration
    when it ends, children inside their parent.  More children than any
    bounded memory of intervals would hold (a scanned step traces
    thousands of jitted functions) are still counted once."""
    trace, lower = (e for e, w in cc._JIT_DURATIONS.items()
                    if w in ("trace_s", "lower_s"))
    before = {name: by_fn(name).get("made_up", 0.0) for name in SECONDS}
    children = 3000
    open_entry.fn = "made_up"
    try:
        cc._on_begin(trace, 0.0)
        cc._on_duration(trace, 0.25)              # a sibling, over
        cc._on_begin(trace, 0.0)                  # the parent
        for _ in range(children):
            cc._on_begin(trace, 0.0)
            cc._on_begin(trace, 0.0)              # a grandchild
            cc._on_duration(trace, 0.0005)
            cc._on_duration(trace, 0.001)
        cc._on_begin(lower, 0.0)                  # a child of another kind
        cc._on_duration(lower, 0.5)
        cc._on_begin("/jax/some/other/scalar", 0.0)          # not ours
        cc._on_duration("/jax/some/other/duration", 5.0)     # not ours
        # nothing is written while the outermost interval is open
        assert by_fn("jit_lower_seconds_total").get("made_up", 0.0) == \
            before["jit_lower_seconds_total"]
        cc._on_duration(trace, 10.0)
        # an end without a beginning (the listeners were registered
        # inside the interval) is counted whole
        cc._on_duration(lower, 0.125)
    finally:
        open_entry.fn = None
    assert cc._EVENTS.open == [] and cc._EVENTS.own == {}
    grew = {name: by_fn(name).get("made_up", 0.0) - before[name]
            for name in SECONDS}
    assert grew["jit_lower_seconds_total"] == pytest.approx(0.625)
    # 0.25 + the parent's 10 less the 0.5 of lowering inside it
    assert grew["jit_trace_seconds_total"] == pytest.approx(9.75)
    assert grew["jit_compile_seconds_total"] == 0.0


# ------------------------------------------------------ loaded or compiled
@pytest.fixture
def cache_on(tmp_path, monkeypatch):
    """JAX's persistent cache on, over a directory of the test's own
    (``tests/conftest.py`` keeps it off for the suite)."""
    from jax.experimental.compilation_cache import compilation_cache as jcc
    prev_dir = jax.config.jax_compilation_cache_dir
    prev_on = jax.config.jax_enable_compilation_cache
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    jax.config.update("jax_compilation_cache_dir", str(tmp_path))
    cc.wire_persistent_cache()
    jax.config.update("jax_enable_compilation_cache", True)
    jcc.reset_cache()
    yield
    jax.config.update("jax_compilation_cache_dir", prev_dir)
    jax.config.update("jax_enable_compilation_cache", prev_on)
    jcc.reset_cache()


def test_a_first_compile_counts_as_compiled_and_the_same_again_as_loaded(
        cache_on):
    def probe(a):
        return a * 7 + 3803          # a program no other test compiles

    def grown(before):
        return {name: total(name) - before[name] for name in JIT}

    ones = jnp.ones((5,)).block_until_ready()  # its own program, made first
    before = {name: total(name) for name in JIT}
    status = cc.persistent_cache_status()
    jax.jit(probe)(ones).block_until_ready()
    first = grown(before)
    assert first["jit_programs_compiled_total"] == 1
    assert first["jit_compile_seconds_total"] > 0.0
    assert first["jit_programs_loaded_total"] == 0
    assert first["jit_cache_load_seconds_total"] == 0.0

    jax.clear_caches()               # drop the in-memory executable
    before = {name: total(name) for name in JIT}
    jax.jit(probe)(ones).block_until_ready()
    second = grown(before)
    assert second["jit_programs_loaded_total"] == 1
    assert second["jit_cache_load_seconds_total"] > 0.0
    assert second["jit_programs_compiled_total"] == 0
    assert second["jit_compile_seconds_total"] == 0.0
    # traced and lowered both times: the cache saves neither
    assert first["jit_trace_seconds_total"] > 0.0
    assert second["jit_trace_seconds_total"] > 0.0
    assert second["jit_lower_seconds_total"] > 0.0
    # one store: the status reads the same counters
    after = cc.persistent_cache_status()
    assert after["hits"] == status["hits"] + 1
    assert after["misses"] == status["misses"] + 1


def test_a_program_compiled_with_the_cache_off_counts_as_compiled():
    assert not jax.config.jax_enable_compilation_cache
    ones = jnp.ones((3,)).block_until_ready()  # its own program, made first
    before = {name: total(name) for name in JIT}
    jax.jit(lambda a: a - 3804.5)(ones).block_until_ready()
    assert total("jit_programs_compiled_total") == \
        before["jit_programs_compiled_total"] + 1
    assert total("jit_compile_seconds_total") > \
        before["jit_compile_seconds_total"]
    assert total("jit_programs_loaded_total") == \
        before["jit_programs_loaded_total"]


# ----------------------------------------------------------- steady steps
@pytest.mark.parametrize("build", CONTAINERS)
def test_five_steady_steps_write_to_none_of_the_counters(build):
    net = build(seed=3805).init()
    net.fit(batches(1))
    before = everything()
    assert before["training_compile_total"]["train_step"] >= 1
    net.fit(batches(5))
    assert everything() == before


# ------------------------------------------------------------ the import
def test_the_imports_two_gauges():
    reg = default_registry()
    took = reg.get("package_import_seconds").value
    assert 0.0 < took < 120.0
    age_now = observability.process_age_s()
    if age_now is None:              # no /proc: the gauge is left unset
        assert reg.get("process_age_at_import_seconds") is None
        return
    at_import = reg.get("process_age_at_import_seconds").value
    assert 0.0 <= at_import < age_now
    assert at_import + took < age_now
    # the kernel's record agrees with a clock read in Python
    t0 = time.perf_counter()
    a0 = observability.process_age_s()
    time.sleep(0.05)
    assert observability.process_age_s() - a0 == pytest.approx(
        time.perf_counter() - t0, abs=0.03)


def test_process_age_is_none_without_proc(monkeypatch):
    import builtins
    real = builtins.open

    def no_proc(path, *a, **k):
        if str(path).startswith("/proc/"):
            raise FileNotFoundError(path)
        return real(path, *a, **k)
    monkeypatch.setattr(builtins, "open", no_proc)
    assert observability.process_age_s() is None


# -------------------------------------------------------------- dl4j.init
@pytest.mark.parametrize("build", CONTAINERS)
def test_init_is_a_recorded_span_and_adds_its_wall_time(build):
    reg = default_registry()
    counter = reg.get("model_init_seconds_total")
    before = 0.0 if counter is None else counter.value
    tracer = Tracer(enabled=True)
    prev = set_default_tracer(tracer)
    try:
        t0 = time.perf_counter()
        build(seed=3806).init()
        wall = time.perf_counter() - t0
    finally:
        set_default_tracer(prev)
    spans = [s for s in tracer.finished_spans if s.name == "dl4j.init"]
    assert len(spans) == 1
    grew = reg.get("model_init_seconds_total").value - before
    assert 0.0 < grew <= wall
    assert grew == pytest.approx(spans[0].duration_s, abs=0.05)


# ------------------------------------------------------------ the wiring
def duration_listeners():
    return [f for f in monitoring.get_event_duration_listeners()
            if f is cc._on_duration]


def all_listeners():
    return ([f for f in monitoring.get_event_listeners()
             if f is cc._on_cache_event],
            [f for f in monitoring.get_scalar_listeners()
             if f is cc._on_begin],
            duration_listeners())


def test_the_listeners_are_registered_once_however_often_it_is_wired(
        tmp_path, monkeypatch):
    prev_dir = jax.config.jax_compilation_cache_dir
    assert len(duration_listeners()) == 1      # the package's import
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    try:
        for _ in range(3):
            assert cc.wire_persistent_cache()["enabled"]
    finally:
        jax.config.update("jax_compilation_cache_dir", prev_dir)
    assert all_listeners() == ([cc._on_cache_event], [cc._on_begin],
                               [cc._on_duration])


def test_an_unwritable_cache_directory_still_gets_the_listeners(
        tmp_path, monkeypatch):
    """As in a process whose first wiring fails (a read-only install):
    nothing listening yet, no directory to be made."""
    blocker = tmp_path / "a-file"
    blocker.write_text("not a directory")
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    monkeypatch.setattr(cc, "DEFAULT_CACHE_DIR", str(blocker / "cache"))
    monkeypatch.setattr(cc, "_PERSISTENT_LISTENING", False)
    monitoring.unregister_event_duration_listener(
        cc._on_duration)
    monitoring.unregister_event_listener(cc._on_cache_event)
    monitoring.unregister_scalar_listener(cc._on_begin)
    status = dict(cc._PERSISTENT_STATUS)
    try:
        assert duration_listeners() == []
        failed = cc.wire_persistent_cache()
        assert failed["enabled"] is False and "error" in failed
        assert all_listeners() == ([cc._on_cache_event], [cc._on_begin],
                                   [cc._on_duration])
        ones = jnp.ones((3,)).block_until_ready()
        before = total("jit_programs_compiled_total")
        jax.jit(lambda a: a / 3807.0)(ones).block_until_ready()
        assert total("jit_programs_compiled_total") == before + 1
    finally:
        cc._PERSISTENT_STATUS = status
        if not duration_listeners():
            cc._PERSISTENT_LISTENING = False
            cc._listen()
    assert all_listeners() == ([cc._on_cache_event], [cc._on_begin],
                               [cc._on_duration])


# ------------------------------------------------------------- the report
def test_the_report_holds_every_part_and_is_logged_once(monkeypatch, caplog):
    MultiLayerNetwork(stack_conf(seed=3808)).init()
    report = observability.startup_report()
    assert set(report) == {"process_age_at_import_s", "package_import_s",
                           "model_init_s", "jit"}
    assert report["package_import_s"] == \
        default_registry().get("package_import_seconds").value
    assert report["model_init_s"] > 0.0
    for fn, row in report["jit"].items():
        assert set(row) == set(startup.JIT_COUNTERS), fn
    assert report["jit"]["eager"]["programs_compiled"] == \
        by_fn("jit_programs_compiled_total")["eager"]

    # the first training program of a process to trace and return says it
    monkeypatch.setattr(startup, "_logged", False)
    with caplog.at_level(logging.INFO, logger=startup.log.name):
        net = MultiLayerNetwork(stack_conf(seed=3809)).init()
        net.fit(batches(2))
        other = MultiLayerNetwork(stack_conf(seed=3810)).init()
        other.fit(batches(1))
    said = [r for r in caplog.records if "start-up by parts" in r.message]
    assert len(said) == 1
    assert "'train_step'" in said[0].getMessage()

    # a step traced inside an epoch's program does not say it: the
    # epoch's own call does, when its seconds have been counted
    monkeypatch.setattr(startup, "_logged", False)
    caplog.clear()
    with caplog.at_level(logging.INFO, logger=startup.log.name):
        x, y = batches(1, rows=32)[0]
        MultiLayerNetwork(stack_conf(seed=3812)).init().fit_on_device(
            x, y, batch_size=8)
    said = [r for r in caplog.records if "start-up by parts" in r.message]
    assert len(said) == 1
    assert "'epoch_scan'" in said[0].getMessage()


def test_a_disabled_registry_is_left_alone():
    from deeplearning4j_tpu.observability.registry import (
        MetricsRegistry, set_default_registry)
    reg = MetricsRegistry(enabled=False)
    prev = set_default_registry(reg)
    try:
        jax.jit(lambda a: a + 3811.25)(jnp.ones((3,))).block_until_ready()
        MultiLayerNetwork(stack_conf(seed=3811)).init()
        assert reg.collect() == []
        assert observability.startup_report() == {
            "process_age_at_import_s": None, "package_import_s": None,
            "model_init_s": None, "jit": {}}
    finally:
        set_default_registry(prev)
    assert deeplearning4j_tpu.persistent_cache_status()["misses"] >= 1
