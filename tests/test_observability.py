"""Unified metrics + tracing subsystem tests: registry semantics under
concurrency, Prometheus exposition format, span nesting/propagation
(threads, ParameterAveragingTrainingMaster workers, serialized contexts
for worker processes), MetricsListener wiring, event log, and the
off-by-default no-op guarantees."""
import json
import re
import threading

import numpy as np
import pytest

from deeplearning4j_tpu.observability import (EventLog, MetricsListener,
                                              MetricsRegistry, SpanContext,
                                              Tracer, default_registry,
                                              render_text,
                                              set_default_registry)
from deeplearning4j_tpu.observability.registry import DEFAULT_BUCKETS


# ---------------------------------------------------------------- registry
class TestRegistry:
    def test_counter_threaded_increments_exact(self):
        reg = MetricsRegistry()
        c = reg.counter("t_ops_total", "ops", ("worker",))

        def work(w):
            child = c.labels(str(w % 2))   # two children, contended
            for _ in range(1000):
                child.inc()

        threads = [threading.Thread(target=work, args=(w,)) for w in range(8)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert c.labels("0").value + c.labels("1").value == 8000

    def test_histogram_bucket_boundaries_inclusive(self):
        reg = MetricsRegistry()
        h = reg.histogram("t_lat", "lat", buckets=(1.0, 2.0, 5.0))
        for v in (0.5, 1.0, 1.5, 10.0):   # 1.0 lands IN the le=1 bucket
            h.observe(v)
        child = h._unlabeled()
        cum = dict(child.cumulative_buckets())
        assert cum[1.0] == 2
        assert cum[2.0] == 3
        assert cum[5.0] == 3
        assert cum[float("inf")] == 4
        assert child.count == 4
        assert child.sum == pytest.approx(13.0)

    def test_histogram_threaded_count_exact(self):
        reg = MetricsRegistry()
        h = reg.histogram("t_lat2", "lat", buckets=DEFAULT_BUCKETS)

        def work():
            for i in range(500):
                h.observe(i * 1e-3)

        threads = [threading.Thread(target=work) for _ in range(4)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        child = h._unlabeled()
        assert child.count == 2000
        assert child.cumulative_buckets()[-1][1] == 2000

    def test_get_or_create_identity_and_mismatch(self):
        reg = MetricsRegistry()
        a = reg.counter("t_same", "x", ("l",))
        assert reg.counter("t_same", "x", ("l",)) is a
        with pytest.raises(ValueError):
            reg.gauge("t_same")
        with pytest.raises(ValueError):
            reg.counter("t_same", "x", ("other",))
        with pytest.raises(ValueError):
            reg.counter("bad name!")
        with pytest.raises(ValueError):
            reg.counter("t_lbl", "x", ("0bad",))
        h = reg.histogram("t_hist", "x", buckets=(1.0, 2.0))
        assert reg.histogram("t_hist", "x", buckets=(2.0, 1.0)) is h  # order-free
        with pytest.raises(ValueError):   # silently mixed bucket layouts
            reg.histogram("t_hist", "x", buckets=(1.0, 5.0))

    def test_counter_rejects_negative(self):
        reg = MetricsRegistry()
        with pytest.raises(ValueError):
            reg.counter("t_neg").inc(-1)

    def test_disabled_registry_is_noop(self):
        reg = MetricsRegistry(enabled=False)
        c = reg.counter("t_off")
        g = reg.gauge("t_off_g")
        h = reg.histogram("t_off_h")
        c.inc(); g.set(5); h.observe(1.0)
        assert c.value == 0 and g.value == 0
        assert h._unlabeled().count == 0
        reg.enable()
        c.inc()
        assert c.value == 1

    def test_gauge_set_inc_dec(self):
        reg = MetricsRegistry()
        g = reg.gauge("t_depth")
        g.set(3); g.inc(); g.dec(2)
        assert g.value == 2


# -------------------------------------------------------------- exposition
_SAMPLE_RE = re.compile(
    r"^[a-zA-Z_:][a-zA-Z0-9_:]*(\{[a-zA-Z_][a-zA-Z0-9_]*=\"(?:[^\"\\\n]|"
    r"\\\\|\\\"|\\n)*\"(,[a-zA-Z_][a-zA-Z0-9_]*=\"(?:[^\"\\\n]|\\\\|\\\"|"
    r"\\n)*\")*\})? (NaN|[+-]Inf|-?[0-9.e+-]+)$")


class TestExposition:
    def _registry(self):
        reg = MetricsRegistry()
        reg.counter("requests_total", "HTTP requests",
                    ("route", "code")).labels("/predict", "200").inc(3)
        reg.gauge("queue_depth", "depth").set(7)
        h = reg.histogram("latency_seconds", "latency", ("route",),
                          buckets=(0.1, 1.0))
        h.labels("/predict").observe(0.05)
        h.labels("/predict").observe(2.0)
        return reg

    def test_text_format_lines_valid(self):
        text = render_text(self._registry())
        for line in text.strip().splitlines():
            if line.startswith("# HELP ") or line.startswith("# TYPE "):
                continue
            assert _SAMPLE_RE.match(line), f"invalid exposition line: {line}"
        assert "# TYPE requests_total counter" in text
        assert "# TYPE latency_seconds histogram" in text
        assert 'requests_total{code="200",route="/predict"} 3' in text
        assert 'latency_seconds_bucket{route="/predict",le="+Inf"} 2' in text
        assert 'latency_seconds_count{route="/predict"} 2' in text

    def test_text_format_deterministic(self):
        reg = self._registry()
        assert render_text(reg) == render_text(reg)

    def test_label_escaping(self):
        reg = MetricsRegistry()
        reg.counter("t_esc", "", ("path",)).labels('a"b\\c\nd').inc()
        text = render_text(reg)
        assert 'path="a\\"b\\\\c\\nd"' in text

    def test_json_snapshot_round_trips(self):
        snap = self._registry().snapshot()
        back = json.loads(json.dumps(snap))
        assert back["requests_total"]["type"] == "counter"
        s = back["latency_seconds"]["samples"][0]
        assert s["count"] == 2
        assert s["buckets"][-1] == ["+Inf", 2]


# ------------------------------------------------------------------ tracer
class TestTracer:
    def test_nesting_parent_child(self):
        t = Tracer(enabled=True, registry=MetricsRegistry())
        with t.span("outer") as outer:
            with t.span("inner") as inner:
                assert inner.trace_id == outer.trace_id
                assert inner.parent_id == outer.span_id
            assert t.current_span() is outer
        assert t.current_span() is None
        names = [s.name for s in t.finished_spans]
        assert names == ["inner", "outer"]   # children close first
        assert all(s.duration_s >= 0 for s in t.finished_spans)

    def test_span_durations_land_in_registry(self):
        reg = MetricsRegistry()
        t = Tracer(enabled=True, registry=reg)
        with t.span("phase"):
            pass
        h = reg.get("span_seconds")
        assert h is not None
        assert h.labels("phase").count == 1

    def test_cross_thread_propagation(self):
        t = Tracer(enabled=True, registry=MetricsRegistry())
        got = {}
        with t.span("master") as root:
            ctx = t.current_context()

            def worker():
                with t.attach(ctx), t.span("worker_fit") as sp:
                    got["span"] = sp

            th = threading.Thread(target=worker)
            th.start(); th.join()
        assert got["span"].trace_id == root.trace_id
        assert got["span"].parent_id == root.span_id

    def test_context_serializes_for_processes(self):
        t = Tracer(enabled=True, registry=MetricsRegistry())
        with t.span("mp.fit"):
            wire = json.dumps(t.current_context().to_dict())
        ctx = SpanContext.from_dict(json.loads(wire))
        with t.attach(ctx), t.span("mp.worker") as sp:
            assert sp.trace_id == ctx.trace_id
            assert sp.parent_id == ctx.span_id

    def test_disabled_tracer_records_nothing(self):
        """A disabled tracer's span is the profiler's bare annotation:
        no Span, no context, nothing kept."""
        import jax
        t = Tracer(enabled=False)
        with t.span("x", worker=3) as sp:
            assert isinstance(sp, jax.profiler.TraceAnnotation)
            assert t.current_span() is None
        assert t.current_context() is None
        assert t.finished_spans == []
        # attach(None) composes silently
        with t.attach(None):
            pass

    def test_attributes(self):
        t = Tracer(enabled=True, registry=MetricsRegistry())
        with t.span("s", worker=3) as sp:
            sp.set_attribute("round", 1)
        s = t.finished_spans[0]
        assert s.attributes == {"worker": 3, "round": 1}

    @pytest.mark.parametrize("enabled", [False, True])
    def test_every_span_is_an_annotation(self, enabled, monkeypatch):
        """One rule: a span is a ``jax.profiler.TraceAnnotation``, written
        whether the tracer records or not (a no-op outside a capture);
        an enabled tracer records the Span around the same interval."""
        import jax
        seen = []

        class Annotation:
            def __init__(self, name, **attributes):
                self.name, self.attributes = name, attributes

            def __enter__(self):
                seen.append(("in", self.name, self.attributes))
                return self

            def __exit__(self, *exc):
                seen.append(("out", self.name, self.attributes))
                return False

        monkeypatch.setattr(jax.profiler, "TraceAnnotation", Annotation)
        t = Tracer(enabled=enabled, registry=MetricsRegistry())
        with t.span("outer"):
            with t.span("inner", worker=3):
                pass
        assert seen == [("in", "outer", {}), ("in", "inner", {"worker": 3}),
                        ("out", "inner", {"worker": 3}),
                        ("out", "outer", {})]
        names = [s.name for s in t.finished_spans]
        assert names == (["inner", "outer"] if enabled else [])

    def test_the_bridge_switches_are_gone(self):
        """Bridging is what a span is: no ``bridge_xprof`` argument, and
        the ``xprof`` value of DL4J_TPU_TRACE means what any value does."""
        with pytest.raises(TypeError):
            Tracer(enabled=True, bridge_xprof=True)
        t = Tracer(enabled=True, registry=MetricsRegistry())
        with t.span("outside a capture") as sp:
            assert sp is not None
        assert t.finished_spans[0].duration_s >= 0

    def test_a_span_that_raises_still_closes(self):
        t = Tracer(enabled=True, registry=MetricsRegistry())
        with pytest.raises(KeyError):
            with t.span("outer"):
                with t.span("inner"):
                    raise KeyError("x")
        assert t.current_span() is None
        assert [s.name for s in t.finished_spans] == ["inner", "outer"]


class TestPerformanceListenerSteadyState:
    def test_first_iteration_excluded_from_rates(self):
        """Satellite: the compile-dominated first iteration only starts
        the clock; rates cover later iterations exclusively."""
        from deeplearning4j_tpu.train.listeners import PerformanceListener

        class FakeModel:
            last_batch_size = 32

        lst = PerformanceListener(frequency=1)
        lst.iteration_done(FakeModel(), 1, 0)
        assert np.isnan(lst.samples_per_sec)      # nothing reported yet
        lst.iteration_done(FakeModel(), 2, 0)
        assert lst.samples_per_sec > 0
        assert lst.batches_per_sec > 0
        # baseline starts at the FIRST hook even off-frequency
        lst2 = PerformanceListener(frequency=5)
        lst2.iteration_done(FakeModel(), 1, 0)
        assert lst2._last_iter == 1
        for i in range(2, 6):
            lst2.iteration_done(FakeModel(), i, 0)
        assert lst2.batches_per_sec > 0           # window = iterations 2-5


# --------------------------------------------------------------- event log
class TestEventLog:
    def test_write_and_read_jsonl(self, tmp_path):
        p = tmp_path / "events.jsonl"
        with EventLog(str(p)) as log:
            log.emit("train_iteration", iteration=1, score=0.5)
            log.emit("epoch_end", epoch=0)
        records = list(EventLog.read(str(p)))
        assert [r["type"] for r in records] == ["train_iteration",
                                                "epoch_end"]
        assert records[0]["iteration"] == 1
        assert all("ts" in r for r in records)

    def test_threaded_lines_stay_atomic(self, tmp_path):
        p = tmp_path / "events.jsonl"
        log = EventLog(str(p))

        def work(w):
            for i in range(100):
                log.emit("e", worker=w, i=i)

        threads = [threading.Thread(target=work, args=(w,)) for w in range(4)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        log.close()
        records = list(EventLog.read(str(p)))   # every line parses
        assert len(records) == 400

    def test_tracer_spans_to_event_log(self, tmp_path):
        p = tmp_path / "spans.jsonl"
        log = EventLog(str(p))
        t = Tracer(enabled=True, registry=MetricsRegistry(), event_log=log)
        with t.span("phase", worker=0):
            pass
        log.close()
        (rec,) = list(EventLog.read(str(p)))
        assert rec["type"] == "span" and rec["name"] == "phase"
        assert rec["attributes"] == {"worker": 0}


# ---------------------------------------------------- training integration
def _iris_net():
    from deeplearning4j_tpu.nn.conf.input_type import InputType
    from deeplearning4j_tpu.nn.conf.multi_layer import NeuralNetConfiguration
    from deeplearning4j_tpu.nn.conf.updaters import Adam
    from deeplearning4j_tpu.nn.layers.feedforward import (DenseLayer,
                                                          OutputLayer)
    from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
    conf = (NeuralNetConfiguration.builder()
            .seed(7).activation("tanh").weight_init("xavier")
            .updater(Adam(learning_rate=0.02))
            .list()
            .layer(DenseLayer(n_out=8))
            .layer(OutputLayer(n_out=3, activation="softmax", loss="mcxent"))
            .set_input_type(InputType.feed_forward(4))
            .build())
    return MultiLayerNetwork(conf).init()


class TestMetricsListenerTraining:
    def test_fit_records_steps_score_and_throughput(self):
        """ISSUE 2 acceptance: training with MetricsListener attached
        records step count, examples/sec, and score in the DEFAULT
        registry."""
        from deeplearning4j_tpu.data.mnist import IrisDataSetIterator
        fresh = MetricsRegistry()
        prev = set_default_registry(fresh)
        try:
            net = _iris_net()
            net.add_listeners(MetricsListener())
            it = IrisDataSetIterator(batch_size=50)
            for _ in range(3):
                it.reset()
                net.fit(it)
            reg = default_registry()
            n_iters = net.iteration
            assert reg.get("model_iterations_total").value == n_iters
            assert reg.get("training_steps_total").value == n_iters
            assert reg.get("model_score").value == pytest.approx(
                net.get_score())
            assert reg.get("model_examples_per_sec").value > 0
            assert reg.get("training_examples_per_sec").value > 0
            assert reg.get("model_grad_norm").value > 0
            # compile/steady split: exactly one compile-phase step
            h = reg.get("training_step_seconds")
            assert h.labels("compile").count == 1
            assert h.labels("steady").count == n_iters - 1
            assert reg.get("model_epochs_total").value == 3
        finally:
            set_default_registry(prev)

    def test_device_scalar_score_not_synced(self):
        """On the ParallelWrapper path the score stays a device scalar
        mid-fit; the listener must skip it (no silent host sync) unless
        force_device_sync opts in."""
        import jax.numpy as jnp
        reg = MetricsRegistry()

        class Wrapperish:
            _score = jnp.asarray(1.5)     # device scalar, not host float
            _last_grad_stats = {"global_norm": jnp.asarray(2.0)}
            last_batch_size = 16

            @staticmethod
            def get_score():
                return float(Wrapperish._score)

        lst = MetricsListener(registry=reg)
        lst.iteration_done(Wrapperish(), 1, 0)
        assert reg.get("model_score") is None or \
            reg.get("model_score").value == 0          # skipped
        assert reg.get("model_iterations_total").value == 1  # counters run
        forced = MetricsListener(registry=reg, force_device_sync=True)
        forced.iteration_done(Wrapperish(), 1, 0)
        assert reg.get("model_score").value == pytest.approx(1.5)
        assert reg.get("model_grad_norm").value == pytest.approx(2.0)

    def test_disabled_registry_training_is_silent(self):
        """The disabled path records nothing (and syncs nothing — the
        listener returns before touching the model)."""
        fresh = MetricsRegistry(enabled=False)
        prev = set_default_registry(fresh)
        try:
            net = _iris_net()
            listener = MetricsListener()
            net.add_listeners(listener)
            x = np.random.default_rng(0).standard_normal((12, 4)).astype(
                np.float32)
            y = np.eye(3, dtype=np.float32)[np.arange(12) % 3]
            net.fit(x, y, epochs=2)
            snap = fresh.snapshot()
            for name, fam in snap.items():
                for s in fam["samples"]:
                    assert s.get("value", 0) == 0 and s.get("count", 0) == 0, \
                        (name, s)
        finally:
            set_default_registry(prev)


class TestMasterSpans:
    def test_parameter_averaging_span_propagation(self):
        """Spans nest across the ParameterAveragingTrainingMaster fan-out:
        worker_fit spans share the master.fit trace and parent onto it."""
        from deeplearning4j_tpu.data.mnist import IrisDataSetIterator
        from deeplearning4j_tpu.parallel.master import (
            ParameterAveragingTrainingMaster)
        tracer = Tracer(enabled=True, registry=MetricsRegistry())
        net = _iris_net()
        master = ParameterAveragingTrainingMaster(
            num_workers=2, averaging_frequency=1, tracer=tracer)
        master.fit(net, IrisDataSetIterator(batch_size=25))
        spans = tracer.finished_spans
        by_name = {}
        for s in spans:
            by_name.setdefault(s.name, []).append(s)
        root = by_name["master.fit"][0]
        assert {"master.split", "master.broadcast", "master.worker_fit",
                "master.aggregation"} <= set(by_name)
        for s in spans:
            assert s.trace_id == root.trace_id
        workers = {s.attributes["worker"] for s in by_name["master.worker_fit"]}
        assert workers == {0, 1}
        # worker spans parent onto the master.fit root via attach(ctx)
        assert all(s.parent_id == root.span_id
                   for s in by_name["master.worker_fit"])

    def test_stats_text_deterministic_with_worker_labels(self):
        from deeplearning4j_tpu.data.mnist import IrisDataSetIterator
        from deeplearning4j_tpu.parallel.master import (
            ParameterAveragingTrainingMaster)
        net = _iris_net()
        master = ParameterAveragingTrainingMaster(num_workers=2,
                                                  averaging_frequency=1)
        master.fit(net, IrisDataSetIterator(batch_size=25))
        text = master.stats.stats_text()
        assert text == master.stats.stats_text()   # deterministic
        lines = text.splitlines()
        assert lines[0].split() == ["phase", "worker", "count", "total_s",
                                    "mean_s"]
        # per-worker fit rows present alongside the aggregate row
        fit_rows = [ln for ln in lines if ln.startswith("fit ")]
        workers = {ln.split()[1] for ln in fit_rows}
        assert {"all", "0", "1"} <= workers
        d = master.stats.as_dict()   # backward-compatible shape
        assert {"split", "broadcast", "fit", "aggregation"} <= set(d)
        for ph in d.values():
            assert set(ph) == {"count", "total_s", "mean_s"}


# ----------------------------------------------------------------- brokers
class TestBrokerMetrics:
    def test_publish_consume_counters_and_depth(self):
        from deeplearning4j_tpu.streaming.broker import LocalMessageBroker
        fresh = MetricsRegistry()
        prev = set_default_registry(fresh)
        try:
            broker = LocalMessageBroker()
            sub = broker.subscribe("topicA")
            broker.publish("topicA", b"one")
            broker.publish("topicA", b"two")
            assert fresh.get("broker_published_total") \
                        .labels("topicA").value == 2
            assert fresh.get("broker_queue_depth") \
                        .labels("topicA").value == 2
            assert sub.poll(timeout=0.1) == b"one"
            assert fresh.get("broker_consumed_total") \
                        .labels("topicA").value == 1
            assert fresh.get("broker_queue_depth") \
                        .labels("topicA").value == 1
        finally:
            set_default_registry(prev)

    def test_drop_oldest_counted(self):
        from deeplearning4j_tpu.streaming.broker import LocalMessageBroker
        fresh = MetricsRegistry()
        prev = set_default_registry(fresh)
        try:
            broker = LocalMessageBroker(max_queue=1)
            broker.subscribe("t")
            broker.publish("t", b"a")
            broker.publish("t", b"b")   # evicts "a"
            assert fresh.get("broker_dropped_total").labels("t").value == 1
        finally:
            set_default_registry(prev)
