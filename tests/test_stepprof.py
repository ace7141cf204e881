"""StepProfiler: per-step phase attribution with sampled device
fences, live device bytes at each fence, and the Chrome-trace /
``/debug/profile`` export surfaces.

The honesty contracts under test:

* phase sums cover the measured step wall (within 5% on fenced steps);
* UNSAMPLED steps add ZERO host syncs — the PR 16 host-sync sweep
  invariant, asserted by counting ``jax.block_until_ready`` calls and
  pinning the compile counters;
* the profiler reads no file and computes no FLOP count: the label a fit
  loop passes is the label, and the one MFU is the benchmark's;
* the trace artifact is checksummed — corruption raises, never loads
  quietly.
"""
import json
import sys
import urllib.error
import urllib.request
from pathlib import Path

import numpy as np
import pytest

REPO_ROOT = Path(__file__).resolve().parents[1]
if str(REPO_ROOT) not in sys.path:
    sys.path.insert(0, str(REPO_ROOT))     # for tools.stepprof

from deeplearning4j_tpu.nn.conf.input_type import InputType
from deeplearning4j_tpu.nn.conf.multi_layer import NeuralNetConfiguration
from deeplearning4j_tpu.nn.conf.updaters import Adam
from deeplearning4j_tpu.nn.layers.feedforward import DenseLayer, OutputLayer
from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
from deeplearning4j_tpu.observability.profiler import (CHANNEL, PHASES,
                                                       StepProfiler,
                                                       chrome_trace,
                                                       dump_chrome_trace,
                                                       load_chrome_trace,
                                                       phase_summary,
                                                       record_slices,
                                                       step_profiler_for,
                                                       stepprof_enabled)
from deeplearning4j_tpu.observability.recorder import (FlightRecorder,
                                                       set_flight_recorder)
from deeplearning4j_tpu.observability.registry import default_registry


def tiny_net(seed=42):
    conf = (NeuralNetConfiguration.builder().seed(seed)
            .updater(Adam(learning_rate=0.02)).list()
            .layer(DenseLayer(n_out=8, activation="tanh"))
            .layer(OutputLayer(n_out=3, activation="softmax", loss="mcxent"))
            .set_input_type(InputType.feed_forward(4)).build())
    return MultiLayerNetwork(conf).init()


def make_batches(n=10, batch=8, seed=0):
    rng = np.random.default_rng(seed)
    return [(rng.standard_normal((batch, 4), dtype=np.float32),
             np.eye(3, dtype=np.float32)[rng.integers(0, 3, batch)])
            for _ in range(n)]


@pytest.fixture
def recorder(tmp_path):
    rec = FlightRecorder(capacity=256, directory=str(tmp_path / "prof"),
                         min_dump_interval_s=0.0)
    prev = set_flight_recorder(rec)
    try:
        yield rec
    finally:
        set_flight_recorder(prev)


def step_records(rec):
    return [r for r in rec.channel(CHANNEL).items() if r["type"] == "step"]


def _compile_counts(reg):
    fam = reg.snapshot().get("training_compile_total")
    if not fam:
        return {}
    return {tuple(sorted(s["labels"].items())): s["value"]
            for s in fam["samples"]}


class TestPhaseAttribution:
    def test_records_phases_and_sampled_coverage(self, recorder,
                                                 monkeypatch):
        monkeypatch.setenv("DL4J_TPU_STEPPROF_SAMPLE", "2")
        net = tiny_net()
        net.fit(iter(make_batches(12)), epochs=1)
        recs = step_records(recorder)
        assert len(recs) == 12
        for r in recs:
            assert set(r["phases"]) == set(PHASES)
            assert r["wall_s"] > 0
        sampled = [r for r in recs if r["sampled"]]
        unsampled = [r for r in recs if not r["sampled"]]
        assert len(sampled) == 6 and unsampled
        # device slice: honest float on fenced steps; on unfenced steps
        # None — unless a later pipeline-aware fence drained the step's
        # in-flight token and attributed its slice ("drained" marker)
        assert all(r["phases"]["device"] > 0 for r in sampled)
        for r in unsampled:
            if r.get("drained"):
                assert r["phases"]["device"] >= 0
            else:
                assert r["phases"]["device"] is None
        # the acceptance contract: on fenced steps the phase breakdown
        # sums to the step wall within 5%
        cov = phase_summary(recs)["sampled_coverage"]
        assert 0.95 <= cov <= 1.05

    def test_unsampled_steps_add_zero_syncs_and_no_retrace(self, recorder,
                                                           monkeypatch):
        import jax
        net = tiny_net()
        batches = make_batches(8)
        # warm: compile outside the counted window
        monkeypatch.setenv("DL4J_TPU_STEPPROF", "0")
        net.fit(iter(batches[:2]), epochs=1)
        reg = default_registry()
        compiles0 = _compile_counts(reg)

        monkeypatch.setenv("DL4J_TPU_STEPPROF", "1")
        monkeypatch.setenv("DL4J_TPU_STEPPROF_SAMPLE", "1000")
        fences = []
        real = jax.block_until_ready
        monkeypatch.setattr(jax, "block_until_ready",
                            lambda x: fences.append(1) or real(x))
        net.fit(iter(batches), epochs=1)
        # every step unsampled -> the profiler never fenced, and the
        # instrumentation did not perturb the traced program
        assert fences == []
        assert _compile_counts(reg) == compiles0
        recs = step_records(recorder)
        assert len(recs) == 8 and not any(r["sampled"] for r in recs)

    def test_fence_cadence_counter_and_depth_gauge(self, recorder,
                                                   monkeypatch):
        import jax
        monkeypatch.setenv("DL4J_TPU_STEPPROF_SAMPLE", "3")
        net = tiny_net()
        net.fit(iter(make_batches(2)), epochs=1)      # compile + warm
        reg = default_registry()
        fam = reg.get("stepprof_fences_total")
        before = fam.labels("train_step").value if fam is not None else 0.0
        fences = []
        real = jax.block_until_ready
        monkeypatch.setattr(jax, "block_until_ready",
                            lambda x: fences.append(1) or real(x))
        net.fit(iter(make_batches(9)), epochs=1)
        assert len(fences) == 3                       # steps 3, 6, 9 only
        fam = reg.get("stepprof_fences_total")
        assert fam is not None
        # the fit loop's own label: the counter the benchmark's
        # profiler_fences.train reads
        assert fam.labels("train_step").value - before == 3.0
        depth = reg.get("training_dispatch_depth")
        # async dispatch pipelines at least the fenced window's steps
        assert depth is not None and depth.value >= 1

    def test_the_fit_loops_label_is_the_label(self, recorder, monkeypatch):
        # the label fit passes reaches every record and the trace's
        # process name; nothing maps it onto another program
        monkeypatch.setenv("DL4J_TPU_STEPPROF_SAMPLE", "2")
        net = tiny_net()
        net.fit(iter(make_batches(4)), epochs=1)
        recs = step_records(recorder)
        assert len(recs) == 4
        assert {r["program"] for r in recs} == {"train_step"}
        procs = {e["args"]["name"] for e in chrome_trace(recs)["traceEvents"]
                 if e["ph"] == "M"}
        assert procs == {"train [train_step]"}
        assert step_profiler_for("serve_probe").program == "serve_probe"

    def test_a_fence_samples_live_bytes_and_nothing_else(self, recorder,
                                                         monkeypatch):
        monkeypatch.setenv("DL4J_TPU_STEPPROF_SAMPLE", "2")
        net = tiny_net()
        net.fit(iter(make_batches(6)), epochs=1)
        recs = step_records(recorder)
        sampled = [r for r in recs if r["sampled"]]
        assert len(sampled) == 3
        # a fence sums the live device bytes into its record; no record
        # carries a FLOP, MFU or budget figure
        assert all(r["live_bytes"] > 0 for r in sampled)
        for r in recs:
            assert not {"mfu", "achieved_flops", "budget_ratio"} & set(r)
        reg = default_registry()
        assert reg.get("device_live_bytes").value == \
            sampled[-1]["live_bytes"]
        for gone in ("training_mfu", "training_achieved_flops",
                     "device_live_bytes_budget_ratio"):
            assert reg.get(gone) is None
        summary = phase_summary(recs)
        assert "mean_mfu" not in summary
        assert "max_budget_ratio" not in summary

    def test_a_profiler_opens_no_file(self, monkeypatch):
        import builtins
        rec = FlightRecorder()
        opened = []
        real = builtins.open
        monkeypatch.setattr(builtins, "open",
                            lambda *a, **k: opened.append(a[0])
                            or real(*a, **k))
        prof = StepProfiler("train_step", recorder=rec)
        assert opened == []
        assert prof.program == "train_step"

    def test_disabled_kills_every_hook(self, recorder, monkeypatch):
        monkeypatch.setenv("DL4J_TPU_STEPPROF", "0")
        assert not stepprof_enabled()
        assert step_profiler_for("train_step") is None
        record_slices("serve", queue_wait_s=0.1)
        net = tiny_net()
        net.fit(iter(make_batches(4)), epochs=1)
        assert recorder.channel(CHANNEL).items() == []

    def test_profiler_never_breaks_training(self, recorder, monkeypatch):
        # telemetry must not take down the fit loop: a profiler whose
        # constructor explodes degrades to None
        monkeypatch.setattr(StepProfiler, "__init__",
                            lambda self, *a, **k: 1 / 0)
        assert step_profiler_for("train_step") is None
        net = tiny_net()
        net.fit(iter(make_batches(3)), epochs=1)      # must not raise


class TestChromeTrace:
    def _records(self):
        return [
            {"ts": 10.0, "type": "step", "program": "train_step",
             "iteration": 1, "wall_s": 0.01, "sampled": True,
             "compile": False, "depth": 2, "live_bytes": 4096,
             "phases": {"etl_wait": 0.001, "h2d": 0.002,
                        "dispatch": 0.003, "device": 0.002,
                        "listener": 0.001, "forensics": 0.001,
                        "checkpoint": 0.0}},
            {"ts": 10.1, "type": "serve", "queue_wait_s": 0.004,
             "batch_form_s": 0.001, "execute_s": 0.006, "batch": 3},
            {"ts": 10.2, "type": "decode", "batch_form_s": 0.001,
             "execute_s": 0.002, "active": 2},
        ]

    def test_trace_layout_train_serve_decode_tracks(self):
        doc = chrome_trace(self._records())
        names = {e["name"] for e in doc["traceEvents"] if e["ph"] == "X"}
        assert {"etl_wait", "h2d", "dispatch", "device", "listener",
                "forensics"} <= names          # checkpoint slice was 0
        assert {"serve:queue_wait", "serve:batch_form", "serve:execute",
                "decode:batch_form", "decode:execute"} <= names
        # three processes: train, serving, generation
        procs = {e["args"]["name"] for e in doc["traceEvents"]
                 if e["ph"] == "M"}
        assert procs == {"train [train_step]", "serving", "generation"}
        # the device slice sits on its own track
        dev = [e for e in doc["traceEvents"] if e["name"] == "device"][0]
        assert dev["tid"] != [e for e in doc["traceEvents"]
                              if e["name"] == "dispatch"][0]["tid"]
        assert dev["args"]["live_bytes"] == 4096

    def test_dump_load_roundtrip_and_corruption_detected(self, tmp_path):
        path = dump_chrome_trace(directory=str(tmp_path),
                                 records=self._records())
        doc = load_chrome_trace(path)
        assert doc["otherData"]["format"].startswith("dl4j-tpu-stepprof")
        # corrupt one byte inside traceEvents -> checksum must catch it
        raw = open(path).read()
        broken = raw.replace('"dispatch"', '"dispatchX"', 1)
        bad = tmp_path / "bad.json"
        bad.write_text(broken)
        with pytest.raises(ValueError, match="checksum mismatch"):
            load_chrome_trace(str(bad))
        # a non-artifact JSON is rejected up front
        notrace = tmp_path / "plain.json"
        notrace.write_text("{}")
        with pytest.raises(ValueError, match="not a stepprof trace"):
            load_chrome_trace(str(notrace))


class TestServingSlices:
    def test_serving_engine_contributes_serve_slices(self, recorder):
        from deeplearning4j_tpu.serving import ServingEngine
        net = tiny_net()
        eng = ServingEngine(net, max_batch_size=8, queue_limit=64)
        try:
            eng.warmup()
            x = np.random.default_rng(0).standard_normal((3, 4)) \
                .astype(np.float32)
            eng.predict(x)
        finally:
            eng.shutdown()
        serves = [r for r in recorder.channel(CHANNEL).items()
                  if r["type"] == "serve"]
        assert serves
        for r in serves:
            assert r["queue_wait_s"] >= 0
            assert r["batch_form_s"] >= 0
            assert r["execute_s"] > 0
            assert r["batch"] >= 1

    def test_generation_engine_contributes_prefill_decode_slices(
            self, recorder):
        from deeplearning4j_tpu.generation import (GenerationConfig,
                                                   GenerationEngine)
        from deeplearning4j_tpu.models import TransformerLM
        lm = TransformerLM(vocab_size=13, seq_len=16, embed=8,
                           n_layers=1, n_heads=2).init()
        eng = GenerationEngine.for_model(
            lm, GenerationConfig(max_slots=2, max_seq=16))
        try:
            eng.generate([1, 2, 3], max_new_tokens=3, temperature=0.0)
        finally:
            eng.shutdown()
        items = recorder.channel(CHANNEL).items()
        prefills = [r for r in items if r["type"] == "prefill"]
        decodes = [r for r in items if r["type"] == "decode"]
        assert prefills and decodes
        assert all(r["execute_s"] > 0 for r in prefills + decodes)
        assert all(r["batch_form_s"] >= 0 for r in prefills + decodes)


class TestDebugProfileEndpoint:
    def _get(self, port, route):
        with urllib.request.urlopen(
                f"http://127.0.0.1:{port}{route}", timeout=10) as r:
            return r.status, json.loads(r.read())

    def test_inference_server_live_view_and_dump(self, recorder,
                                                 monkeypatch):
        monkeypatch.setenv("DL4J_TPU_STEPPROF_SAMPLE", "2")
        from deeplearning4j_tpu.serving.inference_server import \
            InferenceServer
        net = tiny_net()
        net.fit(iter(make_batches(4)), epochs=1)
        srv = InferenceServer(net).start()
        try:
            status, body = self._get(srv.port, "/debug/profile")
            assert status == 200 and body["enabled"] is True
            assert len(body["records"]) == 4
            assert body["summary"]["steps"] >= 3   # compile step excluded
            assert set(body["summary"]["phase_share"]) == set(PHASES)
            status, dump = self._get(srv.port, "/debug/profile?dump=1")
            assert status == 200 and dump["ok"] is True
            loaded = load_chrome_trace(dump["path"])   # checksum-verified
            assert loaded["otherData"]["records"] == 4
        finally:
            srv.stop()

    def test_nn_server_route_and_503_without_recorder(self, recorder):
        from deeplearning4j_tpu.serving.nn_server import \
            NearestNeighborsServer
        pts = np.random.default_rng(0).standard_normal((16, 4)) \
            .astype(np.float32)
        srv = NearestNeighborsServer(pts).start()
        try:
            status, body = self._get(srv.port, "/debug/profile")
            assert status == 200 and body["enabled"] is True
            prev = set_flight_recorder(None)
            try:
                with pytest.raises(urllib.error.HTTPError) as ei:
                    self._get(srv.port, "/debug/profile")
                assert ei.value.code == 503
            finally:
                set_flight_recorder(prev)
        finally:
            srv.stop()


class TestStepprofCli:
    def test_cli_emits_table_and_checksummed_trace(self, tmp_path,
                                                   monkeypatch, capsys):
        import tools.stepprof as cli
        rc = cli.main(["--steps", "8", "--epochs", "1", "--sample", "2",
                       "--out", str(tmp_path)])
        assert rc == 0
        out = capsys.readouterr().out
        assert "phase" in out and "dispatch" in out
        assert "sampled coverage" in out
        tail = json.loads(out.strip().splitlines()[-1])
        assert tail["program"] == "train_step"
        assert tail["steps"] == 7               # compile step excluded
        doc = load_chrome_trace(tail["trace"])  # checksum-verified
        assert doc["otherData"]["records"] == 8
