"""Bounded async dispatch window (ISSUE 18): the fit loops may run the
host up to ``DL4J_TPU_DISPATCH_DEPTH`` steps ahead of the device.

The contracts under test:

* the window is pure scheduling — params after a fit are BITWISE
  identical at depth 1 (the serial loop), 2, and 4, including the tBPTT
  chunked path and ragged epoch tails;
* checkpoint boundaries drain the window first, so a mid-window save
  resumes digest-exact even when the resuming run uses a different
  depth;
* a deferred device failure (NaN at step N) surfaces at a drain within
  the window bound, attributed to step N's own iteration via the
  ``nan_at_drain`` flight-recorder event;
* flipping the depth is host-only: zero recompiles across depths;
* the ``training_dispatch_depth`` gauge reads the CONFIGURED depth in
  steady state — the proof the pipeline actually fills.
"""
import os

import numpy as np
import pytest

from deeplearning4j_tpu import (InputType, MultiLayerNetwork,
                                NeuralNetConfiguration)
from deeplearning4j_tpu.faulttolerance import CheckpointConfig
from deeplearning4j_tpu.nn.conf.updaters import Adam
from deeplearning4j_tpu.nn.dispatch import (DEFAULT_DEPTH, DispatchWindow,
                                            ENV_VAR, configured_depth)
from deeplearning4j_tpu.nn.layers import (DenseLayer, LSTM, OutputLayer,
                                          RnnOutputLayer)
from deeplearning4j_tpu.observability.recorder import (FlightRecorder,
                                                       set_flight_recorder)
from deeplearning4j_tpu.observability.registry import default_registry


def dense_net(seed=42):
    conf = (NeuralNetConfiguration.builder().seed(seed)
            .updater(Adam(learning_rate=0.02)).list()
            .layer(DenseLayer(n_out=8, activation="tanh"))
            .layer(OutputLayer(n_out=3, activation="softmax", loss="mcxent"))
            .set_input_type(InputType.feed_forward(4)).build())
    return MultiLayerNetwork(conf).init()


def tbptt_net(seed=7, T=12):
    conf = (NeuralNetConfiguration.builder().seed(seed)
            .updater(Adam(learning_rate=0.01)).list()
            .layer(LSTM(n_out=6, activation="tanh"))
            .layer(RnnOutputLayer(n_out=2, activation="softmax",
                                  loss="mcxent"))
            .backprop_type("tbptt", fwd=4, back=4)
            .set_input_type(InputType.recurrent(3, T)).build())
    return MultiLayerNetwork(conf).init()


def make_batches(n=10, batch=8, seed=0):
    rng = np.random.default_rng(seed)
    return [(rng.standard_normal((batch, 4), dtype=np.float32),
             np.eye(3, dtype=np.float32)[rng.integers(0, 3, batch)])
            for _ in range(n)]


@pytest.fixture
def recorder(tmp_path):
    rec = FlightRecorder(capacity=256, directory=str(tmp_path / "disp"),
                         min_dump_interval_s=0.0)
    prev = set_flight_recorder(rec)
    try:
        yield rec
    finally:
        set_flight_recorder(prev)


def _compile_counts(reg):
    fam = reg.snapshot().get("training_compile_total")
    if not fam:
        return {}
    return {tuple(sorted(s["labels"].items())): s["value"]
            for s in fam["samples"]}


# --------------------------------------------------- window unit semantics

class _Token:
    """Fake loss token: float() is the sync, so the order of float()
    calls IS the materialization order the window promises."""

    def __init__(self, value, log):
        self.value = value
        self.log = log

    def __float__(self):
        self.log.append(self.value)
        return float(self.value)


class _Prof:
    def __init__(self):
        self.calls = []

    def drained(self, k):
        self.calls.append(k)


class TestWindowSemantics:
    def test_configured_depth_env_parsing(self, monkeypatch):
        monkeypatch.delenv(ENV_VAR, raising=False)
        assert configured_depth() == DEFAULT_DEPTH
        monkeypatch.setenv(ENV_VAR, "4")
        assert configured_depth() == 4
        # the window never goes below the serial loop
        monkeypatch.setenv(ENV_VAR, "0")
        assert configured_depth() == 1
        monkeypatch.setenv(ENV_VAR, "-3")
        assert configured_depth() == 1
        monkeypatch.setenv(ENV_VAR, "two")
        assert configured_depth() == DEFAULT_DEPTH
        monkeypatch.setenv(ENV_VAR, "")
        assert configured_depth() == DEFAULT_DEPTH

    def test_push_blocks_oldest_at_depth(self):
        log = []
        win = DispatchWindow(depth=3)
        for i in range(5):
            win.push(_Token(float(i), log), i)
            # at most depth-1 tokens stay un-materialized after a push,
            # so the NEXT dispatch sees at most `depth` in flight
            assert len(win) <= 2
        # FIFO: the oldest token materializes first, every time
        assert log == [0.0, 1.0, 2.0]
        win.drain()
        assert log == [0.0, 1.0, 2.0, 3.0, 4.0] and len(win) == 0

    def test_depth_one_is_the_serial_loop(self):
        log = []
        win = DispatchWindow(depth=1)
        for i in range(3):
            win.push(_Token(float(i), log), i)
            assert len(win) == 0      # every push materializes its own step
        assert log == [0.0, 1.0, 2.0]

    def test_owner_profiler_and_nan_bookkeeping(self):
        log, nans = [], []
        owner = type("Owner", (), {})()
        prof = _Prof()
        win = DispatchWindow(depth=2, owner=owner, profiler=prof,
                             on_nan=lambda it, v: nans.append((it, v)))
        win.push(_Token(0.5, log), 10)
        win.push(_Token(float("nan"), log), 11)
        win.push(_Token(0.25, log), 12)
        win.drain()
        # each drained token updates the owner's drain-boundary view…
        assert owner.last_drained_score == 0.25
        assert owner.last_drained_iteration == 12
        # …ticks the profiler occupancy once per pop…
        assert prof.calls == [1, 1, 1]
        # …and the NaN fired with ITS OWN iteration, not the latest one
        assert nans == [(11, pytest.approx(float("nan"), nan_ok=True))]
        assert nans[0][0] == 11

    def test_abandon_never_blocks(self):
        log = []
        win = DispatchWindow(depth=4)
        win.push(_Token(1.0, log), 0)
        win.push(_Token(2.0, log), 1)
        win.abandon()
        # no float() ran: the exception path must not sync on in-flight
        # work while unwinding
        assert log == [] and len(win) == 0

    def test_drain_timed_returns_iteration_order(self):
        log = []
        win = DispatchWindow(depth=4)
        for i in range(3):
            win.push(_Token(float(i), log), 100 + i)
        out = win.drain_timed()
        assert [it for it, _ in out] == [100, 101, 102]
        assert all(isinstance(t, float) for _, t in out)
        # completion stamps are monotone — the fence's attribution spacing
        assert all(a[1] <= b[1] for a, b in zip(out, out[1:]))


# ------------------------------------------------- fit-loop integration

class TestDepthParity:
    def test_dense_parity_across_depths(self, monkeypatch):
        batches = make_batches(10)
        flats, scores = [], []
        for depth in (1, 2, 4):
            monkeypatch.setenv(ENV_VAR, str(depth))
            net = dense_net()
            net.fit(iter(batches), epochs=2)
            flats.append(net.params_flat())
            scores.append(net.get_score())
            assert net.iteration == 20
        # pure scheduling: bitwise-identical params and score at every
        # depth, not just allclose
        assert np.array_equal(flats[0], flats[1])
        assert np.array_equal(flats[0], flats[2])
        assert scores[0] == scores[1] == scores[2]

    def test_tbptt_and_ragged_tail_parity(self, monkeypatch):
        rng = np.random.default_rng(3)
        T = 12
        seq_batches = [
            (rng.standard_normal((4, T, 3)).astype(np.float32),
             np.eye(2, dtype=np.float32)[
                 rng.integers(0, 2, (4, T))])
            for _ in range(4)]
        # ragged epoch tail: the last batch is smaller, exercising the
        # ShapePolicy bucket path inside the pipelined loop
        tail = (rng.standard_normal((2, T, 3)).astype(np.float32),
                np.eye(2, dtype=np.float32)[rng.integers(0, 2, (2, T))])
        seq_batches.append(tail)
        flats, iters = [], []
        for depth in (1, 2, 4):
            monkeypatch.setenv(ENV_VAR, str(depth))
            net = tbptt_net(T=T)
            net.fit(iter(seq_batches), epochs=2)
            flats.append(net.params_flat())
            iters.append(net.iteration)
        assert np.array_equal(flats[0], flats[1])
        assert np.array_equal(flats[0], flats[2])
        # tBPTT chunking (3 chunks per T=12 batch) counted identically
        assert iters[0] == iters[1] == iters[2]

    def test_zero_steady_recompiles_across_depth_flips(self, monkeypatch):
        batches = make_batches(6)
        net = dense_net()
        net.fit(iter(batches[:2]), epochs=1)      # compile + warm
        reg = default_registry()
        before = _compile_counts(reg)
        for depth in (1, 2, 4, 2, 1):
            monkeypatch.setenv(ENV_VAR, str(depth))
            net.fit(iter(batches), epochs=1)
        # the depth knob is host-only scheduling: no retrace, ever
        assert _compile_counts(reg) == before


class TestCheckpointBoundary:
    def test_mid_window_checkpoint_resume_digest_exact(self, tmp_path,
                                                       monkeypatch):
        batches = make_batches(10)
        monkeypatch.setenv(ENV_VAR, "4")

        netA = dense_net()
        netA.fit(iter(batches), epochs=2)          # uninterrupted

        netB = dense_net()
        cfg = CheckpointConfig(directory=str(tmp_path),
                               save_every_n_iterations=3, keep_last=10,
                               background=False)
        # save cadence 3 vs window depth 4: every save lands mid-window,
        # so each one exercises the due()-drain boundary
        netB.fit(iter(batches), epochs=2, checkpoint=cfg)
        assert np.array_equal(netA.params_flat(), netB.params_flat())

        mgr = cfg.resolve()
        mid = mgr.checkpoints()[1][1]              # "the kill point"
        # resume at a DIFFERENT depth: the checkpoint captured fully
        # materialized state, so the window depth of the resuming run
        # is irrelevant to the result
        monkeypatch.setenv(ENV_VAR, "1")
        netC = dense_net()
        netC.fit(iter(batches), epochs=2, resume_from=mid)
        assert np.array_equal(netA.params_flat(), netC.params_flat())
        assert netC.iteration == netA.iteration


class TestDeferredFailure:
    def test_nan_surfaces_within_window_with_own_iteration(self, recorder,
                                                           monkeypatch):
        monkeypatch.setenv(ENV_VAR, "4")
        batches = make_batches(8)
        bad_x = batches[3][0].copy()
        bad_x[0, 0] = np.nan
        batches[3] = (bad_x, batches[3][1])
        net = dense_net()
        net.fit(iter(batches), epochs=1)
        events = [r for r in recorder.channel("train").items()
                  if r["type"] == "nan_at_drain"]
        assert events, "deferred NaN never surfaced at a drain"
        # batch index 3 is optimizer iteration 4 on a fresh net; the
        # first NaN drain carries THAT iteration even though the host
        # had already dispatched past it
        assert events[0]["iteration"] == 4
        assert events[0]["score"] != events[0]["score"]
        # the poisoned step propagates: every later drain is NaN too,
        # each attributed to its own iteration, in order
        assert [e["iteration"] for e in events] == \
            sorted(e["iteration"] for e in events)
        # and the loop's final materialization saw it as well
        assert net.get_score() != net.get_score()


class TestDepthGauge:
    @pytest.mark.parametrize("depth", [2, 4])
    def test_steady_state_gauge_reads_configured_depth(self, depth,
                                                       recorder,
                                                       monkeypatch):
        monkeypatch.setenv(ENV_VAR, str(depth))
        monkeypatch.setenv("DL4J_TPU_STEPPROF", "1")
        monkeypatch.setenv("DL4J_TPU_STEPPROF_SAMPLE", "6")
        net = dense_net()
        net.fit(iter(make_batches(2)), epochs=1)   # compile + warm
        net.fit(iter(make_batches(12)), epochs=1)
        gauge = default_registry().get("training_dispatch_depth")
        assert gauge is not None
        # the pipeline actually fills: between sampled fences the window
        # holds exactly the configured number of in-flight steps
        assert gauge.value == float(depth)


class TestNoProcessGlobalXlaFlags:
    """An earlier ``ShardedTrainer`` constructor appended ``--xla_tpu_*``
    overlap flags to ``os.environ["XLA_FLAGS"]``.  This jaxlib's flag
    parser aborts on them, in this process's children and on the chip
    alike, so the function is gone; these pin that it stays gone and
    why."""

    def test_sharded_trainer_never_touches_xla_flags(self):
        from deeplearning4j_tpu.parallel import ShardedTrainer, make_mesh
        before = os.environ.get("XLA_FLAGS")
        st = ShardedTrainer(dense_net(), make_mesh(dp=2))
        assert os.environ.get("XLA_FLAGS") == before
        assert "xla_tpu" not in (before or "")
        assert not hasattr(st, "overlap_armed")

    def test_xla_tpu_flags_in_xla_flags_abort_the_process(self):
        import subprocess
        import sys
        env = dict(os.environ, JAX_PLATFORMS="cpu",
                   XLA_FLAGS="--xla_tpu_enable_async_all_gather=true")
        r = subprocess.run(
            [sys.executable, "-c", "import jax; jax.devices()"],
            env=env, capture_output=True, text=True, timeout=120)
        assert r.returncode != 0
        assert "Unknown flag" in r.stderr and "XLA_FLAGS" in r.stderr
