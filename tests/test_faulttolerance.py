"""Fault-tolerance subsystem (ISSUE 5): crash-consistent checkpoint store,
exact fit resume (parity with the uninterrupted run), SIGKILL/SIGTERM
behavior, and worker-failure recovery in the training masters
(deterministic FaultInjector: retry, straggler timeout, elastic
degradation)."""
import json
import os
import signal
import subprocess
import sys
import time
from pathlib import Path

import numpy as np
import pytest

REPO_ROOT = Path(__file__).resolve().parents[1]

from deeplearning4j_tpu import (InputType, MultiLayerNetwork,  # noqa: E402
                                NeuralNetConfiguration)
from deeplearning4j_tpu.faulttolerance import (  # noqa: E402
    CheckpointConfig, CheckpointManager, CorruptCheckpointError,
    FaultInjector, RetryPolicy)
from deeplearning4j_tpu.faulttolerance.atomic import (  # noqa: E402
    atomic_file, atomic_write_bytes, discard_orphans)
from deeplearning4j_tpu.nn.conf.updaters import Adam, Sgd  # noqa: E402
from deeplearning4j_tpu.nn.layers.feedforward import (  # noqa: E402
    DenseLayer, OutputLayer)
from deeplearning4j_tpu.observability.registry import (  # noqa: E402
    MetricsRegistry, default_registry, set_default_registry)
from deeplearning4j_tpu.parallel.master import (  # noqa: E402
    ParameterAveragingTrainingMaster)


def build_net(seed=42, dropout=None, updater=None):
    dense = dict(n_out=16, activation="relu")
    if dropout:
        dense["dropout"] = dropout
    conf = (NeuralNetConfiguration.builder().seed(seed)
            .updater(updater or Adam(learning_rate=0.02)).list()
            .layer(DenseLayer(**dense))
            .layer(OutputLayer(n_out=3, activation="softmax", loss="mcxent"))
            .set_input_type(InputType.feed_forward(4)).build())
    return MultiLayerNetwork(conf).init()


def make_batches(n=10, batch=8, seed=0):
    rng = np.random.default_rng(seed)
    return [(rng.standard_normal((batch, 4), dtype=np.float32),
             np.eye(3, dtype=np.float32)[rng.integers(0, 3, batch)])
            for _ in range(n)]


@pytest.fixture
def live_registry():
    old = default_registry()
    reg = MetricsRegistry(enabled=True)
    set_default_registry(reg)
    yield reg
    set_default_registry(old)


# ------------------------------------------------------------- atomic layer

def test_atomic_write_commits_or_leaves_previous(tmp_path):
    p = str(tmp_path / "state.bin")
    atomic_write_bytes(p, b"v1")
    assert open(p, "rb").read() == b"v1"
    # a failing writer must leave v1 untouched and no temp litter
    with pytest.raises(RuntimeError):
        with atomic_file(p) as tmp:
            with open(tmp, "wb") as f:
                f.write(b"partial")
            raise RuntimeError("crash mid-write")
    assert open(p, "rb").read() == b"v1"
    assert os.listdir(tmp_path) == ["state.bin"]


def test_discard_orphans(tmp_path):
    (tmp_path / ".tmp-ckpt-1-dead").mkdir()
    (tmp_path / ".tmp-ckpt-1-dead" / "f").write_bytes(b"x")
    (tmp_path / "keep.txt").write_text("y")
    assert discard_orphans(str(tmp_path)) == 1
    assert sorted(os.listdir(tmp_path)) == ["keep.txt"]


# --------------------------------------------------------- checkpoint store

def test_manager_roundtrip_restores_everything(tmp_path, live_registry):
    net = build_net(dropout=0.5)
    batches = make_batches(4)
    net.fit(iter(batches))
    mgr = CheckpointManager(str(tmp_path), background=False)
    path = mgr.save(net, cursor={"fit_epoch": 0, "batch_seq": 4},
                    metric=net.get_score())
    assert mgr.latest() == path
    net2, state = mgr.restore()
    assert np.allclose(net2.params_flat(), net.params_flat())
    assert net2.iteration == net.iteration and net2.epoch == net.epoch
    assert np.array_equal(np.asarray(net2._rng), np.asarray(net._rng))
    assert state["cursor"] == {"fit_epoch": 0, "batch_seq": 4}
    # updater state restored leaf-for-leaf
    import jax
    for a, b in zip(jax.tree_util.tree_leaves(net.opt_state),
                    jax.tree_util.tree_leaves(net2.opt_state)):
        assert np.allclose(np.asarray(a), np.asarray(b))
    c = live_registry.get("checkpoint_restore_total")
    assert c is not None and c.labels("ok").value == 1
    h = live_registry.get("checkpoint_write_seconds")
    assert h is not None and h.labels("sync").count == 1
    assert live_registry.get("checkpoint_bytes").labels().sum > 0


def test_retention_keep_last_every_n_and_best(tmp_path):
    net = build_net()
    batches = make_batches(1)
    mgr = CheckpointManager(str(tmp_path), keep_last=2, keep_every_n=5,
                            keep_best=1, background=False)
    # fake a descending metric so "best" is the last save, and step 5
    # survives via keep_every_n
    metrics = {1: 5.0, 2: 4.0, 3: 0.5, 4: 3.0, 5: 2.0, 6: 1.9, 7: 1.8}
    for it in range(1, 8):
        net.fit_batch(batches[0])
        assert net.iteration == it
        mgr.save(net, metric=metrics[it])
    steps = [s for s, _, _ in mgr.checkpoints()]
    # last two (6,7), every-5th (5), best metric 0.5 (3)
    assert steps == [3, 5, 6, 7]


def _sharded_build_net(seed=42):
    """build_net() laid out ZeRO-3 over a dp=4 mesh (every (4,16)/(16,)
    kernel shards with min_shard_size=0)."""
    from deeplearning4j_tpu.parallel import ShardedTrainer, make_mesh
    net = build_net(seed=seed)
    ShardedTrainer(net, make_mesh(dp=4), min_shard_size=0)
    return net


@pytest.mark.skipif("len(__import__('jax').devices()) < 4")
def test_latest_complete_recognizes_sharded_dirs(tmp_path, live_registry):
    """Satellite (ISSUE 13): the promotion poll and its kind filter see
    the sharded layout — and a corrupt SHARD file makes the dir fall
    back exactly like a torn dense checkpoint."""
    mgr = CheckpointManager(str(tmp_path), background=False)
    net = build_net()
    net.fit_batch(make_batches(1)[0])
    mgr.save(net, step=1)                               # dense
    snet = _sharded_build_net()
    p2 = mgr.save_sharded(snet, step=2)                 # sharded
    assert mgr.latest_complete() == (2, p2)
    assert mgr.latest_complete(kind="sharded") == (2, p2)
    step, path = mgr.latest_complete(kind="dense")
    assert step == 1
    assert mgr.latest_complete(after_step=2) is None
    with pytest.raises(ValueError, match="dense"):
        mgr.latest_complete(kind="zipped")
    # corrupt the newest sharded dir's shard payload: the promotion
    # path must skip it and answer the previous complete checkpoint
    shard = next(f for f in os.listdir(p2) if f.endswith(".npz"))
    with open(os.path.join(p2, shard), "r+b") as f:
        f.seek(25)
        f.write(b"\xde\xad\xbe\xef")
    step, _ = mgr.latest_complete()
    assert step == 1
    assert mgr.latest_complete(kind="sharded") is None
    c = live_registry.get("checkpoint_restore_total")
    assert c is not None and c.labels("skipped").value >= 1


@pytest.mark.skipif("len(__import__('jax').devices()) < 4")
def test_retention_recognizes_sharded_dirs(tmp_path):
    """Satellite (ISSUE 13): keep_last / keep_best retention treats
    barrier-written sharded dirs exactly like dense ones — sweeps the
    old, pins the best recorded metric."""
    mgr = CheckpointManager(str(tmp_path), keep_last=2, keep_best=1,
                            background=False)
    snet = _sharded_build_net()
    metrics = {1: 5.0, 2: 0.5, 3: 4.0, 4: 3.0, 5: 2.0}
    for step in range(1, 6):
        mgr.save_sharded(snet, step=step, metric=metrics[step])
    steps = [s for s, _, _ in mgr.checkpoints()]
    # last two (4,5) plus the best metric 0.5 (2) — 1,3 swept
    assert steps == [2, 4, 5]
    for _, path, manifest in mgr.checkpoints():
        assert manifest.get("sharded")
        assert os.path.isfile(os.path.join(path, "topology.json"))


def test_latest_skips_corrupt_and_restore_refuses(tmp_path, live_registry):
    net = build_net()
    net.fit_batch(make_batches(1)[0])
    mgr = CheckpointManager(str(tmp_path), background=False)
    good = mgr.save(net)
    net.fit_batch(make_batches(1)[0])
    bad = mgr.save(net)
    # flip bytes inside the newest checkpoint's params payload
    target = os.path.join(bad, "model.zip")
    blob = bytearray(open(target, "rb").read())
    blob[len(blob) // 2] ^= 0xFF
    open(target, "wb").write(bytes(blob))
    assert mgr.latest() == good                    # corrupt one skipped
    with pytest.raises(CorruptCheckpointError) as ei:
        mgr.restore(path=bad)
    assert "model.zip" in str(ei.value)
    c = live_registry.get("checkpoint_restore_total")
    assert c.labels("corrupt").value >= 1
    assert c.labels("skipped").value >= 1


def test_sigkill_mid_checkpoint_leaves_skippable_partial(tmp_path):
    """A saver SIGKILLed mid-stage leaves only a .tmp- orphan: discovery
    ignores it, restore refuses it, sweep removes it — the previous
    committed checkpoint stays the latest."""
    store = str(tmp_path / "store")
    child = subprocess.Popen(
        [sys.executable, "-c", f"""
import os, sys
sys.path.insert(0, {str(REPO_ROOT)!r})
import numpy as np
from tests.test_faulttolerance import build_net, make_batches
from deeplearning4j_tpu.faulttolerance import CheckpointManager
net = build_net()
net.fit_batch(make_batches(1)[0])
mgr = CheckpointManager({store!r}, background=False)
mgr.save(net)                      # one good committed checkpoint
print("SAVED1", flush=True)
net.fit_batch(make_batches(1)[0])
mgr._test_slow_s = 60.0            # stall between staged files
mgr.save(net)                      # parent SIGKILLs us mid-stage
"""],
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
        env=dict(os.environ, JAX_PLATFORMS="cpu",
                 PYTHONPATH=str(REPO_ROOT)), cwd=str(REPO_ROOT))
    try:
        line = child.stdout.readline()
        assert "SAVED1" in line, line
        deadline = time.time() + 60
        orphan = None
        while orphan is None and time.time() < deadline:
            tmps = [n for n in os.listdir(store) if n.startswith(".tmp-")]
            orphan = os.path.join(store, tmps[0]) if tmps else None
            if orphan is None:
                time.sleep(0.02)
        assert orphan is not None, "staging dir never appeared"
        # give the slow writer a beat to be inside the inter-file sleep
        time.sleep(0.1)
        child.kill()
        child.wait(timeout=30)
    finally:
        if child.poll() is None:
            child.kill()
    mgr = CheckpointManager(store, background=False)
    assert [s for s, _, _ in mgr.checkpoints()] == [1]   # good one only
    assert mgr.latest().endswith("ckpt-00000001")
    with pytest.raises(CorruptCheckpointError):
        mgr.restore(path=orphan)
    assert mgr.sweep_orphans() == 1
    assert not [n for n in os.listdir(store) if n.startswith(".tmp-")]


# --------------------------------------------------------------- fit resume

def test_fit_resume_parity_and_no_recompiles(tmp_path, live_registry):
    """The acceptance parity: a run checkpointed every k steps, 'killed',
    and resumed from a mid checkpoint ends with params matching the
    uninterrupted run — dropout included (RNG restore) — and the resumed
    fit triggers ZERO extra train-step compiles (shared trace cache +
    restored ShapePolicy history)."""
    batches = make_batches(10)

    netA = build_net(dropout=0.5)
    netA.fit(iter(batches), epochs=2)              # uninterrupted

    netB = build_net(dropout=0.5)
    cfg = CheckpointConfig(directory=str(tmp_path),
                           save_every_n_iterations=3, keep_last=10,
                           background=False)
    netB.fit(iter(batches), epochs=2, checkpoint=cfg)
    # checkpointing is an observer: identical params with it on
    assert np.allclose(netA.params_flat(), netB.params_flat())
    mgr = cfg.resolve()
    steps = [s for s, _, _ in mgr.checkpoints()]
    assert steps[0] % 3 == 0 and len(steps) >= 3
    mid = mgr.checkpoints()[1][1]                   # "the kill point"

    def compiles():
        c = live_registry.get("training_compile_total")
        return 0.0 if c is None else sum(
            child.value for _, child in c.samples())

    before = compiles()
    netC = build_net(dropout=0.5)
    netC.fit(iter(batches), epochs=2, resume_from=mid)
    assert compiles() == before                     # counter-verified
    assert np.allclose(netA.params_flat(), netC.params_flat())
    assert netC.iteration == netA.iteration
    assert netC.epoch == netA.epoch


def test_fit_resume_mid_epoch_cursor(tmp_path):
    """Resume lands mid-epoch at the exact batch-seq cursor (not an epoch
    boundary): checkpoint at iteration 4 of a 7-batch epoch."""
    batches = make_batches(7)
    netA = build_net(updater=Sgd(learning_rate=0.05))
    netA.fit(iter(batches), epochs=1)
    netB = build_net(updater=Sgd(learning_rate=0.05))
    cfg = CheckpointConfig(directory=str(tmp_path),
                           save_every_n_iterations=4, background=False)
    netB.fit(iter(batches), epochs=1, checkpoint=cfg)
    ck = cfg.resolve().checkpoints()[0]
    assert ck[0] == 4
    state = json.load(open(os.path.join(ck[1], "training_state.json")))
    assert state["cursor"] == {"fit_epoch": 0, "batch_seq": 4}
    netC = build_net(updater=Sgd(learning_rate=0.05))
    netC.fit(iter(batches), epochs=1, resume_from=ck[1])
    assert np.allclose(netA.params_flat(), netC.params_flat())


def test_fit_on_device_epoch_checkpoint_and_resume(tmp_path):
    rng = np.random.default_rng(5)
    x = rng.standard_normal((32, 4), dtype=np.float32)
    y = np.eye(3, dtype=np.float32)[rng.integers(0, 3, 32)]

    netA = build_net(seed=9)
    cfgA = CheckpointConfig(directory=str(tmp_path / "a"),
                            save_every_n_epochs=1, keep_last=8,
                            background=False)
    netA.fit_on_device(x, y, batch_size=8, epochs=4, checkpoint=cfgA)

    netB = build_net(seed=9)
    cfgB = CheckpointConfig(directory=str(tmp_path / "b"),
                            save_every_n_epochs=1, keep_last=8,
                            background=False)
    netB.fit_on_device(x, y, batch_size=8, epochs=4, checkpoint=cfgB)
    ckpts = cfgB.resolve().checkpoints()
    assert len(ckpts) == 4
    mid = ckpts[1][1]                               # after epoch 2
    state = json.load(open(os.path.join(mid, "training_state.json")))
    assert state["cursor"]["fit_epoch"] == 2

    netC = build_net(seed=9)
    netC.fit_on_device(x, y, batch_size=8, epochs=4, resume_from=mid)
    assert np.allclose(netA.params_flat(), netC.params_flat())
    assert netC.epoch == netA.epoch == 4


def test_computation_graph_fit_resume_parity(tmp_path):
    from deeplearning4j_tpu.nn.computation_graph import ComputationGraph

    def build_graph():
        conf = (NeuralNetConfiguration.builder().seed(3)
                .updater(Sgd(learning_rate=0.05))
                .graph_builder()
                .add_inputs("in")
                .add_layer("d", DenseLayer(n_out=8, activation="tanh"), "in")
                .add_layer("out", OutputLayer(n_out=3, activation="softmax",
                                              loss="mcxent"), "d")
                .set_outputs("out")
                .set_input_types(InputType.feed_forward(4))
                .build())
        return ComputationGraph(conf).init()

    batches = make_batches(6)
    gA = build_graph()
    gA.fit(iter(batches), epochs=2)
    gB = build_graph()
    cfg = CheckpointConfig(directory=str(tmp_path),
                           save_every_n_iterations=4, background=False)
    gB.fit(iter(batches), epochs=2, checkpoint=cfg)
    mid = cfg.resolve().checkpoints()[0][1]
    gC = build_graph()
    gC.fit(iter(batches), epochs=2, resume_from=mid)
    import jax
    for a, b in zip(jax.tree_util.tree_leaves(gA.params),
                    jax.tree_util.tree_leaves(gC.params)):
        assert np.allclose(np.asarray(a), np.asarray(b))


def test_sigterm_triggers_final_save_and_clean_return(tmp_path):
    """save_on_preempt: a SIGTERM mid-fit takes one final synchronous
    checkpoint at the next iteration boundary and fit returns cleanly
    (exit 0) instead of dying — the preemption contract."""
    store = str(tmp_path / "store")
    child = subprocess.Popen(
        [sys.executable, "-c", f"""
import json, os, sys, time
sys.path.insert(0, {str(REPO_ROOT)!r})
import numpy as np
from tests.test_faulttolerance import build_net
from deeplearning4j_tpu.faulttolerance import CheckpointConfig
from deeplearning4j_tpu.train.listeners import TrainingListener

class Ready(TrainingListener):
    def iteration_done(self, model, iteration, epoch):
        if iteration == 1:
            print("READY", flush=True)
        time.sleep(0.01)           # keep the fit alive for the signal

def batches():
    rng = np.random.default_rng(0)
    for _ in range(100000):
        yield (rng.standard_normal((8, 4), dtype=np.float32),
               np.eye(3, dtype=np.float32)[rng.integers(0, 3, 8)])

net = build_net()
net.set_listeners(Ready())
cfg = CheckpointConfig(directory={store!r}, save_on_preempt=True,
                       background=False)
net.fit(batches(), epochs=1, checkpoint=cfg)
print(json.dumps({{"iteration": net.iteration}}), flush=True)
"""],
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
        env=dict(os.environ, JAX_PLATFORMS="cpu",
                 PYTHONPATH=str(REPO_ROOT)), cwd=str(REPO_ROOT))
    try:
        assert "READY" in child.stdout.readline()
        child.send_signal(signal.SIGTERM)
        out, _ = child.communicate(timeout=120)
    finally:
        if child.poll() is None:
            child.kill()
    assert child.returncode == 0, out
    result = json.loads(out.strip().splitlines()[-1])
    assert result["iteration"] >= 1
    mgr = CheckpointManager(store, background=False)
    latest = mgr.latest()
    assert latest is not None
    net2, state = mgr.restore()
    assert net2.iteration == result["iteration"]
    assert state["cursor"]["batch_seq"] >= 1


# ------------------------------------------------- master failure recovery

def master_batches(n=8, seed=1):
    return make_batches(n, seed=seed)


def seq_reference(order, batches, seed=7):
    net = build_net(seed=seed, updater=Sgd(learning_rate=0.05))
    for i in order:
        net.fit_batch(batches[i])
    return net.params_flat()


def test_master_transient_fault_retry_recovers(live_registry):
    """A worker failing once is retried from its round-start snapshot;
    the run's final params equal the fault-free run's."""
    batches = master_batches()
    inj = FaultInjector(seed=0).fail(worker=1, rnd=0, times=1)
    m = ParameterAveragingTrainingMaster(
        2, averaging_frequency=2, max_retries=2, retry_backoff_s=0.001,
        fault_injector=inj)
    netF = build_net(seed=7, updater=Sgd(learning_rate=0.05))
    m.fit(netF, iter(batches))
    m0 = ParameterAveragingTrainingMaster(2, averaging_frequency=2)
    netR = build_net(seed=7, updater=Sgd(learning_rate=0.05))
    m0.fit(netR, iter(batches))
    assert np.allclose(netF.params_flat(), netR.params_flat())
    assert m.retry_counts == {1: 1}
    assert m.lost_workers == set()
    c = live_registry.get("training_worker_retries_total")
    assert c.labels("threads").value == 1
    assert ("fail", 1, 0) in inj.events


def test_master_permanent_failure_elastic_rechunk(live_registry):
    """ISSUE acceptance: one injected permanently-failed worker — fit()
    completes via elastic degradation (round re-chunked over survivors,
    shard redistributed) with deterministically correct params."""
    batches = master_batches()
    inj = FaultInjector(seed=0).fail(worker=1, rnd=0, times=-1)
    m = ParameterAveragingTrainingMaster(
        2, averaging_frequency=2, max_retries=2, retry_backoff_s=0.001,
        fault_injector=inj)
    net = build_net(seed=7, updater=Sgd(learning_rate=0.05))
    m.fit(net, iter(batches))
    assert m.lost_workers == {1}
    assert m.retry_counts == {1: 2}            # full retry budget spent
    # shards: w0=[0,2,4,6], w1=[1,3,5,7], freq=2.  Round 0: w0 runs [0,2];
    # w1's [1,3] re-chunks onto w0; w1's queue [5,7] rides w0's queue.
    # Surviving execution order on w0: 0,2,1,3 | 4,6 | 5,7.
    expect = seq_reference([0, 2, 1, 3, 4, 6, 5, 7], batches)
    assert np.allclose(net.params_flat(), expect)
    c = live_registry.get("training_worker_lost_total")
    assert c.labels("threads").value == 1
    assert live_registry.get(
        "training_worker_retries_total").labels("threads").value == 2


def test_master_straggler_timeout_elastic(live_registry):
    """A worker exceeding the straggler timeout is excluded and its work
    re-chunked; fit completes with the same params as the permanent-loss
    case (the straggler's replica never re-enters aggregation)."""
    batches = master_batches()
    inj = FaultInjector(seed=0).delay(worker=1, rnd=0, seconds=1.5)
    m = ParameterAveragingTrainingMaster(
        2, averaging_frequency=2, max_retries=1, retry_backoff_s=0.001,
        straggler_timeout_s=0.25, fault_injector=inj)
    net = build_net(seed=7, updater=Sgd(learning_rate=0.05))
    t0 = time.monotonic()
    m.fit(net, iter(batches))
    assert m.lost_workers == {1}
    expect = seq_reference([0, 2, 1, 3, 4, 6, 5, 7], batches)
    assert np.allclose(net.params_flat(), expect)
    assert live_registry.get(
        "training_worker_lost_total").labels("threads").value == 1
    assert time.monotonic() - t0 < 30


def test_master_dropped_result_is_retried():
    batches = master_batches()
    inj = FaultInjector(seed=0).drop(worker=0, rnd=1, times=1)
    m = ParameterAveragingTrainingMaster(
        2, averaging_frequency=2, max_retries=2, retry_backoff_s=0.001,
        fault_injector=inj)
    net = build_net(seed=7, updater=Sgd(learning_rate=0.05))
    m.fit(net, iter(batches))
    m0 = ParameterAveragingTrainingMaster(2, averaging_frequency=2)
    netR = build_net(seed=7, updater=Sgd(learning_rate=0.05))
    m0.fit(netR, iter(batches))
    assert np.allclose(net.params_flat(), netR.params_flat())
    assert m.retry_counts == {0: 1}
    assert ("drop", 0, 1) in inj.events


def test_master_rechunk_survivor_transient_fault_recovers():
    """A transient survivor hiccup DURING elastic re-chunk (injector key
    (0, -1)) is retried from a snapshot instead of aborting the fit the
    recovery machinery just saved."""
    batches = master_batches()
    inj = (FaultInjector(seed=0).fail(worker=1, rnd=0, times=-1)
           .fail(worker=0, rnd=-1, times=1))      # re-chunk replay hiccup
    m = ParameterAveragingTrainingMaster(
        2, averaging_frequency=2, max_retries=2, retry_backoff_s=0.001,
        fault_injector=inj)
    net = build_net(seed=7, updater=Sgd(learning_rate=0.05))
    m.fit(net, iter(batches))
    assert m.lost_workers == {1}
    expect = seq_reference([0, 2, 1, 3, 4, 6, 5, 7], batches)
    assert np.allclose(net.params_flat(), expect)


def test_master_straggler_raise_joins_lingering_threads():
    """elastic=False + straggler: the raise path must still join the
    zombie thread before control returns to the caller (its replica is
    the caller's model)."""
    batches = master_batches(4)
    inj = FaultInjector(seed=0).delay(worker=1, rnd=0, seconds=0.8)
    m = ParameterAveragingTrainingMaster(
        2, averaging_frequency=2, max_retries=1, retry_backoff_s=0.001,
        straggler_timeout_s=0.1, fault_injector=inj, elastic=False)
    net = build_net(seed=7, updater=Sgd(learning_rate=0.05))
    with pytest.raises(RuntimeError, match="straggler"):
        m.fit(net, iter(batches))
    assert all(not t.is_alive() for t in m._lingering)


def test_checkpoint_validation_error_leaves_sigterm_handler(tmp_path):
    """A validation raise before training starts must not leak the
    save-on-preempt SIGTERM handler (it is installed only after every
    early raise and uninstalled in the loop's finally)."""
    before = signal.getsignal(signal.SIGTERM)
    conf = (NeuralNetConfiguration.builder().seed(1)
            .updater(Sgd(learning_rate=0.1))
            .optimization_algo("lbfgs").list()
            .layer(DenseLayer(n_out=8, activation="tanh"))
            .layer(OutputLayer(n_out=3, activation="softmax", loss="mcxent"))
            .set_input_type(InputType.feed_forward(4)).build())
    net = MultiLayerNetwork(conf).init()
    cfg = CheckpointConfig(directory=str(tmp_path), save_on_preempt=True,
                           save_every_n_iterations=1, background=False)
    with pytest.raises(ValueError, match="SGD path"):
        net.fit(iter(make_batches(2)), checkpoint=cfg)
    assert signal.getsignal(signal.SIGTERM) is before
    # bad-input raise inside a checkpointed fit also restores the handler
    net2 = build_net()
    with pytest.raises(ValueError, match="fit\\(\\) needs"):
        net2.fit(object(), checkpoint=CheckpointConfig(
            directory=str(tmp_path), save_on_preempt=True,
            save_every_n_iterations=1, background=False))
    assert signal.getsignal(signal.SIGTERM) is before


def test_master_all_workers_lost_raises():
    batches = master_batches(4)
    inj = (FaultInjector(seed=0).fail(worker=0, rnd=0, times=-1)
           .fail(worker=1, rnd=0, times=-1))
    m = ParameterAveragingTrainingMaster(
        2, averaging_frequency=2, max_retries=1, retry_backoff_s=0.001,
        fault_injector=inj)
    net = build_net(seed=7, updater=Sgd(learning_rate=0.05))
    with pytest.raises(RuntimeError, match="all 2 workers lost"):
        m.fit(net, iter(batches))


def test_master_elastic_off_propagates():
    batches = master_batches(4)
    inj = FaultInjector(seed=0).fail(worker=1, rnd=0, times=-1)
    m = ParameterAveragingTrainingMaster(
        2, averaging_frequency=2, max_retries=1, retry_backoff_s=0.001,
        fault_injector=inj, elastic=False)
    net = build_net(seed=7, updater=Sgd(learning_rate=0.05))
    with pytest.raises(Exception, match="injected failure"):
        m.fit(net, iter(batches))


def test_retry_policy_backoff_seeded_and_bounded():
    a = RetryPolicy(max_retries=3, backoff_s=0.1, seed=5)
    b = RetryPolicy(max_retries=3, backoff_s=0.1, seed=5)
    da = [a.backoff(k) for k in range(1, 5)]
    db = [b.backoff(k) for k in range(1, 5)]
    assert da == db                              # seeded => reproducible
    for k, d in enumerate(da, start=1):
        assert 0.05 * 2 ** (k - 1) <= d <= min(0.15 * 2 ** (k - 1), 5.0)
    c = RetryPolicy(backoff_s=10.0, max_backoff_s=1.0, seed=0)
    assert c.backoff(5) == 1.0                   # clamped


# -------------------------------------------------------- listener re-base

def test_checkpoint_listener_no_iteration_zero_save(tmp_path):
    from deeplearning4j_tpu.train.listeners import CheckpointListener
    lst = CheckpointListener(str(tmp_path), save_every_n_iterations=2)
    net = build_net()
    # the old listener saved on iteration 0 (0 % n == 0) — an empty
    # pre-training artifact; the re-based one must not
    lst.iteration_done(net, 0, 0)
    assert lst.saved == []
    net.iteration = 2
    lst.iteration_done(net, 2, 0)
    assert len(lst.saved) == 1
    from deeplearning4j_tpu.utils.model_serializer import restore_model
    back = restore_model(lst.saved[-1])          # dirs restore directly
    assert back.num_params() == net.num_params()
