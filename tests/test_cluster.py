"""Elastic multi-host runtime: lease membership, generation fencing,
checkpoint-mediated rejoin, dead-peer drain, and the process-level chaos
harness (ISSUE 7).

Fast tests prove the control plane in-process (lease stores are just a
shared directory).  The ``chaos``-marked soak tests spawn real OS
processes and SIGKILL them mid-run — the acceptance criterion is that
training completes with final params EXACTLY matching the fault-free
run (checkpoint-mediated resume restores params + updater + RNG +
cursor, so recovery is bit-reproducible, not merely approximate).
"""
import json
import os
import signal
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

from deeplearning4j_tpu.faulttolerance.cluster import (
    ClusterCoordinator, ClusterMember, ClusterView, FileLeaseStore,
    shard_owner)
from deeplearning4j_tpu.faulttolerance.faults import (ChaosBroker,
                                                      ChaosSchedule,
                                                      RetryPolicy)
from deeplearning4j_tpu.observability.exposition import render_text
from deeplearning4j_tpu.observability.registry import default_registry

HELPER = os.path.join(os.path.dirname(__file__), "helpers",
                      "chaos_elastic.py")


# ------------------------------------------------------------ lease store

def test_shard_owner_deterministic_rechunking():
    # ownership depends only on (index, world): any two workers agreeing
    # on the view agree on the split, at ANY world size
    for world in (1, 2, 3, 5):
        owners = [shard_owner(i, world) for i in range(20)]
        assert owners == [i % world for i in range(20)]
        # full coverage, no overlap: each index has exactly one owner
        for i in range(20):
            assert sum(1 for r in range(world)
                       if shard_owner(i, world) == r) == 1
    with pytest.raises(ValueError):
        shard_owner(3, 0)


def test_lease_renew_expire_evict(tmp_path):
    store = FileLeaseStore(str(tmp_path))
    coord = ClusterCoordinator(store, lease_ttl_s=10.0)
    store.renew(0, ttl_s=10.0)
    store.renew(1, ttl_s=0.05)          # about to expire
    live, evicted = coord.sweep()
    assert set(live) == {0, 1} and evicted == []
    time.sleep(0.1)
    live, evicted = coord.sweep()
    assert set(live) == {0} and evicted == [1]
    assert coord.evicted_total == 1
    # the evicted lease file is revoked: a later sweep doesn't re-evict
    _, evicted = coord.sweep()
    assert evicted == []
    assert coord.evicted_total == 1


def test_member_heartbeat_keeps_lease_alive(tmp_path):
    store = FileLeaseStore(str(tmp_path))
    coord = ClusterCoordinator(store, lease_ttl_s=0.4)
    with ClusterMember(store, 7, lease_ttl_s=0.4) as m:
        time.sleep(1.0)                  # several ttls: must stay live
        live, evicted = coord.sweep()
        assert 7 in live and evicted == []
        assert m.renew_count >= 3
    # clean leave revokes immediately
    live, _ = coord.sweep()
    assert 7 not in live


def test_generation_bumps_and_fences_stale_worker(tmp_path):
    store = FileLeaseStore(str(tmp_path))
    coord = ClusterCoordinator(store, lease_ttl_s=0.3)
    store.renew(0, ttl_s=10.0)
    store.renew(1, ttl_s=0.15)
    view1 = coord.begin_round(0)
    assert view1.members == (0, 1) and view1.world_size == 2
    gen1 = view1.generation
    assert coord.accept(gen1)

    time.sleep(0.25)                     # worker 1's lease expires
    view2 = coord.begin_round(1)
    assert view2.members == (0,)
    assert view2.generation == gen1 + 1
    # the fence: worker 1 still tags frames with gen1 — rejected
    assert not coord.accept(gen1)
    assert coord.accept(view2.generation)

    # rejoin at a later boundary: admitted, generation bumps again
    store.renew(1, ttl_s=10.0, incarnation=1)
    view3 = coord.begin_round(2)
    assert view3.members == (0, 1)
    assert view3.generation == view2.generation + 1
    assert coord.rejoined_total == 1
    assert not coord.accept(view2.generation)
    # a member reads the same view from the shared store
    assert store.read_view().generation == view3.generation
    # membership metrics are in the Prometheus exposition
    text = render_text(default_registry())
    assert "cluster_generation" in text
    assert "cluster_members" in text
    assert "cluster_evictions_total" in text
    assert "cluster_rejoins_total" in text
    assert "cluster_heartbeat_age_seconds" in text


def test_same_membership_does_not_bump_generation(tmp_path):
    store = FileLeaseStore(str(tmp_path))
    coord = ClusterCoordinator(store, lease_ttl_s=10.0)
    store.renew(0, ttl_s=10.0)
    g1 = coord.begin_round(0).generation
    g2 = coord.begin_round(1).generation
    assert g1 == g2                      # nothing changed: same fence
    assert store.read_view().round_index == 1


# ------------------------------------------------------------ retry policy

def test_retry_policy_concurrent_callers_deterministic():
    """Satellite: numpy Generators are not thread-safe — per-worker
    streams must produce each worker's exact serial sequence no matter
    how N threads interleave."""
    n_workers, n_draws = 8, 200
    # serial reference: one fresh policy consumed worker-by-worker gives
    # each worker's canonical stream (streams are independent by seed)
    expected = {}
    for w in range(n_workers):
        ref = RetryPolicy(seed=11)
        expected[w] = [ref.backoff(k, worker=w)
                       for k in range(1, n_draws + 1)]
    shared = RetryPolicy(seed=11)
    got = {w: [] for w in range(n_workers)}
    errors = []
    start = threading.Barrier(n_workers)

    def run(w):
        try:
            start.wait(timeout=10)
            for k in range(1, n_draws + 1):
                got[w].append(shared.backoff(k, worker=w))
        except Exception as e:       # pragma: no cover - failure reporting
            errors.append(e)

    threads = [threading.Thread(target=run, args=(w,))
               for w in range(n_workers)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=30)
    assert not errors
    for w in range(n_workers):
        assert got[w] == expected[w], f"worker {w} stream diverged"


# ------------------------------------------------------- broker reconnect

def _hub(port=0):
    from deeplearning4j_tpu.streaming.broker import TcpMessageBroker
    return TcpMessageBroker(port=port).serve()


def test_broker_publish_survives_hub_restart_and_counts():
    from deeplearning4j_tpu.streaming.broker import TcpMessageBroker
    hub = _hub()
    port = hub.port
    client = TcpMessageBroker(port=port)
    before = default_registry().counter(
        "broker_reconnects_total", "x", ("op",)).labels("publish").value
    client.publish("t", b"one")          # healthy path
    hub.shutdown()
    hub2 = _hub(port=port)               # hub restarts on the same port
    try:
        sub = hub2.subscribe("t", ack=True)
        # the first write into the dead socket can be silently buffered
        # by TCP before the RST lands (at-most-once transport); within a
        # couple of publishes the client must detect the stale socket,
        # reconnect under the bounded policy, and deliver again
        got = None
        for i in range(5):
            client.publish("t", b"two-%d" % i)
            got = sub.poll(timeout=0.5)
            if got is not None:
                break
        assert got is not None and got.startswith(b"two-")
        after = default_registry().counter(
            "broker_reconnects_total", "x", ("op",)).labels(
                "publish").value
        assert after > before
    finally:
        hub2.shutdown()


def test_broker_publish_budget_exhausted_raises_clear_error():
    from deeplearning4j_tpu.faulttolerance.faults import RetryPolicy
    from deeplearning4j_tpu.streaming.broker import TcpMessageBroker
    hub = _hub()
    port = hub.port
    client = TcpMessageBroker(
        port=port, reconnect_policy=RetryPolicy(max_retries=2,
                                                backoff_s=0.01))
    client.publish("t", b"ok")
    hub.shutdown()                       # hub never comes back
    with pytest.raises(ConnectionError, match="2 reconnect attempts"):
        for _ in range(5):               # first write may buffer pre-RST
            client.publish("t", b"lost")
            time.sleep(0.05)


def test_broker_subscription_resubscribes_after_hub_restart():
    hub = _hub()
    port = hub.port
    from deeplearning4j_tpu.streaming.broker import TcpMessageBroker
    client = TcpMessageBroker(port=port)
    sub = client.subscribe("t", ack=True)
    hub.publish("t", b"before")
    assert sub.poll(timeout=2.0) == b"before"
    hub.shutdown()
    assert sub.poll(timeout=0.2) is None     # EOF observed, not an error
    hub2 = _hub(port=port)
    try:
        assert sub.poll(timeout=0.2) is None  # triggers the resubscribe
        hub2.publish("t", b"after")
        assert sub.poll(timeout=2.0) == b"after"
    finally:
        sub.close()
        hub2.shutdown()


# ---------------------------------------------- gradient sharing hardening

def _sharing_pair():
    from deeplearning4j_tpu.parallel.remote import RemoteGradientSharing
    from deeplearning4j_tpu.streaming.broker import LocalMessageBroker
    broker = LocalMessageBroker(max_queue=0)
    a = RemoteGradientSharing(broker, 0)
    b = RemoteGradientSharing(broker, 1)
    return broker, a, b


def test_apply_updates_drain_bounded_against_flooding_peer():
    """Satellite: a fast peer must not starve the caller's training step
    inside one drain call — the bound returns control, leftovers stay
    queued for the next call."""
    _, a, b = _sharing_pair()
    vec = np.zeros(16, np.float32)
    flood = np.ones(16, np.float32) * 0.01
    for _ in range(40):
        b.publish_update(flood)
    out = a.apply_updates(vec, max_messages=10)
    assert a.messages_applied == 10          # bounded: not all 40
    partial = np.asarray(out).copy()
    # the rest is NOT lost — the next (unbounded) drain applies it
    out = a.apply_updates(out, max_messages=0)
    assert a.messages_applied == 40
    full = np.asarray(out)
    assert np.all(full > partial) and np.all(partial > 0)
    # default bound exists and is finite
    assert a.max_drain == a.DEFAULT_MAX_DRAIN > 0


def test_drain_barrier_excludes_dead_peer():
    """An evicted peer (lease verdict via the master's eviction notice)
    stops counting against the drain barrier immediately."""
    _, a, b = _sharing_pair()
    b.publish_update(np.ones(4, np.float32))
    a.apply_updates(np.zeros(4, np.float32), max_messages=0)
    # peer 1 declared 3 but only 1 arrived; peer 2 never declared
    declared = {1: 3}
    missing = a.unresolved_peers(declared, 3, resids_seen={1: None})
    assert missing == [1, 2]
    a.mark_dead(2)
    assert a.unresolved_peers(declared, 3, resids_seen={1: None}) == [1]
    a.mark_dead(1)
    assert a.unresolved_peers(declared, 3) == []


# ------------------------------------------------------------ chaos harness

def test_chaos_schedule_randomized_is_deterministic():
    p1 = ChaosSchedule.randomized(seed=5, workers=[0, 1, 2], horizon_s=10,
                                  kills=4)
    p2 = ChaosSchedule.randomized(seed=5, workers=[0, 1, 2], horizon_s=10,
                                  kills=4)
    assert p1._kills == p2._kills and len(p1._kills) == 4
    p3 = ChaosSchedule.randomized(seed=6, workers=[0, 1, 2], horizon_s=10,
                                  kills=4)
    assert p1._kills != p3._kills


def test_chaos_broker_partition_window_drop_and_delay():
    from deeplearning4j_tpu.streaming.broker import LocalMessageBroker
    inner = LocalMessageBroker()
    sched = ChaosSchedule(seed=0).partition(0.0, 0.25, topic="grads",
                                            mode="drop")
    sched.partition(0.0, 0.25, topic="other", mode="delay", delay_s=0.05)
    broker = ChaosBroker(inner, sched)
    sub_g = broker.subscribe("grads")
    sub_o = broker.subscribe("other")
    sched.arm()
    broker.publish("grads", b"lost")         # inside the drop window
    t0 = time.monotonic()
    broker.publish("other", b"slow")         # inside the delay window
    assert time.monotonic() - t0 >= 0.04
    assert sub_g.poll(timeout=0.05) is None
    assert sub_o.poll(timeout=0.5) == b"slow"
    time.sleep(0.3)                          # window closes, link heals
    broker.publish("grads", b"healed")
    assert sub_g.poll(timeout=0.5) == b"healed"
    kinds = {e[0] for e in sched.events}
    assert "drop_publish" in kinds and "delay_publish" in kinds


def test_chaos_monkey_sigkills_target_process():
    p = subprocess.Popen([sys.executable, "-c",
                          "import time; time.sleep(60)"])
    try:
        sched = ChaosSchedule(seed=0).kill_process(0, 0.1)
        sched.start(lambda: {0: p.pid})
        rc = p.wait(timeout=10)
        assert rc == -signal.SIGKILL
        assert any(e[0] == "kill" for e in sched.events)
    finally:
        sched.stop()
        if p.poll() is None:
            p.kill()


# --------------------------------------------------- elastic trainer (fast)

def _elastic_model(seed=42):
    from deeplearning4j_tpu.nn.conf.input_type import InputType
    from deeplearning4j_tpu.nn.conf.multi_layer import NeuralNetConfiguration
    from deeplearning4j_tpu.nn.conf.updaters import Adam
    from deeplearning4j_tpu.nn.layers.feedforward import (DenseLayer,
                                                          OutputLayer)
    from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
    conf = (NeuralNetConfiguration.builder()
            .seed(seed).activation("tanh").weight_init("xavier")
            .updater(Adam(learning_rate=0.02))
            .list()
            .layer(DenseLayer(n_out=12))
            .layer(OutputLayer(n_out=3, activation="softmax", loss="mcxent"))
            .set_input_type(InputType.feed_forward(6))
            .build())
    return MultiLayerNetwork(conf).init()


def _elastic_batches(n=12, seed=7):
    rng = np.random.default_rng(seed)
    out = []
    for _ in range(n):
        x = rng.standard_normal((8, 6)).astype(np.float32)
        out.append((x, np.eye(3, dtype=np.float32)[rng.integers(0, 3, 8)]))
    return out


def _flat_params(model):
    from jax.flatten_util import ravel_pytree
    flat, _ = ravel_pytree(model.params)
    return np.asarray(flat)


def test_elastic_trainer_rides_checkpoint_manager(tmp_path):
    """Tentpole acceptance: no ad-hoc ``ckpt_*.zip`` — durable state goes
    through CheckpointManager's atomic store, and resume is exact."""
    from deeplearning4j_tpu.parallel.distributed import ElasticTrainer
    batches = _elastic_batches()

    ref = _elastic_model()
    ElasticTrainer(ref, str(tmp_path / "ref"), save_freq=4).fit(
        lambda: iter(batches))
    ref_params = _flat_params(ref)

    m = _elastic_model()
    t = ElasticTrainer(m, str(tmp_path / "run"), save_freq=4)
    assert t.fit(lambda: iter(batches), max_steps=7) == 7
    names = sorted(os.listdir(tmp_path / "run"))
    assert all(not n.endswith(".zip") for n in names), names
    assert any(n.startswith("ckpt-") for n in names), names

    # a fresh process (fresh model object) resumes exactly
    m2 = _elastic_model(seed=1)          # different init: restore replaces
    t2 = ElasticTrainer(m2, str(tmp_path / "run"), save_freq=4)
    done = t2.fit(lambda: iter(batches))
    assert done == len(batches)
    assert t2.last_restored_step == 7
    np.testing.assert_array_equal(_flat_params(m2), ref_params)


def test_elastic_trainer_skips_corrupt_newest_checkpoint(tmp_path):
    """Satellite: truncate the newest checkpoint — restore must fall back
    to the previous COMPLETE one (checksum verification), not abort the
    rejoin, and the re-trained result still matches the fault-free run
    exactly."""
    from deeplearning4j_tpu.parallel.distributed import ElasticTrainer
    batches = _elastic_batches()

    ref = _elastic_model()
    ElasticTrainer(ref, str(tmp_path / "ref"), save_freq=4).fit(
        lambda: iter(batches))
    ref_params = _flat_params(ref)

    m = _elastic_model()
    t = ElasticTrainer(m, str(tmp_path / "run"), save_freq=4, keep_last=3)
    t.fit(lambda: iter(batches))
    ckpts = sorted(n for n in os.listdir(tmp_path / "run")
                   if n.startswith("ckpt-"))
    assert len(ckpts) >= 2
    newest = tmp_path / "run" / ckpts[-1]
    with open(newest / "model.zip", "wb") as f:   # truncate/corrupt
        f.write(b"torn")

    m2 = _elastic_model(seed=1)
    t2 = ElasticTrainer(m2, str(tmp_path / "run"), save_freq=4)
    step = t2.restore_latest()
    assert step == int(ckpts[-2].split("-")[1])   # previous complete one
    done = t2.fit(lambda: iter(batches))
    assert done == len(batches)
    np.testing.assert_array_equal(_flat_params(m2), ref_params)


def test_elastic_trainer_membership_rechunks_over_world(tmp_path):
    """Two members share one store: ownership splits the batch sequence
    deterministically; when a member's lease expires mid-run the
    survivor's ownership re-covers the lost shard at the next boundary."""
    from deeplearning4j_tpu.parallel.distributed import ElasticTrainer
    store = FileLeaseStore(str(tmp_path / "leases"))
    coord = ClusterCoordinator(store, lease_ttl_s=10.0)
    # the TEST owns the member lifecycle (started here): a trainer that
    # finishes first must not revoke its lease under its still-running
    # peer — the membership view stays stable for both fits
    m0 = ClusterMember(store, 0, lease_ttl_s=10.0).start()
    m1 = ClusterMember(store, 1, lease_ttl_s=10.0).start()
    coord.begin_round(0)
    batches = _elastic_batches()

    try:
        t0 = ElasticTrainer(_elastic_model(), str(tmp_path / "ck0"),
                            save_freq=4, member=m0, coordinator=coord)
        t1 = ElasticTrainer(_elastic_model(), str(tmp_path / "ck1"),
                            save_freq=4, member=m1)
        done1 = {}
        th = threading.Thread(
            target=lambda: done1.setdefault(
                "n", t1.fit(lambda: iter(batches))))
        th.start()
        n0 = t0.fit(lambda: iter(batches))
        th.join(timeout=60)
        assert n0 == len(batches) and done1["n"] == len(batches)
        # full coverage, no overlap: rank 0 owns evens, rank 1 owns odds
        assert t0.trained_steps + t1.trained_steps == len(batches)
        assert t0.trained_steps == 6 and t1.trained_steps == 6
    finally:
        m0.stop()
        m1.stop()

    # --- survivor takeover: worker 1's lease expires mid-run ------------
    coord2 = ClusterCoordinator(FileLeaseStore(str(tmp_path / "s2")),
                                lease_ttl_s=0.3)
    store2 = coord2.store
    mm0 = ClusterMember(store2, 0, lease_ttl_s=5.0)
    mm0.renew_once()
    store2.renew(1, ttl_s=0.3)           # a "member" that will die silently
    coord2.begin_round(0)
    slow = [(b, 0.08) for b in batches]

    def slow_batches():
        for b, nap in slow:
            time.sleep(nap)
            yield b

    tt0 = ElasticTrainer(_elastic_model(), str(tmp_path / "s2"),
                         save_freq=2, member=mm0, coordinator=coord2)
    n = tt0.fit(slow_batches)
    assert n == len(batches)
    assert coord2.evicted_total == 1
    assert tt0.last_view.world_size == 1
    assert tt0.last_view.generation >= 2
    # FULL coverage: the survivor owns the dead member's shard from the
    # eviction boundary on, and the orphan-replay window re-covers the
    # batches the zombie lease "held" before the eviction verdict
    assert tt0.trained_steps == len(batches)
    assert tt0.replayed_steps >= 1


# ------------------------------------------------- chaos soak (subprocess)

def _run_chaos_helper(outdir, out_json, chaos="", batches=24, save_freq=4,
                      step_sleep=0.0, timeout=240):
    env = dict(os.environ)
    env.update({"JAX_PLATFORMS": "cpu",
                "CE_DIR": str(outdir), "CE_OUT": str(out_json),
                "CE_BATCHES": str(batches), "CE_SAVE_FREQ": str(save_freq),
                "CE_STEP_SLEEP": str(step_sleep), "CE_CHAOS": chaos})
    log = open(str(out_json) + ".log", "a")
    try:
        return subprocess.run([sys.executable, HELPER], env=env,
                              stdout=log, stderr=subprocess.STDOUT,
                              timeout=timeout).returncode
    finally:
        log.close()


@pytest.mark.chaos
def test_chaos_sigkill_elastic_host_between_checkpoints(tmp_path):
    """Chaos acceptance (b): SIGKILL an ElasticTrainer host between
    checkpoints; the restarted host restores the newest complete
    checkpoint and finishes with params EXACTLY matching the fault-free
    run."""
    ref_out = tmp_path / "ref.json"
    assert _run_chaos_helper(tmp_path / "ref", ref_out) == 0
    ref = json.loads(ref_out.read_text())

    out = tmp_path / "kill.json"
    rc = _run_chaos_helper(tmp_path / "kill", out, chaos="kill:0.4",
                           step_sleep=0.05)
    assert rc == -signal.SIGKILL, f"expected SIGKILL death, got rc={rc}"
    assert not out.exists()
    # restart, no chaos: checkpoint-mediated rejoin
    assert _run_chaos_helper(tmp_path / "kill", out) == 0
    got = json.loads(out.read_text())
    assert got["resumed_from"] > 0, got
    assert got["steps"] == ref["steps"]
    assert got["param_digest"] == ref["param_digest"]


@pytest.mark.chaos
def test_chaos_crash_mid_checkpoint_commit(tmp_path):
    """Chaos acceptance (c): a hard crash BETWEEN staged checkpoint file
    writes leaves only a ``.tmp-`` orphan; recovery skips it, restores
    the previous complete checkpoint, and the result is exact."""
    ref_out = tmp_path / "ref.json"
    assert _run_chaos_helper(tmp_path / "ref", ref_out) == 0
    ref = json.loads(ref_out.read_text())

    out = tmp_path / "crash.json"
    rc = _run_chaos_helper(tmp_path / "crash", out, chaos="commit:8:1")
    assert rc == ChaosSchedule.CRASH_EXIT_CODE
    # the torn write is a staging orphan, never a committed directory
    names = os.listdir(tmp_path / "crash")
    assert any(n.startswith(".tmp-") for n in names), names
    assert not any(n == "ckpt-00000008" for n in names), names
    assert _run_chaos_helper(tmp_path / "crash", out) == 0
    got = json.loads(out.read_text())
    assert got["resumed_from"] == 4          # previous complete checkpoint
    assert got["steps"] == ref["steps"]
    assert got["param_digest"] == ref["param_digest"]
    # the orphan was swept on restart
    assert not any(n.startswith(".tmp-")
                   for n in os.listdir(tmp_path / "crash"))


def _mp_model(seed=7):
    from deeplearning4j_tpu.nn.conf.input_type import InputType
    from deeplearning4j_tpu.nn.conf.multi_layer import NeuralNetConfiguration
    from deeplearning4j_tpu.nn.conf.updaters import Adam
    from deeplearning4j_tpu.nn.layers.feedforward import (DenseLayer,
                                                          OutputLayer)
    from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
    conf = (NeuralNetConfiguration.builder()
            .seed(seed).activation("tanh").weight_init("xavier")
            .updater(Adam(learning_rate=0.05))
            .list()
            .layer(DenseLayer(n_out=16))
            .layer(OutputLayer(n_out=3, activation="softmax", loss="mcxent"))
            .set_input_type(InputType.feed_forward(4))
            .build())
    return MultiLayerNetwork(conf).init()


def _mp_batches(n_batches=8, bs=16, seed=0):
    rng = np.random.default_rng(seed)
    out = []
    for _ in range(n_batches):
        x = rng.standard_normal((bs, 4)).astype(np.float32)
        yc = (x[:, 0] > 0).astype(int) + (x[:, 1] > 0).astype(int)
        out.append((x, np.eye(3, dtype=np.float32)[yc]))
    return out


WORKER_ENV = {"JAX_PLATFORMS": "cpu"}


@pytest.mark.chaos
def test_chaos_sigkill_mp_worker_mid_round(tmp_path):
    """Chaos acceptance (a): a seeded ChaosSchedule SIGKILLs a master_mp
    worker process mid-run; the master respawns it (re-execution from the
    last averaged frame) and the final params EXACTLY match the
    fault-free run."""
    from deeplearning4j_tpu.parallel.master_mp import MultiprocessMaster
    batches = _mp_batches(n_batches=8)

    ref = _mp_model()
    MultiprocessMaster(num_workers=2, mode="averaging",
                       averaging_frequency=2, worker_env=WORKER_ENV,
                       retry_backoff_s=0.05).fit(
        ref, iter(batches), jobdir=str(tmp_path / "ref"))
    ref_params = _flat_params(ref)

    model = _mp_model()
    # slow_start pins worker 1 alive past the kill time (the fault hook
    # applies only to the first incarnation, so the respawn runs clean)
    master = MultiprocessMaster(num_workers=2, mode="averaging",
                                averaging_frequency=2,
                                worker_env=WORKER_ENV,
                                retry_backoff_s=0.05,
                                fault_injection={"slow_start": {"1": 5.0}})
    sched = ChaosSchedule(seed=3).kill_process(1, 6.0)
    sched.start(lambda: {w: p.pid
                         for w, p in getattr(master, "_procs", {}).items()
                         if p.poll() is None})
    try:
        master.fit(model, iter(batches), jobdir=str(tmp_path / "chaos"))
    finally:
        sched.stop()
    assert any(e[0] == "kill" for e in sched.events), sched.events
    assert 1 in master.retried_workers
    np.testing.assert_array_equal(_flat_params(model), ref_params)


@pytest.mark.chaos
def test_mp_heartbeat_watchdog_evicts_wedged_worker(tmp_path):
    """A worker whose process stays alive but whose training loop wedges
    (heartbeats keep arriving with frozen progress) is killed and
    respawned by the straggler watchdog — the job completes instead of
    hanging until the master's full timeout."""
    from deeplearning4j_tpu.parallel.master_mp import MultiprocessMaster
    batches = _mp_batches(n_batches=8)
    model = _mp_model()
    master = MultiprocessMaster(
        num_workers=2, mode="averaging", averaging_frequency=2,
        worker_env=WORKER_ENV, retry_backoff_s=0.05,
        straggler_timeout_s=8.0,
        fault_injection={"hang_after_batches": {"1": 1}})
    before = model.score(x=batches[0][0], y=batches[0][1])
    master.fit(model, iter(batches), jobdir=str(tmp_path))
    assert 1 in master.evicted_workers
    assert 1 in master.retried_workers
    after = model.score(x=batches[0][0], y=batches[0][1])
    assert np.isfinite(after) and after < before
    # the watchdog fed the membership gauges
    text = render_text(default_registry())
    assert "cluster_heartbeat_age_seconds" in text
    assert 'cluster_evictions_total{reason="heartbeat_stall"}' in text
