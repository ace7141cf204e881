"""Elastic sharded training (ISSUE 13): multi-writer barrier checkpoints
and survivor-mesh recovery.

Fast tests prove the two-phase barrier protocol in-process (two emulated
writers of one store — every shard block is addressable from one
process, so both writers stage complete block sets and restore dedupes
by start offset) and the ``ElasticTrainer`` + ``ShardedTrainer`` wiring:
sharded checkpoint dirs, ``restore_sharded(mesh=survivors)`` rejoin,
survivor-mesh rebuild on membership change, ONE train-step trace across
topology changes.

The ``chaos``-marked tests spawn two REAL OS processes sharing one
store (each training an identical ZeRO-3 replica on its process-local
mesh — this CPU backend executes no cross-process computation) and
hard-kill writers mid-protocol: a non-primary mid-block, the primary
between barrier and commit, the primary on the manifest, and a
partition during the barrier.  Acceptance: no torn checkpoint is ever
restorable, ``latest()`` falls back to the previous complete sharded
dir, and post-recovery param digests EXACTLY match the fault-free run.
"""
import hashlib
import json
import os
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

import jax

from deeplearning4j_tpu import (InputType, MultiLayerNetwork,
                                NeuralNetConfiguration)
from deeplearning4j_tpu.faulttolerance.checkpoint import (
    CheckpointManager, ShardBarrier, ShardBarrierError)
from deeplearning4j_tpu.faulttolerance.cluster import (
    ClusterCoordinator, ClusterMember, ClusterView, FileLeaseStore,
    live_ranks)
from deeplearning4j_tpu.nn.conf.updaters import Adam
from deeplearning4j_tpu.nn.layers.feedforward import (DenseLayer,
                                                      OutputLayer)
from deeplearning4j_tpu.observability.registry import default_registry
from deeplearning4j_tpu.parallel import ShardedTrainer, make_mesh
from deeplearning4j_tpu.parallel.distributed import ElasticTrainer
from deeplearning4j_tpu.parallel.mesh import DATA_AXIS

pytestmark = pytest.mark.skipif(
    len(jax.devices()) < 8, reason="needs 8 virtual devices")

HELPER = os.path.join(os.path.dirname(__file__), "helpers",
                      "shard_chaos.py")


def mlp(seed=19, hidden=32, features=8, classes=4):
    b = (NeuralNetConfiguration.builder().seed(seed)
         .updater(Adam(learning_rate=0.02)))
    lb = b.list()
    lb.layer(DenseLayer(n_out=hidden, activation="tanh"))
    lb.layer(OutputLayer(n_out=classes, activation="softmax",
                         loss="mcxent"))
    conf = lb.set_input_type(InputType.feed_forward(features)).build()
    return MultiLayerNetwork(conf).init()


def batch(n=32, features=8, classes=4, seed=0):
    rng = np.random.default_rng(seed)
    x = rng.standard_normal((n, features)).astype(np.float32)
    y = np.eye(classes, dtype=np.float32)[rng.integers(0, classes, n)]
    return x, y


def batches(n=12, features=8, classes=4, seed=7, bs=8):
    rng = np.random.default_rng(seed)
    out = []
    for _ in range(n):
        x = rng.standard_normal((bs, features)).astype(np.float32)
        out.append((x, np.eye(classes,
                              dtype=np.float32)[rng.integers(0, classes,
                                                             bs)]))
    return out


def digests(params):
    return {f"{ln}/{pn}": hashlib.sha256(
        np.ascontiguousarray(np.array(params[ln][pn])).tobytes()
    ).hexdigest() for ln in sorted(params) for pn in sorted(params[ln])}


def compiles():
    c = default_registry().get("training_compile_total")
    return 0.0 if c is None else c.labels("train_step").value


def sharded_net(seed=19, dp=4, **kw):
    net = mlp(seed=seed, **kw)
    st = ShardedTrainer(net, make_mesh(dp=dp), min_shard_size=0)
    return net, st


# ------------------------------------------------ barrier protocol (fast)

def _two_writer_save(mgr, net, step, generation=1, timeout_s=10.0,
                     live=None):
    """Emulate both writers of a 2-process world from one process: the
    non-primary stages its block + marker first, then the primary
    commits.  Every shard is addressable here so both stage complete
    block sets — restore dedupes by start offset."""
    mgr.save_sharded(net, process_index=1, process_count=2, step=step,
                     barrier=ShardBarrier(generation=generation,
                                          timeout_s=timeout_s))
    return mgr.save_sharded(
        net, process_index=0, process_count=2, step=step,
        barrier=ShardBarrier(generation=generation, timeout_s=timeout_s,
                             live_fn=live))


def test_two_writer_barrier_commit_restores_cross_topology(tmp_path):
    """Tentpole acceptance: a dp=4 two-writer barrier save commits only
    after both blocks land, and restores onto dp=2 (and dp=8) with exact
    param + updater digests."""
    x, y = batch()
    net, st = sharded_net(dp=4)
    for _ in range(3):
        st.fit(x, y)
    mgr = CheckpointManager(str(tmp_path / "store"), background=False)
    path = _two_writer_save(mgr, net, step=3)
    assert os.path.isdir(path)
    names = sorted(os.listdir(path))
    # both writers' blocks, both generation-fenced markers, one manifest
    assert {"shards-p00.npz", "shards-p01.npz", "block-p00.json",
            "block-p01.json", "topology.json",
            "manifest.json"} <= set(names)
    with open(os.path.join(path, "topology.json")) as f:
        assert json.load(f)["process_count"] == 2
    want = digests(net.params)
    opt_want = [np.array(l) for l in
                jax.tree_util.tree_leaves(net.opt_state)]
    for dp in (2, 8):
        net2, _ = mgr.restore_sharded(path, mesh=make_mesh(dp=dp),
                                      min_shard_size=0)
        assert digests(net2.params) == want
        for a, b in zip(opt_want,
                        jax.tree_util.tree_leaves(net2.opt_state)):
            np.testing.assert_array_equal(a, np.array(b))


def test_barrier_primary_waits_for_late_writer(tmp_path):
    """The barrier is a real rendezvous: the primary blocks until the
    late writer's marker lands, then commits."""
    net, st = sharded_net(seed=23)
    mgr = CheckpointManager(str(tmp_path / "store"), background=False)
    done = {}

    def primary():
        done["path"] = mgr.save_sharded(
            net, process_index=0, process_count=2, step=1,
            barrier=ShardBarrier(generation=7, timeout_s=30))

    th = threading.Thread(target=primary)
    th.start()
    time.sleep(0.3)
    assert th.is_alive()          # still waiting on writer 1's marker
    mgr2 = CheckpointManager(mgr.directory, background=False)
    mgr2.save_sharded(net, process_index=1, process_count=2, step=1,
                      barrier=ShardBarrier(generation=7, timeout_s=30))
    th.join(timeout=30)
    assert not th.is_alive()
    assert os.path.isdir(done["path"])
    assert mgr.latest() == done["path"]


def test_barrier_abort_on_eviction_and_orphan_sweep(tmp_path):
    """Satellite: a writer evicted mid-barrier aborts the round — the
    staging dir is a ``.tmp-`` orphan (never restorable, reclaimed by
    sweep), ``latest()`` still answers the previous complete dir."""
    x, y = batch(seed=3)
    net, st = sharded_net(seed=29)
    st.fit(x, y)
    mgr = CheckpointManager(str(tmp_path / "store"), background=False)
    prev = _two_writer_save(mgr, net, step=1)          # a complete round
    st.fit(x, y)
    with pytest.raises(ShardBarrierError, match="evicted mid-barrier"):
        mgr.save_sharded(net, process_index=0, process_count=2, step=2,
                         barrier=ShardBarrier(generation=2, timeout_s=30,
                                              live_fn=lambda: {0}))
    names = os.listdir(mgr.directory)
    orphans = [n for n in names if n.startswith(".tmp-")]
    assert orphans and not any(n == "ckpt-00000002" for n in names)
    # the orphan is invisible to discovery and never restorable
    assert mgr.latest() == prev
    net2, _ = mgr.restore_sharded(mesh=make_mesh(dp=2), min_shard_size=0)
    assert net2.iteration == 1
    assert mgr.sweep_orphans() == len(orphans)
    assert not any(n.startswith(".tmp-")
                   for n in os.listdir(mgr.directory))
    reg = default_registry()
    c = reg.get("checkpoint_barrier_aborts_total")
    assert c is None or c.labels().value >= 1


def test_barrier_abort_on_timeout(tmp_path):
    net, st = sharded_net(seed=31)
    mgr = CheckpointManager(str(tmp_path / "store"), background=False)
    t0 = time.monotonic()
    with pytest.raises(ShardBarrierError, match="never landed"):
        mgr.save_sharded(net, process_index=0, process_count=2, step=1,
                         barrier=ShardBarrier(generation=1,
                                              timeout_s=0.4))
    assert time.monotonic() - t0 < 10
    assert mgr.latest() is None


def test_stale_generation_writer_cannot_land_block(tmp_path):
    """Satellite: generation fencing end to end.  A stale-generation
    writer stages into a DIFFERENT (orphan) staging dir, and even a
    forged marker with the wrong generation inside the live round's dir
    is rejected — it can never satisfy (or pollute) a newer round."""
    net, st = sharded_net(seed=37)
    mgr = CheckpointManager(str(tmp_path / "store"), background=False)
    final = mgr.path_for(1)
    # the stale writer (missed the gen 3 -> 4 bump) posts its block
    mgr.save_sharded(net, process_index=1, process_count=2, step=1,
                     barrier=ShardBarrier(generation=3, timeout_s=5))
    stale_dir = mgr.barrier_staging(final, 3)
    live_dir = mgr.barrier_staging(final, 4)
    assert os.path.isdir(stale_dir) and stale_dir != live_dir
    # a forged wrong-generation marker inside the live round's dir
    os.makedirs(live_dir, exist_ok=True)
    with open(os.path.join(live_dir, "block-p01.json"), "w") as f:
        json.dump({"process_index": 1, "generation": 3,
                   "complete": True}, f)
    assert mgr._scan_block_markers(live_dir, 4) == set()
    # so the gen-4 primary can only time out — the stale block never
    # lands in the newer round's checkpoint
    with pytest.raises(ShardBarrierError, match="never landed"):
        mgr.save_sharded(net, process_index=0, process_count=2, step=1,
                         barrier=ShardBarrier(generation=4,
                                              timeout_s=0.4))
    assert mgr.latest() is None
    assert mgr.sweep_orphans() >= 2        # both rounds' staging dirs


def test_barrier_chaos_stages_fire_in_order(tmp_path):
    """The torn-store probe windows stay SIGKILL-testable: primary fires
    stages 1 (container staged), 2 (mid-block), 3 (post-barrier,
    pre-manifest), 4 (post-manifest, pre-rename); a non-primary fires
    only stage 2."""
    net, st = sharded_net(seed=41)

    class Probe:
        def __init__(self):
            self.stages = []

        def on_commit_stage(self, step, stage):
            self.stages.append((step, stage))

    mgr = CheckpointManager(str(tmp_path / "store"), background=False)
    mgr.chaos = Probe()
    mgr.save_sharded(net, process_index=1, process_count=2, step=5,
                     barrier=ShardBarrier(generation=1, timeout_s=5))
    assert mgr.chaos.stages == [(5, 2)]
    mgr.chaos = Probe()
    mgr.save_sharded(net, process_index=0, process_count=2, step=5,
                     barrier=ShardBarrier(generation=1, timeout_s=5))
    assert mgr.chaos.stages == [(5, 1), (5, 2), (5, 3), (5, 4)]


def test_live_ranks_reads_leases_without_revoking(tmp_path):
    store = FileLeaseStore(str(tmp_path))
    store.renew(3, ttl_s=10.0)
    store.renew(9, ttl_s=0.01)
    view = ClusterView(generation=1, members=(3, 7, 9))
    time.sleep(0.05)
    assert live_ranks(store, view) == {0}      # 3 -> rank 0; 9 expired
    # reads only: the expired lease file is still there for the
    # coordinator's eviction verdict
    assert store.read(9) is not None


# --------------------------------- elastic trainer over sharded (fast)

def test_elastic_sharded_solo_and_survivor_mesh_restore(tmp_path):
    """Acceptance: ElasticTrainer writes SHARDED checkpoint dirs for a
    ShardedTrainer model; a restart on a smaller survivor mesh skips a
    corrupt newest checkpoint, restores the previous COMPLETE one
    through restore_sharded(mesh=survivors) digest-exact, trains on —
    and the train step keeps ONE trace across the dp=4 -> dp=2 topology
    change (counter-verified)."""
    bs = batches()
    store = str(tmp_path / "run")
    before = compiles()
    net1 = mlp(seed=19, hidden=40)
    t1 = ElasticTrainer(
        ShardedTrainer(net1, make_mesh(dp=4), min_shard_size=0),
        store, save_freq=4, keep_last=3)
    assert t1.fit(lambda: iter(bs)) == len(bs)
    ck = sorted(n for n in os.listdir(store) if n.startswith("ckpt-"))
    assert len(ck) >= 2
    # every committed checkpoint is a sharded dir
    for name in ck:
        assert os.path.isfile(os.path.join(store, name, "topology.json"))
    mgr = CheckpointManager(store, background=False)
    want_prev = digests(mgr.restore_sharded(
        os.path.join(store, ck[-2]))[0].params)

    # corrupt the NEWEST checkpoint's shard file: restore must fall
    # back to the previous complete sharded dir, not abort the rejoin
    newest = os.path.join(store, ck[-1])
    shard = next(f for f in os.listdir(newest) if f.endswith(".npz"))
    with open(os.path.join(newest, shard), "r+b") as f:
        f.seek(30)
        f.write(b"\xde\xad\xbe\xef")

    net2 = mlp(seed=19, hidden=40)
    t2 = ElasticTrainer(
        ShardedTrainer(net2, make_mesh(dp=2), min_shard_size=0),
        store, save_freq=4)
    prev_step = int(ck[-2].split("-")[1])
    step0 = t2.restore_latest()
    assert step0 == prev_step
    # restored onto the dp=2 survivor mesh digest-exact
    assert digests(net2.params) == want_prev
    assert any("data" in str(l.sharding.spec)
               for l in jax.tree_util.tree_leaves(net2.params))
    done = t2.fit(lambda: iter(bs))
    assert done == len(bs)
    assert np.isfinite(net2.get_score())
    # hidden=40 is unique to this test: the dp=4 run, the dp=2 restore
    # and the resumed fit all share ONE Python trace of the train step
    assert compiles() - before == 1


def test_elastic_sharded_membership_loss_rebuilds_survivor_mesh(tmp_path):
    """Tentpole (b): a member lost mid-run aborts its in-flight barrier
    round (never a torn store), is evicted at the next boundary, and the
    survivor rebuilds the mesh over itself via
    restore_sharded(mesh=survivors) — then finishes every batch."""
    bs = batches()
    # prewarm the train-step compile with a throwaway same-topology net:
    # the short fake lease below must expire MID-BARRIER (after the
    # first boundary begins), not during the first step's XLA compile
    warm = mlp(seed=19, hidden=48)
    ShardedTrainer(warm, make_mesh(dp=4), min_shard_size=0).fit_batch(
        bs[0])
    store = FileLeaseStore(str(tmp_path))
    coord = ClusterCoordinator(store, lease_ttl_s=0.4)
    m0 = ClusterMember(store, 0, lease_ttl_s=5.0)
    m0.renew_once()
    net = mlp(seed=19, hidden=48)
    st = ShardedTrainer(net, make_mesh(dp=4), min_shard_size=0)
    t = ElasticTrainer(st, str(tmp_path), save_freq=2, member=m0,
                       coordinator=coord,
                       mesh_factory=lambda w: make_mesh(dp=2 * w),
                       barrier_timeout_s=5.0)
    store.renew(1, ttl_s=0.45)            # will die silently mid-run
    coord.begin_round(0)

    def slow():
        for b in bs:
            time.sleep(0.06)
            yield b

    try:
        n = t.fit(slow)
    finally:
        m0.stop()
    assert n == len(bs) and t.trained_steps == len(bs)
    # the dead member's round aborted instead of tearing the store
    assert t.barrier_aborts >= 1
    assert t.last_view.members == (0,)
    # survivor mesh: dp followed the world size through mesh_factory
    assert st.mesh.shape[DATA_AXIS] == 2
    assert len(t.reshard_events) == 1
    ev = t.reshard_events[0]
    assert ev["dp"] == 2 and ev["world_size"] == 1
    assert ev["via"] == "restore_sharded"
    # every committed checkpoint is complete and restorable
    mgr = CheckpointManager(str(tmp_path), background=False)
    for _, path, manifest in mgr.checkpoints():
        assert manifest.get("sharded")
    assert mgr.latest() is not None
    net2, _ = mgr.restore_sharded(mesh=make_mesh(dp=2), min_shard_size=0)
    assert np.isfinite(
        float(np.sum(np.array(net2.params["layer_0"]["W"]))))


def test_restore_sharded_indivisible_dp_replicates_digest_exact(tmp_path):
    """Satellite: restoring onto a survivor mesh whose dp divides NO
    axis of a leaf falls back to replication per the zero3/min_shard
    rules — digest-exact (re-placement moves bytes, never arithmetic)."""
    x, y = batch(seed=5)
    net, st = sharded_net(seed=43, dp=4, hidden=32, features=8)
    st.fit(x, y)
    mgr = CheckpointManager(str(tmp_path / "store"), background=False)
    mgr.save_sharded(net, step=1)
    want = digests(net.params)
    # dp=3 divides neither 8 nor 32 evenly... except 32 % ... 32=3*10+2:
    # no axis of (8,32)/(32,)/(32,4)/(4,) is divisible by 3 -> P()
    net2, _ = mgr.restore_sharded(mesh=make_mesh(dp=3), min_shard_size=0)
    assert digests(net2.params) == want
    specs = {str(l.sharding.spec)
             for l in jax.tree_util.tree_leaves(net2.params)}
    assert specs == {"PartitionSpec()"}
    # and training continues on the survivor mesh
    st2 = ShardedTrainer(net2, make_mesh(dp=3), min_shard_size=0)
    st2.fit(x, y)
    assert np.isfinite(net2.get_score())


# ------------------------------------------------ chaos (two real writers)

def _run_shard_worker(pid, store, out_json, chaos="", batches_n=12,
                      step_sleep=0.0, lease_ttl=2.0, barrier_timeout=90,
                      timeout=300):
    env = dict(os.environ)
    env.update({"JAX_PLATFORMS": "cpu",
                "XLA_FLAGS": "--xla_force_host_platform_device_count=2",
                "SC_DIR": str(store), "SC_OUT": str(out_json),
                "SC_PID": str(pid), "SC_BATCHES": str(batches_n),
                "SC_SAVE_FREQ": "4",
                "SC_STEP_SLEEP": str(step_sleep),
                "SC_LEASE_TTL_S": str(lease_ttl),
                "SC_BARRIER_TIMEOUT_S": str(barrier_timeout),
                "SC_CHAOS": chaos})
    log = open(str(out_json) + ".log", "w")
    p = subprocess.Popen([sys.executable, HELPER], env=env, stdout=log,
                         stderr=subprocess.STDOUT)
    p._logfile = log
    p._deadline = time.time() + timeout
    return p


def _finish(procs):
    rcs = []
    try:
        for p in procs:
            try:
                rcs.append(p.wait(timeout=max(p._deadline - time.time(),
                                              10)))
            except subprocess.TimeoutExpired:
                p.kill()
                rcs.append(p.wait(timeout=30))
    finally:
        # a wedged worker must not outlive its test: kill stragglers
        # before surfacing whatever failed
        for p in procs:
            if p.poll() is None:
                p.kill()
            p._logfile.close()
    return rcs


def _read(out_json):
    with open(out_json) as f:
        return json.load(f)


def _log(out_json):
    try:
        with open(str(out_json) + ".log") as f:
            return f.read()
    except OSError:
        return "<no log>"


def _recover_in_process(store, n=12, dp=2):
    """The survivor-mesh recovery phase: a fresh single-process trainer
    restores the store's newest COMPLETE checkpoint onto a dp=``dp``
    mesh and trains the remaining batches."""
    net = None
    from tests.helpers.shard_chaos import build_model, make_batches
    net = build_model()
    st = ShardedTrainer(net, make_mesh(dp=dp), min_shard_size=0)
    t = ElasticTrainer(st, str(store), save_freq=4)
    t.fit(lambda: iter(make_batches(n)))
    from jax.flatten_util import ravel_pytree
    flat, _ = ravel_pytree(net.params)
    flat = np.asarray(flat, np.float64)
    return (hashlib.sha256(flat.tobytes()).hexdigest(),
            t.last_restored_step)


@pytest.fixture(scope="module")
def fault_free(tmp_path_factory):
    """One fault-free two-writer run shared by every chaos test: the
    digest every recovery must reproduce exactly, plus a store whose
    barrier checkpoints prove the multi-writer commit is restorable."""
    root = tmp_path_factory.mktemp("shard_ref")
    store = root / "store"
    outs = [root / "r0.json", root / "r1.json"]
    procs = [_run_shard_worker(i, store, outs[i]) for i in (0, 1)]
    rcs = _finish(procs)
    assert rcs == [0, 0], f"ref run failed:\n{_log(outs[0])}\n" \
                          f"{_log(outs[1])}"
    res = [_read(o) for o in outs]
    assert res[0]["param_digest"] == res[1]["param_digest"]
    assert res[0]["barrier_aborts"] == 0
    return {"store": str(store), "digest": res[0]["param_digest"],
            "results": res}


@pytest.mark.chaos
def test_shard_chaos_fault_free_barrier_store_reshards(fault_free):
    """The fault-free rig itself: every committed checkpoint is a
    complete TWO-writer barrier dir, and the earliest (written while
    both members were live) restores onto dp=2 AND dp=4 with identical
    digests — the cross-topology claim on a real multi-writer store."""
    mgr = CheckpointManager(fault_free["store"], background=False)
    ckpts = mgr.checkpoints()
    assert ckpts
    two_writer = [p for _, p, m in ckpts
                  if os.path.isfile(os.path.join(p, "shards-p01.npz"))]
    assert two_writer, [p for _, p, _ in ckpts]
    path = two_writer[0]
    a, _ = mgr.restore_sharded(path, mesh=make_mesh(dp=2),
                               min_shard_size=0)
    b, _ = mgr.restore_sharded(path, mesh=make_mesh(dp=4),
                               min_shard_size=0)
    da = {k: v for k, v in digests(a.params).items()}
    assert da == digests(b.params)


@pytest.mark.chaos
def test_shard_chaos_non_primary_dies_mid_block(tmp_path, fault_free):
    """A non-primary shard writer hard-dies MID-BLOCK (bytes staged,
    marker never posted) at the final save: the primary's barrier times
    out and aborts, latest() falls back to the previous complete sharded
    dir, and recovery on the survivor mesh matches the fault-free digest
    exactly."""
    store = tmp_path / "store"
    outs = [tmp_path / "r0.json", tmp_path / "r1.json"]
    # lease far beyond the run: the primary's verdict is the bounded
    # barrier TIMEOUT, deterministic regardless of scheduling skew
    procs = [
        _run_shard_worker(0, store, outs[0], lease_ttl=600,
                          barrier_timeout=6),
        _run_shard_worker(1, store, outs[1], chaos="block:12",
                          lease_ttl=600, barrier_timeout=6),
    ]
    rcs = _finish(procs)
    assert rcs[1] == 23, _log(outs[1])          # hard-died mid-block
    assert rcs[0] == 0, _log(outs[0])
    res0 = _read(outs[0])
    assert res0["steps"] == 12
    assert res0["barrier_aborts"] >= 1
    assert res0["param_digest"] == fault_free["digest"]
    # no torn checkpoint: the aborted round is a .tmp- orphan, latest()
    # is the previous complete barrier dir (step 8)
    names = os.listdir(store)
    assert not any(n == "ckpt-00000012" for n in names), names
    assert any(n.startswith(".tmp-") for n in names), names
    mgr = CheckpointManager(str(store), background=False)
    latest = mgr.latest()
    assert latest is not None and latest.endswith("ckpt-00000008")
    # survivor-mesh recovery: restore + train the remaining batches
    digest, resumed = _recover_in_process(store)
    assert resumed == 8
    assert digest == fault_free["digest"]
    # the orphan was swept by the recovery trainer
    assert not any(n.startswith(".tmp-") for n in os.listdir(store))


@pytest.mark.chaos
@pytest.mark.parametrize("mode,step", [("precommit", 8), ("manifest", 8)])
def test_shard_chaos_primary_dies_before_commit(tmp_path, fault_free,
                                                mode, step):
    """The PRIMARY hard-dies after the barrier passed — between barrier
    and commit (stage 3) or on the manifest (stage 4): everything is
    staged, nothing is committed.  Only complete checkpoints remain and
    recovery from the previous complete dir is digest-exact."""
    store = tmp_path / "store"
    outs = [tmp_path / "r0.json", tmp_path / "r1.json"]
    procs = [
        _run_shard_worker(0, store, outs[0], chaos=f"{mode}:{step}"),
        _run_shard_worker(1, store, outs[1]),
    ]
    rcs = _finish(procs)
    assert rcs[0] == 23, _log(outs[0])
    assert rcs[1] == 0, _log(outs[1])
    res1 = _read(outs[1])
    assert res1["steps"] == 12          # the non-primary trains on
    assert res1["param_digest"] == fault_free["digest"]
    names = os.listdir(store)
    assert not any(n == f"ckpt-{step:08d}" for n in names), names
    mgr = CheckpointManager(str(store), background=False)
    latest = mgr.latest()
    assert latest is not None and latest.endswith("ckpt-00000004")
    digest, resumed = _recover_in_process(store)
    assert resumed == 4
    assert digest == fault_free["digest"]


@pytest.mark.chaos
def test_shard_chaos_partition_during_barrier(tmp_path, fault_free):
    """A PARTITIONED member (heartbeats stop, process stalls) expires
    mid-barrier: the primary aborts the round on the eviction verdict,
    the survivors train every remaining batch, the stale member comes
    back fenced out (trains nothing, writes nothing), and the final
    state matches the fault-free run exactly."""
    store = tmp_path / "store"
    outs = [tmp_path / "r0.json", tmp_path / "r1.json"]
    procs = [
        _run_shard_worker(0, store, outs[0], step_sleep=0.3,
                          lease_ttl=4.0),
        _run_shard_worker(1, store, outs[1], step_sleep=0.3,
                          lease_ttl=4.0, chaos="partition:7:25"),
    ]
    rcs = _finish(procs)
    assert rcs == [0, 0], f"{_log(outs[0])}\n{_log(outs[1])}"
    res0, res1 = _read(outs[0]), _read(outs[1])
    assert res0["steps"] == 12
    assert res0["param_digest"] == fault_free["digest"]
    # the partitioned member was fenced out by the generation bump: it
    # consumed the stream but never trained or wrote past the partition
    assert res1["evicted"] is True
    # the primary either aborted a round mid-barrier or evicted the
    # partitioned member at the boundary before the barrier began —
    # both leave ONLY complete checkpoints behind
    mgr = CheckpointManager(str(store), background=False)
    latest = mgr.latest()
    assert latest is not None and latest.endswith("ckpt-00000012")
    digest, resumed = _recover_in_process(store)
    assert resumed == 12
    assert digest == fault_free["digest"]
