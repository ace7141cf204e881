"""Compilation-aware execution (ISSUE 4): shared trace cache, static-shape
bucketing, and the persistent XLA compile cache.

Acceptance criteria covered here:
  - ParameterAveragingTrainingMaster with 4 replicas performs exactly ONE
    train-step compile (counter-verified);
  - a ragged-last-batch fit performs at most 2 compiles (steady bucket +
    the label-masked padded variant), with the padded batch numerically
    matching the unpadded reference;
  - clone() carries a split RNG stream (regression: replicas used to draw
    identical dropout masks).
"""
import copy
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu import (InputType, MultiLayerNetwork,
                                NeuralNetConfiguration)
from deeplearning4j_tpu.data.shapes import (ShapePolicy, default_shape_policy,
                                            next_pow2)
from deeplearning4j_tpu.nn.compile_cache import (DEFAULT_CACHE_DIR,
                                                 persistent_cache_status,
                                                 topology_signature,
                                                 wire_persistent_cache)
from deeplearning4j_tpu.nn.conf.updaters import Adam, Sgd
from deeplearning4j_tpu.nn.layers.feedforward import (DenseLayer,
                                                      OutputLayer)
from deeplearning4j_tpu.nn.layers.recurrent import LSTM, RnnOutputLayer
from deeplearning4j_tpu.observability.registry import default_registry


def mlp(seed=42, hidden=16, lr=0.02, dropout=None, features=4, classes=3):
    b = (NeuralNetConfiguration.builder().seed(seed)
         .updater(Adam(learning_rate=lr)))
    lb = b.list()
    lb.layer(DenseLayer(n_out=hidden, activation="tanh", dropout=dropout))
    lb.layer(OutputLayer(n_out=classes, activation="softmax", loss="mcxent"))
    conf = lb.set_input_type(InputType.feed_forward(features)).build()
    return MultiLayerNetwork(conf).init()


def compiles(fn="train_step"):
    c = default_registry().get("training_compile_total")
    return 0.0 if c is None else c.labels(fn).value


def batch(n, features=4, classes=3, seed=0):
    rng = np.random.default_rng(seed)
    x = rng.standard_normal((n, features)).astype(np.float32)
    y = np.eye(classes, dtype=np.float32)[rng.integers(0, classes, n)]
    return x, y


# ------------------------------------------------------------- signature
def test_signature_stable_under_deepcopy():
    net = mlp(hidden=21)
    assert topology_signature(net.conf) == \
        topology_signature(copy.deepcopy(net.conf))


def test_signature_changes_on_conf_edits():
    a, b = mlp(hidden=22), mlp(hidden=22)
    assert topology_signature(a.conf) == topology_signature(b.conf)
    b.conf.defaults["gradient_normalization"] = "clipl2perlayer"
    assert topology_signature(a.conf) != topology_signature(b.conf)
    c = mlp(hidden=22, lr=0.5)   # updater spec is part of the signature
    assert topology_signature(a.conf) != topology_signature(c.conf)


def test_invalidate_compile_cache_rekeys():
    net = mlp(hidden=23)
    f1 = net._get_jitted("output")
    net.conf.defaults["cache_mode"] = "remat"   # in-place conf edit
    net.invalidate_compile_cache()
    f2 = net._get_jitted("output")
    assert f1 is not f2


# ----------------------------------------------------- shared trace cache
def test_clone_shares_compiled_step_zero_extra_compiles():
    net = mlp(hidden=24)
    x, y = batch(32)
    net.fit(x, y)
    base = compiles()
    replicas = [net.clone() for _ in range(3)]
    for r in replicas:
        assert r._get_jitted("train_step") is net._get_jitted("train_step")
        r.fit_batch((x, y))
    assert compiles() == base   # replicas 2..K add ZERO compiles


def test_master_four_replicas_single_compile():
    """ISSUE 4 acceptance: 4-worker parameter averaging = 1 compile."""
    from deeplearning4j_tpu.parallel.master import \
        ParameterAveragingTrainingMaster
    net = mlp(hidden=25, seed=99)   # unique topology: compile counted HERE
    before = compiles()
    master = ParameterAveragingTrainingMaster(num_workers=4,
                                              averaging_frequency=2)
    batches = [batch(16, seed=i) for i in range(8)]
    master.fit(net, iter(batches))
    assert compiles() - before == 1.0
    # same-topology second round: still nothing new to compile
    master.fit(net, iter(batches))
    assert compiles() - before == 1.0


def test_ragged_last_batch_fit_at_most_two_compiles():
    net = mlp(hidden=26, seed=7)
    before = compiles()
    xs, ys = batch(48, seed=1)
    net.fit(iter([(xs, ys, None, None),
                  (xs[:31], ys[:31], None, None),
                  (xs[:17], ys[:17], None, None)]))
    # steady bucket + ONE padded (label-masked) variant, reused by both tails
    assert compiles() - before <= 2.0


def test_clone_rng_split_regression():
    """clone() must not restart every replica from PRNGKey(conf.seed)."""
    net = mlp(hidden=27, dropout=0.5)
    c1, c2 = net.clone(), net.clone()
    keys = [np.asarray(m._rng) for m in (net, c1, c2)]
    assert not np.array_equal(keys[0], keys[1])
    assert not np.array_equal(keys[1], keys[2])
    x, _ = batch(64)
    # train=True keeps dropout active: replica outputs must differ
    o1 = np.asarray(c1.output(x, train=True))
    o2 = np.asarray(c2.output(x, train=True))
    assert not np.allclose(o1, o2)


# ------------------------------------------------------- shape bucketing
def test_padded_batch_matches_unpadded_reference():
    """Loss/grad parity: one padded step == one unpadded step, exactly."""
    xs, ys = batch(37, seed=3)
    ref = mlp(hidden=28)
    ref.shape_policy = ShapePolicy("off")
    padded = mlp(hidden=28)
    padded.shape_policy = ShapePolicy("auto")
    padded.shape_policy.observe("train", 64)      # a compiled bucket exists
    s_ref = ref.score(x=xs, y=ys)
    ref.fit_batch((xs, ys))
    s_pad = padded.score(x=xs, y=ys)
    padded.fit_batch((xs, ys))                    # pads 37 -> 64
    assert s_pad == pytest.approx(s_ref, rel=1e-6)
    assert padded.get_score() == pytest.approx(ref.get_score(), rel=1e-6)
    for k in ref.params:
        for p in ref.params[k]:
            np.testing.assert_allclose(np.asarray(ref.params[k][p]),
                                       np.asarray(padded.params[k][p]),
                                       rtol=1e-6, atol=1e-8)


def test_eval_and_score_ride_buckets():
    net = mlp(hidden=29)
    xs, ys = batch(64, seed=4)
    net.fit(xs, ys)
    full = np.asarray(net.output(xs))
    before = compiles("output")
    ragged = np.asarray(net.output(xs[:13]))      # pads to 64, slices back
    assert compiles("output") == before           # no new forward compile
    np.testing.assert_allclose(ragged, full[:13], rtol=1e-6)
    # score on a ragged batch: exact masked-mean parity with policy off
    s_bucketed = net.score(x=xs[:13], y=ys[:13])
    net.shape_policy = ShapePolicy("off")
    s_plain = net.score(x=xs[:13], y=ys[:13])
    assert s_bucketed == pytest.approx(s_plain, rel=1e-6)


def test_tbptt_ragged_tail_chunk_parity():
    """T % L != 0: the short final chunk pads to L with zero-masked steps
    and must match the unpadded reference step for step."""
    def rnn_net():
        b = (NeuralNetConfiguration.builder().seed(5)
             .updater(Sgd(learning_rate=0.05)))
        lb = b.list()
        lb.layer(LSTM(n_out=6))
        lb.layer(RnnOutputLayer(n_out=2, activation="softmax",
                                loss="mcxent"))
        lb.backprop_type("tbptt", fwd=4, back=4)
        conf = lb.set_input_type(InputType.recurrent(3, 10)).build()
        return MultiLayerNetwork(conf).init()

    rng = np.random.default_rng(0)
    x = rng.standard_normal((8, 10, 3)).astype(np.float32)   # 10 = 4+4+2
    y = np.eye(2, dtype=np.float32)[
        rng.integers(0, 2, (8, 10))].astype(np.float32)
    ref, pad = rnn_net(), rnn_net()
    ref.shape_policy = ShapePolicy("off")
    ref.fit(x, y)
    pad.fit(x, y)
    assert pad.get_score() == pytest.approx(ref.get_score(), rel=1e-5)
    for k in ref.params:
        for p in ref.params[k]:
            np.testing.assert_allclose(np.asarray(ref.params[k][p]),
                                       np.asarray(pad.params[k][p]),
                                       rtol=1e-5, atol=1e-7)


def test_shape_policy_modes_and_env():
    p = ShapePolicy("pow2")
    assert p.target_batch("t", 37) == 64 and next_pow2(1) == 1
    p = ShapePolicy("buckets", batch_buckets=[8, 32])
    assert p.target_batch("t", 9) == 32
    assert p.target_batch("t", 100) == 100     # beyond top bucket: as-is
    assert default_shape_policy({"DL4J_TPU_SHAPE_BUCKETS": "off"}).mode \
        == "off"
    assert default_shape_policy({"DL4J_TPU_SHAPE_BUCKETS": "8,16"}) \
        .batch_buckets == [8, 16]
    assert default_shape_policy({}).mode == "auto"
    with pytest.raises(ValueError):
        default_shape_policy({"DL4J_TPU_SHAPE_BUCKETS": "nonsense"})


def test_yolo_loss_never_padded():
    """The YOLO head ignores masks, so training-side padding is refused."""
    from deeplearning4j_tpu.nn.layers.objdetect import Yolo2OutputLayer
    assert Yolo2OutputLayer().SUPPORTS_LOSS_MASK is False


def test_moe_aux_loss_gates_all_padding():
    """AUX_LOSS stacks couple rows (expert capacity + whole-batch aux
    term): no padding on any path, including inference."""
    from deeplearning4j_tpu.nn.layers import MixtureOfExpertsLayer
    from deeplearning4j_tpu.nn.layers.recurrent import RnnOutputLayer
    conf = (NeuralNetConfiguration.builder().seed(2)
            .updater(Adam(learning_rate=0.02)).list()
            .layer(MixtureOfExpertsLayer(n_out=8, n_experts=2, hidden=16,
                                         activation="relu"))
            .layer(RnnOutputLayer(n_out=3, activation="softmax",
                                  loss="mcxent"))
            .set_input_type(InputType.recurrent(5, 7)).build())
    net = MultiLayerNetwork(conf).init()
    assert not net._pad_output_safe()
    assert not net._pad_eval_safe()
    assert not net._pad_train_safe()
    # a plain dense stack keeps all three
    assert mlp(hidden=30)._pad_train_safe()


def test_eval_pad_ratio_cap():
    """output(1) after one large-batch dispatch must not pay the large
    batch's compute forever — auto mode caps eval padding at 8x."""
    p = ShapePolicy("auto")
    p.observe("eval", 512)
    x = jnp.ones((1, 4))
    padded, n = p.pad_eval_rows(x)
    assert n == 1 and padded.shape[0] == 1          # capped: no 512x pad
    p2 = ShapePolicy("auto")
    p2.observe("eval", 64)
    padded, n = p2.pad_eval_rows(jnp.ones((13, 4)))
    assert n == 13 and padded.shape[0] == 64        # within 8x: pads


def test_compile_phase_label_tracks_real_traces():
    """The compile/steady metrics split keys off REAL trace events: a
    clone's cache-hit first step reads steady."""
    net = mlp(hidden=31, seed=11)
    x, y = batch(24)
    net.fit_batch((x, y))
    assert net._last_step_traced                    # cold: traced
    net.fit_batch((x, y))
    assert not net._last_step_traced                # steady
    replica = net.clone()
    replica.fit_batch((x, y))
    assert not replica._last_step_traced            # cache hit != compile


# ----------------------------------------------------- persistent cache
@pytest.fixture
def cache_config():
    """Restore the jax cache config and the module status around a test
    that re-wires the persistent cache (tests/conftest.py keeps it OFF for
    the suite)."""
    from jax.experimental.compilation_cache import compilation_cache as jcc
    prev_dir = jax.config.jax_compilation_cache_dir
    prev_on = jax.config.jax_enable_compilation_cache
    yield
    jax.config.update("jax_compilation_cache_dir", prev_dir)
    jax.config.update("jax_enable_compilation_cache", prev_on)
    jcc.reset_cache()


def test_cache_dir_placed_from_outside_is_never_set_in_code(
        tmp_path, monkeypatch, cache_config):
    """With JAX_COMPILATION_CACHE_DIR set the code sets NO directory (JAX
    reads the variable itself); it reports that directory, and a second
    wiring sees the entries the 'first process' left behind."""
    cache_dir = tmp_path / "xla-cache"
    cache_dir.mkdir()
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(cache_dir))
    sentinel = str(tmp_path / "whatever-jax-read-at-start-up")
    jax.config.update("jax_compilation_cache_dir", sentinel)
    s1 = wire_persistent_cache()
    assert jax.config.jax_compilation_cache_dir == sentinel   # untouched
    assert s1 == {"enabled": True, "dir": str(cache_dir),
                  "placed_by": "JAX_COMPILATION_CACHE_DIR",
                  "existing_entries": 0}
    # thresholds lowered so small programs persist too
    assert jax.config.jax_persistent_cache_min_compile_time_secs == 0.0
    assert jax.config.jax_persistent_cache_min_entry_size_bytes == -1
    (cache_dir / "jit__entry_from_an_earlier_process").write_bytes(b"x")
    assert wire_persistent_cache()["existing_entries"] == 1
    g = default_registry().get("training_persistent_cache_entries")
    assert g is not None and g.value == 1


def test_default_cache_dir_is_one_fixed_path_in_the_checkout(
        monkeypatch, cache_config):
    """Without the variable the cache lives at ONE fixed git-ignored path
    inside the checkout — no temp name, pid or timestamp — and a compile
    that goes through it is counted as a miss, then a hit."""
    import deeplearning4j_tpu
    from jax.experimental.compilation_cache import compilation_cache as jcc
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    s1 = wire_persistent_cache()
    pkg = os.path.dirname(os.path.abspath(deeplearning4j_tpu.__file__))
    assert DEFAULT_CACHE_DIR == os.path.join(pkg, "_compile_cache")
    assert s1["enabled"] and s1["placed_by"] == "checkout"
    assert s1["dir"] == DEFAULT_CACHE_DIR == \
        jax.config.jax_compilation_cache_dir
    assert wire_persistent_cache()["dir"] == s1["dir"]        # stable
    ignored = open(os.path.join(os.path.dirname(pkg), ".gitignore")).read()
    assert "deeplearning4j_tpu/_compile_cache/" in ignored.split()
    # the suite keeps the cache off: status says so rather than pretend
    assert persistent_cache_status()["enabled"] is False
    jax.config.update("jax_enable_compilation_cache", True)
    jcc.reset_cache()
    before = persistent_cache_status()

    def probe(a):
        return a * 3 + 41            # a program no other test compiles
    jax.jit(probe)(jnp.ones((5,))).block_until_ready()
    jax.clear_caches()               # drop the in-memory executable
    jax.jit(probe)(jnp.ones((5,))).block_until_ready()
    after = persistent_cache_status()
    assert after["enabled"] is True
    assert after["misses"] + after["hits"] >= \
        before["misses"] + before["hits"] + 2
    assert after["hits"] >= before["hits"] + 1
    assert after["entries"] >= 1
