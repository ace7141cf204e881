"""Step-time engine (ISSUE 6): cost-model shape bucketing, PrecisionPolicy
mixed precision with dynamic loss scaling, and scan-over-layers.

Covers the acceptance criteria: the cost model stops padding recurring
small shapes onto large buckets (the s=128 regression class), bf16/f32
train-step parity with f32 updater state, the fp16 overflow-skip path,
scan-vs-unrolled exact parity plus the trace+compile-time reduction
(timer-verified through ``training_compile_seconds``), and precision
policies participating in the compile-cache topology signature.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from deeplearning4j_tpu import (InputType, MultiLayerNetwork,
                                NeuralNetConfiguration, PrecisionPolicy)
from deeplearning4j_tpu.data.shapes import ShapePolicy
from deeplearning4j_tpu.nn import precision as precision_mod
from deeplearning4j_tpu.nn import scan_layers as scan_mod
from deeplearning4j_tpu.nn.compile_cache import topology_signature
from deeplearning4j_tpu.nn.conf.updaters import Adam, Sgd
from deeplearning4j_tpu.nn.layers.feedforward import DenseLayer, OutputLayer
from deeplearning4j_tpu.observability.registry import default_registry


def mlp(depth=2, hidden=16, seed=3, **builder_kw):
    b = NeuralNetConfiguration.builder().seed(seed).updater(
        Adam(learning_rate=0.02))
    for k, v in builder_kw.items():
        b = getattr(b, k)(v)
    lb = b.list()
    for _ in range(depth):
        lb = lb.layer(DenseLayer(n_out=hidden, activation="tanh"))
    conf = (lb.layer(OutputLayer(n_out=3, activation="softmax",
                                 loss="mcxent"))
            .set_input_type(InputType.feed_forward(4)).build())
    return MultiLayerNetwork(conf).init()


def batch(n, seed=0):
    rng = np.random.default_rng(seed)
    x = rng.standard_normal((n, 4)).astype(np.float32)
    y = np.eye(3, dtype=np.float32)[rng.integers(0, 3, n)]
    return x, y


# ------------------------------------------------------- cost-model buckets
def test_cost_model_recurring_small_batch_stops_padding():
    """The s=128 regression class: a small shape that keeps recurring must
    NOT pad onto a large compiled bucket forever — after the cumulative
    padding waste rivals one compile, it gets its own bucket."""
    p = ShapePolicy("auto", compile_cost_s=1.0, step_cost_s=0.1)
    p.observe("train", 512)
    # waste_frac = 3.0 -> padded step costs 0.3 compile-equivalents;
    # ski-rental switches on the 4th recurrence (4 * 0.3 >= 1.0)
    assert p.target_batch("train", 128) == 512
    assert p.target_batch("train", 128) == 512
    assert p.target_batch("train", 128) == 512
    assert p.target_batch("train", 128) == 128
    # from here on 128 is a compiled bucket: dispatched natively
    assert p.target_batch("train", 128) == 128
    # and nearby smaller sizes now ride the CLOSE bucket, not the 512 one
    assert p.target_batch("train", 120) == 128


def test_cost_model_one_off_tail_still_pads():
    """A ragged epoch tail seen once per epoch keeps padding — one compile
    always dwarfs one padded step."""
    p = ShapePolicy("auto", compile_cost_s=2.0, step_cost_s=0.01)
    p.observe("train", 64)
    for _ in range(10):
        assert p.target_batch("train", 37) == 64


def test_cost_model_skip_emits_metric():
    reg = default_registry()
    p = ShapePolicy("auto", compile_cost_s=0.01, step_cost_s=1.0)
    p.observe("train", 512)

    def skipped():
        c = reg.get("training_padding_skipped_total")
        return c.labels("train").value if c is not None else 0.0

    before = skipped()
    assert p.target_batch("train", 128) == 128   # declined immediately
    assert skipped() == before + 1


def test_bucket_ladder_lru_bounded():
    p = ShapePolicy("auto", max_buckets=4, compile_cost_s=1e9)
    for size in (8, 16, 32, 64, 128, 256):
        p.observe("train", size)
    seen = dict((tuple(e[:2]), e[2]) for e in p.snapshot()["seen"])
    ladder = seen[("train", "batch")]
    assert len(ladder) == 4
    assert 8 not in ladder and 16 not in ladder      # oldest evicted
    assert ladder[-1] == 256                          # most recent last
    # the gauge tracks the live ladder size per path
    g = default_registry().get("training_shape_buckets")
    assert g is not None and g.labels("train").value == 4


def test_snapshot_restore_round_trips_cap_and_counts():
    p = ShapePolicy("auto", max_buckets=5, compile_cost_s=1.0,
                    step_cost_s=0.1)
    p.observe("train", 512)
    p.target_batch("train", 128)        # count 1 (pads)
    p.target_batch("train", 128)        # count 2 (pads)
    snap = p.snapshot()
    assert snap["cap"] == 5
    q = ShapePolicy("auto", compile_cost_s=1.0, step_cost_s=0.1)
    q.restore_state(snap)
    assert q.max_buckets == 5
    # the restored policy continues the SAME decision sequence: one more
    # padded dispatch, then the native compile on recurrence #4
    assert q.target_batch("train", 128) == 512
    assert q.target_batch("train", 128) == 128


def test_restore_accepts_legacy_snapshot():
    q = ShapePolicy("auto")
    q.restore_state({"mode": "auto", "seen": [["train", "batch", [64]]]})
    assert q.target_batch("train", 40) == 64


# ------------------------------------------------------------ precision
def test_bf16_policy_parity_and_f32_updater_state():
    """bf16 train step: loss tracks the f32 reference within tolerance,
    master params AND updater state stay f32 (acceptance criterion)."""
    x, y = batch(64, seed=1)
    f32 = mlp(seed=7)
    bf16 = mlp(seed=7, precision="bfloat16")
    for _ in range(15):
        f32.fit(x, y)
        bf16.fit(x, y)
    assert bf16.get_score() == pytest.approx(f32.get_score(), rel=0.08)
    for leaf in jax.tree_util.tree_leaves(bf16.params):
        assert leaf.dtype == jnp.float32
    for leaf in jax.tree_util.tree_leaves(bf16.opt_state):
        if hasattr(leaf, "dtype") and jnp.issubdtype(leaf.dtype,
                                                     jnp.floating):
            assert leaf.dtype == jnp.float32


def test_f16_dynamic_loss_scaling_overflow_skips_step():
    """Injected non-finite gradients: the step is SKIPPED (params and
    updater untouched), the scale halves, the overflow counter ticks —
    all inside the one jitted step."""
    x, y = batch(32, seed=2)
    net = mlp(precision="float16")
    net.fit(x, y)                                  # one good step
    ls = net.state[precision_mod.SCALE_STATE_KEY]
    scale0 = float(ls["scale"])
    assert scale0 == 2.0 ** 15 and int(ls["overflow_steps"]) == 0
    p_before = jax.tree_util.tree_map(np.asarray, net.params)
    o_before = jax.tree_util.tree_map(
        lambda a: np.asarray(a) if hasattr(a, "dtype") else a,
        net.opt_state)
    x_bad = x.copy()
    x_bad[0, 0] = 1e30                             # inf in f16 forward
    net.fit(x_bad, y)
    ls = net.state[precision_mod.SCALE_STATE_KEY]
    assert float(ls["scale"]) == scale0 * 0.5
    assert int(ls["overflow_steps"]) == 1
    for k in p_before:
        for name in p_before[k]:
            np.testing.assert_array_equal(
                p_before[k][name], np.asarray(net.params[k][name]))
    for a, b in zip(jax.tree_util.tree_leaves(o_before),
                    jax.tree_util.tree_leaves(net.opt_state)):
        if hasattr(a, "dtype"):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # recovery: the next clean step trains normally at the reduced scale
    s_before = net.get_score()
    net.fit(x, y)
    assert np.isfinite(net.get_score())
    assert int(net.state[precision_mod.SCALE_STATE_KEY]
               ["overflow_steps"]) == 1
    del s_before


def test_f16_tbptt_overflow_does_not_poison_carries():
    """A single overflowed tBPTT chunk must hand the NEXT chunk its
    pre-step recurrent carries: only the poisoned chunk is skipped, not
    the whole rest of the sequence (regression: the skip select used to
    cover params/state but not the carries)."""
    from deeplearning4j_tpu.nn.layers.recurrent import LSTM, RnnOutputLayer

    b = (NeuralNetConfiguration.builder().seed(2)
         .updater(Adam(learning_rate=0.01)).precision("float16"))
    lb = b.list()
    lb.layer(LSTM(n_out=6))
    lb.layer(RnnOutputLayer(n_out=2, activation="softmax", loss="mcxent"))
    lb.backprop_type("tbptt", fwd=4, back=4)
    conf = lb.set_input_type(InputType.recurrent(3, 12)).build()
    net = MultiLayerNetwork(conf).init()
    rng = np.random.default_rng(0)
    x = rng.standard_normal((4, 12, 3)).astype(np.float32)
    x[:, 0, :] = 1e30                       # chunk 1 of 3 overflows in f16
    y = np.eye(2, dtype=np.float32)[rng.integers(0, 2, (4, 12))]
    net.fit(x, y)
    ls = net.state[precision_mod.SCALE_STATE_KEY]
    # only the poisoned chunk skipped; chunks 2 and 3 trained on clean
    # pre-step carries (pre-fix this read 3: inf carries cascaded)
    assert int(ls["overflow_steps"]) == 1


def test_precision_policy_distinguishes_compile_cache_signature():
    """Acceptance: f32 and bf16 variants never share a trace; identical
    policies still do."""
    f32 = mlp(seed=9)
    bf16_a = mlp(seed=9, precision="bfloat16")
    bf16_b = mlp(seed=9, precision="bfloat16")
    f16 = mlp(seed=9, precision="float16")
    sigs = {topology_signature(n.conf)
            for n in (f32, bf16_a, f16)}
    assert len(sigs) == 3
    assert topology_signature(bf16_a.conf) == topology_signature(bf16_b.conf)

    def compiles():
        c = default_registry().get("training_compile_total")
        return c.labels("train_step").value if c is not None else 0.0

    x, y = batch(16, seed=3)
    bf16_a.fit(x, y)
    before = compiles()
    bf16_b.fit(x, y)                       # identical policy: shared trace
    assert compiles() == before
    f16.fit(x, y)                          # different policy: own trace
    assert compiles() == before + 1


def test_precision_policy_object_knobs():
    """A full PrecisionPolicy object round-trips through the builder with
    per-layer overrides excluded from the low-precision cast."""
    pol = PrecisionPolicy(compute_dtype="bfloat16",
                          overrides={"layer0": "float32"})
    net = mlp(depth=2, precision=pol)
    x, y = batch(16, seed=5)
    net.fit(x, y)
    assert np.isfinite(net.get_score())


# ------------------------------------------------------- scan-over-layers
def test_scan_runs_detected_and_gated():
    net = mlp(depth=8, scan_layers=4)
    runs = scan_mod.scan_runs(net.conf, 8, mask_present=False,
                              carries_present=False, collect=False)
    # layer 0 has n_in=4 (input-sized), layers 1..7 are homogeneous
    assert runs == [(1, 8)]
    off = mlp(depth=8, scan_layers=False)
    assert scan_mod.scan_runs(off.conf, 8, mask_present=False,
                              carries_present=False, collect=False) == []
    # collect mode (feed_forward) always walks unrolled
    assert scan_mod.scan_runs(net.conf, 8, mask_present=False,
                              carries_present=False, collect=True) == []


@pytest.mark.parametrize("updater,exact", [
    (Sgd(learning_rate=0.05), True), (Adam(learning_rate=0.02), False)],
    ids=["sgd", "adam"])
def test_scan_exact_parity_params_and_loss_bit_identical(updater, exact):
    """Acceptance: scanned stack == unrolled stack under f32 (params AND
    loss), including dropout RNG (fold_in keys are scanned).

    Under SGD bit for bit, every step: the scanned walk's forward and its
    gradients ARE the unrolled walk's.  Under Adam the first step is bit
    for bit too, and from the second on the scanned layers' leaves differ
    in the last place (9.7e-8 of a leaf's largest element after two steps,
    2.1e-7 after four, on this jax): XLA contracts the moments' update
    ``b * m + (1 - b) * g`` otherwise where ``g`` is a slice of the scan's
    stacked output, which is round-off of a reassociated sum and no fault
    of the walk.  So Adam is held to 1e-6 of each leaf's largest element,
    the tolerance of ``tests/test_scan_saved.py`` for the same pair of
    programs."""
    x, y = batch(48, seed=4)
    scanned = mlp(depth=10, hidden=24, scan_layers=4, updater=updater)
    unrolled = mlp(depth=10, hidden=24, scan_layers=False, updater=updater)
    scanned.fit(x, y)
    unrolled.fit(x, y)
    assert scanned.get_score() == unrolled.get_score()   # bit-identical
    for a, b in zip(jax.tree_util.tree_leaves(scanned.params),
                    jax.tree_util.tree_leaves(unrolled.params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    for _ in range(3):
        scanned.fit(x, y)
        unrolled.fit(x, y)

    def same(a, b):
        a, b = np.asarray(a), np.asarray(b)
        np.testing.assert_allclose(
            a, b, rtol=0, atol=0 if exact else 1e-6 * np.abs(b).max())

    same(scanned.get_score(), unrolled.get_score())
    for k in scanned.params:
        for name in scanned.params[k]:
            same(scanned.params[k][name], unrolled.params[k][name])
    # inference path too
    same(scanned.output(x), unrolled.output(x))


def test_scan_parity_under_remat_and_bf16():
    """Scan composes with jax.checkpoint (remat carry) and the precision
    policy without changing results vs the unrolled walk."""
    x, y = batch(32, seed=6)
    a = mlp(depth=8, scan_layers=4, cache_mode="remat",
            precision="bfloat16")
    b = mlp(depth=8, scan_layers=False, cache_mode="remat",
            precision="bfloat16")
    for _ in range(3):
        a.fit(x, y)
        b.fit(x, y)
    assert a.get_score() == pytest.approx(b.get_score(), rel=1e-5)
    for k in a.params:
        for name in a.params[k]:
            np.testing.assert_allclose(np.asarray(a.params[k][name]),
                                       np.asarray(b.params[k][name]),
                                       rtol=2e-5, atol=2e-7)


def _transformer(n_layers, scan):
    # SGD, not Adam: parity across two separately-compiled XLA programs is
    # float-reassociation-exact (~1e-6); Adam's first-step g/sqrt(v) turns
    # that into full sign flips on near-zero-gradient biases, which would
    # test the optimizer's conditioning, not the scan transform
    from deeplearning4j_tpu.models import TransformerLM
    from deeplearning4j_tpu.nn.conf.updaters import Sgd
    m = TransformerLM(vocab_size=64, seq_len=16, embed=32,
                      n_layers=n_layers, n_heads=2, sparse_labels=True,
                      updater=Sgd(learning_rate=0.05))
    net = m.init()
    if not scan:
        net.conf.defaults["scan_layers"] = False
        net.invalidate_compile_cache()
    return net


def _compile_seconds():
    h = default_registry().get("training_compile_seconds")
    return sum(ch.sum for _, ch in h.samples()) if h is not None else 0.0


def _token_batch(n=4, seq=16, vocab=64, seed=0):
    rng = np.random.default_rng(seed)
    ids = rng.integers(0, vocab, (n, seq + 1))
    return jnp.asarray(ids[:, :-1]), jnp.asarray(ids[:, 1:])


def test_transformer_scan_cuts_trace_compile_time_and_keeps_parity():
    """A homogeneous transformer stack traces ONE block body instead of N:
    trace+compile wall time (training_compile_seconds) must drop vs the
    unrolled build, with f32 parity on the result.  12 blocks here keeps
    the test fast; the 24-block acceptance run is the slow-marked test
    below."""
    x, y = _token_batch()
    t0 = _compile_seconds()
    scanned = _transformer(12, scan=True)
    scanned.fit((x, y))
    scan_cost = _compile_seconds() - t0
    t0 = _compile_seconds()
    unrolled = _transformer(12, scan=False)
    unrolled.fit((x, y))
    unrolled_cost = _compile_seconds() - t0
    assert scan_cost < unrolled_cost, \
        f"scan trace+compile {scan_cost:.2f}s not below unrolled " \
        f"{unrolled_cost:.2f}s"
    assert scanned.get_score() == pytest.approx(unrolled.get_score(),
                                                rel=1e-5)
    for k in scanned.params:
        for name in scanned.params[k]:
            np.testing.assert_allclose(
                np.asarray(scanned.params[k][name]),
                np.asarray(unrolled.params[k][name]), rtol=1e-4,
                atol=1e-5)


@pytest.mark.slow
def test_transformer_24_layer_scan_acceptance():
    """ISSUE 6 acceptance: 24-layer homogeneous stack, trace+compile time
    reduced (timer-verified via training_compile_seconds) with exact f32
    parity vs the unrolled path."""
    x, y = _token_batch()
    t0 = _compile_seconds()
    scanned = _transformer(24, scan=True)
    scanned.fit((x, y))
    scan_cost = _compile_seconds() - t0
    t0 = _compile_seconds()
    unrolled = _transformer(24, scan=False)
    unrolled.fit((x, y))
    unrolled_cost = _compile_seconds() - t0
    assert scan_cost < unrolled_cost
    for k in scanned.params:
        for name in scanned.params[k]:
            np.testing.assert_allclose(
                np.asarray(scanned.params[k][name]),
                np.asarray(unrolled.params[k][name]), rtol=1e-4,
                atol=1e-5)
