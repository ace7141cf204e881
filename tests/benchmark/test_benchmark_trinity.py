"""The ``trinity`` family's part of the benchmark at a size a test can
hold, on the CPU: its FLOP functions against counts by hand and by brute
force, its parameter count, what its configuration keeps of the published
one, a run of its traffic kind below ``run.py``'s look for a chip — sound,
then with the reference one precision below in the program's place, with
the window ignored, with ``route_scale`` left out, with the state left
unchanged — and its three readers on made-up operations, on nothing, and
on the small trace recorded on the chip."""
import json
import os
import sys

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, REPO)

from benchmark import common, run  # noqa: E402
from benchmark import trace_reduce as tr  # noqa: E402
from benchmark.check import train as check_train  # noqa: E402

CELL = "trinity-mini-5l.train-fit-8k"
TRACE = os.path.join(HERE, "data", "small-trinity.xplane.pb")


def data(name):
    with open(os.path.join(HERE, "data", name + ".json")) as f:
        return json.load(f)


CFG, TINY = data("tiny-trinity"), data("tiny-trinity.train-fit-8k")
FULL = common.load_json("configs", "trinity-mini-5l.json")
flops = common.load_module("flops", "trinity")


def drive(seed=7):
    import jax
    return run.execute(CELL, seed, 0.5, False, jax.devices()[:1],
                       manifest=common.load_manifest(), cell=TINY, cfg=CFG)


# ------------------------------------------------------------ counts by hand
def test_the_share_holds_705_5_million_parameters():
    ref = common.load_module("reference", "trinity")
    attention = 3 * 2048 * 4096 + 2 * 2048 * 512 + 2 * 128
    dense = attention + 4 * 2048 + 3 * 2048 * 6144
    routed = attention + 4 * 2048 + 2048 * 128 + (1 + 16) * 3 * 2048 * 1024
    whole = dense + 4 * routed + 2 * 25024 * 2048 + 2048
    assert ref.n_params(FULL) == whole == FULL["parameters"] == 705_473_792
    assert 12 * whole == pytest.approx(8.466e9, rel=1e-3)


def test_a_token_meets_276_7_million_matmul_parameters():
    attention = 3 * 2048 * 4096 + 2 * 2048 * 512
    # router, the shared expert, and of 16 held experts 8 * 16 / 128 = 1
    routed = 2048 * 128 + (1 + 1) * 3 * 2048 * 1024
    assert flops.matmul_params(FULL) == \
        5 * attention + 3 * 2048 * 6144 + 4 * routed + 2048 * 25024
    assert flops.matmul_params(FULL) == pytest.approx(276.7e6, rel=1e-3)


@pytest.mark.parametrize("t,window", [(64, 16), (64, 63), (100, 7),
                                      (96, 32), (50, 50), (50, 80)])
def test_the_band_counts_the_pairs_a_query_sees(t, window):
    i, j = np.arange(t)[:, None], np.arange(t)[None, :]
    seen = int(((j <= i) & (j > i - window)).sum())
    # the band's formula leaves out half a pair a row of the band's edge,
    # the square's half a pair a row of the diagonal
    assert flops.pairs(t, window) == pytest.approx(
        seen, abs=0.5 * min(window, t) + 1e-9)
    assert flops.pairs(t) == 0.5 * t * t


def test_a_step_is_18_1_tflop_and_a_window_does_44_percent_of_a_square():
    full = flops.attention_flops_per_row(FULL, False)
    band = flops.attention_flops_per_row(FULL, True)
    assert full == 3 * 32 * 2 * 2 * 8192 * 8192 * 128 // 2
    assert band == 3 * 32 * 2 * 2 * (2048 * 8192 - 2048 * 2048 // 2) * 128
    assert band / full == pytest.approx(0.4375)
    assert flops.sliding_layers(FULL) == [True, True, True, False, True]
    step = flops.train_step_flops(FULL, 1)
    assert step == 8192 * 6 * flops.matmul_params(FULL) + full + 4 * band
    assert step == pytest.approx(1.814e13, rel=1e-3)


@pytest.mark.parametrize("kernel,products,arrays", [
    ("fwd", 2, 4), ("bwd_dq", 3, 6), ("bwd_dkv", 4, 7)])
def test_kernel_calls_count_the_square_and_the_band(kernel, products,
                                                    arrays):
    f, b = flops.kernel_call(FULL, 1, "flash_" + kernel)
    assert f == products * 32 * 8192 * 8192 * 128
    assert b == arrays * 32 * 8192 * 128 * 2
    fw, bw = flops.window_kernel_call(FULL, 1, "flash_win_" + kernel)
    assert fw == products * 2 * 32 * (2048 * 8192 - 2048 * 1024) * 128
    assert bw == b and fw / f == pytest.approx(0.4375)


def test_the_configuration_holds_every_published_key_but_the_reduced():
    with open("/opt/skills/guides/model-configs/architectures.jsonl") as f:
        rows = [json.loads(line) for line in f]
    row = [r for r in rows if r["name"] == "Trinity-Mini"]
    if not row:
        pytest.skip("no catalog beside the guides here")
    published = row[0]["config"]
    entry = [c for c in common.load_manifest()["configs"]
             if c["name"] == "trinity-mini-5l"][0]
    assert entry["source"] == row[0]["source_url"]
    reduced = entry["reduced"]
    assert reduced == FULL["reduced"] == [
        "num_hidden_layers", "num_dense_layers", "num_experts",
        "vocab_size", "layer_types"]
    assert {k: FULL[k] for k in published if k not in reduced} == \
        {k: v for k, v in published.items() if k not in reduced}
    assert FULL["layer_types"] == published["layer_types"][:5]
    assert FULL["published"]["num_experts"] == published["num_experts"]
    assert FULL["published"]["vocab_size"] == published["vocab_size"]
    # the floors of a share: an eighth of the experts and of the vocabulary
    assert FULL["num_experts"] * 8 == published["num_experts"]
    assert FULL["vocab_size"] * 8 == published["vocab_size"]
    assert FULL["experts_held"] == [0, FULL["num_experts"]]


def test_the_cell_asks_for_both_kinds_of_kernel_and_all_four_limits():
    cell = common.load_json("workloads", CELL + ".json")
    assert cell["rows"] == 1 and cell["check_steps"] == 1
    assert set(cell["require_kernels"]) == {
        "flash_fwd", "flash_bwd_dq", "flash_bwd_dkv", "flash_win_fwd",
        "flash_win_bwd_dq", "flash_win_bwd_dkv"}
    assert set(cell["limits"]) == {"loss_gap", "grad_gap", "delta_gap",
                                   "routing_agreement"}
    manifest = common.load_manifest()
    mine = {m["name"] for m in manifest["per_layer"]
            if CELL in m.get("workloads", ())}
    assert {"moe_device_pct.train", "moe_dispatch_device_pct.train",
            "flash_window_roofline", "flash_roofline",
            "step_mfu_pct.train", "device_idle_pct.train"} <= mine


# ------------------------------------------------- a sound run, then faults
@pytest.fixture(scope="module")
def sound():
    return drive()


def test_a_sound_run_is_correct(sound):
    assert sound["correct"], sound["compared"]
    assert sound["attempted"] > 0 and sound["failed"] == 0
    assert set(sound["metrics"]) == {"train_step_ms", "setup_s"}
    assert set(sound["compared"]) == {"loss_gap", "grad_gap", "delta_gap",
                                      "routing_agreement", "failed_steps"}
    assert sound["compared"]["routing_agreement"]["value"] > 0.95


@pytest.fixture(scope="module")
def job():
    import jax
    return common.load_module("traffic", TINY["kind"]).Job(
        TINY, CFG, 7, jax.devices()[:1])


@pytest.fixture(scope="module")
def reference(job):
    return job.reference(job.checked_batches())


@pytest.mark.parametrize("what,kw", [
    ("one precision below", {"precision": check_train.BELOW[
        CFG["precision"]]}),
    ("the window ignored", {"fault": "no_window"}),
    ("the window ignored, as tools/readings.py asks", {"keep_rows": [0]}),
    ("route_scale left out", {"fault": "no_route_scale"}),
])
def test_a_wrong_reference_in_the_programs_place_is_not_correct(
        job, reference, what, kw):
    other = job.reference(job.checked_batches(), **kw)
    correct, _, read = job.compare(other, reference)
    assert not correct, read


def test_the_reference_in_its_own_place_is_correct(job, reference):
    correct, _, read = job.compare(reference, reference)
    assert correct and read["routing_agreement"] == 1.0


def test_a_state_left_unchanged_is_not_correct(monkeypatch):
    import jax
    import deeplearning4j_tpu.nn.multilayer as multilayer
    fit = multilayer.MultiLayerNetwork.fit

    def broken(self, *args, **kwargs):
        keep = jax.tree_util.tree_map(lambda a: a + 0,
                                      (self.params, self.opt_state))
        fit(self, *args, **kwargs)
        self.params, self.opt_state = keep
        return self
    monkeypatch.setattr(multilayer.MultiLayerNetwork, "fit", broken)
    result = drive()
    assert not result["correct"], result["compared"]


def test_a_routing_that_disagrees_is_not_correct(job, reference):
    """Every choice moved to an expert the reference did not choose: the
    three gaps stand, the agreement falls under its floor."""
    other = dict(reference)
    other["route_choices"] = 15 - np.asarray(reference["route_choices"])
    correct, compared, read = job.compare(other, reference)
    assert read["routing_agreement"] < TINY["limits"]["routing_agreement"]
    assert not correct
    assert all(read[gap] == 0 for gap in ("loss_gap", "grad_gap",
                                           "delta_gap"))
    assert compared["routing_agreement"]["limit"] == \
        TINY["limits"]["routing_agreement"]


# ------------------------------------------------------------- the readers
def empty_ctx():
    return {"trace": None, "stretch": {"steps": 0}, "cell": {"rows": 1},
            "cfg": FULL, "chips": 1, "flops_module": flops,
            "flops_per_step": None,
            "peaks": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9},
            "counters_before": None, "counters_after": None}


def op(name, start, dur):
    return tr.Event("/device:TPU:0", "XLA Ops", name, float(start),
                    float(dur))


@pytest.mark.parametrize("name", ["moe_device_pct.train",
                                  "moe_dispatch_device_pct.train",
                                  "flash_window_roofline"])
def test_nothing_to_read_returns_nothing(name):
    reader = common.load_module("metrics", name)
    assert reader.read(empty_ctx()) is None


def test_the_window_roofline_reads_windowed_calls_alone():
    reader = common.load_module("metrics", "flash_window_roofline")
    f, _ = flops.window_kernel_call(FULL, 1, "flash_win_fwd")
    floor_ns = 1e9 * f / 197e12
    ops = [op("flash_win_fwd", 0, 2 * floor_ns),
           op("flash_win_fwd.1", 10 * floor_ns, 2 * floor_ns),
           op("flash_fwd", 20 * floor_ns, 5 * floor_ns),      # a full call
           op("fusion.7", 30 * floor_ns, floor_ns)]
    ctx = dict(empty_ctx(), trace={"ops": ops})
    assert reader.read(ctx) == pytest.approx(50.0)
    # a program without windowed kernels: nothing, not nought
    assert reader.read(dict(ctx, trace={"ops": ops[2:]})) is None
    # a family whose flops module counts no windowed call: nothing
    gpt2 = common.load_module("flops", "gpt2")
    assert reader.read(dict(ctx, flops_module=gpt2)) is None


@pytest.mark.skipif(not os.path.exists(TRACE),
                    reason="the small trace is recorded on the chip")
def test_the_readers_on_the_small_recorded_trace():
    """Three steps of the test-sized cell, traced on the chip: the five
    scopes are there, the dispatch part is a part of the whole, and the
    windowed kernels ran beside the full ones."""
    moe = common.load_module("metrics", "moe_device_pct.train")
    by_scope, busy = moe.scope_shares(TRACE)
    assert busy > 0 and all(by_scope[s] > 0 for s in moe.SCOPES)
    ctx = dict(empty_ctx(), trace={"window_ns": 1.0}, xplane=TRACE,
               cell=TINY, cfg=CFG)
    whole = moe.read(ctx)
    part = common.load_module(
        "metrics", "moe_dispatch_device_pct.train").read(ctx)
    assert 0 < part < whole < 100
    assert whole == pytest.approx(100 * sum(by_scope.values()) / busy)
    reduced = tr.reduce(tr.load_events(TRACE))
    for kernel in ("flash_win_fwd", "flash_win_bwd_dq", "flash_win_bwd_dkv",
                   "flash_fwd", "flash_bwd_dq", "flash_bwd_dkv"):
        assert tr.kernel_events(reduced["ops"], kernel), kernel
    roofline = common.load_module("metrics", "flash_window_roofline").read(
        dict(ctx, trace=reduced, cell=dict(TINY, rows=2),
             cfg=dict(CFG)))
    assert 0 < roofline < 100
