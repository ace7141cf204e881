"""The ``evabyte`` family's part of the benchmark at a size a test can
hold, on the CPU: its FLOP functions against counts by hand, its parameter
count, and a run of its traffic kind below ``run.py``'s look for a chip —
sound, then with the reference one precision below in the program's place,
with the summaries left out, with half of the targets left out and with the
state left unchanged."""
import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, REPO)

from benchmark import common, run  # noqa: E402
from benchmark.check import train as check_train  # noqa: E402

CELL = "evabyte-4l.train-fit-long"


def data(name):
    with open(os.path.join(HERE, "data", name + ".json")) as f:
        return json.load(f)


CFG, TINY = data("tiny-evabyte"), data("tiny-evabyte.train-fit-long")


def drive(seed=7):
    import jax
    return run.execute(CELL, seed, 0.5, False, jax.devices()[:1],
                       manifest=common.load_manifest(), cell=TINY, cfg=CFG)


# ------------------------------------------------------------ counts by hand
def test_evabyte_4l_parameters_are_821_4_million():
    ref = common.load_module("reference", "evabyte")
    cfg = common.load_json("configs", "evabyte-4l.json")
    layer = 4 * 4096 ** 2 + 3 * 4096 * 11008 + 4 * 4096   # phi, mu, 2 gains
    whole = 4 * layer + 320 * 4096 + 4096 + 4096 * 8 * 320
    assert ref.n_params(cfg) == whole == cfg["parameters"] == 821_366_784
    assert cfg["num_hidden_layers"] == 4 and \
        cfg["published"]["num_hidden_layers"] == 32


def test_evabyte_4l_step_is_42_3_tflop():
    flops = common.load_module("flops", "evabyte")
    cfg = common.load_json("configs", "evabyte-4l.json")
    assert flops.matmul_params(cfg) == \
        4 * (4 * 4096 ** 2 + 3 * 4096 * 11008) + 4096 * 2560
    # a layer and row, forward: 4 windows x 32 heads of half a 2048 x 2048
    # square, two products of 2*w*w*d each; windows 1-3 see 128, 256, 384
    # summaries, two products of 2*w*n*d; pooling 3 products of 2*t*d
    local = 4 * 32 * 2 * 2 * 2048 * 2048 * 128 // 2
    summaries = 32 * 2 * 2 * 2048 * (128 + 256 + 384) * 128
    pooling = 32 * 3 * 2 * 8192 * 128
    assert flops.attention_flops_per_row(cfg) == \
        3 * (local + summaries + pooling)
    step = flops.train_step_flops(cfg, 1)
    assert step == 8192 * 6 * flops.matmul_params(cfg) + \
        4 * 3 * (local + summaries + pooling)
    assert step == pytest.approx(4.226e13, rel=1e-3)


@pytest.mark.parametrize("kernel,products,arrays", [
    ("flash_fwd", 2, 4), ("flash_bwd_dq", 3, 6), ("flash_bwd_dkv", 4, 7)])
def test_window_kernel_call_counts(kernel, products, arrays):
    flops = common.load_module("flops", "evabyte")
    cfg = common.load_json("configs", "evabyte-4l.json")
    f, b = flops.kernel_call(cfg, 1, kernel)
    # 4 windows x 32 heads of [2048, 128], causal: half of 2*w*w*d
    assert f == products * 128 * 2048 * 2048 * 128
    assert b == arrays * 128 * 2048 * 128 * 2


def test_the_configuration_holds_every_published_key():
    cfg = common.load_json("configs", "evabyte-4l.json")
    published = {
        "attention_bias": False, "attention_class": "eva", "chunk_size": 16,
        "hidden_act": "silu", "hidden_size": 4096, "init_std": 0.01275,
        "intermediate_size": 11008, "max_position_embeddings": 32768,
        "model_type": "evabyte", "norm_add_unit_offset": True,
        "num_attention_heads": 32, "num_key_value_heads": 32,
        "num_pred_heads": 8, "rms_norm_eps": 1e-05, "rope_theta": 100000,
        "tie_word_embeddings": False, "vocab_size": 320,
        "window_size": 2048}
    assert {k: cfg[k] for k in published} == published
    manifest = common.load_manifest()
    entry = [c for c in manifest["configs"] if c["name"] == "evabyte-4l"][0]
    assert entry["reduced"] == ["num_hidden_layers"]


# ------------------------------------------------- a sound run, then faults
@pytest.fixture(scope="module")
def sound():
    return drive()


def test_a_sound_run_is_correct(sound):
    assert sound["correct"], sound["compared"]
    assert sound["attempted"] > 0 and sound["failed"] == 0
    assert set(sound["metrics"]) == {"train_step_ms", "setup_s"}
    assert set(sound["compared"]) == {"loss_gap", "grad_gap", "delta_gap",
                                      "failed_steps"}


@pytest.fixture(scope="module")
def job():
    import jax
    return common.load_module("traffic", TINY["kind"]).Job(
        TINY, CFG, 7, jax.devices()[:1])


@pytest.mark.parametrize("what,kw", [
    ("one precision below", {"precision": check_train.BELOW[
        CFG["precision"]]}),
    ("no summaries", {"fault": "no_summaries"}),
    ("half the targets", {"keep_rows": [0, 1]}),
])
def test_a_wrong_reference_in_the_programs_place_is_not_correct(job, what,
                                                                kw):
    batches = job.checked_batches()
    read = check_train.readings(job.reference(batches, **kw),
                                job.reference(batches))
    assert not check_train.verdict(read, TINY["limits"])[0], read
    if what == "no summaries":
        # phi and mu are leaves like any other: with the summaries gone
        # their gradient is nought, and the gap of each is its whole norm
        assert read["_where"]["grad_gap"].split(".")[1] in ("phi", "mu")


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


def test_the_traffic_targets_the_next_bytes():
    traffic = common.load_module("traffic", TINY["kind"])
    x, y, features_mask, mask = traffic.byte_batches(3, 2, 2, 40, 32, 4)[1]
    assert features_mask is None and x.shape == (2, 40)
    assert y.shape == mask.shape == (2, 40, 4)
    for n in range(4):
        assert (y[:, :39 - n, n] == x[:, 1 + n:]).all()
        assert mask[:, :39 - n, n].all() and not mask[:, 39 - n:, n].any()
    assert mask.sum() == 2 * (4 * 40 - (1 + 2 + 3 + 4))
