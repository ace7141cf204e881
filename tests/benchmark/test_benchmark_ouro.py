"""The ``ouro`` family's part of the benchmark at a size a test can hold, on
the CPU: its parameter count and FLOP functions against counts by hand and
against XLA's count of the lowered tiny step, what its configuration keeps
of the published one, the manifest's new entries **found by name**, a run
of its traffic kind below ``run.py``'s look for a chip — sound, then with
the reference one precision below in the program's place, with each named
fault planted, with the state left unchanged — and its two readers on
made-up operations and on nothing."""
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

CELL = "ouro-2p6b-8l.train-fit-8k"
CONFIG = "ouro-2p6b-8l"


def data(name):
    with open(os.path.join(HERE, "data", name + ".json")) as f:
        return json.load(f)


CFG, TINY = data("tiny-ouro"), data("tiny-ouro.train-fit-8k")
FULL = common.load_json("configs", CONFIG + ".json")
flops = common.load_module("flops", "ouro")

LAYER = 4 * 2048 * 2048 + 3 * 2048 * 5632        # one layer's matrices


def drive(seed=7):
    import jax
    return run.execute(CELL, seed, 0.5, False, jax.devices()[:1],
                       manifest=common.load_manifest(), cell=TINY, cfg=CFG)


# ------------------------------------------------------------ counts by hand
def test_the_cut_holds_612_4_million_parameters():
    ref = common.load_module("reference", "ouro")
    whole = 2 * 49152 * 2048 + 8 * (LAYER + 4 * 2048) + 2048 + 2049
    assert ref.n_params(FULL) == whole == FULL["parameters"] == 612_438_017
    assert 12 * whole == pytest.approx(7.35e9, rel=1e-3)


def test_a_token_meets_every_layer_four_times_and_the_head_at_four_exits():
    assert LAYER == 51_380_224 and flops.layer_passes(FULL) == 32
    assert flops.matmul_params(FULL) == \
        32 * LAYER + 4 * (2048 * 49152 + 2048)
    # 102.8 MFLOP of products and 33.6 of attention a token and layer-pass
    assert 2 * LAYER == pytest.approx(102.8e6, rel=1e-3)
    assert flops.attention_flops_per_row(FULL) / 3 / 8192 == \
        pytest.approx(33.55e6, rel=1e-3)


def test_a_step_is_127_tflop_and_the_head_16_percent_of_it():
    layer = flops.attention_flops_per_row(FULL)
    assert layer == 3 * 16 * 2 * (8192 * 8192 // 2) * 2 * 128
    step = flops.train_step_flops(FULL, 1)
    assert step == 8192 * 6 * flops.matmul_params(FULL) + 32 * layer
    assert step == pytest.approx(1.27e14, rel=1e-3)
    head = 4 * 8192 * 6 * 2048 * 49152
    assert head / step == pytest.approx(0.156, abs=2e-3)
    # two rows are twice one
    assert flops.train_step_flops(FULL, 2) == 2 * step


@pytest.mark.parametrize("kernel,products,arrays", [
    ("flash_fwd", 2, 4), ("flash_bwd_dq", 3, 6), ("flash_bwd_dkv", 4, 7)])
def test_kernel_calls_count_half_the_square_at_16_heads_of_128(
        kernel, products, arrays):
    f, b = flops.kernel_call(FULL, 1, kernel)
    assert f == products * 16 * 8192 * 8192 * 128
    assert b == arrays * 16 * 8192 * 128 * 2
    # what flops/gpt2.py counts at the same embedding width and length
    gpt2 = common.load_module("flops", "gpt2")
    assert (f, b) == gpt2.kernel_call(
        {"n_positions": 8192, "n_embd": 2048}, 1, kernel)


def test_the_flop_function_against_xlas_count_of_the_lowered_tiny_step(
        monkeypatch):
    """The tiny step lowered with nothing scanned (XLA counts a loop's
    body once) and nothing replayed, in float32: XLA's count is the
    function's with the attention's whole square (the CPU's attention
    masks a full product) and, on top, the element-wise work and Adam: 0
    to 5 % more."""
    import jax.numpy as jnp
    monkeypatch.setenv("DL4J_TPU_SCAN_LAYERS", "0")
    cfg = dict(CFG, precision="float32", cache_mode="none")
    traffic = common.load_module("traffic", TINY["kind"])
    net = traffic.build(cfg)
    rows = 2
    x = jnp.zeros((rows, cfg["train_seq_len"]), jnp.int32)
    lowered = net._get_jitted("train_step").audit_lower(
        ((net.params, net.state, net.opt_state, net._rng, x, x, None, None),
         {}))
    counted = lowered.compile().cost_analysis()["flops"]
    square = rows * flops.layer_passes(cfg) * \
        flops.attention_flops_per_row(cfg)
    model = flops.train_step_flops(cfg, rows)
    assert 1.0 <= counted / (model + square) <= 1.05
    # and the head at every exit is in it: without it the count is short
    head = rows * cfg["train_seq_len"] * 6 * cfg["total_ut_steps"] * \
        cfg["hidden_size"] * cfg["vocab_size"]
    assert counted > model + square - 0.5 * head


def test_the_configuration_holds_every_published_key_but_the_reduced():
    with open("/opt/skills/guides/model-configs/architectures.jsonl") as f:
        rows = [json.loads(line) for line in f]
    row = [r for r in rows if r["name"] == "Ouro-2.6B"]
    if not row:
        pytest.skip("no catalog beside the guides here")
    published = row[0]["config"]
    entry = [c for c in common.load_manifest()["configs"]
             if c["name"] == CONFIG][0]
    assert entry["source"] == row[0]["source_url"]
    reduced = entry["reduced"]
    assert reduced == FULL["reduced"] == ["num_hidden_layers", "layer_types"]
    assert {k: FULL[k] for k in published if k not in reduced} == \
        {k: v for k, v in published.items() if k not in reduced}
    assert FULL["num_hidden_layers"] == 8 and \
        FULL["layer_types"] == published["layer_types"][:8]
    assert FULL["published"] == {"num_hidden_layers": 48,
                                 "layer_types": "48 x full_attention"}
    assert FULL["total_ut_steps"] == 4 and FULL["vocab_size"] == 49152
    assert "pipeline stages of eight" in FULL["deployment"]
    assert len(FULL["assumed"]) >= 12 and FULL["cache_mode"] == "remat"
    assert any("summed over the passes in float32" in line
               for line in FULL["assumed"])


def test_the_manifests_new_entries_found_by_name():
    manifest = common.load_manifest()
    cells = {w["name"]: w for w in manifest["workloads"]}
    assert cells[CELL] == {
        "name": CELL, "config": CONFIG, "traffic": "train-fit-8k",
        "chips": 1, "why": common.load_json("workloads",
                                            CELL + ".json")["why"]}
    assert len(cells[CELL]["why"]) <= 200
    configs = {c["name"]: c for c in manifest["configs"]}
    assert configs[CONFIG]["file"] == f"benchmark/configs/{CONFIG}.json"
    metrics = {m["name"]: m for m in manifest["per_layer"]}
    for name in ("loop_device_pct.train", "exit_device_pct.train"):
        assert metrics[name] == {
            "name": name, "unit": "%", "better": "lower",
            "source": "device_trace", "layer": "Step program",
            "moves": "train_step_ms", "workloads": [CELL]}
    mine = {name for name, m in metrics.items()
            if CELL in m.get("workloads", ())}
    assert {"device_idle_pct.train", "step_mfu_pct.train", "flash_roofline",
            "window_compiles.train", "remat_device_pct.train",
            "scan_saved_device_pct.train", "head_device_pct.train",
            "xla_remat_device_pct.train", "backward_device_pct.train",
            "optimizer_device_pct.train", "compile_s.setup"} <= mine
    # no window, no experts, no latent attention, no second stream
    assert not {"flash_window_roofline", "moe_device_pct.train",
                "mla_device_pct.train", "mtp_device_pct.train"} & mine
    # the step profiler fences every 16th step of a fit and no fit of the
    # cell is that long, so its counter is never made: nothing to read
    assert common.load_json("workloads", CELL + ".json")["trace_steps"] < 16
    assert "profiler_fences.train" not in mine
    # joining a list appends the cell and changes nothing else
    for name in mine - {"loop_device_pct.train", "exit_device_pct.train"}:
        assert metrics[name]["workloads"][-1] == CELL


def test_the_cell_asks_for_the_full_kernels_and_its_three_limits():
    cell = common.load_json("workloads", CELL + ".json")
    assert (cell["rows"], cell["check_steps"], cell["trace_steps"],
            cell["distinct_batches"]) == (1, 1, 6, 64)
    assert cell["kind"] == "loop_lm_fit_stream"
    assert cell["require_kernels"] == ["flash_fwd", "flash_bwd_dq",
                                       "flash_bwd_dkv"]
    assert set(cell["limits"]) == {"loss_gap", "grad_gap", "delta_gap"}
    assert set(cell["limits_why"]) >= set(cell["limits"])


def test_batches_are_rows_of_t_plus_one_ids_from_the_seed():
    traffic = common.load_module("traffic", TINY["kind"])
    import jax
    a = traffic.Job(TINY, CFG, 2 ** 31 + 5, jax.devices()[:1]).batches
    b = traffic.Job(TINY, CFG, 2 ** 31 + 5, jax.devices()[:1]).batches
    c = traffic.Job(TINY, CFG, 2 ** 31 + 6, jax.devices()[:1]).batches
    assert len(a) == TINY["distinct_batches"]
    x, y = a[0]
    assert x.shape == y.shape == (2, 256) and x.max() < 256
    np.testing.assert_array_equal(x[:, 1:], y[:, :-1])
    np.testing.assert_array_equal(x, b[0][0])
    assert not np.array_equal(x, c[0][0])


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


@pytest.fixture(scope="module")
def reference(job):
    return job.reference(job.checked_batches())


def test_the_references_loss_is_both_terms(reference):
    parts = reference["loss_parts"]
    assert parts["expected"] > 0 and parts["entropy"] > 0
    assert reference["losses"][0] == pytest.approx(
        parts["expected"] - 0.1 * parts["entropy"], rel=1e-6)
    assert sum(reference["exit_mass"]) == pytest.approx(1.0, abs=1e-5)
    assert len(reference["exit_mass"]) == CFG["total_ut_steps"]
    assert {"gate_w", "gate_b", "layers.3.W2"} <= set(
        reference["grad_norms"])


@pytest.mark.parametrize("what,kw", [
    ("one precision below", {"precision": check_train.BELOW[
        CFG["precision"]]}),
    ("one pass fewer", {"fault": "passes_3"}),
    ("one pass fewer, as tools/readings.py asks", {"keep_rows": [0]}),
    ("the weights' gradient of the last pass alone",
     {"fault": "last_pass_grad"}),
    ("no gradient through the exit distribution",
     {"fault": "gate_detached"}),
])
def test_a_wrong_reference_in_the_programs_place_is_not_correct(
        job, reference, what, kw):
    other = job.reference(job.checked_batches(), **kw)
    correct, _, read = job.compare(other, reference)
    assert not correct, read


def test_a_gate_without_gradient_fails_by_the_gradients_gap(job, reference):
    other = job.reference(job.checked_batches(), fault="gate_detached")
    _, compared, read = job.compare(other, reference)
    assert read["grad_gap"] == pytest.approx(1.0)
    assert read["_where"]["grad_gap"] in ("gate_w", "gate_b")
    assert compared["grad_gap"]["value"] > compared["grad_gap"]["limit"]
    assert read["loss_gap"] < 1e-6


def test_the_reference_in_its_own_place_is_correct(job, reference):
    correct, _, read = job.compare(reference, reference)
    assert correct and read["grad_gap"] == 0.0
    own = job.reference(job.checked_batches(), precision=CFG["precision"])
    assert job.compare(own, reference)[0]


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


def test_an_unknown_fault_is_refused(job):
    with pytest.raises(ValueError, match="no such fault"):
        job.reference(job.checked_batches(), fault="no_window")


# ------------------------------------------------------------- the readers
def empty_ctx():
    return {"trace": None, "stretch": {"steps": 0}, "cell": {"rows": 1},
            "cfg": FULL, "chips": 1, "flops_module": flops,
            "flops_per_step": None,
            "peaks": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9},
            "counters_before": None, "counters_after": None}


@pytest.mark.parametrize("name", ["loop_device_pct.train",
                                  "exit_device_pct.train"])
def test_nothing_to_read_returns_nothing(name):
    reader = common.load_module("metrics", name)
    assert reader.read(empty_ctx()) is None


def test_the_readers_put_each_operation_down_to_its_scope(monkeypatch):
    """Made-up self times by whole scope: the loop's share takes what lies
    under ``loop`` in the scan over the passes alone and under no layer
    class; the exits' takes everything under ``exit_gate``; a program
    without the scopes, as the parent, has neither."""
    mla = common.load_module("metrics", "mla_device_pct.train")
    loop = common.load_module("metrics", "loop_device_pct.train")
    exits = common.load_module("metrics", "exit_device_pct.train")
    fwd, bwd = "jit(step)/jvp(forward)/", "jit(step)/transpose(jvp(forward))/"
    inner = "loop/while/body/closed_call/while/body/"
    selfs = {fwd + "loop/while": 1.0,
             fwd + "loop/while/body/convert_element_type": 4.0,
             fwd + "loop/while/body/dynamic_update_slice": 3.0,
             fwd + "loop/broadcast_in_dim": 1.0,
             bwd + "loop/while/body/add_any": 6.0,
             # the run's own scan, and the layers, are not the loop's
             fwd + inner + "dynamic_update_slice": 5.0,
             fwd + inner + "checkpoint/TransformerBlock/dot_general": 30.0,
             fwd + "loop/while/body/RMSNormLayer/mul": 2.0,
             bwd + inner + "checkpoint/rematted_computation/"
             "TransformerBlock/attn_full/flash_fwd": 20.0,
             fwd + "ExitGateOutputLayer/exit_gate/dot_general": 1.5,
             bwd + "ExitGateOutputLayer/exit_gate/mul": 0.5,
             fwd + "ExitGateOutputLayer/dot_general": 16.0,
             "jit(step)/optimizer/add": 10.0}
    monkeypatch.setattr(mla, "scope_self_times",
                        lambda path: (selfs, 100.0))
    ctx = dict(empty_ctx(), trace={"window_ns": 1.0}, xplane="made-up")
    assert loop.read(ctx) == pytest.approx(15.0)
    assert exits.read(ctx) == pytest.approx(2.0)
    without = {k: v for k, v in selfs.items()
               if "loop" not in k and "exit_gate" not in k}
    monkeypatch.setattr(mla, "scope_self_times",
                        lambda path: (without, 100.0))
    assert loop.read(ctx) is None and exits.read(ctx) is None
    monkeypatch.setattr(mla, "scope_self_times", lambda path: None)
    assert loop.read(ctx) is None and exits.read(ctx) is None


def test_the_roofline_reads_this_familys_kernel_calls():
    """A call that took twice its floor reads 50."""
    reader = common.load_module("metrics", "flash_roofline")
    floors = {k: 1e9 * flops.kernel_call(FULL, 1, k)[0] / 197e12
              for k in reader.KERNELS}
    assert floors["flash_fwd"] == pytest.approx(1.395e6, rel=1e-3)
    ops, at = [], 0.0
    for kernel, floor in floors.items():
        ops.append(tr.Event("/device:TPU:0", "XLA Ops", kernel, at,
                            2 * floor))
        at += 3 * floor
    ctx = dict(empty_ctx(), trace={"ops": ops})
    assert reader.read(ctx) == pytest.approx(50.0)
