"""The ``joyai`` family's part of the benchmark at a size a test can hold, on
the CPU: its parameter count and FLOP functions against counts by hand,
what its configuration keeps of the published one, a run of its traffic
kind below ``run.py``'s look for a chip — sound, then with the reference
one precision below in the program's place, with the rotary key left out of
the keys, with the module's term left out of the loss, with the state left
unchanged — and its two readers on made-up operations, on nothing, and on
the small trace recorded on the chip."""
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

CELL = "joyai-llm-flash-5l.train-fit-8k"
TRACE = os.path.join(HERE, "data", "small-joyai.xplane.pb")


def data(name):
    with open(os.path.join(HERE, "data", name + ".json")) as f:
        return json.load(f)


CFG, TINY = data("tiny-joyai"), data("tiny-joyai.train-fit-8k")
FULL = common.load_json("configs", "joyai-llm-flash-5l.json")
flops = common.load_module("flops", "joyai")

# one latent-attention layer's five matrices, as ISSUE 35 writes them
MLA = 2048 * 1536 + 1536 * 6144 + 2048 * 576 + 512 * 8192 + 4096 * 2048


def drive(seed=7):
    import jax
    return run.execute(CELL, seed, 0.5, False, jax.devices()[:1],
                       manifest=common.load_manifest(), cell=TINY, cfg=CFG)


# ------------------------------------------------------------ counts by hand
def test_the_share_holds_680_4_million_parameters():
    ref = common.load_module("reference", "joyai")
    attention = MLA + 1536 + 512              # and the two latent norms
    dense = attention + 2 * 2048 + 3 * 2048 * 7168
    routed = attention + 2 * 2048 + 2048 * 256 + (1 + 16) * 3 * 2048 * 768
    module = 3 * 2048 + 4096 * 2048 + routed  # two norms, its final norm
    whole = dense + 4 * routed + module + 2 * 16160 * 2048 + 2048
    assert MLA == 26_345_472
    assert ref.n_params(FULL) == whole == FULL["parameters"] == 680_439_808
    assert 12 * whole == pytest.approx(8.165e9, rel=1e-3)


def test_a_token_meets_314_7_million_matmul_parameters():
    # router, the shared expert, and of 16 held experts 8 * 16 / 256 = 0.5
    routed = 2048 * 256 + (1 + 0.5) * 3 * 2048 * 768
    assert flops.latent_params(FULL) == MLA
    assert flops.routed_params(FULL) == routed
    assert flops.matmul_params(FULL) == (
        6 * MLA + 3 * 2048 * 7168 + 5 * routed + 2 * 2048 * 2048
        + 2 * 2048 * 16160)
    assert flops.matmul_params(FULL) == pytest.approx(314.7e6, rel=1e-3)


def test_a_step_is_27_8_tflop_and_attention_44_percent_of_it():
    layer = flops.attention_flops_per_row(FULL)
    # QK^T at 192 and PV at 128 over half the square, 32 heads, x3
    assert layer == 3 * 32 * 2 * (8192 * 8192 // 2) * (192 + 128)
    assert flops.blocks(FULL) == 6
    step = flops.train_step_flops(FULL, 1)
    assert step == 8192 * 6 * flops.matmul_params(FULL) + 6 * layer
    assert step == pytest.approx(2.784e13, rel=1e-3)
    assert 6 * layer / step == pytest.approx(0.444, abs=2e-3)
    # latent attention, projections and pairs: 72 % of the model's work
    mla = 6 * layer + 8192 * 6 * 6 * MLA
    assert mla / step == pytest.approx(0.72, abs=0.01)


@pytest.mark.parametrize("kernel,at_qk,at_v,arrays_qk,arrays_v", [
    ("flash_fwd", 1, 1, 2, 2), ("flash_bwd_dq", 2, 1, 3, 3),
    ("flash_bwd_dkv", 2, 2, 3, 4)])
def test_kernel_calls_count_192_wide_products_and_128_wide_ones(
        kernel, at_qk, at_v, arrays_qk, arrays_v):
    f, b = flops.kernel_call(FULL, 1, kernel)
    assert f == 32 * 8192 * 8192 * (at_qk * 192 + at_v * 128)
    assert b == 32 * 8192 * (arrays_qk * 192 + arrays_v * 128) * 2
    # at equal widths it is what flops/gpt2.py and flops/trinity.py count
    equal = dict(FULL, qk_nope_head_dim=64, qk_rope_head_dim=64)
    f, b = flops.kernel_call(equal, 1, kernel)
    assert f == (at_qk + at_v) * 32 * 8192 * 8192 * 128
    assert b == (arrays_qk + arrays_v) * 32 * 8192 * 128 * 2
    # never above what padding v to 192 would count
    assert flops.kernel_call(FULL, 1, kernel)[0] < \
        (at_qk + at_v) * 32 * 8192 * 8192 * 192


def test_the_configuration_holds_every_published_key_but_the_reduced():
    with open("/opt/skills/guides/model-configs/architectures.jsonl") as f:
        rows = [json.loads(line) for line in f]
    row = [r for r in rows if r["name"] == "JoyAI-LLM-Flash"]
    if not row:
        pytest.skip("no catalog beside the guides here")
    published = row[0]["config"]
    entry = [c for c in common.load_manifest()["configs"]
             if c["name"] == "joyai-llm-flash-5l"][0]
    assert entry["source"] == row[0]["source_url"]
    reduced = entry["reduced"]
    assert reduced == FULL["reduced"] == [
        "num_hidden_layers", "n_routed_experts", "vocab_size"]
    assert {k: FULL[k] for k in published if k not in reduced} == \
        {k: v for k, v in published.items() if k not in reduced}
    assert FULL["published"] == {k: published[k] for k in reduced}
    # the share: a sixteenth of the experts (at least 8), an eighth of the
    # vocabulary, the leading dense layer and four routed ones
    assert FULL["n_routed_experts"] * 16 == published["n_routed_experts"]
    assert FULL["vocab_size"] * 8 == published["vocab_size"]
    assert FULL["experts_held"] == [0, FULL["n_routed_experts"]]
    assert FULL["num_hidden_layers"] - FULL["first_k_dense_replace"] == 4
    assert "sixteen chips" in FULL["deployment"]
    assert len(FULL["assumed"]) >= 12 and FULL["cache_mode"] == "none"


def test_the_cell_asks_for_the_full_kernels_and_all_four_limits():
    cell = common.load_json("workloads", CELL + ".json")
    assert cell["rows"] == 1 and cell["check_steps"] == 1
    assert cell["kind"] == "mtp_lm_fit_stream"
    assert cell["require_kernels"] == ["flash_fwd", "flash_bwd_dq",
                                       "flash_bwd_dkv"]
    assert set(cell["limits"]) == {"loss_gap", "grad_gap", "delta_gap",
                                   "routing_agreement"}
    manifest = common.load_manifest()
    assert [w["chips"] for w in manifest["workloads"]] == [1] * 5
    mine = {m["name"] for m in manifest["per_layer"]
            if CELL in m.get("workloads", ())}
    assert {"mla_device_pct.train", "mtp_device_pct.train",
            "moe_device_pct.train", "moe_dispatch_device_pct.train",
            "flash_roofline", "step_mfu_pct.train",
            "device_idle_pct.train"} <= mine
    # nothing is scanned, nothing recomputed, no window
    assert not {"scan_saved_device_pct.train", "remat_device_pct.train",
                "flash_window_roofline"} & mine
    new = [m for m in manifest["per_layer"]
           if m["name"] in ("mla_device_pct.train", "mtp_device_pct.train")]
    assert [m["workloads"] for m in new] == [[CELL], [CELL]]
    assert manifest["per_layer"][-2:] == new


def test_batches_are_rows_of_t_plus_one_ids_from_the_seed():
    traffic = common.load_module("traffic", TINY["kind"])
    a = traffic.mtp_batches(2 ** 31 + 5, 3, 2, 16, 50, 0.3)
    b = traffic.mtp_batches(2 ** 31 + 5, 3, 2, 16, 50, 0.3)
    c = traffic.mtp_batches(2 ** 31 + 6, 3, 2, 16, 50, 0.3)
    assert len(a) == 3
    xs, ys, ms, lms = a[0]
    assert xs[0].shape == (2, 17) and ys[0].shape == lms[0].shape == (2, 32)
    assert ms is None and xs[0].max() < 50
    np.testing.assert_array_equal(xs[0], b[0][0][0])
    assert not np.array_equal(xs[0], c[0][0][0])
    np.testing.assert_array_equal(ys[0][:, :16], xs[0][:, 1:])
    np.testing.assert_array_equal(ys[0][:, 16:31], xs[0][:, 2:])
    np.testing.assert_allclose(lms[0][0], [1] * 16 + [0.3] * 15 + [0])


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


def test_the_references_loss_is_both_terms(reference):
    parts = reference["loss_parts"]
    assert parts["main"] > 0 and parts["mtp"] > 0
    assert reference["losses"][0] == pytest.approx(
        parts["main"] + 0.3 * parts["mtp"], rel=1e-6)
    # the trunk's two routed layers and the module's
    assert np.asarray(reference["route_choices"]).shape == (2, 3, 256, 4)
    assert any(name.startswith("mtp.") for name in reference["grad_norms"])


@pytest.mark.parametrize("what,kw", [
    ("one precision below", {"precision": check_train.BELOW[
        CFG["precision"]]}),
    ("the rotary key left out", {"fault": "no_k_rope"}),
    ("the rotary key left out, as tools/readings.py asks",
     {"keep_rows": [0]}),
    ("the module's term left out of the loss", {"fault": "no_mtp"}),
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
    import deeplearning4j_tpu.nn.computation_graph as graph
    fit = graph.ComputationGraph.fit

    def broken(self, *args, **kwargs):
        keep = jax.tree_util.tree_map(lambda a: a + 0,
                                      (self.params, self.opt_state))
        fit(self, *args, **kwargs)
        self.params, self.opt_state = keep
        return self
    monkeypatch.setattr(graph.ComputationGraph, "fit", broken)
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


def op(name, start, dur):
    return tr.Event("/device:TPU:0", "XLA Ops", name, float(start),
                    float(dur))


@pytest.mark.parametrize("name", ["mla_device_pct.train",
                                  "mtp_device_pct.train"])
def test_nothing_to_read_returns_nothing(name):
    reader = common.load_module("metrics", name)
    assert reader.read(empty_ctx()) is None


def test_the_readers_put_each_operation_down_to_its_scope(monkeypatch):
    """Made-up self times by whole scope: the latent share takes
    ``mla_project`` and the ``attn_full`` between, wherever they lie; the
    module's takes everything under ``mtp``; a program without
    ``mla_project`` has no latent share, whatever ``attn_full`` holds."""
    mla = common.load_module("metrics", "mla_device_pct.train")
    mtp = common.load_module("metrics", "mtp_device_pct.train")
    fwd, bwd = "jit(train_step)/jvp(forward)/", \
        "jit(train_step)/transpose(jvp(forward))/"
    selfs = {fwd + "TransformerBlock/mla_project/dot_general": 10.0,
             fwd + "TransformerBlock/attn_full/flash_fwd": 20.0,
             bwd + "TransformerBlock/attn_full/flash_bwd_dq": 30.0,
             fwd + "mtp/TransformerBlock/mla_project/dot_general": 4.0,
             bwd + "mtp/TransformerBlock/moe_experts/ragged_dot": 6.0,
             fwd + "mtp/NextTokenMerge/dot_general": 5.0,
             fwd + "TransformerBlock/moe_shared/dot_general": 15.0,
             "jit(train_step)/optimizer/add": 10.0}
    monkeypatch.setattr(mla, "scope_self_times",
                        lambda path: (selfs, 100.0))
    ctx = dict(empty_ctx(), trace={"window_ns": 1.0}, xplane="made-up")
    assert mla.read(ctx) == pytest.approx(64.0)
    assert mtp.read(ctx) == pytest.approx(15.0)
    without = {k: v for k, v in selfs.items() if "mla_project" not in k
               and "/mtp/" not in k}
    monkeypatch.setattr(mla, "scope_self_times",
                        lambda path: (without, 100.0))
    assert mla.read(ctx) is None and mtp.read(ctx) is None
    monkeypatch.setattr(mla, "scope_self_times", lambda path: None)
    assert mla.read(ctx) is None and mtp.read(ctx) is None


def test_the_roofline_reads_the_work_at_the_published_widths():
    """A call that took twice its floor reads 50, the floor from QK^T at
    192 and PV at 128."""
    reader = common.load_module("metrics", "flash_roofline")
    floors = {k: 1e9 * flops.kernel_call(FULL, 1, k)[0] / 197e12
              for k in reader.KERNELS}
    assert floors["flash_fwd"] == pytest.approx(3.488e6, rel=1e-3)
    ops, at = [], 0.0
    for kernel, floor in floors.items():
        ops.append(op(kernel, at, 2 * floor))
        at += 3 * floor
    ctx = dict(empty_ctx(), trace={"ops": ops})
    assert reader.read(ctx) == pytest.approx(50.0)


@pytest.mark.skipif(not os.path.exists(TRACE),
                    reason="the small trace is recorded on the chip")
def test_the_readers_on_the_small_recorded_trace():
    """Three steps of the test-sized cell, traced on the chip: both scopes
    are there, the module's share is a part, the latent share holds the
    kernels, and the kernels ran at unequal widths."""
    mla = common.load_module("metrics", "mla_device_pct.train")
    selfs, busy = mla.scope_self_times(TRACE)
    assert busy > 0
    assert any("/mtp/" in s and "mla_project" in s for s in selfs)
    assert any("NextTokenMerge" in s for s in selfs)
    ctx = dict(empty_ctx(), trace={"window_ns": 1.0}, xplane=TRACE,
               cell=TINY, cfg=CFG)
    latent = mla.read(ctx)
    module = common.load_module("metrics", "mtp_device_pct.train").read(ctx)
    assert 0 < module < 100 and 0 < latent < 100
    by_scope, _ = mla.under(ctx, mla.SCOPES)
    assert by_scope["mla_project"] > 0 and by_scope["attn_full"] > 0
    # the routed FFN's reader finds its scopes in this family's trace too
    moe = common.load_module("metrics", "moe_device_pct.train").read(ctx)
    assert 0 < moe < 100
    reduced = tr.reduce(tr.load_events(TRACE))
    for kernel in ("flash_fwd", "flash_bwd_dq", "flash_bwd_dkv"):
        assert tr.kernel_events(reduced["ops"], kernel), kernel
    roofline = common.load_module("metrics", "flash_roofline").read(
        dict(ctx, trace=reduced, cell=dict(TINY, rows=2), cfg=dict(CFG)))
    assert 0 < roofline < 100
