"""Row-shape tests for the bench line sets (tiny CPU configs), and the
contract of ``bench.py``'s row runner: a row that raises is printed with a
null value and fails the run."""
import json
import sys
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))
import bench  # noqa: E402


def test_run_row_prints_rows_and_reports_success(monkeypatch, capsys):
    from deeplearning4j_tpu.utils import benchmarks as B
    monkeypatch.setattr(B, "lint_time_ms",
                        lambda: [{"metric": "a", "value": 1},
                                 {"metric": "b", "value": 2}])
    assert bench.run_row("lint_time_ms", "ms") is True
    rows = [json.loads(l) for l in capsys.readouterr().out.splitlines()]
    assert [r["metric"] for r in rows] == ["a", "b"]
    assert all("env" in r for r in rows)


def test_run_row_failure_prints_null_value_and_reports_it(monkeypatch,
                                                          capsys):
    from deeplearning4j_tpu.utils import benchmarks as B

    def boom():
        raise RuntimeError("measured nothing")
    monkeypatch.setattr(B, "lint_time_ms", boom)
    assert bench.run_row("lint_time_ms", "ms") is False
    row = json.loads(capsys.readouterr().out.splitlines()[-1])
    assert row["value"] is None and row["unit"] == "ms"
    assert "measured nothing" in row["error"]


def _main_with(monkeypatch, headline, failing=()):
    for env_var, _name, _unit in bench.SIDE_ROWS:
        monkeypatch.delenv(env_var, raising=False)
    monkeypatch.delenv("DL4J_TPU_BENCH_SIDE", raising=False)
    monkeypatch.delenv("DL4J_TPU_BENCH_STRICT", raising=False)
    monkeypatch.setattr(bench, "headline", headline)
    ran = []
    monkeypatch.setattr(
        bench, "run_row",
        lambda name, unit="", **kw: ran.append(name) or name not in failing)
    return ran


def test_main_exits_nonzero_when_a_row_measured_nothing(monkeypatch):
    """Every side row still runs after a failure, and the process then
    exits non-zero — a run that measured nothing must not pass."""
    ran = _main_with(monkeypatch, lambda: False, failing={"compile_reuse"})
    assert bench.main() == 1
    assert ran == [name for _e, name, _u in bench.SIDE_ROWS]
    ran = _main_with(monkeypatch, lambda: False)
    assert bench.main() == 0


def test_main_headline_failure_propagates(monkeypatch):
    """No bail-and-return: a headline that cannot run ends the process
    with its exception (exit code != 0), before any side row."""
    def headline():
        raise RuntimeError("no device")
    ran = _main_with(monkeypatch, headline)
    with pytest.raises(RuntimeError, match="no device"):
        bench.main()
    assert ran == []


def test_serve_latency_ms_rows():
    """The serving-engine bench line (ISSUE 8): per-impl rows (engine vs
    per-request) at each concurrency, with p50/p99 + req/s, the engine's
    vs_per_request ratio, and a compile-counter-verified zero-recompile
    steady state.  Tiny CPU config."""
    from deeplearning4j_tpu.nn.conf.input_type import InputType
    from deeplearning4j_tpu.nn.conf.multi_layer import NeuralNetConfiguration
    from deeplearning4j_tpu.nn.conf.updaters import Adam
    from deeplearning4j_tpu.nn.layers.feedforward import (DenseLayer,
                                                          OutputLayer)
    from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
    from deeplearning4j_tpu.utils import benchmarks as B

    conf = (NeuralNetConfiguration.builder().seed(7)
            .updater(Adam(learning_rate=0.05)).list()
            .layer(DenseLayer(n_out=8, activation="relu"))
            .layer(OutputLayer(n_out=3, activation="softmax",
                               loss="mcxent"))
            .set_input_type(InputType.feed_forward(4)).build())
    net = MultiLayerNetwork(conf).init()
    rows = B.serve_latency_ms(concurrencies=(2,), n_requests=32,
                              model=net, max_batch=8)
    assert [r["metric"] for r in rows] == [
        "serve_latency_ms[per_request,c=2]", "serve_latency_ms[engine,c=2]"]
    for row in rows:
        assert row["value"] > 0 and row["p99_ms"] >= row["value"]
        assert row["requests_per_sec"] > 0
        assert row["errors"] == 0 and row["unit"] == "ms p50"
    engine_row = rows[1]
    assert engine_row["vs_per_request"] > 0
    # the warmed bucket ladder held: no steady-state XLA recompiles
    assert engine_row["steady_recompiles"] == 0
    assert engine_row["batches_dispatched"] > 0


def test_step_time_ms_rows():
    """The step-time engine bench line (ISSUE 6): auto-vs-off rows per
    (seq, dtype) with the cost-model adaptation count.  Tiny CPU config;
    injected costs make the cost model switch to a native compile
    immediately, exercising the adaptation loop end to end."""
    from deeplearning4j_tpu.utils import benchmarks as B

    rows = B.step_time_ms(seqs=(16,), dtypes=("float32",), batch=4,
                          big_mult=2, embed=32, n_layers=2, n_heads=2,
                          vocab=64, steps=2, adapt_cap=50,
                          compile_cost_s=0.01, step_cost_s=1.0)
    assert len(rows) == 1
    row = rows[0]
    assert row["metric"] == "step_time_ms[s=16,f32]"
    assert row["value"] > 0 and row["off_policy_ms"] > 0
    # vs_off is computed from the UNROUNDED timings; recomputing from
    # the rounded row fields can differ at the 3rd-decimal boundary
    assert row["vs_off"] == pytest.approx(
        row["value"] / row["off_policy_ms"], abs=2e-3)
    assert row["big_bucket"] == 8 and row["dtype"] == "float32"
    # step cost >> compile cost: the very first small step compiles its
    # own bucket, so adaptation needs at most one probe chunk
    assert 0 < row["adapt_steps"] <= 25


def test_obs_overhead_ms_row():
    """The observability-overhead bench line (ISSUE 10): row shape for
    the paired recorder+monitor on-vs-off measurement.  A tiny run keeps
    the test fast; the <2% claim itself is a steady-state property of
    the full bench.py run (target_pct documents it in the row), not
    something a 2-round CI sample could assert without flaking."""
    from deeplearning4j_tpu.utils import benchmarks as B

    row = B.obs_overhead_ms(n_batches=12, runs=2)
    assert row["metric"] == "obs_overhead_ms"
    assert row["unit"].startswith("ms/step")
    assert row["value"] > 0 and row["off_ms"] > 0
    # the paired-delta median can dip negative under host noise, but it
    # must stay a small fraction of the step itself
    assert isinstance(row["overhead_ms"], float)
    assert abs(row["overhead_ms"]) < row["value"]
    assert row["overhead_pct"] is not None
    assert row["target_pct"] == 2.0
    assert row["steps"] == 12 and row["runs"] == 2


def test_lint_time_ms_row():
    """The lint wall-time bench line (ISSUE 9): row shape + a sane
    measurement over a small path subset (the full-package budget is
    asserted in test_lint.py; here the row contract is what's tested)."""
    from pathlib import Path

    from deeplearning4j_tpu.utils import benchmarks as B
    subset = str(Path(__file__).resolve().parents[1]
                 / "deeplearning4j_tpu" / "serving")
    row = B.lint_time_ms(paths=[subset], runs=1)
    assert row["metric"] == "lint_time_ms"
    assert row["unit"].startswith("ms")
    assert row["value"] > 0
    assert row["files"] >= 3          # serving/ has engine + 2 servers
    assert row["rules"] == 32
    assert row["findings"] == 0       # the swept package stays clean
    assert row["runs"] == 1


def test_audit_time_ms_row():
    """The IR-audit bench line (ISSUE 14; diff slice ISSUE 16): row
    shape for the canonical program-set build + full graftaudit wall
    time + the budgets.json differential gate.  A name-filtered subset
    keeps the test fast (the dense + bf16 train steps — no sharded
    meshes, no generation engine); the full-set 60s acceptance budget
    is asserted in tests/test_audit.py where the whole set is built
    anyway, and the full diff gate in tests/test_audit_diff.py."""
    from deeplearning4j_tpu.utils import benchmarks as B

    row = B.audit_time_ms(include=["train_step[dense]",
                                   "train_step[bf16]"])
    assert row["metric"] == "audit_time_ms"
    assert row["unit"].startswith("ms full canonical-set")
    assert row["value"] > 0
    assert row["value"] == pytest.approx(
        row["build_ms"] + row["audit_ms"] + row["diff_ms"], abs=0.16)
    assert row["programs"] == 2
    assert row["skipped"] == []      # under-coverage must be explicit
    assert row["rules"] == 10
    assert row["findings"] == 0       # the swept canonical set is clean
    assert row["stale_budgets"] == []  # subset rows count as skipped
    assert row["budget_ms"] == 60000.0
    assert row["value"] < row["budget_ms"]


def test_decode_tokens_per_sec_rows():
    """The generation bench line (ISSUE 11): one row per mix
    (decode-heavy / prefill-heavy) with engine + naive tokens/sec, the
    vs_naive ratio, and the counter-verified zero-recompile steady
    state.  Tiny CPU config — the engine-beats-naive acceptance gate is
    asserted at the real bench scale, where the naive baseline pays 48
    full-sequence forwards per request; at this toy scale only the row
    contract and the recompile counter are stable."""
    from deeplearning4j_tpu.models import TransformerLM
    from deeplearning4j_tpu.utils import benchmarks as B

    lm = TransformerLM(vocab_size=17, seq_len=32, embed=16, n_layers=2,
                       n_heads=2).init()
    rows = B.decode_tokens_per_sec(model=lm, max_slots=2, max_seq=32,
                                   mixes=(("decode_heavy", 3, 4, 6),
                                          ("prefill_heavy", 3, 20, 3)))
    assert [r["metric"] for r in rows] == [
        "decode_tokens_per_sec[decode_heavy]",
        "decode_tokens_per_sec[prefill_heavy]",
        "decode_tokens_per_sec[slot_capacity]"]
    for row in rows[:2]:
        assert row["unit"] == "tokens/sec"
        assert row["value"] > 0 and row["naive_tokens_per_sec"] > 0
        assert row["vs_naive"] > 0
        assert row["tokens"] == row["requests"] * row["new_tokens"]
        assert row["decode_steps"] > 0
        # paged-KV sizing columns (ISSUE 19)
        assert row["cache_bytes"] > 0
        assert row["slots_per_gb"] > 0
        # the warmed two-program set held across the whole mixed run
        assert row["steady_recompiles"] == 0
    cap = rows[2]
    assert cap["unit"] == "x_dense_slots"
    # the whole 4x fleet was simultaneously resident inside the dense
    # ring's K/V byte budget with the steady program set intact
    assert cap["value"] == 4.0
    assert cap["peak_active"] == cap["paged_slots"] == 4 * cap["dense_slots"]
    assert cap["bytes_vs_dense"] <= 1.0
    assert cap["slots_per_gb"] > cap["dense_slots_per_gb"]
    assert cap["steady_recompiles"] == 0


def test_ttft_ms_rows():
    """The time-to-first-token bench line (ISSUE 19, dense ring arm
    removed in ISSUE 20): one row per arm (paged cold / paged
    shared-prefix) with p50/p99 TTFT, the shared arm's prefix-hit
    accounting, and the counter-verified zero-recompile steady state.
    Tiny CPU config — the >= 2x shared-vs-cold acceptance gate is
    asserted at the real bench scale where the shared prefix is 64 of
    72 prompt tokens; at this toy scale only the row contract, the hit
    counters, and the recompile counter are stable."""
    from deeplearning4j_tpu.models import TransformerLM
    from deeplearning4j_tpu.utils import benchmarks as B

    lm = TransformerLM(vocab_size=17, seq_len=32, embed=16, n_layers=2,
                       n_heads=2).init()
    rows = B.ttft_ms(model=lm, max_slots=2, max_seq=32, n_requests=4,
                     prefix_len=16, suffix_len=4, new_tokens=2)
    assert [r["metric"] for r in rows] == [
        "ttft_ms[paged_cold]", "ttft_ms[paged_shared]"]
    for row in rows:
        assert row["unit"] == "ms"
        assert row["value"] > 0 and row["p99_ms"] >= row["value"]
        assert row["requests"] == 4
        assert row["steady_recompiles"] == 0
    # only the shared arm re-uses registered prefix blocks: every
    # request after the first skips the shared 16-token prefix
    assert rows[0]["prefix_hits"] == 0
    assert rows[1]["prefix_hits"] == 3
    assert rows[1]["prefill_tokens_saved"] > 0
    assert rows[1]["vs_cold"] > 0


def test_serve_fleet_rows():
    """The serving-fleet bench line set (ISSUE 20): predict req/s and
    decode tokens/s rows per replica count with ``vs_one_replica``
    ratios, plus the kill-one-replica chaos row.  Tiny CPU config at 2
    replicas — the >= 3x-at-4-replicas acceptance gate is asserted at
    the real bench scale (device-paced replicas make it
    near-linear); here the row contract, the migration accounting, and
    the zero-recompile steady state are what's stable."""
    from deeplearning4j_tpu.models import TransformerLM
    from deeplearning4j_tpu.utils import benchmarks as B

    lm = TransformerLM(vocab_size=17, seq_len=32, embed=16, n_layers=2,
                       n_heads=2).init()
    # concurrency stays >= 2 full batches PER REPLICA at the widest
    # count — a replica whose queue drains between paced batches stalls
    # its pipeline and the scaling ratio with it
    rows = B.serve_fleet(replica_counts=(1, 2), lm=lm, pace_ms=4.0,
                         concurrency=16, n_requests=96, max_slots=2,
                         new_tokens=6, kill_tokens=16, max_seq=32)
    assert [r["metric"] for r in rows] == [
        "serve_fleet[predict,r=1]", "serve_fleet[predict,r=2]",
        "serve_fleet[decode,r=1]", "serve_fleet[decode,r=2]",
        "serve_fleet[recovery]"]
    for row in rows:
        assert row["value"] is not None and row["value"] > 0
        assert row["steady_recompiles"] == 0
    # scaling ratios ride every non-baseline throughput row
    assert rows[1]["vs_one_replica"] > 1.0   # paced replicas overlap
    assert rows[3]["vs_one_replica"] > 1.0
    assert rows[0]["errors"] == rows[1]["errors"] == 0
    # the chaos row: the victim's sessions moved and every stream
    # finished — shed or served, never hung (ISSUE 20 acceptance)
    chaos = rows[-1]
    assert chaos["migrated"] >= 1
    assert chaos["completed"] == chaos["sessions"]
    assert chaos["errors"] == 0


def test_elastic_reshard_ms_row():
    """The elastic-reshard bench line (ISSUE 13): row shape for the
    member-loss -> first-clean-sharded-step measurement on the survivor
    mesh.  Tiny CPU config; the window includes lease expiry, the
    aborted barrier round, eviction, and the
    restore_sharded(mesh=survivors) re-placement."""
    import jax

    from deeplearning4j_tpu.utils import benchmarks as B

    if len(jax.devices()) < 8:
        pytest.skip("needs 8 virtual devices")
    row = B.elastic_reshard_ms(n_batches=12)
    assert row["metric"] == "elastic_reshard_ms"
    assert row["unit"].startswith("ms member loss")
    assert row["value"] is not None and row["value"] > 0
    assert row["restore_ms"] is not None and row["restore_ms"] > 0
    # the detection slice (lease expiry + boundary wait) dominates and
    # both slices sit inside the total window
    assert row["detect_ms"] is not None
    assert row["restore_ms"] < row["value"]
    assert row["dp_before"] == 4 and row["dp_after"] == 2
    assert row["world_before"] == 2 and row["world_after"] == 1
    assert row["steps"] == 12


def test_embedding_grad_exchange_ms_rows():
    """The sparse-embedding bench line (ISSUE 15): one row per
    (vocab, touched-fraction) with the densified-exchange and
    dense-all-reduce step times, the vs_dense ratio, and the
    counter-verified zero-recompile steady state.  Tiny CPU config —
    the densified-wins acceptance gate is asserted at the real bench
    scale (vocab >= 50k, where the dense path ships a multi-MB
    all-reduce per step); at toy vocab only the row contract and the
    recompile counter are stable."""
    import jax

    from deeplearning4j_tpu.utils import benchmarks as B

    if len(jax.devices()) < 8:
        pytest.skip("needs 8 virtual devices")
    rows = B.embedding_grad_exchange_ms(vocabs=(2048,),
                                        touched_fracs=(0.1,), dim=8,
                                        batch=64, steps=2, warm=1)
    assert [r["metric"] for r in rows] == [
        "embedding_grad_exchange_ms[v=2048,t=0.1]"]
    row = rows[0]
    assert row["unit"].startswith("ms/step")
    assert row["value"] > 0 and row["dense_all_reduce_ms"] > 0
    assert row["vs_dense"] == pytest.approx(
        row["value"] / row["dense_all_reduce_ms"], abs=2e-3)
    assert row["densified_wins"] == (row["value"]
                                     < row["dense_all_reduce_ms"])
    # the exchange block is the exact static bound min(batch, vocab)
    assert row["capacity"] == 64
    assert row["touched_rows_max"] == 204   # 0.1 * 2048, the id pool
    assert row["vocab"] == 2048 and row["dp"] == 8
    # both programs compiled during warmup; the timed windows added none
    assert row["steady_recompiles"] == 0


def test_sharded_step_time_ms_row():
    """The sharded-training bench line (ISSUE 12): sharded + replicated
    step ms at a fixed global batch, the per-device param-bytes ~1/dp
    memory win, and the counter-verified single trace shared by both
    paths.  Tiny CPU config — on the 1-core rig the collectives are
    memcpy loops, so only the row contract, the bytes ratio, and the
    trace count are stable (the ms ratio is asserted at real scale)."""
    import jax

    from deeplearning4j_tpu.utils import benchmarks as B

    if len(jax.devices()) < 8:
        pytest.skip("needs 8 virtual devices")
    row = B.sharded_step_time_ms(hidden=64, features=32, classes=8,
                                 batch=32, steps=3, warm=1,
                                 min_shard_size=0)
    assert row["metric"] == "sharded_step_time_ms"
    assert row["unit"].startswith("ms/step")
    assert row["value"] > 0 and row["replicated_ms"] > 0
    assert row["vs_replicated"] > 0
    assert row["dp"] == 8
    # the ZeRO-3 memory win: with every eligible leaf sharded, the
    # per-device bytes land well under replicated — here all four dense
    # kernels shard, so the ratio sits near 1/dp (biases replicate)
    assert row["param_bytes_per_device"] < row["replicated_param_bytes"]
    assert row["param_bytes_ratio"] <= 0.25
    assert row["global_param_bytes"] == row["replicated_param_bytes"]
    # sharding lives in the arguments, not the trace: the replicated and
    # sharded runs share ONE trace of the train step
    assert row["train_step_traces"] == 1


def test_profiler_overhead_ms_row():
    """The step-profiler overhead bench line (ISSUE 17): row shape for
    the paired stepprof on-vs-off measurement plus the fully-fenced
    attribution coverage check.  A tiny run keeps the test fast; the
    <2% claim is a steady-state property of the full bench.py run
    (target_pct documents it), but the coverage contract — phase sums
    within 5% of step wall on fenced steps — IS asserted here, since it
    is a structural property of the attribution, not a timing one."""
    from deeplearning4j_tpu.utils import benchmarks as B

    row = B.profiler_overhead_ms(n_batches=12, runs=2)
    assert row["metric"] == "profiler_overhead_ms"
    assert row["unit"].startswith("ms/step")
    assert row["value"] > 0 and row["off_ms"] > 0
    assert isinstance(row["overhead_ms"], float)
    assert abs(row["overhead_ms"]) < row["value"]
    assert row["overhead_pct"] is not None
    assert row["target_pct"] == 2.0
    assert 0.95 <= row["phase_coverage"] <= 1.05
    assert set(row["phase_share"]) == {
        "etl_wait", "h2d", "dispatch", "device", "listener", "forensics",
        "checkpoint"}
    assert row["steps"] == 12 and row["runs"] == 2


def test_dispatch_pipeline_ms_row():
    """The bounded-dispatch pipeline bench line (ISSUE 18): row shape
    for the paired depth=1-vs-windowed measurement on both arms.  A
    tiny run keeps the test fast; the >=1.3x headline claim is a
    full-bench property, but the structural guarantees — both arms
    report every depth, ratios are finite, and flipping the host-only
    depth knob never retraces — ARE asserted here."""
    from deeplearning4j_tpu.utils import benchmarks as B

    row = B.dispatch_pipeline_ms(depths=(2,), n_batches=6, runs=2)
    assert row["metric"] == "dispatch_pipeline_ms"
    assert row["unit"].startswith("ms/step")
    assert row["depths"] == [2]
    for arm in ("dispatch_bound", "compute_bound"):
        sub = row[arm]
        assert sub["depth1_ms_vs2"] > 0
        assert sub["depth2_ms"] > 0
        assert sub["speedup_depth2"] > 0
    assert row["value"] == row["dispatch_bound"]["depth2_ms"]
    # the depth knob lives host-side: two arms, two one-time compiles,
    # zero retraces across every depth flip
    assert row["train_step_traces_total"] <= 2
    assert row["steady_recompiles"] == 0
    assert row["steps"] == 6 and row["runs"] == 2


def test_env_fingerprint_on_every_row():
    """The provenance block (ISSUE 17 satellite): env_fingerprint()
    carries the host/runtime facts, is captured once per process, and
    bench.py's _stamp attaches it to every emitted row."""
    import json as _json

    from bench import _dumps, _stamp
    from deeplearning4j_tpu.utils import benchmarks as B

    env = B.env_fingerprint(refresh=True)
    assert env["cpus"] >= 1
    assert env["python"].count(".") >= 1
    assert env["jax"] and env["jaxlib"]
    assert isinstance(env["x64"], bool)
    assert isinstance(env["overrides"], dict)
    assert all(k.startswith("DL4J_TPU_") for k in env["overrides"])
    # cached: the same dict object stamps every row of a process
    assert B.env_fingerprint() is env

    row = _stamp({"metric": "m", "value": 1})
    assert row["env"] is env
    line = _json.loads(_dumps({"metric": "m2", "value": 2}))
    assert line["env"]["cpus"] == env["cpus"]
    # an explicit env on a row is never clobbered
    assert _stamp({"env": "mine"})["env"] == "mine"


def test_transformer_lm_flops_source_card_vs_analytic(tmp_path,
                                                      monkeypatch):
    """ISSUE 17 satellite: transformer_lm_step_time routes
    achieved_tflops through the committed graftaudit card when one
    exists for the program, and labels the analytic estimate as the
    fallback otherwise."""
    import json as _json

    from deeplearning4j_tpu.utils import benchmarks as B

    kw = dict(batch=2, seq=8, embed=8, n_layers=1, n_heads=2, vocab=32,
              impls=("reference",), nbatch=2, epochs=1, blocks=1)
    monkeypatch.setenv("DL4J_TPU_CARDS_DIR", str(tmp_path))
    rows = B.transformer_lm_step_time(**kw)
    # no card in the empty dir: labeled analytic fallback (the toy-size
    # analytic estimate itself rounds to ~0 TFLOP/s — the label is the
    # contract here, not the magnitude)
    assert rows[0]["flops_source"] == "analytic"

    # the card filename mirrors graftaudit's sanitize of the program name
    card = tmp_path / "transformer_lm_reference_s_8_.json"
    card.write_text(_json.dumps({"program": "transformer_lm[reference,s=8]",
                                 "flops": 1e12}))
    rows = B.transformer_lm_step_time(**kw)
    row = rows[0]
    assert row["flops_source"] == "card"
    # card flops (1 TFLOP) over the measured ms: the two sources differ
    # by orders of magnitude at this toy size, so routing is observable
    assert row["achieved_tflops"] == pytest.approx(
        1e12 / (row["value"] * 1e-3) / 1e12, rel=0.05)
