"""Benchmark entry point (ROADMAP S1 replaces this file with a table of
cells; until then it only has to neither hang nor pass on a run that
measured nothing).

First JSON line, the headline: flagship (ResNet50-ImageNet, BASELINE.md
north star) training throughput through the framework's device-resident
epoch path (``fit_on_device``: the dataset lives in HBM and one jitted
program scans the train step over all minibatches — one dispatch per epoch
instead of one per step), as the MEDIAN of N timed runs
(DL4J_TPU_BENCH_RUNS, default 3).  ``vs_baseline`` compares against
``BASELINE_EXAMPLES_PER_SEC`` and ``regression`` is true below
FAIL_THRESHOLD.  A headline that raises ends the process non-zero with
the traceback.  Env knobs: DL4J_TPU_BENCH_BATCH / _IMAGE / _DTYPE /
_NBATCH / _EPOCHS / _RUNS for smoke-testing the bench path.

Then one line (or line set) per row of ``SIDE_ROWS`` below, each from the
function of that name in ``deeplearning4j_tpu.utils.benchmarks`` (its
docstring says what the row measures) and each with its own
``DL4J_TPU_BENCH_*=0`` opt-out.  A row that raises is printed with
``"value": null`` and its error, the remaining rows still run, and the
process then exits non-zero: a run in which something measured nothing
does not pass.  ``DL4J_TPU_BENCH_SIDE=1`` adds the ``EXTRA_ROWS``
(BASELINE.md's secondary configs and the transformer rows).

Every row runs in THIS process: the chip belongs to one process at a
time, so nothing here starts a child that would need it.

Every printed row carries an ``env`` provenance block (cpu count,
at-start load average, jax/jaxlib versions, x64 flag, DL4J_TPU_*
overrides in effect) so round-over-round comparisons can separate
framework regressions from environment drift.
"""
import json
import os
import sys
import time

import numpy as np

# The first driver-recorded ResNet50 figure.  It predates today's code and
# every record of that run has been deleted; S1 replaces the constant with
# the ledger.
BASELINE_EXAMPLES_PER_SEC = 2055.4
# vs_baseline below this is a regression (the N-run median absorbs
# run-to-run variance).
FAIL_THRESHOLD = 0.95

# (opt-out env var, function in utils/benchmarks, unit for a failed row)
SIDE_ROWS = (
    ("DL4J_TPU_BENCH_PIPELINE", "input_pipeline_examples_per_sec",
     "examples/sec"),
    ("DL4J_TPU_BENCH_COMPILE", "compile_reuse", "x cold/clone first-step"),
    ("DL4J_TPU_BENCH_CKPT", "checkpoint_overhead",
     "ms/save async stall (idle writer)"),
    ("DL4J_TPU_BENCH_STEP", "step_time_ms", "ms/step (auto policy)"),
    ("DL4J_TPU_BENCH_RECOVERY", "recovery_time_ms",
     "ms kill -> first post-recovery step (sync retry)"),
    ("DL4J_TPU_BENCH_SERVE", "serve_latency_ms", "ms p50"),
    ("DL4J_TPU_BENCH_LINT", "lint_time_ms", "ms full-package graftlint"),
    ("DL4J_TPU_BENCH_OBS", "obs_overhead_ms",
     "ms/step recorder+monitor enabled"),
    ("DL4J_TPU_BENCH_DECODE", "decode_tokens_per_sec", "tokens/sec"),
    ("DL4J_TPU_BENCH_SHARD", "sharded_step_time_ms",
     "ms/step (ZeRO-3 sharded)"),
    ("DL4J_TPU_BENCH_RESHARD", "elastic_reshard_ms",
     "ms member loss -> first clean sharded step (survivor mesh)"),
    ("DL4J_TPU_BENCH_AUDIT", "audit_time_ms",
     "ms full canonical-set IR audit (build + audit)"),
    ("DL4J_TPU_BENCH_EMBED", "embedding_grad_exchange_ms",
     "ms/step (densified index/value exchange, row-sharded table)"),
    ("DL4J_TPU_BENCH_STEPPROF", "profiler_overhead_ms",
     "ms/step stepprof enabled"),
    ("DL4J_TPU_BENCH_PIPELINE_DEPTH", "dispatch_pipeline_ms",
     "ms/step dispatch-bound arm"),
    ("DL4J_TPU_BENCH_TTFT", "ttft_ms", "ms"),
    ("DL4J_TPU_BENCH_FLEET", "serve_fleet", "req/s"),
)

# DL4J_TPU_BENCH_SIDE=1: (function, kwargs) — BASELINE.md's secondary
# configs and the transformer rows at the four lengths of the old campaign
EXTRA_ROWS = (
    ("lenet_step_time", {}),
    ("char_lstm_step_time", {}),
    ("word2vec_words_per_sec", {}),
    ("paragraph_vectors_words_per_sec", {"seq_algo": "dbow"}),
    ("paragraph_vectors_words_per_sec", {"seq_algo": "dm"}),
    ("transformer_lm_step_time", {}),
    ("transformer_lm_step_time",
     {"batch": 64, "seq": 128, "impls": ("auto", "reference")}),
    ("transformer_lm_step_time",
     {"batch": 4, "seq": 2048, "impls": ("auto", "reference")}),
    ("transformer_lm_step_time",
     {"batch": 1, "seq": 8192, "impls": ("auto", "flash"), "nbatch": 3,
      "epochs": 1}),
    ("transformer_lm_step_time",
     {"batch": 1, "seq": 8192, "impls": ("reference",), "nbatch": 2,
      "epochs": 1, "blocks": 1}),
    ("serving_latency", {}),
)


def _stamp(row):
    """Attach the host/runtime provenance block (ISSUE 17 satellite) to a
    bench row in place: cpu count, at-start load average, jax/jaxlib
    versions, the x64 flag, and every DL4J_TPU_* override in effect —
    the facts that separate framework regressions from environment
    drift.  Best-effort: a row must never be lost to its fingerprint."""
    try:
        from deeplearning4j_tpu.utils.benchmarks import env_fingerprint
        row.setdefault("env", env_fingerprint())
    except Exception:
        pass
    return row


def _dumps(row) -> str:
    """One stamped bench JSON line (every printed row goes through here)."""
    return json.dumps(_stamp(row))


def run_row(name: str, unit: str = "", **kwargs) -> bool:
    """Print the row(s) of one ``utils.benchmarks`` function.  A row that
    raises is printed with ``"value": null`` and the error, so the rows
    after it still run; returns False then, and ``main`` exits non-zero."""
    try:
        from deeplearning4j_tpu.utils import benchmarks
        rows = getattr(benchmarks, name)(**kwargs)
    except Exception as e:  # a boundary that must keep the other rows running
        print(_dumps({"metric": name, "value": None, "unit": unit,
                      "error": f"{type(e).__name__}: {e}"[:300]}))
        return False
    for row in rows if isinstance(rows, list) else [rows]:
        print(_dumps(row))
    return True


def headline() -> bool:
    """The ResNet50 line; returns whether it regressed."""
    import jax.numpy as jnp
    from deeplearning4j_tpu.models import available_bench_model

    batch = int(os.environ.get("DL4J_TPU_BENCH_BATCH", "256"))
    image = int(os.environ.get("DL4J_TPU_BENCH_IMAGE", "224"))
    nbatch = int(os.environ.get("DL4J_TPU_BENCH_NBATCH", "10"))
    epochs = int(os.environ.get("DL4J_TPU_BENCH_EPOCHS", "4"))
    cdtype = os.environ.get("DL4J_TPU_BENCH_DTYPE", "bfloat16")

    n = batch * nbatch
    model, (x, y) = available_bench_model(batch=n, image=image)
    # device-resident dataset in the compute dtype (a real input pipeline
    # feeds decoded uint8→bf16; keeping the HBM copy f32 would double the
    # per-step gather traffic for no numerical benefit)
    xdt = jnp.float32 if cdtype == "float32" else jnp.dtype(cdtype)
    x = jnp.asarray(x, xdt)
    y = jnp.asarray(y)

    runs = max(1, int(os.environ.get("DL4J_TPU_BENCH_RUNS", "3")))

    # warm: compile + first execution of BOTH programs the timed runs use
    # (epochs=1 single-epoch scan, then the fused multi-epoch scan)
    model.fit_on_device(x, y, batch_size=batch, epochs=1)
    if epochs > 1:
        model.fit_on_device(x, y, batch_size=batch, epochs=epochs)
    rates = []
    for _ in range(runs):
        t0 = time.perf_counter()
        model.fit_on_device(x, y, batch_size=batch, epochs=epochs)
        # the clock closes on a host fetch of the final loss, i.e. on real
        # device completion
        float(model.get_score())
        dt = time.perf_counter() - t0
        rates.append(epochs * n / dt)

    examples_per_sec = float(np.median(rates))
    vs_baseline = examples_per_sec / BASELINE_EXAMPLES_PER_SEC
    regressed = vs_baseline < FAIL_THRESHOLD
    print(_dumps({
        "metric": "train_examples_per_sec",
        "value": round(examples_per_sec, 2),
        "unit": "examples/sec",
        "vs_baseline": round(vs_baseline, 3),
        "runs": runs,
        "spread": round((max(rates) - min(rates)) / examples_per_sec, 3),
        "fail_threshold": FAIL_THRESHOLD,
        "regression": bool(regressed),
    }))
    if regressed:
        print(f"REGRESSION: median vs_baseline {vs_baseline:.3f} < "
              f"{FAIL_THRESHOLD} over {runs} runs", file=sys.stderr)
    return regressed


def main() -> int:
    regressed = headline()
    ok = True
    for env_var, name, unit in SIDE_ROWS:
        if os.environ.get(env_var, "1") != "0":
            ok = run_row(name, unit) and ok
    # the extra rows run even on regressed runs — they're the diagnosis data
    if os.environ.get("DL4J_TPU_BENCH_SIDE"):
        for name, kwargs in EXTRA_ROWS:
            ok = run_row(name, **kwargs) and ok
    if not ok:
        return 1
    # opt-in hard failure on a regressed headline for CI-style gating
    if regressed and os.environ.get("DL4J_TPU_BENCH_STRICT"):
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
