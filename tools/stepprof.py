"""stepprof CLI: capture a step-profiler window and commit the artifact.

Run from the repo root: ``python -m tools.stepprof``.  Drives a short fit
of the canonical dense MLP (the exact net behind the ``train_step[dense]``
graftaudit card, ``tools/graftaudit/canonical.py``) with the
:class:`~deeplearning4j_tpu.observability.profiler.StepProfiler` armed,
then emits:

1. a checksummed Chrome-trace artifact (``stepprof-<pid>-<ts>.json``,
   loadable in chrome://tracing / Perfetto) via the atomic-commit path —
   the same artifact ``GET /debug/profile?dump=1`` serves from a live
   trainer; and
2. a text phase table — mean seconds + share of step wall per phase over
   steady steps, with the sampled-fence coverage check.

Replaces the round-2 ``profile_capture.py`` Xprof-glob script: Xprof
answers "which op is slow on the device"; this answers the prior
question — "is the time even ON the device" — without chip tooling.

Options::

  --steps N     minibatches per epoch          (default 48)
  --epochs E    epochs                         (default 2)
  --sample N    fence cadence (1 = every step) (default 8)
  --out DIR     artifact directory             (default .)
"""
from __future__ import annotations

import argparse
import json
import os
import sys

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO_ROOT not in sys.path:
    sys.path.insert(0, REPO_ROOT)


def _fmt_s(v) -> str:
    return "      —" if v is None else f"{v * 1e3:7.3f}"


def phase_table(summary: dict) -> str:
    """Render a phase_summary() dict as the text table the runbook shows."""
    from deeplearning4j_tpu.observability.profiler import PHASES
    lines = [f"{'phase':<12} {'mean ms':>8} {'share':>7}",
             "-" * 29]
    mean = summary.get("mean_phase_s") or {}
    share = summary.get("phase_share") or {}
    for name in PHASES:
        lines.append(f"{name:<12} {_fmt_s(mean.get(name)):>8} "
                     f"{share.get(name, 0.0):>6.1%}")
    lines.append("-" * 29)
    lines.append(f"{'step wall':<12} {_fmt_s(summary.get('mean_wall_s')):>8} "
                 f"{'over':>4} {summary['steps']} steps")
    cov = summary.get("sampled_coverage")
    if cov is not None:
        lines.append(f"sampled coverage {cov:.1%} of wall attributed "
                     f"({summary.get('sampled_steps', 0)} fenced steps)")
    return "\n".join(lines)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        prog="python -m tools.stepprof",
        description="short canonical fit -> Chrome trace + phase table")
    ap.add_argument("--steps", type=int, default=48)
    ap.add_argument("--epochs", type=int, default=2)
    ap.add_argument("--sample", type=int, default=8)
    ap.add_argument("--out", default=".")
    args = ap.parse_args(argv)

    # env, not API: the capture must exercise the exact default-on wiring
    # a production fit runs (fit() -> step_profiler_for -> env knobs)
    os.environ["DL4J_TPU_STEPPROF"] = "1"
    os.environ["DL4J_TPU_STEPPROF_SAMPLE"] = str(max(1, args.sample))

    from deeplearning4j_tpu.observability.profiler import (CHANNEL,
                                                           chrome_trace,
                                                           dump_chrome_trace,
                                                           phase_summary)
    from deeplearning4j_tpu.observability.recorder import (FlightRecorder,
                                                           set_flight_recorder)
    from tools.graftaudit.canonical import _batch, _mlp

    # a dedicated recorder: the window holds exactly this capture's steps
    rec = FlightRecorder(capacity=max(256, args.steps * args.epochs + 16))
    prev = set_flight_recorder(rec)
    try:
        net = _mlp()
        x, y = _batch()
        net.fit([(x, y)] * args.steps, epochs=args.epochs)
    finally:
        set_flight_recorder(prev)

    records = rec.channel(CHANNEL).items()
    if not records:
        print("no profile records captured (is DL4J_TPU_STEPPROF forced "
              "off?)", file=sys.stderr)
        return 1
    summary = phase_summary(records)
    path = dump_chrome_trace(directory=args.out, records=records)
    doc = chrome_trace(records)
    print(phase_table(summary))
    print(f"\ntrace: {path} ({len(doc['traceEvents'])} events — load in "
          "chrome://tracing or ui.perfetto.dev)")
    print(json.dumps({"program": records[-1].get("program"),
                      "steps": summary.get("steps"),
                      "sampled_steps": summary.get("sampled_steps"),
                      "mean_wall_ms": round(
                          (summary.get("mean_wall_s") or 0) * 1e3, 3),
                      "trace": path}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
