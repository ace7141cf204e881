"""Set-up by parts, as the program accounts for it itself: what the eight
``*.setup`` metrics under ``metrics/`` read.

``run.py`` reads every registry counter, summed over its labels, after
``job.setup()`` and before the traced stretch (``ctx["counters_before"]``).
In a process that has done nothing else those are set-up's totals exactly,
so a counter of seconds of set-up needs no second reading.  The two
gauges of the import are set once and read from the registry.  A program
without such a counter or gauge, as the parent of the PR that added them
is, gives ``None``.

``say_table`` prints the program's own ``startup_report()`` by ``fn``
(which jitted program the seconds belong to) on standard error, once a
run.  By then the traced stretch and the check have run too: the rows
``train_step`` / ``epoch_scan`` and ``init`` are set-up's own (the window
compiles nothing: ``window_compiles.train``), the row ``eager`` also holds
what the check traced and compiled, and the line under the table says how
much of each total came after set-up.
"""
from __future__ import annotations

from benchmark import common

SECONDS = {"trace_s": "jit_trace_seconds_total",
           "lower_s": "jit_lower_seconds_total",
           "cache_load_s": "jit_cache_load_seconds_total",
           "compile_s": "jit_compile_seconds_total"}
_said = False


def counter(ctx, name: str):
    """A registry counter as it stood when set-up ended."""
    before = ctx.get("counters_before")
    if before is None or name not in before:
        return None
    return float(before[name])


def gauge(name: str):
    """A registry gauge as it stands (the import's two are set once)."""
    from deeplearning4j_tpu.observability.registry import default_registry
    inst = default_registry().get(name)
    return None if inst is None else float(inst.value)


def say_table(ctx) -> None:
    """The program's table by ``fn``, the parts' sum, and the process's
    age now; nothing where the program has no ``startup_report``."""
    global _said
    from deeplearning4j_tpu import observability
    report = getattr(observability, "startup_report", None)
    if _said or report is None:
        return
    _said = True
    r = report()
    common.say("set-up by parts, by the program's own counters (s):")
    common.say(f"  before the package's import "
               f"{r['process_age_at_import_s']}, the import "
               f"{r['package_import_s']}, init() {r['model_init_s']}")
    for fn, row in sorted(r["jit"].items()):
        common.say(f"  {fn:<12} " + "  ".join(
            f"{k} {v:.3f}" if k.endswith("_s") else f"{k} {v:.0f}"
            for k, v in row.items()))
    before = ctx.get("counters_before") or {}
    after_setup = {k: sum(row[k] for row in r["jit"].values())
                   - before.get(name, 0.0) for k, name in SECONDS.items()}
    common.say("  of those, after set-up (the stretch and the check): "
               + "  ".join(f"{k} {v:.3f}" for k, v in after_setup.items()))
    # init()'s programs lie inside its wall time: they are counted once
    inside_init = r["jit"].get("init", {})
    parts = [r["process_age_at_import_s"], r["package_import_s"],
             r["model_init_s"]] + [
        before.get(name, 0.0) - inside_init.get(k, 0.0)
        for k, name in SECONDS.items()]
    age = observability.process_age_s()
    common.say(f"  the parts of set-up sum to "
               f"{sum(p or 0.0 for p in parts):.3f} s; the process is now "
               f"{'%.3f' % age if age is not None else 'of unknown age'}"
               f"{' s old' if age is not None else ''}")
