#!/usr/bin/env python3
"""The benchmark's one command: one process, one cell, one result line.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Everything that belongs to one cell, configuration, traffic kind or
per-layer metric sits in a file of its own, found by the name that
``BENCHMARK.json`` or the cell's file gives:

    workloads/<cell>.json      the traffic mix: its kind and parameters, limits
    configs/<config>.json      the sizes as run, the ``family``, the precision
    traffic/<kind>.py          builds the program's model, warms up, runs windows
    metrics/<metric>.py        one reader per per-layer metric
    flops/<family>.py          operations and bytes from the shapes
    reference/<family>.py      the plain reference

    check/<kind>.py            the comparison that decides ``correct``

The last line on standard output is the result; progress, and each number
compared beside its limit, go to standard error.  A machine without the
chips the cell asks for gets no result and a non-zero exit.  A traced run
deletes its trace once it is reduced, unless ``BENCHMARK_KEEP_TRACE`` is set
(the tools under ``tools/`` set it, to look at a trace by hand).
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse          # noqa: E402
import gc                # noqa: E402
import json              # noqa: E402
import os                # noqa: E402
import shutil            # noqa: E402
import sys               # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmark import common          # noqa: E402
from benchmark.common import say      # noqa: E402


def accelerators(chips: int):
    """``jax.devices()`` when they are at least ``chips`` TPU chips."""
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < chips:
        return None
    return devices[:chips]


def counters() -> dict:
    """The program's registry counters, summed over their labels."""
    from deeplearning4j_tpu.observability.registry import default_registry
    out = {}
    for inst in default_registry().collect():
        if type(inst).__name__ != "Counter":
            continue
        total = 0.0
        for _labels, child in inst.samples():
            v = child.value
            total += float(v() if callable(v) else v)
        out[inst.name] = total
    return out


def cell_metrics(manifest: dict, cell_name: str, section: str):
    """The metrics of ``section`` that this cell reports."""
    return [m for m in manifest[section]
            if "workloads" not in m or cell_name in m["workloads"]]


def execute(cell_name: str, seed: int, seconds: float, trace: bool, devices,
            manifest: dict = None, cell: dict = None, cfg: dict = None,
            t_start: float = None) -> dict:
    """Everything below the look for a chip: returns the result line's
    object.  ``manifest``, ``cell`` and ``cfg`` default to the files."""
    t_start = time.perf_counter() if t_start is None else t_start
    manifest = manifest or common.load_manifest()
    cell = cell or common.load_json("workloads", cell_name + ".json")
    cfg = cfg or common.load_json("configs", cell["config"] + ".json")
    try:
        flops_module = common.load_module("flops", cfg["family"])
    except FileNotFoundError:
        flops_module = None
    job = common.load_module("traffic", cell["kind"]).Job(
        cell, cfg, seed, devices)
    say(f"imports and files took {time.perf_counter() - t_start:.1f} s")
    job.setup()
    # tracing and compiling leave the host much to collect; do it now, in
    # set-up, and not at some step of the window
    gc.collect()

    result = {"attempted": 0, "failed": 0, "metrics": {}}
    if not trace:
        run = job.window(seconds, t_start)
        values = dict(run["metrics"])
        wanted = cell_metrics(manifest, cell_name, "end_to_end")
    else:
        import jax
        trace_dir = os.path.join(common.OUT, "trace", cell_name)
        shutil.rmtree(trace_dir, ignore_errors=True)
        before = counters()
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        jax.profiler.start_trace(trace_dir, profiler_options=options)
        try:
            run = job.traced_stretch()
        finally:
            jax.profiler.stop_trace()
        after = counters()
        values, wanted = {}, cell_metrics(manifest, cell_name, "per_layer")
    result["attempted"], result["failed"] = run["attempted"], run["failed"]
    peak_bytes = common.memory_peak_bytes(devices)

    # the comparison with the reference: after the window, after the peak
    # was read, with the program's state freed
    t_check = time.perf_counter()
    correct, compared = job.check()
    say(f"check took {time.perf_counter() - t_check:.1f} s")
    if run["failed"]:
        correct = False
    compared["failed_steps"] = {"value": run["failed"], "limit": 0}

    device = common.device_line(devices, peak_bytes)
    if trace:
        from benchmark import trace_reduce
        path = trace_reduce.newest_xplane(trace_dir)
        events = trace_reduce.load_events(path)
        reduced = trace_reduce.reduce(events)
        device["busy_s"] = reduced["busy_ns_mean"] / 1e9
        device["window_s"] = reduced["window_ns"] / 1e9
        result["breakdown"] = {"device_ops": reduced["device_ops"],
                               "idle_gaps": reduced["idle_gaps"]}
        ctx = {"trace": reduced, "stretch": run, "cell": cell, "cfg": cfg,
               "chips": len(devices), "flops_module": flops_module,
               "flops_per_step": (job.flops_per_step(flops_module)
                                  if flops_module is not None else None),
               "peaks": common.peaks_for(devices[0].device_kind),
               "counters_before": before, "counters_after": after}
        for m in wanted:
            value = common.load_module("metrics", m["name"]).read(ctx)
            if value is not None:
                values[m["name"]] = value
        if not os.environ.get("BENCHMARK_KEEP_TRACE"):
            shutil.rmtree(trace_dir, ignore_errors=True)

    for m in wanted:
        if m["name"] in values:
            result["metrics"][m["name"]] = {"value": values[m["name"]],
                                            "unit": m["unit"]}
        elif not trace:
            raise RuntimeError(f"the run gave no '{m['name']}'")
    result = {"correct": bool(correct), **result, "device": device,
              "compared": compared}
    say(f"correct: {bool(correct)}; each number compared, beside its limit:")
    for name, c in compared.items():
        say(f"compared {name}: {c['value']!r} limit {c['limit']!r}")
    return result


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--seconds", type=float, required=True)
    parser.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = parser.parse_args(argv)

    manifest = common.load_manifest()
    cells = {w["name"]: w for w in manifest["workloads"]}
    if args.workload not in cells:
        say(f"benchmark: no cell '{args.workload}' in BENCHMARK.json")
        return 2
    common.keep_writes_inside()
    devices = accelerators(cells[args.workload]["chips"])
    if devices is None:
        say("benchmark: JAX found no TPU, or fewer chips than the cell asks "
            "for; there is no fallback")
        return 3
    try:
        import deeplearning4j_tpu  # noqa: F401
    except ImportError as e:
        say(f"benchmark: the program is not beside the benchmark: {e}")
        return 2
    result = execute(args.workload, args.seed, args.seconds, bool(args.trace),
                     devices, manifest=manifest, t_start=T_START)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
