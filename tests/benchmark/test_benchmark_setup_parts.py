"""The eight ``*.setup`` metrics (``benchmark/metrics/*.setup.py`` over
``benchmark/setup_parts.py``): each gives the number it reads on a made
context, nothing where the program has no such counter or gauge, and is
in the manifest under its name with the five cells and ``setup_s``."""
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, REPO)

from benchmark import common  # noqa: E402
from benchmark import setup_parts  # noqa: E402
from deeplearning4j_tpu.observability.registry import (  # noqa: E402
    MetricsRegistry, set_default_registry)

COUNTERS = {"init_s.setup": "model_init_seconds_total",
            "jit_trace_s.setup": "jit_trace_seconds_total",
            "jit_lower_s.setup": "jit_lower_seconds_total",
            "cache_load_s.setup": "jit_cache_load_seconds_total",
            "compile_s.setup": "jit_compile_seconds_total",
            "programs_compiled.setup": "jit_programs_compiled_total"}
GAUGES = {"pre_import_s.setup": "process_age_at_import_seconds",
          "import_s.setup": "package_import_seconds"}
LAYERS = {"pre_import_s.setup": "Start-up", "import_s.setup": "Start-up",
          "init_s.setup": "Start-up"}
CELLS = ["gpt2-medium.train-fit", "resnet50-224.train-ondevice",
         "evabyte-4l.train-fit-long", "trinity-mini-5l.train-fit-8k",
         "joyai-llm-flash-5l.train-fit-8k"]


def ctx_with(before):
    return {"trace": None, "stretch": {"steps": 20}, "cell": {}, "cfg": {},
            "chips": 1, "flops_module": None, "flops_per_step": None,
            "peaks": {}, "counters_before": before, "counters_after": before}


@pytest.fixture
def registry():
    reg = MetricsRegistry()
    prev = set_default_registry(reg)
    yield reg
    set_default_registry(prev)


@pytest.fixture
def quiet(monkeypatch):
    """The table is said once a process: keep the tests from spending it."""
    monkeypatch.setattr(setup_parts, "_said", True)


@pytest.mark.parametrize("name", sorted(COUNTERS))
def test_a_counters_reader_gives_what_stood_when_setup_ended(name, quiet):
    reader = common.load_module("metrics", name)
    before = {c: float(i + 1) for i, c in enumerate(sorted(COUNTERS.values()))}
    assert reader.read(ctx_with(before)) == before[COUNTERS[name]]
    # a warm run: the counter is there and reads nought
    assert reader.read(ctx_with(dict(before, **{COUNTERS[name]: 0.0}))) == 0.0


@pytest.mark.parametrize("name", sorted(COUNTERS))
def test_a_counters_reader_gives_nothing_where_there_is_nothing(name, quiet):
    reader = common.load_module("metrics", name)
    assert reader.read(ctx_with(None)) is None
    others = {c: 1.0 for c in COUNTERS.values() if c != COUNTERS[name]}
    assert reader.read(ctx_with(others)) is None       # the parent's program
    assert reader.read(ctx_with({})) is None


@pytest.mark.parametrize("name", sorted(GAUGES))
def test_a_gauges_reader_reads_the_registry(name, registry):
    reader = common.load_module("metrics", name)
    # nothing set, as in the parent's program, whatever the counters say
    assert reader.read(ctx_with(None)) is None
    assert reader.read(ctx_with({"jit_trace_seconds_total": 1.0})) is None
    registry.gauge(GAUGES[name], "made").set(3.25)
    assert reader.read(ctx_with(None)) == 3.25


def test_the_table_is_said_once_and_the_parts_are_summed(
        registry, monkeypatch, capsys):
    monkeypatch.setattr(setup_parts, "_said", False)
    registry.gauge("process_age_at_import_seconds").set(4.0)
    registry.gauge("package_import_seconds").set(1.5)
    registry.counter("model_init_seconds_total").inc(2.0)
    seconds = {"jit_trace_seconds_total": {"train_step": 4.5, "init": 0.25,
                                           "eager": 1.0},
               "jit_lower_seconds_total": {"train_step": 1.25},
               "jit_cache_load_seconds_total": {"train_step": 2.5,
                                                "init": 0.5},
               "jit_compile_seconds_total": {},
               "jit_programs_loaded_total": {"train_step": 1, "init": 160},
               "jit_programs_compiled_total": {}}
    for name, by_fn in seconds.items():
        counter = registry.counter(name, "made", ("fn",))
        for fn, v in by_fn.items():
            counter.labels(fn).inc(v)
    # set-up ended before 0.5 s of eager's tracing (the check's)
    before = {name: float(sum(by_fn.values()))
              for name, by_fn in seconds.items()}
    before["jit_trace_seconds_total"] -= 0.5
    reader = common.load_module("metrics", "jit_trace_s.setup")
    assert reader.read(ctx_with(before)) == 5.25
    assert reader.read(ctx_with(before)) == 5.25
    said = capsys.readouterr().err
    assert said.count("set-up by parts") == 1
    assert "train_step" in said and "trace_s 4.500" in said
    assert "programs_loaded 160" in said
    assert "trace_s 0.500  lower_s 0.000" in said
    # 4 + 1.5 + 2 + (5.25 - 0.25) + 1.25 + (3.0 - 0.5) + 0
    assert "sum to 16.250 s" in said


def test_no_table_without_the_programs_report(registry, monkeypatch, capsys):
    from deeplearning4j_tpu import observability
    monkeypatch.setattr(setup_parts, "_said", False)
    monkeypatch.delattr(observability, "startup_report")
    setup_parts.say_table(ctx_with({}))
    assert capsys.readouterr().err == ""
    assert setup_parts._said is False


@pytest.mark.parametrize("name", sorted({**COUNTERS, **GAUGES}))
def test_the_metric_is_in_the_manifest_by_its_name(name):
    entries = [m for m in common.load_manifest()["per_layer"]
               if m["name"] == name]
    assert len(entries) == 1
    entry = entries[0]
    assert set(CELLS) <= set(entry["workloads"])
    assert entry["moves"] == "setup_s"
    assert entry["source"] == "program_counter"
    assert entry["better"] == "lower"
    assert entry["layer"] == LAYERS.get(name, "Dispatch")
    assert entry["unit"] == ("count" if name.startswith("programs") else "s")
    assert os.path.isfile(os.path.join(REPO, "benchmark", "metrics",
                                       name + ".py"))


def test_every_cell_of_the_manifest_reports_them():
    manifest = common.load_manifest()
    assert set(CELLS) <= {w["name"] for w in manifest["workloads"]}
    assert "setup_s" in [m["name"] for m in manifest["end_to_end"]]
