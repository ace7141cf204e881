"""``benchmark/metrics/head_device_pct.train`` and
``xla_remat_device_pct.train``: which layers count as the head and which
names as XLA's own rematerialization, the arithmetic on made-up operations,
nothing to read, the small traces recorded on the chip (test-sized LM,
Trinity and JoyAI cells: a head in each, and no operation the compiler had
to repeat at that size), and the manifest's entries."""
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, REPO)

from benchmark import common  # noqa: E402
from benchmark import program_spans as ps  # noqa: E402
from benchmark import trace_reduce as tr  # noqa: E402

HEAD = "head_device_pct.train"
REMAT = "xla_remat_device_pct.train"
DATA = os.path.join(HERE, "data")
SPANS = os.path.join(DATA, "small-spans.xplane.pb")
PLAIN = os.path.join(DATA, "small.xplane.pb")
LM_CELLS = ["gpt2-medium.train-fit", "evabyte-4l.train-fit-long",
            "trinity-mini-5l.train-fit-8k", "joyai-llm-flash-5l.train-fit-8k"]


def reader(name):
    return common.load_module("metrics", name)


def empty_ctx():
    return {"trace": None, "stretch": {"steps": 0}, "cell": {}, "cfg": {},
            "chips": 1, "flops_module": None, "flops_per_step": None,
            "peaks": {}, "counters_before": None, "counters_after": None}


def op(name, start, dur):
    return tr.Event("/device:TPU:0", "XLA Ops", name, float(start),
                    float(dur))


@pytest.mark.parametrize("name,counted", [
    ("fusion.416.remat", True), ("fusion.1652.remat2", True),
    ("copy.696.remat", True), ("fusion.447.remat.1", True),
    ("fusion.416", False), ("rematted", False), ("while.3", False)])
def test_which_names_are_xlas_own_rematerialization(name, counted):
    rows, busy = reader(REMAT).repeated([op(name, 0, 10)])
    assert busy == 10.0
    assert rows == ({name: 10.0} if counted else {})


def test_repeated_work_is_counted_by_self_time():
    ops = [op("while.2", 0, 100),               # 100 - 30 - 50 of its own
           op("fusion.7.remat", 10, 30), op("fusion.7", 40, 50),
           op("fusion.7.remat", 200, 20), op("fusion.9.remat2", 230, 5),
           op("fusion.11", 300, 45)]
    rows, busy = reader(REMAT).repeated(ops)
    assert busy == 170.0
    assert rows == {"fusion.7.remat": 50.0, "fusion.9.remat2": 5.0}


@pytest.mark.parametrize("metric", [HEAD, REMAT])
def test_nothing_to_read_returns_nothing(metric):
    assert reader(metric).read(empty_ctx()) is None


def test_a_trace_without_scopes_has_no_head_to_read():
    # the trace PR 25 recorded carries no scope
    ctx = dict(empty_ctx(), trace={"window_ns": 1.0}, xplane=PLAIN)
    assert reader(HEAD).read(ctx) is None


@pytest.mark.parametrize("trace,layers,head_ns", [
    ("small-spans", 4, 52658.0), ("small-trinity", 4, None),
    ("small-joyai", 6, None)])
def test_the_head_of_the_recorded_traces(trace, layers, head_ns):
    """Every recorded step program has one ``RnnOutputLayer``; its share is
    its row of ``program_spans``' table of layers over the busy time."""
    path = os.path.join(DATA, trace + ".xplane.pb")
    t = ps.tables_of(path)
    assert len(t["device_by_layer"]) == layers
    if head_ns is not None:
        assert t["device_by_layer"]["RnnOutputLayer"] == head_ns
    ctx = dict(empty_ctx(), trace={"window_ns": 1.0}, xplane=path)
    got = reader(HEAD).read(ctx)
    assert got == pytest.approx(
        100.0 * t["device_by_layer"]["RnnOutputLayer"] / t["device_self_ns"])
    assert 0.0 < got < 15.0


@pytest.mark.parametrize("trace,busy", [
    ("small-spans", 742576.0), ("small-trinity", 2238426.0),
    ("small-joyai", 1884888.0)])
def test_the_recorded_steps_repeat_nothing(trace, busy):
    """At the test's sizes the compiler had room for everything: the busy
    time is ``program_spans``' own, no name holds ``.remat``, and the
    metric reads 0 (a number, so the ledger gets the cell's before)."""
    path = os.path.join(DATA, trace + ".xplane.pb")
    rows, got_busy = reader(REMAT).stretch_repeated(path)
    assert got_busy == busy == ps.tables_of(path)["device_self_ns"]
    assert rows == {}
    ctx = dict(empty_ctx(), trace={"window_ns": 1.0}, xplane=path)
    assert reader(REMAT).read(ctx) == 0.0


@pytest.mark.parametrize("metric,cells", [
    (HEAD, LM_CELLS), (REMAT, LM_CELLS[1:])])
def test_the_metrics_are_in_the_manifest_with_their_cells(metric, cells):
    manifest = common.load_manifest()
    entry = [m for m in manifest["per_layer"] if m["name"] == metric]
    assert entry == [{
        "name": metric, "unit": "%", "better": "lower",
        "source": "device_trace", "layer": "Step program",
        "moves": "train_step_ms", "workloads": cells}]
