"""``benchmark/metrics/scan_saved_device_pct.train``: which scopes count as
a scan moving what it saves, the arithmetic on made-up operations, nothing
to read, and the reading on the small trace recorded on the chip (three
steps of the test-sized LM cell, whose six blocks run under a scan that
stacks every intermediate: the program before PR 30)."""
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, REPO)

from benchmark import common  # noqa: E402
from benchmark import trace_reduce as tr  # noqa: E402

NAME = "scan_saved_device_pct.train"
SPANS = os.path.join(HERE, "data", "small-spans.xplane.pb")
PLAIN = os.path.join(HERE, "data", "small.xplane.pb")
FWD = "jit(train_step)/jvp(forward)"
BWD = "jit(train_step)/transpose(jvp(forward))"


@pytest.fixture(scope="module")
def reader():
    return common.load_module("metrics", NAME)


def empty_ctx():
    return {"trace": None, "stretch": {"steps": 0}, "cell": {}, "cfg": {},
            "chips": 1, "flops_module": None, "flops_per_step": None,
            "peaks": {}, "counters_before": None, "counters_after": None}


@pytest.mark.parametrize("scope,row", [
    (FWD + "/while/body/dynamic_update_slice",
     ("forward", "dynamic_update_slice")),
    (BWD + "/while/body/dynamic_slice", ("backward", "dynamic_slice")),
    (BWD + "/while/body/squeeze", ("backward", "squeeze")),
    # under jax.checkpoint the body is a closed call: still the scan's
    (BWD + "/while/body/closed_call/checkpoint/dynamic_slice",
     ("backward", "closed_call/checkpoint/dynamic_slice")),
    # what the layer computes, recomputed or not, keeps its class
    (FWD + "/while/body/closed_call/TransformerBlock/dot_general", None),
    (BWD + "/while/body/closed_call/checkpoint/TransformerBlock/mul", None),
    # the loop itself, and what lies outside any body
    (FWD + "/while", None),
    (BWD + "/broadcast_in_dim", None),
    (FWD + "/RnnOutputLayer/dot_general", None),
    # a loop of the optimizer is no scan over layers
    ("jit(train_step)/optimizer/while/body/add", None),
    ("", None)])
def test_which_scopes_are_a_scan_moving_what_it_saves(reader, scope, row):
    assert reader.copy_row(scope) == row


def test_copies_are_counted_by_self_time_and_by_row(reader):
    def op(scope, start, dur):
        return tr.Event("/device:TPU:0", "XLA Ops", scope, float(start),
                        float(dur))
    ops = [op(FWD + "/while", 0, 100),            # 100 - 30 - 50 of its own
           op(FWD + "/while/body/dynamic_update_slice", 10, 30),
           op(FWD + "/while/body/closed_call/TransformerBlock/add", 40, 50),
           op(BWD + "/while/body/closed_call/checkpoint/dynamic_slice",
              200, 20),
           op(BWD + "/while/body/dynamic_slice", 230, 5),
           op("jit(train_step)/optimizer/add", 300, 45)]
    rows, busy = reader.scan_copies(ops)
    assert busy == 170.0
    assert rows == {("forward", "dynamic_update_slice"): 30.0,
                    ("backward", "closed_call/checkpoint/dynamic_slice"): 20.0,
                    ("backward", "dynamic_slice"): 5.0}


def test_nothing_to_read_returns_nothing(reader):
    assert reader.read(empty_ctx()) is None
    # the trace PR 25 recorded carries no scope
    ctx = dict(empty_ctx(), trace={"window_ns": 1.0}, xplane=PLAIN)
    assert reader.read(ctx) is None


def test_on_the_recorded_trace(reader):
    ctx = dict(empty_ctx(), trace={"window_ns": 1.0}, xplane=SPANS)
    rows, busy = reader.stretch_copies(SPANS)
    assert busy == 742576.0        # program_spans' device_self_ns there
    assert rows[("forward", "dynamic_update_slice")] == 17348.0
    assert rows[("backward", "dynamic_slice")] == 38231.0
    assert reader.read(ctx) == pytest.approx(100.0 * 79164.0 / 742576.0,
                                             rel=1e-12)


def test_the_metric_is_in_the_manifest_with_its_cells():
    manifest = common.load_manifest()
    entry = [m for m in manifest["per_layer"] if m["name"] == NAME]
    assert entry == [manifest["per_layer"][-1]]
    assert entry[0] == {
        "name": NAME, "unit": "%", "better": "lower",
        "source": "device_trace", "layer": "Step program",
        "moves": "train_step_ms",
        "workloads": ["gpt2-medium.train-fit", "evabyte-4l.train-fit-long"]}
