"""``benchmark/metrics/remat_device_pct.train``: which scopes count as a
forward pass replayed in the backward, the arithmetic on made-up
operations, nothing to read, and the small trace recorded on the chip
(three steps of the test-sized LM cell under a plain scan: a program that
replays nothing, so the reader has nothing to report there)."""
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, REPO)

from benchmark import common  # noqa: E402
from benchmark import trace_reduce as tr  # noqa: E402

NAME = "remat_device_pct.train"
SPANS = os.path.join(HERE, "data", "small-spans.xplane.pb")
PLAIN = os.path.join(HERE, "data", "small.xplane.pb")
FWD = "jit(train_step)/jvp(forward)"
BWD = "jit(train_step)/transpose(jvp(forward))"
REMAT = BWD + "/while/body/closed_call/checkpoint/rematted_computation"


@pytest.fixture(scope="module")
def reader():
    return common.load_module("metrics", NAME)


def empty_ctx():
    return {"trace": None, "stretch": {"steps": 0}, "cell": {}, "cfg": {},
            "chips": 1, "flops_module": None, "flops_per_step": None,
            "peaks": {}, "counters_before": None, "counters_after": None}


def op(scope, start, dur):
    return tr.Event("/device:TPU:0", "XLA Ops", scope, float(start),
                    float(dur))


@pytest.mark.parametrize("scope,row", [
    (REMAT + "/TransformerBlock/dot_general", "TransformerBlock/dot_general"),
    (REMAT + "/TransformerBlock/eva_window/flash_fwd/pallas_call",
     "TransformerBlock/eva_window/flash_fwd/pallas_call"),
    # the unrolled walk's per-layer checkpoint: no while body around it
    (BWD + "/DenseLayer/checkpoint/rematted_computation/tanh", "tanh"),
    (REMAT, "rematted_computation"),
    # the backward's own work, the forward, the scan's copies, the update
    (BWD + "/while/body/closed_call/checkpoint/TransformerBlock/mul", None),
    (FWD + "/while/body/closed_call/TransformerBlock/dot_general", None),
    (BWD + "/while/body/dynamic_slice", None),
    ("jit(train_step)/optimizer/add", None),
    # a part of a name is not the name
    (BWD + "/not_rematted_computation_at_all/mul", None),
    ("", None)])
def test_which_scopes_are_a_forward_replayed(reader, scope, row):
    rows, busy = reader.replayed([op(scope, 0, 10)])
    assert busy == 10.0
    assert rows == ({row: 10.0} if row else {})


def test_replayed_work_is_counted_by_self_time_and_by_row(reader):
    ops = [op(BWD + "/while", 0, 100),            # 100 - 30 - 50 of its own
           op(REMAT + "/TransformerBlock/dot_general", 10, 30),
           op(BWD + "/while/body/closed_call/checkpoint/TransformerBlock/"
              "dot_general", 40, 50),
           op(REMAT + "/TransformerBlock/dot_general", 200, 20),
           op(REMAT + "/TransformerBlock/reduce_sum", 230, 5),
           op("jit(train_step)/optimizer/add", 300, 45)]
    rows, busy = reader.replayed(ops)
    assert busy == 170.0
    assert rows == {"TransformerBlock/dot_general": 50.0,
                    "TransformerBlock/reduce_sum": 5.0}


def test_nothing_to_read_returns_nothing(reader):
    assert reader.read(empty_ctx()) is None
    # the trace PR 25 recorded carries no scope
    ctx = dict(empty_ctx(), trace={"window_ns": 1.0}, xplane=PLAIN)
    assert reader.read(ctx) is None


def test_a_program_that_replays_nothing_reads_nothing(reader):
    """The recorded steps ran a plain scan, which saves every intermediate
    and replays none: busy time is there, no row is, and the result line
    leaves the metric out."""
    rows, busy = reader.stretch_replayed(SPANS)
    assert busy == 742576.0        # program_spans' device_self_ns there
    assert rows == {}
    ctx = dict(empty_ctx(), trace={"window_ns": 1.0}, xplane=SPANS)
    assert reader.read(ctx) is None


def test_the_metric_is_in_the_manifest_with_its_cells():
    manifest = common.load_manifest()
    entry = [m for m in manifest["per_layer"] if m["name"] == NAME]
    assert entry == [{
        "name": NAME, "unit": "%", "better": "lower",
        "source": "device_trace", "layer": "Step program",
        "moves": "train_step_ms",
        "workloads": ["gpt2-medium.train-fit", "evabyte-4l.train-fit-long"]}]
