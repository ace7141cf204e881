"""The arithmetic of ``trace_reduce`` on made-up events, and its reader on
a small trace recorded on the chip (three steps of the test-sized LM cell,
cut by ``benchmark/tools/cut_trace.py``)."""
import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, REPO)

from benchmark import common, trace_reduce as tr  # noqa: E402

DEV, HOST = "/device:TPU:0", "/host:CPU"


def ev(name, start, dur, plane=DEV, line="XLA Ops"):
    return tr.Event(plane, line, name, float(start), float(dur))


@pytest.mark.parametrize("intervals,total", [
    ([], 0), ([(0, 10)], 10), ([(0, 10), (5, 12)], 12),
    ([(0, 10), (20, 25)], 15), ([(3, 4), (0, 10)], 10),
    ([(0, 1), (1, 2), (2, 3)], 3)])
def test_busy_union(intervals, total):
    assert tr.busy_union(intervals) == total


def test_idle_gaps_longest_first_and_clipped():
    gaps = tr.idle_gaps([(-5, 2), (4, 5), (9, 30)], 0, 20)
    assert gaps == [(5, 4), (2, 2)]
    assert tr.idle_gaps([], 0, 7) == [(0, 7)]


def test_self_time_leaves_out_what_is_nested():
    ops = [ev("while", 0, 100), ev("fusion.1", 10, 20), ev("fusion.2", 40, 30),
           ev("fusion.1", 70, 10), ev("after", 100, 5)]
    selfs = tr.self_times(ops)
    assert selfs == {"while": 40, "fusion.1": 30, "fusion.2": 30, "after": 5}


def test_kernel_events_are_calls_not_consumers():
    ops = [ev("flash_fwd.6", 0, 1), ev("flash_fwd", 2, 1),
           ev("flash_fwd_consumer_fusion", 4, 1), ev("flash_bwd_dq.1", 6, 1)]
    assert [e.start for e in tr.kernel_events(ops, "flash_fwd")] == [0, 2]
    assert tr.sums_by_name(ops, "flash_fwd.")["flash_fwd.6"] == (1, 1)


def test_short_name_keeps_the_operations_own_name():
    long = ('%flash_fwd.6 = (bf16[48,1024,64]{2,1,0}) custom-call(bf16[48] '
            '%bitcast.2787), custom_call_target="tpu_custom_call"')
    assert tr.short_name(long) == "flash_fwd.6"
    assert tr.short_name("bench.fit") == "bench.fit"


def test_span_open_at_is_the_innermost():
    spans = [ev("bench.fit", 0, 100, HOST, "main"),
             ev("bench.next", 10, 5, HOST, "main")]
    assert tr.span_open_at(spans, 12) == "bench.next"
    assert tr.span_open_at(spans, 50) == "bench.fit"
    assert tr.span_open_at(spans, 200) is None


def test_the_operations_line_is_found_by_name_or_by_being_busiest():
    events = [ev("m", 0, 100, line="XLA Modules"), ev("a", 0, 10),
              ev("b", 0, 50, line="Async XLA Ops")]
    assert tr.ops_line(events, DEV) == "XLA Ops"
    events = [ev("m", 0, 100, line="XLA Modules"),
              ev("a", 0, 10, line="ops"), ev("b", 0, 5, line="other")]
    assert tr.ops_line(events, DEV) == "ops"


def test_reduce_on_made_up_events():
    events = [
        ev("bench.fit", 100, 1000, HOST, "main"),
        ev("bench.next", 150, 20, HOST, "main"),
        ev("before", 0, 150),            # clipped to the stretch
        ev("fusion.1", 300, 200), ev("fusion.2", 500, 100),
        ev("late", 1050, 500),           # clipped too
        ev("module", 0, 2000, line="XLA Modules")]
    r = tr.reduce(events)
    assert r["window_ns"] == 1000
    assert r["busy_ns_busiest"] == r["busy_ns_mean"] == 50 + 300 + 50
    assert r["idle_gaps"][0] == ["bench.fit", 450 / 1e9]
    assert r["idle_gaps"][1] == ["bench.next", 150 / 1e9]
    assert r["device_ops"][0] == ["late", 500 / 1e9]
    with pytest.raises(ValueError):
        tr.reduce([e for e in events if e.name != "bench.fit"])
    with pytest.raises(ValueError):
        tr.reduce([e for e in events if e.plane != DEV])


@pytest.fixture(scope="module")
def recorded():
    return tr.reduce(tr.load_events(os.path.join(HERE, "data",
                                                 "small.xplane.pb")))


def test_reader_on_the_recorded_trace(recorded):
    # what run.py printed on the chip from the uncut trace (my chip run,
    # PR 25): three steps of 3 x 256 tokens through 4 layers
    assert recorded["window_ns"] == 15425920.0
    assert recorded["busy_ns_busiest"] == 743546.0
    assert recorded["device_ops"][:3] == [["flash_fwd.6", 9.1869e-05],
                                          ["flash_bwd_dkv.10", 5.1148e-05],
                                          ["flash_bwd_dq.10", 3.9871e-05]]
    assert recorded["idle_gaps"][0] == ["bench.fit", 0.005395336]
    assert len(recorded["device_ops"]) == 10 and \
        len(recorded["idle_gaps"]) == 5


@pytest.mark.parametrize("kernel,calls", [("flash_fwd", 12),
                                          ("flash_bwd_dq", 12),
                                          ("flash_bwd_dkv", 12)])
def test_kernels_of_the_recorded_trace(recorded, kernel, calls):
    # 3 steps x 4 layers
    assert len(tr.kernel_events(recorded["ops"], kernel)) == calls


@pytest.mark.parametrize("metric,value", [
    ("device_idle_pct.train", 95.17989202588889),
    ("flash_roofline", 26.777019839440555),
    ("step_mfu_pct.train", 0.447185397862094),
    ("window_compiles.train", 0.0)])
def test_metric_readers_on_the_recorded_trace(recorded, metric, value):
    with open(os.path.join(HERE, "data", "tiny-gpt2.json")) as f:
        cfg = json.load(f)
    with open(os.path.join(HERE, "data", "tiny-gpt2.train-fit.json")) as f:
        cell = json.load(f)
    flops = common.load_module("flops", cfg["family"])
    ctx = {"trace": recorded, "stretch": {"steps": 3}, "cell": cell,
           "cfg": cfg, "chips": 1, "flops_module": flops,
           "flops_per_step": flops.train_step_flops(cfg, cell["rows"]),
           "peaks": common.peaks_for("TPU v5 lite"),
           "counters_before": {"training_compile_total": 4.0},
           "counters_after": {"training_compile_total": 4.0}}
    got = common.load_module("metrics", metric).read(ctx)
    assert got == pytest.approx(value, rel=1e-9)


@pytest.mark.parametrize("metric", ["device_idle_pct.train",
                                    "flash_roofline",
                                    "step_mfu_pct.train",
                                    "window_compiles.train"])
def test_a_reader_with_nothing_to_read_returns_nothing(metric):
    ctx = {"trace": None, "stretch": {"steps": 0}, "cell": {}, "cfg": {},
           "chips": 1, "flops_module": None, "flops_per_step": None,
           "peaks": {}, "counters_before": None, "counters_after": None}
    assert common.load_module("metrics", metric).read(ctx) is None
