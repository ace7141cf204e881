"""``benchmark/program_spans``: the gap-splitting and scope arithmetic on
made-up events, the hand-written reader of the trace's file (an operation
joined to its scope by its plane's metadata id), the loud failure where
the executable lacks the program's scopes, each new per-layer reader with
nothing to read, and the whole on a small trace
recorded on the chip with the program's spans in it (three steps of the
test-sized LM cell, cut by ``benchmark/tools/cut_trace_spans.py``)."""
import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, REPO)

from benchmark import common, program_spans as ps  # noqa: E402
from benchmark import trace_reduce as tr  # noqa: E402

DEV, HOST = "/device:TPU:0", "/host:CPU"
SPANS = os.path.join(HERE, "data", "small-spans.xplane.pb")
PLAIN = os.path.join(HERE, "data", "small.xplane.pb")

NEW_READERS = ["idle_entry_pct.train", "idle_dispatch_pct.train",
               "call_train_step_ms.train", "idle_fence_pct.train",
               "profiler_fences.train", "gc_pause_ms.train",
               "backward_device_pct.train", "optimizer_device_pct.train",
               "batchnorm_device_pct.train"]


def span(name, start, dur, line="main"):
    return tr.Event(HOST, line, name, float(start), float(dur))


def op(scope, start, dur):
    return tr.Event(DEV, "XLA Ops", scope, float(start), float(dur))


# ------------------------------------------------------------ gap splitting
def test_segments_give_each_instant_to_the_innermost_span():
    spans = [span("dl4j.fit", 0, 100), span("dl4j.call.train_step", 10, 30),
             span("dl4j.gc", 20, 5), span("dl4j.window_wait", 40, 50)]
    assert ps.innermost_segments(spans, 0, 100) == [
        (0, 10, "dl4j.fit"), (10, 20, "dl4j.call.train_step"),
        (20, 25, "dl4j.gc"), (25, 40, "dl4j.call.train_step"),
        (40, 90, "dl4j.window_wait"), (90, 100, "dl4j.fit")]


def test_segments_are_clipped_and_leave_the_uncovered_out():
    spans = [span("dl4j.h2d", -5, 10), span("dl4j.sync", 50, 100)]
    assert ps.innermost_segments(spans, 0, 60) == [
        (0, 5, "dl4j.h2d"), (50, 60, "dl4j.sync")]
    assert ps.innermost_segments([], 0, 60) == []


def test_of_two_spans_on_two_threads_the_later_start_is_innermost():
    spans = [span("dl4j.fit", 0, 50), span("dl4j.gc", 30, 40, line="other")]
    assert ps.innermost_segments(spans, 0, 100) == [
        (0, 30, "dl4j.fit"), (30, 70, "dl4j.gc")]


@pytest.mark.parametrize("gaps,by_name,uncovered", [
    # covered by two spans, one after the other
    ([(5, 10)], {"a": 5, "b": 5}, 0),
    # by nested spans: the inner takes its part, the outer the rest
    ([(18, 10)], {"b": 2, "c": 4, "d": 4}, 0),
    # by none
    ([(40, 7)], {}, 7),
    # half covered, and two gaps at once
    ([(28, 4), (40, 7)], {"d": 2}, 9),
    ([], {}, 0)])
def test_a_gap_is_split_among_the_spans_that_cover_it(gaps, by_name,
                                                      uncovered):
    segments = [(0, 10, "a"), (10, 20, "b"), (20, 24, "c"), (24, 30, "d")]
    assert ps.split_gaps(gaps, segments) == (by_name, uncovered)


@pytest.mark.parametrize("name,group", [
    ("dl4j.fit", "entry"), ("dl4j.fit_on_device", "entry"),
    ("dl4j.input_wait", "entry"), ("dl4j.sync", "entry"),
    ("dl4j.call.train_step", "dispatch"),
    ("dl4j.call.epoch_scan", "dispatch"), ("dl4j.h2d", "dispatch"),
    ("dl4j.profiler_fence", "fence"), ("dl4j.window_wait", "window_wait"),
    ("dl4j.gc", "gc"), ("dl4j.something_new", "other")])
def test_each_span_has_its_layer(name, group):
    assert ps.group_of(name) == group


# ------------------------------------------------------------------- scopes
@pytest.mark.parametrize("scope,phase,layer", [
    ("jit(train_step)/jvp(forward)/TransformerBlock/dot_general",
     "forward", "TransformerBlock"),
    ("jit(train_step)/transpose(jvp(forward))/while/body/closed_call/"
     "TransformerBlock/dot_general", "backward", "TransformerBlock"),
    ("jit(epoch_scan)/while/body/closed_call/jit(train_step)/jvp(forward)/"
     "BatchNormalization/reduce_sum", "forward", "BatchNormalization"),
    ("jit(train_step)/forward/OutputLayer/jit(log_softmax)/reduce_max",
     "forward", "OutputLayer"),
    ("jit(train_step)/optimizer/mul", "optimizer", None),
    ("jit(train_step)/grad_post/sqrt", "grad_post", None),
    ("jit(train_step)/while", "outside", None),
    ("jit(_shuffle)/sort", "outside", None),
    ("", "outside", None)])
def test_a_scope_names_its_phase_and_its_layer(scope, phase, layer):
    assert ps.phase_of(scope) == phase
    assert ps.layer_of(scope) == layer


def test_device_shares_are_self_times_by_scope():
    f, b = "jit(t)/jvp(forward)", "jit(t)/transpose(jvp(forward))"
    ops = [op(f + "/while", 0, 100),           # a loop: 30 of its own
           op(f + "/while/body/Dense/dot", 10, 40),
           op(f + "/while/body/BatchNormalization/reduce", 50, 30),
           op(b + "/BatchNormalization/mul", 100, 20),
           op(b + "/Dense/dot", 120, 60),
           op("jit(t)/optimizer/sub", 180, 10),
           op("jit(t)/grad_post/sqrt", 190, 5),
           op("", 195, 5)]
    by_phase, by_layer, busy = ps.device_shares(ops)
    assert by_phase == {"forward": 100, "backward": 80, "optimizer": 10,
                        "grad_post": 5, "outside": 5}
    assert by_layer == {"Dense": 100, "BatchNormalization": 50}
    assert busy == 200


def stretch():
    host = [span("bench.fit", 100, 1000), span("dl4j.fit", 110, 980),
            span("dl4j.input_wait", 120, 10),
            span("dl4j.call.train_step", 150, 200),
            span("dl4j.gc", 200, 20), span("dl4j.window_wait", 400, 500),
            span("dl4j.profiler_fence", 900, 100),
            span("dl4j.window_wait", 910, 40),
            span("dl4j.sync", 1050, 20),
            span("dl4j.call.train_step", 2000, 10)]     # after the stretch
    f = "jit(train_step)/jvp(forward)/DenseLayer/dot_general"
    b = "jit(train_step)/transpose(jvp(forward))/DenseLayer/dot_general"
    device = [op("", 0, 150),                  # clipped to the stretch
              op(f, 300, 200), op(b, 500, 400),
              op("jit(train_step)/optimizer/sub", 960, 100),
              tr.Event(DEV, "XLA Modules", "", 0.0, 5000.0)]
    return host, device


def test_the_tables_of_a_made_up_stretch():
    t = ps.summarize(*stretch(), True)
    assert t["window_ns"] == 1000
    # busy: 50 + 200 + 400 + 100
    assert t["idle_ns"] == 250
    assert t["idle_by_span"] == {
        "dl4j.call.train_step": 130, "dl4j.gc": 20,
        "dl4j.profiler_fence": 20, "dl4j.window_wait": 40,
        "dl4j.sync": 10, "dl4j.fit": 20}
    assert t["idle_by_group"] == {
        "entry": 30, "dispatch": 130, "fence": 20, "window_wait": 40,
        "gc": 20, "other": 0, "uncovered": 10}
    # the parts add up to the idle share
    assert sum(t["idle_by_group"].values()) == t["idle_ns"]
    assert t["span_ns"]["dl4j.call.train_step"] == (1, 200)
    assert t["span_ns"]["dl4j.window_wait"] == (2, 540)
    assert t["device_by_phase"] == {"outside": 50, "forward": 200,
                                    "backward": 400, "optimizer": 100}
    assert t["device_by_layer"] == {"DenseLayer": 600}
    assert t["device_self_ns"] == 750
    assert t["longest_gaps"][0] == (150, {"dl4j.call.train_step": 130,
                                          "dl4j.gc": 20})


def test_a_stretch_without_spans_or_scopes_has_no_tables_for_them():
    host, device = stretch()
    t = ps.summarize([e for e in host if e.name == "bench.fit"], device,
                     False)
    assert t["idle_ns"] == 250
    assert t["idle_by_span"] is None and t["idle_by_group"] is None
    assert t["device_by_phase"] is None and t["device_by_layer"] is None
    assert ps.summarize([e for e in host if e.name != "bench.fit"], device,
                        True) is None
    assert ps.summarize(host, [], True) is None


# --------------------------------------------------------------- wire reader
def varint(n):
    out = bytearray()
    while True:
        out.append((n & 0x7F) | (0x80 if n > 0x7F else 0))
        n >>= 7
        if not n:
            return bytes(out)


def field(number, payload):
    if isinstance(payload, int):
        return varint(number << 3) + varint(payload)
    return varint(number << 3 | 2) + varint(len(payload)) + payload


def test_wire_fields_reads_varints_and_bytes_and_passes_fixed_width_over():
    message = (field(1, 300) + field(2, b"abc")
               + varint(3 << 3 | 1) + b"\x00" * 8
               + varint(4 << 3 | 5) + b"\x00" * 4 + field(5, 1))
    assert [(n, v if isinstance(v, int) else bytes(v))
            for n, v in ps.wire_fields(message)] == [
                (1, 300), (2, b"abc"), (5, 1)]
    with pytest.raises(ValueError):
        list(ps.wire_fields(varint(1 << 3 | 3)))


def stat(meta_id, text=None, ref=None):
    body = field(1, meta_id)
    return body + (field(5, text) if text is not None else field(7, ref))


def event_meta(key, name, stats=()):
    body = field(1, key) + field(2, name)
    for s in stats:
        body += field(5, s)
    return field(4, field(1, key) + field(2, body))


def stat_meta(key, name):
    return field(5, field(1, key) + field(2, field(1, key) + field(2, name)))


def line(name, timestamp_ns, events):
    body = field(2, name) + field(3, timestamp_ns)
    for metadata_id, offset_ps, duration_ps in events:
        body += field(4, field(1, metadata_id) + field(2, offset_ps)
                      + field(3, duration_ps))
    return field(3, body)


def made_up_space():
    """Two programs on one device, each with a ``fusion.1`` of its own
    (the compiler's names hold within one program), and a host plane whose
    metadata ids are the device's over again."""
    device = (field(2, DEV.encode())
              + event_meta(1, b"%fusion.1 = f32[] fusion()",
                           [stat(7, b"other"),
                            stat(9, b"jit(train_step)/forward/x:")])
              + event_meta(2, b"%copy.2 = f32[] copy()", [stat(7, b"y")])
              + event_meta(3, b"%fusion.3 = f32[] fusion()", [stat(9, ref=11)])
              + event_meta(4, b"%fusion.1 = f32[] fusion()",
                           [stat(9, b"jit(_shuffle)/sort:")])
              + stat_meta(7, b"hlo_category") + stat_meta(9, b"tf_op")
              + stat_meta(11, b"jit(train_step)/optimizer/mul:")
              + line(b"XLA Ops", 1000, [(1, 0, 5999), (4, 6000, 2000),
                                        (2, 9000, 1000), (3, 10500, 500)]))
    host = (field(2, HOST.encode())
            + event_meta(1, b"dl4j.fit", [stat(9, b"not a device")])
            + event_meta(2, b"bench.fit") + event_meta(3, b"PjitFunction")
            + stat_meta(9, b"tf_op")
            + line(b"python3", 990, [(2, 0, 30000), (1, 1000, 20000),
                                     (3, 2000, 1000)]))
    return field(1, device) + field(1, host)


def test_read_planes_joins_events_and_scopes_by_the_planes_own_ids():
    (dev, dev_lines, dev_meta), (host, host_lines, host_meta) = \
        ps.read_planes(made_up_space())
    assert (dev, host) == (DEV, HOST)
    assert dev_meta == {
        1: ("%fusion.1 = f32[] fusion()", "jit(train_step)/forward/x:"),
        2: ("%copy.2 = f32[] copy()", None),
        3: ("%fusion.3 = f32[] fusion()", "jit(train_step)/optimizer/mul:"),
        4: ("%fusion.1 = f32[] fusion()", "jit(_shuffle)/sort:")}
    assert dev_lines == [("XLA Ops", 1000, [
        (1, 0, 5999), (4, 6000, 2000), (2, 9000, 1000), (3, 10500, 500)])]
    # every plane is read alike; ``load`` takes no scope from a host's
    assert host_meta == {1: ("dl4j.fit", "not a device"),
                         2: ("bench.fit", None), 3: ("PjitFunction", None)}
    assert host_lines[0][:2] == ("python3", 990)


def test_load_gives_two_operations_of_one_name_each_its_own_scope(tmp_path):
    path = tmp_path / "made-up.xplane.pb"
    path.write_bytes(made_up_space())
    host, scoped, any_scope = ps.load(str(path))
    assert any_scope
    # whole nanoseconds, as ProfileData gives them
    assert scoped == [
        tr.Event(DEV, "XLA Ops", "jit(train_step)/forward/x", 1000.0, 5.0),
        tr.Event(DEV, "XLA Ops", "jit(_shuffle)/sort", 1006.0, 2.0),
        tr.Event(DEV, "XLA Ops", "", 1009.0, 1.0),
        tr.Event(DEV, "XLA Ops", "jit(train_step)/optimizer/mul", 1010.0,
                 0.0)]
    assert host == [tr.Event(HOST, "python3", "bench.fit", 990.0, 30.0),
                    tr.Event(HOST, "python3", "dl4j.fit", 991.0, 20.0)]


@pytest.mark.parametrize("path", [SPANS, PLAIN])
def test_load_reads_what_profile_data_reads(path):
    from jax.profiler import ProfileData
    host, scoped, _ = ps.load(path)
    want_host, want_device = [], []
    for plane in ProfileData.from_file(path).planes:
        device = bool(tr.DEVICE_PLANE.match(plane.name))
        for ln in plane.lines:
            for ev in ln.events:
                at = (plane.name, ln.name, float(ev.start_ns),
                      float(ev.duration_ns))
                if device:
                    want_device.append(at)
                elif ev.name.startswith(("dl4j.", "bench.")):
                    want_host.append(at + (ev.name,))
    assert [(e.plane, e.line, e.start, e.dur) for e in scoped] == want_device
    assert [(e.plane, e.line, e.start, e.dur, e.name)
            for e in host] == want_host


# ---------------------------------------------- scopes the executable lacks
def test_a_training_entry_whose_operations_lack_the_scopes_fails_loudly():
    """What a compile cache filled before the scopes changed serves: the
    program writes its spans, the executable has the old scopes or none."""
    host, device = stretch()
    bare = [e._replace(name="") for e in device]
    with pytest.raises(ps.StaleScopes, match="forward.*backward.*optimizer"):
        ps.summarize(host, bare, False)
    no_optimizer = [e._replace(name="") if "optimizer" in e.name else e
                    for e in device]
    with pytest.raises(ps.StaleScopes, match=r"\['optimizer'\]"):
        ps.summarize(host, no_optimizer, True)
    # the parent writes no dl4j.* span: nothing to read, nothing to raise
    parent = [e for e in host if e.name == "bench.fit"]
    assert ps.summarize(parent, bare, False)["device_by_phase"] is None
    # nor does a stretch in which no training entry ran
    calls = [e for e in host if e.name in ("bench.fit", "dl4j.call.output")]
    assert ps.summarize(calls, bare, False)["device_by_phase"] is None


def test_a_cells_trace_is_looked_for_in_its_own_directory(tmp_path,
                                                          monkeypatch):
    monkeypatch.setattr(common, "OUT", str(tmp_path))
    for cell, age in (("gpt2-medium.train-fit", 100),
                      ("resnet50-224.train-ondevice", 200)):
        d = tmp_path / "trace" / cell / "plugins" / "profile" / "run"
        d.mkdir(parents=True)
        (d / "host.xplane.pb").write_bytes(made_up_space())
        os.utime(d / "host.xplane.pb", (age, age))
    lm = common.load_json("workloads", "gpt2-medium.train-fit.json")
    assert ps.trace_dir_of(lm) == str(
        tmp_path / "trace" / "gpt2-medium.train-fit")
    # the other cell's file is the newer one, and is not this cell's
    seen = []
    monkeypatch.setattr(ps, "tables_of", seen.append)
    ps.tables(dict(empty_ctx(), trace={"window_ns": 1.0}, cell=lm))
    assert seen == [str(tmp_path / "trace" / "gpt2-medium.train-fit"
                        / "plugins" / "profile" / "run" / "host.xplane.pb")]
    with pytest.raises(KeyError):
        ps.trace_dir_of({"config": "gpt2-medium", "traffic": "no-such"})


# ------------------------------------------------------------------ readers
def empty_ctx():
    return {"trace": None, "stretch": {"steps": 0}, "cell": {}, "cfg": {},
            "chips": 1, "flops_module": None, "flops_per_step": None,
            "peaks": {}, "counters_before": None, "counters_after": None}


@pytest.mark.parametrize("metric", NEW_READERS)
def test_a_new_reader_with_nothing_to_read_returns_nothing(metric):
    assert common.load_module("metrics", metric).read(empty_ctx()) is None


@pytest.mark.parametrize("metric", NEW_READERS)
def test_a_new_reader_on_the_parents_trace_returns_nothing(metric):
    """The trace PR 25 recorded has ``bench.fit`` and no ``dl4j.*`` span,
    no scope and no new counter: what the parent of PR 27 writes."""
    ctx = dict(empty_ctx(), trace={"window_ns": 1.0}, xplane=PLAIN,
               stretch={"steps": 3},
               counters_before={"training_compile_total": 4.0},
               counters_after={"training_compile_total": 4.0})
    assert common.load_module("metrics", metric).read(ctx) is None


def test_the_counters_readers():
    ctx = dict(empty_ctx(), stretch={"steps": 20},
               counters_before={"stepprof_fences_total": 2.0,
                                "host_gc_pause_seconds_total": 1.0},
               counters_after={"stepprof_fences_total": 3.0,
                               "host_gc_pause_seconds_total": 1.5})
    read = lambda m: common.load_module("metrics", m).read(ctx)  # noqa: E731
    assert read("profiler_fences.train") == 1.0
    assert read("gc_pause_ms.train") == pytest.approx(25.0)
    assert ps.counter_delta(ctx, "host_gc_pause_seconds_total") == 0.5
    assert ps.counter_delta(ctx, "no_such_counter") is None


def test_every_new_reader_is_in_the_manifest_with_its_cells():
    manifest = common.load_manifest()
    listed = {m["name"]: m for m in manifest["per_layer"]}
    cells = {w["name"] for w in manifest["workloads"]}
    for name in NEW_READERS:
        assert listed[name]["moves"] == "train_step_ms"
        assert set(listed[name]["workloads"]) <= cells


# ------------------------------------------- the trace recorded on the chip
@pytest.fixture(scope="module")
def recorded():
    return ps.tables_of(SPANS)


def test_tables_of_the_recorded_trace(recorded):
    # three steps of the test-sized LM cell through fit (my chip run,
    # PR 27): the numbers run.py printed there from the uncut trace
    t = recorded
    assert t["window_ns"] == 16601148.0 and t["idle_ns"] == 15858572.0
    assert t["idle_by_span"] == {
        "dl4j.fit": 4297117.0, "dl4j.input_wait": 121729.0,
        "dl4j.h2d": 2167340.0, "dl4j.call.train_step": 7034756.0,
        "dl4j.gc": 228760.0, "dl4j.window_wait": 1634210.0,
        "dl4j.sync": 13410.0}
    assert t["idle_by_group"]["uncovered"] == 361250.0
    assert sum(t["idle_by_group"].values()) == t["idle_ns"]
    assert {k: n for k, (n, _) in t["span_ns"].items()} == {
        "dl4j.fit": 1, "dl4j.input_wait": 4, "dl4j.h2d": 3,
        "dl4j.call.train_step": 3, "dl4j.gc": 3, "dl4j.window_wait": 3,
        "dl4j.sync": 2}
    assert t["device_by_phase"] == {
        "outside": 46648.0, "forward": 285790.0, "backward": 328847.0,
        "grad_post": 17853.0, "optimizer": 63438.0}
    assert t["device_by_layer"] == {
        "EmbeddingSequenceLayer": 27834.0, "PositionalEncodingLayer": 3477.0,
        "TransformerBlock": 387995.0, "RnnOutputLayer": 52658.0}
    # self times part the busy time: the same as the reducer's union
    assert t["device_self_ns"] == t["window_ns"] - t["idle_ns"]


def test_the_recorded_scopes_are_the_step_programs(recorded):
    with open(SPANS, "rb") as f:
        scopes = {scope for plane, _, metadata in ps.read_planes(f.read())
                  if plane == DEV for _, scope in metadata.values() if scope}
    assert any(s.startswith("jit(train_step)/jvp(forward)/") and
               "/TransformerBlock/flash_fwd/" in s for s in scopes)
    assert any("/transpose(jvp(forward))/" in s and
               "/TransformerBlock/flash_bwd_dq/" in s for s in scopes)
    assert any(s.startswith("jit(train_step)/optimizer/") for s in scopes)
    assert any(s.startswith("jit(train_step)/grad_post/") for s in scopes)


@pytest.mark.parametrize("metric,value", [
    ("device_idle_pct.train", 95.52695994277023),
    ("idle_entry_pct.train", 26.698490971829177),
    ("idle_dispatch_pct.train", 55.430479868018764),
    ("call_train_step_ms.train", 2.5862996666666667),
    ("idle_fence_pct.train", 0.0),
    ("backward_device_pct.train", 44.28462541207903),
    ("optimizer_device_pct.train", 10.947162310659111)])
def test_metric_readers_on_the_recorded_trace(metric, value):
    with open(os.path.join(HERE, "data", "tiny-gpt2.json")) as f:
        cfg = json.load(f)
    with open(os.path.join(HERE, "data", "tiny-gpt2.train-fit.json")) as f:
        cell = json.load(f)
    reduced = tr.reduce(tr.load_events(SPANS))
    ctx = dict(empty_ctx(), trace=reduced, xplane=SPANS, cell=cell, cfg=cfg,
               stretch={"steps": 3})
    got = common.load_module("metrics", metric).read(ctx)
    assert got == pytest.approx(value, rel=1e-9, abs=1e-12)


def test_no_batchnorm_in_the_lm_and_the_parts_add_up(recorded):
    ctx = dict(empty_ctx(), trace={"window_ns": 1.0}, xplane=SPANS)
    read = lambda m: common.load_module("metrics", m).read(ctx)  # noqa: E731
    assert read("batchnorm_device_pct.train") is None
    t = recorded
    parts = [ps.idle_pct(ctx, g) for g in ps.GROUPS]
    assert sum(parts) == pytest.approx(100.0 * t["idle_ns"] / t["window_ns"],
                                       abs=1e-9)
    # what no span covers is a small part of the idle time
    assert ps.idle_pct(ctx, "uncovered") < 0.1 * sum(parts)
