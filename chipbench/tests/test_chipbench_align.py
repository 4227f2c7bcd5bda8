"""The program's spans on the device clock, idle by span, busy by scope."""
import gzip
import json
import pathlib
import time

import pytest

from chipbench import attribute, cells, harness, trace_align, trace_reduce
from chipbench.tests.tiny import tiny_root
from repro.obs import SpanEvent

PLANE = "/device:TPU:0"
ROOT = pathlib.Path(__file__).resolve().parents[2]
TESTDATA = ROOT / "chipbench" / "testdata"
OFFSET = 7_000_000_123.0  # host = device + OFFSET


def _span(name, cat, t0, t1, depth=0):
    """A span on the host clock, from device-clock times."""
    return SpanEvent(name=name, cat=cat, t0_ns=int(t0 + OFFSET), t1_ns=int(t1 + OFFSET),
                     tid=1, depth=depth, track=None, attrs={})


def _synthetic(host_slack=(2_500, 2_500)):
    """Marks at 100, 600 and 1100 us on the device, each seen by the host
    ``host_slack`` ns before its start and after its end.  Ops: a chunk's
    local training and ravel, an op under no scope, then a second chunk
    whose first op starts 10 us into its run."""
    K = 1000.0
    marks = [(100 * K, 1 * K), (600 * K, 1 * K), (1100 * K, 1 * K)]
    modules = [["jit_chipbench_mark(1)", s, d] for s, d in marks]
    modules += [["jit__chunk_impl(2)", 200 * K, 300 * K], ["jit__chunk_impl(2)", 700 * K, 300 * K]]
    path = "jit(_chunk_impl)/while/body/{}/vmap(jvp(conv))"
    ops = [
        ["while.1", 200 * K, 300 * K, "jit(_chunk_impl)/while"],  # holds the next three
        ["fusion.1", 210 * K, 200 * K, path.format("local_train")],
        ["concatenate.2", 420 * K, 50 * K, path.format("ravel")],
        ["fused_aggregate_2d", 480 * K, 10 * K, path.format("aggregate")],
        ["convolution.3", 710 * K, 290 * K, path.format("local_train")],
    ]
    pre, post = host_slack
    hosts = [(OFFSET + s - pre, OFFSET + s + d + post) for s, d in marks]
    return {"ops": {PLANE: ops}, "modules": {PLANE: modules}}, hosts


def test_clock_offset_recovers_a_known_offset_and_its_uncertainty():
    trace, hosts = _synthetic()
    runs = trace_align.mark_runs(trace["modules"][PLANE])
    got = trace_align.clock_offset(runs, hosts)
    # every mark bounds the offset to [OFFSET - 2500, OFFSET + 2500]
    assert got["uncertainty_ns"] == pytest.approx(5_000)
    assert got["offset_ns"] == pytest.approx(OFFSET)
    # a mark seen more tightly narrows the intersection to its own bound
    hosts[1] = (hosts[1][0] + 1_500, hosts[1][1] - 2_500)  # [OFFSET - 1000, OFFSET]
    got = trace_align.clock_offset(runs, hosts)
    assert got["uncertainty_ns"] == pytest.approx(1_000)
    assert got["offset_ns"] - 1_000 / 2 <= OFFSET <= got["offset_ns"] + 1_000 / 2
    # bounds that do not meet, and marks that do not pair up, give nothing
    assert trace_align.clock_offset(runs, [(h0 + 10_000, h1 + 10_000) for h0, h1 in hosts[:1]]
                                    + hosts[1:]) is None
    assert trace_align.clock_offset(runs, hosts[:2]) is None


def test_spans_are_placed_only_within_a_millisecond():
    trace, hosts = _synthetic()
    spans = [_span("prefetch.stage", "stage", 105_000, 190_000)]
    found = trace_align.attribute(trace, hosts, spans)
    assert found["idle_by_span"]["stage"] == pytest.approx(85e-6)
    trace, hosts = _synthetic(host_slack=(600_000, 600_000))
    found = trace_align.attribute(trace, hosts, spans)
    assert found["uncertainty_ns"] == pytest.approx(1.2e6)
    assert found["idle_by_span"] is None and found["gaps"] is None
    assert found["scopes"]["local_train"] > 0  # the device's own numbers stay
    # bounds that do not meet: no offset, nothing placed, the scopes stay
    hosts[1] = (hosts[1][0] + 2e6, hosts[1][1] + 2e6)
    found = trace_align.attribute(trace, hosts, spans)
    assert found["offset_ns"] is None and found["idle_by_span"] is None
    assert found["marks"] == (3, 3) and len(found["bounds"]) == 3
    assert found["scopes"]["local_train"] > 0


def test_idle_goes_to_the_innermost_span_and_adds_up():
    spans = [
        {"t0": 0, "t1": 100, "cat": "control", "name": "trainer.stop"},
        {"t0": 10, "t1": 40, "cat": "stage", "name": "prefetch.stage"},
        {"t0": 20, "t1": 30, "cat": "solve", "name": "opt_alpha.solve"},
        {"t0": 40, "t1": 60, "cat": "h2d", "name": "prefetch.h2d"},
        {"t0": 150, "t1": 170, "cat": "fetch", "name": "trainer.fetch"},
    ]
    idle = [(5, 25), (35, 50), (90, 160), (180, 200)]
    got = trace_align.attribute_idle(idle, spans)
    assert got == {
        "control": 5 + 10,       # 5..10, 90..100
        "stage": 10 + 5,         # 10..20, 35..40
        "solve": 5,              # 20..25
        "h2d": 10,               # 40..50
        None: 50 + 20,           # 100..150, 180..200
        "fetch": 10,             # 150..160
    }
    assert sum(got.values()) == sum(e - s for s, e in idle)
    # two spans that start together: the shorter is the inner one
    tie = [{"t0": 0, "t1": 10, "cat": "dispatch", "name": "a"},
           {"t0": 0, "t1": 5, "cat": "compile", "name": "b"}]
    assert trace_align.attribute_idle([(0, 10)], tie) == {"compile": 5, "dispatch": 5}


def test_attribute_synthetic_trace():
    trace, hosts = _synthetic()
    spans = [
        _span("trainer.stop", "control", 90_000, 120_000),
        _span("opt_alpha.solve", "solve", 120_000, 130_000, depth=1),
        _span("prefetch.stage", "stage", 130_000, 180_000, depth=1),
        _span("prefetch.h2d", "h2d", 180_000, 195_000, depth=1),
        _span("pipelined.chunk", "dispatch", 195_000, 205_000, depth=1),
        _span("trainer.fetch", "fetch", 205_000, 510_000),
        _span("prefetch.stage", "stage", 700_000, 900_000),  # hidden by the chunk
    ]
    found = trace_align.attribute(trace, hosts, spans)
    assert found["plane"] == PLANE
    assert found["window_s"] == pytest.approx(999e-6)  # 101 .. 1100 us
    # idle: 101..200, 500..710 (its last 10 us inside a program), 1000..1100 us
    assert found["idle_s"] == pytest.approx((99 + 210 + 100) * 1e-6)
    assert found["idle_in_program_s"] == pytest.approx(10e-6)
    b = trace_align.idle_buckets(found["idle_by_span"])
    # the stage hidden behind the second chunk takes that chunk's own gap
    assert b["staging"] == pytest.approx((10 + 50 + 15 + 10) * 1e-6)
    assert b["burst_edge"] == pytest.approx((19 + 10) * 1e-6)  # 101..120, 500..510
    assert b["remainder"] == {"dispatch": pytest.approx(5e-6)}
    assert b["unattributed"] == pytest.approx((190 + 100) * 1e-6)
    total = b["staging"] + b["burst_edge"] + b["unattributed"] + sum(b["remainder"].values())
    assert total == pytest.approx(found["idle_s"])
    # busy by scope: the while loop keeps only what its body leaves
    assert found["scopes"] == {
        "none": pytest.approx(40e-6), "local_train": pytest.approx(490e-6),
        "ravel": pytest.approx(50e-6), "aggregate": pytest.approx(10e-6),
    }
    summary = trace_reduce.reduce(trace_align.three_fields(trace))
    assert sum(found["scopes"].values()) == pytest.approx(summary["busy_s"])
    # the gaps over 10 ms: none here; over 50 us, each with its spans
    gaps = trace_align.gap_spans(trace_align.idle_intervals(
        trace["ops"][PLANE], 101_000, 1_100_000), [
        {"t0": sp.t0_ns - OFFSET, "t1": sp.t1_ns - OFFSET, "cat": sp.cat, "name": sp.name}
        for sp in spans], min_ns=150_000)
    assert [(s, n) for s, n, _ in gaps] == [(500_000, 210_000)]
    assert gaps[0][2] == [
        ("(no span)", None, 190_000),
        ("trainer.fetch", "fetch", 10_000),
        ("prefetch.stage", "stage", 10_000),
    ]


def test_scope_of_takes_the_innermost_layer():
    assert trace_align.scope_of("jit(f)/while/body/local_train/vmap(jvp(conv))") == "local_train"
    assert trace_align.scope_of("jit(f)/ravel/aggregate/x") == "aggregate"
    assert trace_align.scope_of("jit(f)/while/body/closed_call/ravel:") == "ravel"
    assert trace_align.scope_of("jit(f)/local_training/x") == "none"
    assert trace_align.scope_of("") == "none"


def _fig5():
    with gzip.open(TESTDATA / "trace_fig5.json.gz", "rt") as f:
        return json.load(f)


class _Span:
    def __init__(self, cat, dur_ns):
        self.cat, self.dur_ns = cat, dur_ns


def _art(trace):
    cell = cells.resolve(ROOT, "resnet20_n10.fig5")
    return cell, {
        "cell": cell, "peak": harness.load_peaks(ROOT)["TPU v5 lite"],
        "trace": trace_reduce.reduce(trace), "traced_rounds": 200, "window_rounds": 500,
        "spans": [_Span("solve", 3e6), _Span("stage", 1e9), _Span("solve", 1e6)],
        "stage_wait_s": 1.5, "flops_per_example": cell.model().flops_per_example(cell.config),
        "n_clients": 10, "active_clients": 10, "local_steps": 2, "local_batch": 32,
    }


# what the five readers read on the recorded trace before op scopes existed
FIG5_READINGS = {
    "device_idle_share": 89.03262051107846,
    "round_mfu": 1.9928940441458887,
    "relay_kernel_roofline": 53.645817940962424,
    "opt_alpha_ms_per_round": 0.008,
    "stage_wait_ms_per_round": 3.0,
}


@pytest.mark.parametrize("scoped", [False, True])
def test_existing_readers_read_as_before_on_the_recorded_trace(scoped):
    """Ops with a scope as a fourth field read, through ``three_fields``,
    exactly what the three-field trace reads; the new readers find
    nothing to read in what the harness gathers."""
    trace = _fig5()
    if scoped:
        trace["ops"] = {p: [op + ["jit(x)/local_train/y"] for op in ops]
                        for p, ops in trace["ops"].items()}
        trace = trace_align.three_fields(trace)
    cell, art = _art(trace)
    got = {m["name"]: cell.reader(m["name"]).read(art) for m in cell.per_layer}
    assert got == {k: pytest.approx(v, rel=1e-12) for k, v in FIG5_READINGS.items()}
    for name in attribute.NEW_METRICS:
        assert cell.reader(name).read(art) is None


def test_attribute_command_runs_on_the_cpu(tmp_path, monkeypatch):
    """The command end to end on a small cell on the CPU: the tracer on the
    trainer records its spans and no compile in the window; no TPU plane,
    so nothing is placed on a device clock.  Each burst boundary's
    ``trainer.stop`` holds one ``bench.mark`` span, one level down, that
    covers the benchmark's own work there."""
    seen = []
    real = trace_align.attribute

    def spy(trace, hosts, spans):
        seen.extend(spans)
        return real(trace, hosts, spans)

    monkeypatch.setattr(trace_align, "attribute", spy)
    root = tiny_root(tmp_path)
    for profile in (False, True):
        out = attribute.run(root, "tiny_resnet.tiny_fig5", 2**33 + 5, 0.2, profile,
                            time.perf_counter(), require_tpu=False)
        assert ("rounds_per_s" in out["metrics"]) is not profile
        assert out["metrics"].get("window_compiles") == (0 if profile else None)
        assert "idle_staging_ms_per_round" not in out["metrics"]
    stops = sorted((s for s in seen if s.name == "trainer.stop"), key=lambda s: s.t0_ns)
    marks = sorted((s for s in seen if s.name == "bench.mark"), key=lambda s: s.t0_ns)
    assert stops and len(marks) == len(stops)
    for stop, mark in zip(stops, marks):
        assert mark.cat == attribute.MARK and mark.depth == stop.depth + 1
        assert stop.t0_ns <= mark.t0_ns <= mark.t1_ns <= stop.t1_ns


def test_the_benchmarks_boundary_work_is_not_the_burst_edge():
    """Idle under the benchmark's ``bench.mark`` span, nested in the
    trainer's stop poll, goes to the remainder; the poll's own idle around
    it stays the burst edge's."""
    spans = [{"t0": 0, "t1": 100, "cat": "control", "name": "trainer.stop"},
             {"t0": 10, "t1": 90, "cat": attribute.MARK, "name": "bench.mark"}]
    by_cat = trace_align.attribute_idle([(0, 100)], spans)
    assert by_cat == {"control": 20, attribute.MARK: 80}
    b = trace_align.idle_buckets({k: v / 1e9 for k, v in by_cat.items()})
    assert b["burst_edge"] == pytest.approx(20e-9)
    assert b["remainder"] == {attribute.MARK: pytest.approx(80e-9)}


def test_attribute_reports_and_writes_a_fixture(tmp_path, capsys):
    """What the command prints and keeps, on the synthetic trace."""
    trace, hosts = _synthetic()
    spans = [_span("prefetch.stage", "stage", 105_000, 190_000),
             _span("trainer.fetch", "fetch", 300_000, 520_000)]
    found = trace_align.attribute(trace, hosts, spans)
    art = {"traced_rounds": 2, "idle_buckets": trace_align.idle_buckets(found["idle_by_span"])}
    attribute._report(found, art, trace_reduce.reduce(trace_align.three_fields(trace)))
    err = capsys.readouterr().err
    assert "3 mark runs on /device:TPU:0, 3 timed on the host" in err
    assert "uncertainty 5000 ns" in err
    assert "idle parts sum 0.2045 ms/round (idle 0.2045)" in err
    assert "scope local_train: 0.000490 s" in err
    path = tmp_path / "spans.json.gz"
    attribute._write_fixture(path, trace, found, {"hosts": hosts, "from": 8, "to": 10}, spans)
    with gzip.open(path, "rt") as f:
        doc = json.load(f)
    assert doc["traced_rounds"] == 2 and doc["marks_host"] == [list(h) for h in hosts]
    assert [s[0] for s in doc["spans"]] == ["prefetch.stage", "trainer.fetch"]
    # from the window's start to 50 ms into its first chunk: every op here
    assert len(doc["ops"][PLANE]) == len(trace["ops"][PLANE])


def test_readers_on_the_recorded_chip_fixture():
    """``resnet20_n10.fig5`` on a TPU v5 lite (``attribute.py --fixture``):
    the window's spans, its marks' host intervals, and its device ops up to
    50 ms into its first chunk program.  The spans land on the device clock
    within a millisecond; the burst's first two gaps (its first chunk staged
    with nothing in flight, then that chunk's inputs copied while the host
    stages the next) go to staging; the scopes name the device time."""
    with gzip.open(TESTDATA / "trace_fig5_spans.json.gz", "rt") as f:
        doc = json.load(f)
    (plane,) = doc["ops"]
    spans = [SpanEvent(name=n, cat=c, t0_ns=t0, t1_ns=t1, tid=0, depth=d, track=None,
                       attrs=a) for n, c, t0, t1, d, a in doc["spans"]]
    runs = trace_align.mark_runs(doc["modules"][plane])
    offset = trace_align.clock_offset(runs, doc["marks_host"])
    assert offset["uncertainty_ns"] == pytest.approx(627_710.75)  # under 1 ms
    ops = doc["ops"][plane]
    # the ops were kept up to 50 ms into the chunk program: cut there
    lo, cut = runs[0][1], max(op[1] for op in ops)
    idle = trace_align.idle_intervals(ops, lo, cut)
    c = offset["offset_ns"]
    placed = [{"t0": s.t0_ns - c, "t1": s.t1_ns - c, "cat": s.cat, "name": s.name}
              for s in spans]
    by_cat = trace_align.attribute_idle(idle, placed)
    assert sum(by_cat.values()) == pytest.approx(sum(e - s for s, e in idle))
    b = trace_align.idle_buckets({k: v / 1e9 for k, v in by_cat.items()})
    # stage 448.7 ms, solve 10.4 ms, h2d enqueue 1.8 ms
    assert b["staging"] == pytest.approx(0.461036, rel=1e-5)
    assert b["remainder"] == {"dispatch": pytest.approx(0.020666, rel=1e-4)}
    scopes = {k: v / 1e9 for k, v in trace_align.scope_times(ops, lo, cut).items()}
    cell = cells.resolve(ROOT, "resnet20_n10.fig5")
    art = {"scopes": scopes, "traced_rounds": doc["traced_rounds"]}
    local = cell.reader("local_train_ms_per_round").read(art)
    ravel = cell.reader("ravel_ms_per_round").read(art)
    # 50 ms of device time over the window's 200 rounds
    assert local == pytest.approx(47.363051 / 200, rel=1e-6)
    assert ravel == pytest.approx(0.0192325 / 200, rel=1e-6)
    assert scopes["local_train"] > 0.9 * sum(scopes.values())


def test_device_times_prefer_the_devices_own_clock():
    class Event:
        start_ns, duration_ns = 1_000.0, 50.0

        def __init__(self, stats):
            self.stats = stats

    own = Event([("device_offset_ps", "990000"), ("device_duration_ps", "49500"),
                 ("Time Scale Multiplier", "1.0")])
    assert trace_align._device_times(own) == (990.0, 49.5)
    assert trace_align._device_times(Event([])) == (1_000.0, 50.0)


def test_scope_paths_read_the_event_metadata(tmp_path):
    """An op's path is the ``tf_op`` stat of its event metadata, given as a
    string or as a reference to an interned one; host planes are skipped."""
    space = trace_align._xspace_class()()
    plane = space.planes.add(name=PLANE)
    for key, name in {1: "tf_op", 2: "jit(f)/while/body/ravel/x", 3: "hlo_category"}.items():
        entry = plane.stat_metadata.add(key=key)
        entry.value.id, entry.value.name = key, name
    fusion = plane.event_metadata.add(key=10).value
    fusion.name = "%fusion.1 = f32[] fusion()"
    fusion.stats.add(metadata_id=1, ref_value=2)
    copy = plane.event_metadata.add(key=11).value
    copy.name = "%copy.2 = f32[] copy()"
    copy.stats.add(metadata_id=3, str_value="data formatting")
    copy.stats.add(metadata_id=1, str_value="jit(f)/local_train/y")
    space.planes.add(name="/host:CPU").event_metadata.add(key=1).value.name = "x"
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(space.SerializeToString())
    assert trace_align.scope_paths(path) == {PLANE: {
        "%fusion.1 = f32[] fusion()": "jit(f)/while/body/ravel/x",
        "%copy.2 = f32[] copy()": "jit(f)/local_train/y",
    }}
