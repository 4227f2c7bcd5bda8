"""The reduction from a profiler trace to busy time, op time and gaps."""
import pytest

from chipbench import trace_reduce

PLANE = "/device:TPU:0"


def test_union_merges_overlaps_and_touching_intervals():
    got = trace_reduce.union([(5, 7), (0, 2), (1, 3), (3, 4), (10, 11), (6, 9)])
    assert got == [[0, 4], [5, 9], [10, 11]]
    assert trace_reduce.union([]) == []


def test_self_times_take_nested_ops_out_of_their_parent():
    got = trace_reduce.self_times([
        ("while", 0, 100), ("conv", 10, 40), ("fusion", 50, 60), ("copy", 120, 130),
        ("inner", 15, 20),
    ])
    own = {name: o for name, _, _, o in got}
    assert own == {"while": 60, "conv": 25, "inner": 5, "fusion": 10, "copy": 10}


def test_op_names_come_from_the_hlo_text():
    assert trace_reduce.op_name("%fusion.12 = f32[8]{0} fusion(...)") == "fusion.12"
    assert trace_reduce.op_name("while.3 = (s32[]) while(...)") == "while.3"
    assert trace_reduce.op_name("plain") == "plain"


def _synthetic():
    # times in microseconds (K ns): marks end at 100 and start at 1100, so
    # the window is 100 .. 1100 us
    K = 1000.0
    modules = [
        ["jit_chipbench_mark(1)", 90.0, 10.0],
        ["jit_convert_element_type(3)", 120.0, 0.001],  # a conversion: not work
        ["jit__chunk_impl(2)", 150.0, 250.0],   # 150 .. 400
        ["jit__chunk_impl(2)", 600.0, 400.0],   # 600 .. 1000
        ["jit_chipbench_mark(1)", 1100.0, 5.0],
    ]
    ops = [
        ["while.1", 150.0, 250.0],          # 150 .. 400, holds the next two
        ["_kernel", 200.0, 100.0],          # 200 .. 300
        ["fusion.1", 300.0, 50.0],          # 300 .. 350
        ["while.1", 600.0, 150.0],          # 600 .. 750
        ["convolution.3", 800.0, 200.0],    # 800 .. 1000
        ["fusion.1", 50.0, 40.0],           # before the window: ignored
    ]
    scale = lambda evs: [[n, s * K, d * K] for n, s, d in evs]  # noqa: E731
    return {"ops": {PLANE: scale(ops)}, "modules": {PLANE: scale(modules)}}


def test_reduce_synthetic_trace():
    s = trace_reduce.reduce(_synthetic())
    busy = (400 - 150) + (750 - 600) + (1000 - 800)
    assert s["window_s"] == pytest.approx(1000e-6)
    assert s["busy_s"] == pytest.approx(busy * 1e-6)
    assert s["ops"]["_kernel"] == (1, pytest.approx(100e-6), pytest.approx(100e-6))
    assert s["ops"]["while.1"][0] == 2
    assert s["ops"]["while.1"][2] == pytest.approx((250 - 100 - 50 + 150) * 1e-6)
    gaps = [(round(g * 1e6), name) for name, g in s["breakdown"]["idle_gaps"]]
    assert sorted(gaps) == [
        (50, "burst start: first chunk staged with nothing in flight"),  # 100 .. 150
        (50, "inside a program"),  # 750 .. 800
        (100, "after a burst: result fetch and stop check"),  # 1000 .. 1100
        (200, "between programs: staging not hidden"),  # 400 .. 600
    ]
    assert s["breakdown"]["device_ops"][0][0] == "while.1"


def test_reduce_without_two_marks_is_none():
    t = _synthetic()
    t["modules"][PLANE] = [m for m in t["modules"][PLANE] if "mark" not in m[0]][:1]
    assert trace_reduce.reduce(t) is None


def test_recorded_chip_trace():
    """A trace of ``resnet20_n10.fig5`` from a TPU v5 lite, trimmed to the
    ops of the first 50 ms after the first mark (every program run kept)."""
    import gzip
    import json
    import pathlib

    from chipbench import cells

    path = pathlib.Path(__file__).resolve().parents[1] / "testdata" / "trace_fig5.json.gz"
    with gzip.open(path, "rt") as f:
        trace = json.load(f)
    s = trace_reduce.reduce(trace)
    (plane,) = trace["modules"]
    marks = [m for m in trace["modules"][plane] if trace_reduce.MARK in m[0]]
    assert len(marks) == 3  # before, between and after the two traced bursts
    assert s["window_s"] == pytest.approx((marks[-1][1] - marks[0][1] - marks[0][2]) / 1e9)
    # the trim kept the ops that start in the first chunk's first 50 ms,
    # its while loop among them: busy is that chunk program's run
    chunk = min((m for m in trace["modules"][plane] if "_chunk_impl" in m[0]),
                key=lambda m: m[1])
    assert s["busy_s"] == pytest.approx(chunk[2] / 1e9, rel=0.01)
    kernel = [k for k in s["ops"] if cells.load_module(
        pathlib.Path(trace_reduce.__file__).parent / "metrics" / "relay_kernel_roofline.py"
    ).KERNEL.match(k)]
    assert kernel
    assert len(s["breakdown"]["device_ops"]) == 10
    names = {name for name, _ in s["breakdown"]["idle_gaps"]}
    assert names <= {
        "inside a program", "after a burst: result fetch and stop check",
        "burst start: first chunk staged with nothing in flight",
        "between programs: staging not hidden",
    }
