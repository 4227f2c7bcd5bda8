"""Every run times its phases, and reads the peak memory before the check."""
import contextlib
import io
import re
import time

import pytest

from chipbench import harness
from chipbench.tests.tiny import tiny_root

PHASES = ("weights", "checked", "warm", "window", "trace_stop", "read", "check")


@pytest.fixture(scope="module")
def ran(tmp_path_factory):
    """One run of the tiny cell on the CPU: its stderr, its wall seconds
    and the order in which it read the peak memory and called the check."""
    root = tiny_root(tmp_path_factory.mktemp("tiny"))
    order: list = []
    peak_memory, check = harness.peak_memory, harness.check

    def record(name, f):
        def recorded(*args, **kwargs):
            order.append(name)
            return f(*args, **kwargs)
        return recorded

    err = io.StringIO()
    with pytest.MonkeyPatch.context() as mp, contextlib.redirect_stderr(err):
        mp.setattr(harness, "peak_memory", record("peak_memory", peak_memory))
        mp.setattr(harness, "check", record("check", check))
        t0 = time.perf_counter()
        harness.run(root, "tiny_resnet.tiny_fig5", 2**32 + 3, 0.2, False, t0,
                    require_tpu=False)
        wall = time.perf_counter() - t0
    return err.getvalue(), wall, order


def test_a_run_prints_one_phases_line_whose_parts_sum_to_its_wall_time(ran):
    stderr, wall, _ = ran
    lines = [ln for ln in stderr.splitlines() if ln.startswith("phases: ")]
    assert len(lines) == 1, stderr
    parts = dict(re.findall(r"(\w+)=([0-9.]+)", lines[0]))
    assert tuple(parts) == PHASES + ("wall",)
    seconds = {k: float(v) for k, v in parts.items()}
    assert all(seconds[k] >= 0.0 for k in PHASES)
    assert sum(seconds[k] for k in PHASES) == pytest.approx(seconds["wall"], abs=0.01)
    assert abs(seconds["wall"] - wall) < 1.0
    # before the check lines that print_result adds
    assert stderr.index("phases: ") > stderr.index("reading ")


def test_the_peak_memory_is_read_before_the_check(ran):
    stderr, _, order = ran
    assert order == ["peak_memory", "check"]
    assert stderr.count("chip bytes in use before the check: ") == 1
