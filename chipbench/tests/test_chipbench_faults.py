"""Runs with the timed path broken are not correct.

Each test drives ``harness.run`` past its look for a chip, on a small
cell on the CPU, with a fault planted beneath the round the window runs,
and sees ``correct`` come out false under the limits that the chip cell
uses (and true with none)."""
import contextlib
import time

import pytest

from chipbench import faults, harness
from chipbench.tests.tiny import tiny_root

ROUND_FAULTS = tuple(f for f in faults.FAULTS if not f.startswith("alpha_"))


@pytest.fixture(scope="module")
def tiny(tmp_path_factory):
    return tiny_root(tmp_path_factory.mktemp("tiny"))


def run_with(root, fault):
    with contextlib.ExitStack() as stack:
        if fault != "none":
            stack.enter_context(faults.planted(fault))
        return harness.run(root, "tiny_resnet.tiny_fig5", 2**33 + 1, 0.2, False,
                           time.perf_counter(), require_tpu=False)


@pytest.mark.parametrize("fault", ("none",) + ROUND_FAULTS)
def test_run_with_the_timed_path_broken_is_not_correct(tiny, fault):
    result = run_with(tiny, fault)
    assert result["correct"] is (fault == "none"), result["checks"]
    assert result["attempted"] > 0 and result["failed"] == 0
    assert list(result)[-1] == "checks"
