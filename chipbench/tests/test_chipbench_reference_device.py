"""The check's replay runs on the host's CPU where the model's loss runs a
convolution, and on the cell's first device where it does not.

On a host whose only device is its CPU the two are one device, so the
check runs in a child process with two CPU devices, whose ``jax.devices()``
lists the second first: that one stands for the chip.  Stand-ins for the
model's ``init`` and for ``reference.run_rounds`` record where they were
asked to run; then the MLP cell is checked for real on each device."""
import json
import os
import pathlib
import subprocess
import sys

import pytest

from chipbench.tests.tiny import tiny_root

ROOT = pathlib.Path(__file__).resolve().parents[2]
CELLS = {"tiny_resnet.tiny_fig5": "cpu_device", "tiny_mlp.tiny_fig5": "chip_device"}


def record_replay_devices(root) -> dict:
    """In the child: set each tiny cell up and check it with stand-ins that
    record their devices; then check the MLP cell with the replay on the
    stand-in chip and on the CPU.  Returns the devices and the numbers."""
    import jax

    from chipbench import cells, harness, reference

    all_devices = jax.devices
    jax.devices = lambda backend=None: all_devices(backend) if backend else all_devices()[::-1]
    out = {"cpu_device": str(jax.devices("cpu")[0]), "chip_device": str(jax.devices()[0])}
    real_run_rounds = reference.run_rounds
    for name in CELLS:
        cell = cells.resolve(root, name)
        prep = harness.prepare(cell, 2**31 + 9)
        prep.trainer = None
        model = cell.model()
        real_init = model.init
        seen: list = []

        def init(key, cfg, real_init=real_init, seen=seen):
            seen.append(("init", str(jax.config.jax_default_device)))
            return real_init(key, cfg)

        def run_rounds(model, params, rounds, *, lr, wd, dtype=None, device=None,
                       prep=prep, seen=seen):
            seen.append(("run_rounds", str(device)))
            return {k: prep.program[k] for k in ("after", "losses", "delta_norms")}

        model.init, reference.run_rounds = init, run_rounds
        harness.check(cell, prep)
        model.init, reference.run_rounds = real_init, real_run_rounds
        out[name] = seen
    # the last cell is the MLP's: its replay goes to the stand-in chip
    numbers = {"chip": harness.check(cell, prep)}
    reference.replay_device = lambda *_: all_devices("cpu")[0]
    numbers["cpu"] = harness.check(cell, prep)
    out["numbers"] = numbers
    return out


@pytest.fixture(scope="module")
def seen(tmp_path_factory):
    root = tiny_root(tmp_path_factory.mktemp("tiny"))
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=2",
               PYTHONPATH=os.pathsep.join([str(ROOT / "src"), str(ROOT)]))
    code = ("import json, sys; from chipbench.tests.test_chipbench_reference_device "
            "import record_replay_devices as r; print(json.dumps(r(sys.argv[1])))")
    proc = subprocess.run([sys.executable, "-c", code, str(root)], cwd=ROOT, env=env,
                          capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-4000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("cell", sorted(CELLS))
def test_the_replay_runs_on_the_cpu_for_a_convolution_else_on_the_chip(seen, cell):
    assert seen["cpu_device"] != seen["chip_device"]
    where = seen[CELLS[cell]]
    # the first init, on shapes alone and with no default device, is the
    # one from which the device is picked
    assert seen[cell] == [["init", "None"], ["init", where], ["run_rounds", where]]


def test_check_numbers_are_the_same_with_the_replay_on_cpu_or_chip(seen):
    numbers = seen["numbers"]
    assert numbers["chip"] == numbers["cpu"]
    assert numbers["chip"]["program"]["p0_gap"] == 0.0
