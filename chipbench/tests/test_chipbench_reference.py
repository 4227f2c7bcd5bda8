"""The plain reference against the system, and the check against faults.

At a small size on the CPU the system computes the round in exact float32,
so it agrees with the reference to rounding; a 0.1% fault is far above
that.  The control, the reference in bfloat16, fails the limits that the
chip cell uses."""
import pathlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import cells, harness, reference
from chipbench.tests.tiny import tiny_root
from repro.fl.simulator import FLSimulator


@pytest.fixture(scope="module")
def tiny(tmp_path_factory):
    return tiny_root(tmp_path_factory.mktemp("tiny"))


@pytest.fixture(scope="module")
def prepared(tiny):
    cell = cells.resolve(tiny, "tiny_resnet.tiny_fig5")
    prep = harness.prepare(cell, 2**31 + 5)
    prep.trainer = None
    return cell, prep


def test_reference_agrees_with_the_simulator_and_a_planted_fault_fails(prepared):
    cell, prep = prepared
    numbers = harness.check(cell, prep)["program"]
    assert numbers["tau_mismatch"] == 0 and numbers["nonfinite_rounds"] == 0
    assert numbers["p0_gap"] == 0.0 and numbers["alpha_off_support"] == 0.0
    # the CPU's float32 agrees to rounding; ResNet-20 amplifies it over
    # rounds, so the first round's numbers are held tighter than the third's
    assert max(numbers["loss1"], numbers["step1"]) < 1e-4
    limits = cell.limits["limits"]
    assert all(numbers[k] <= limits[k] for k in limits), (numbers, limits)
    faulty = dict(prep.program)
    p0, p1 = faulty["p0"], faulty["after"][0]
    faulty["after"] = [jax.tree.map(lambda a, b: a + 1.001 * (b - a), p0, p1)] + faulty["after"][1:]
    prep_f = harness.Prepared(**{**prep.__dict__, "program": faulty})
    assert harness.check(cell, prep_f)["program"]["step1"] > 5e-4


def test_mlp_reference_agrees_with_the_simulator(tiny):
    # the plain MLP whose replay runs on the chip: exact float32 on the CPU
    cell = cells.resolve(tiny, "tiny_mlp.tiny_fig5")
    prep = harness.prepare(cell, 2**31 + 7)
    prep.trainer = None
    numbers = harness.check(cell, prep)["program"]
    assert numbers["p0_gap"] == 0.0 and numbers["tau_mismatch"] == 0
    assert max(numbers["loss"], numbers["step1"], numbers["change3"]) < 1e-4, numbers
    limits = cell.limits["limits"]
    assert all(numbers[k] <= limits[k] for k in limits), (numbers, limits)


def test_the_control_fails_the_cell_limits(prepared):
    cell, prep = prepared
    out = harness.check(cell, prep, control=True)
    numbers, program = out["control_bf16"], out["program"]
    limits = cell.limits["limits"]
    # its rounding of the weights fails p0_gap; its rounds lie far from
    # the CPU's exact float32 ones, if within the chip's limits
    assert numbers["p0_gap"] > limits["p0_gap"]
    assert numbers["step1"] > 100 * program["step1"], (numbers, program)


def test_resnet20_reference_round_matches_the_simulator():
    model = cells.load_module(pathlib.Path(harness.__file__).parent / "models" / "resnet20.py")
    from repro.bench.scenarios import ScenarioSpec, build

    cfg = {"model": {"n_classes": 10}}
    n, steps, b = 3, 1, 2
    bundle = build(ScenarioSpec(name="t", model="resnet20", n_clients=n, local_steps=steps,
                                local_batch=b, strategy="colrel_fused", n_train=16))
    key = jax.random.key(3)
    params = bundle.init_fn(key)
    rng = np.random.default_rng(0)
    batch = {"images": rng.normal(size=(n, steps, b, 32, 32, 3)).astype(np.float32),
             "labels": rng.integers(0, 10, size=(n, steps, b)).astype(np.int32)}
    A = np.full((n, n), 0.5) + np.eye(n)
    p = np.array([0.9, 0.5, 0.7])
    sim = FLSimulator(bundle.loss_fn, n_clients=n, strategy="colrel_fused", A=A, p=p,
                      local_steps=steps)
    round_key = jax.random.key(11)
    new, _, m = sim.run_round(round_key, params, sim.init_server_state(params), batch, 0.05)
    tau = np.asarray(jax.random.bernoulli(round_key, jnp.asarray(p, jnp.float32)), float)
    ref = reference.run_rounds(
        model, jax.device_get(model.init(key, cfg)),
        [{"batch": batch, "tau": tau, "A": A, "active": None}], lr=0.05, wd=1e-4,
    )
    got = jax.tree.leaves(jax.device_get(new))
    want = jax.tree.leaves(ref["after"][0])
    assert max(float(np.max(np.abs(g - w))) for g, w in zip(got, want)) < 1e-5
    assert abs(float(m["loss"]) - ref["losses"][0]) < 1e-5 * abs(ref["losses"][0])
    assert abs(float(m["delta_norm"]) - ref["delta_norms"][0]) < 1e-5 * ref["delta_norms"][0]
