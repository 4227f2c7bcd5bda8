"""Benchmark subsystem: registry, harness, JSON schema, regression gate."""
import dataclasses
import json

import jax
import pytest

from repro.bench import harness, report as report_lib, scenarios

TINY = scenarios.ScenarioSpec(
    name="tiny_test",
    description="harness unit-test scenario",
    n_clients=4,
    rounds=8,
    local_steps=1,
    local_batch=4,
    dim=8,
    width=4,
    n_train=64,
    adj_every=4,
    p_every=4,
    drift_hold=1,
    chunk=4,
)


# -------------------------------------------------------------- registry


def test_registry_contains_the_shipped_scenarios():
    names = [s.name for s in scenarios.list_scenarios()]
    assert "bench_smoke" in names
    assert "fig5_500" in names
    assert "fig6_500" in names
    assert "corr_shadow_500" in names
    assert "corr_uplink_500" in names
    assert "mesh_corr_500" in names
    for name in names:
        assert scenarios.get_scenario(name).name == name
    corr = scenarios.get_scenario("corr_uplink_500")
    assert corr.fading == "corr_uplink" and corr.drift == "static"
    assert scenarios.get_scenario("mesh_corr_500").step == "mesh"


def test_registry_rejects_unknown_and_duplicate():
    with pytest.raises(KeyError, match="unknown scenario"):
        scenarios.get_scenario("no_such_scenario")
    spec = scenarios.get_scenario("bench_smoke")
    with pytest.raises(ValueError, match="already registered"):
        scenarios.register(spec)


def test_acceptance_scenario_is_fig5_at_paper_scale():
    spec = scenarios.get_scenario("fig5_500")
    assert spec.rounds == 500
    assert spec.n_clients == 10
    assert spec.topology == "ring" and spec.fading == "markov"
    assert spec.policy == "adaptive"


# ---------------------------------------------------------------- harness


def test_harness_runs_all_engines_bitwise_identical():
    result = harness.run_scenario(TINY)
    runs = result["runs"]
    assert set(runs) == {"loop", "scan", "pipelined"}
    assert result["bitwise_match"] is True
    assert result["speedup"] > 0
    assert set(result["speedups"]) == {"scan", "pipelined"}
    for run in runs.values():
        assert run.wall_s > 0
        assert run.rounds_per_sec > 0
        assert run.final_loss == runs["loop"].final_loss  # same trajectory
    assert runs["loop"].trace_count == 1
    assert runs["scan"].trace_count <= 2
    assert runs["scan"].dispatches < runs["loop"].dispatches
    # the pipelined engine fuses τ into the chunk: same dispatch count as
    # scan, plus measured overlap stats (loop/scan report None there)
    assert runs["pipelined"].trace_count <= 2
    assert runs["pipelined"].dispatches == runs["scan"].dispatches
    assert 0.0 <= runs["pipelined"].overlap_fraction <= 1.0
    assert runs["pipelined"].host_prep_s > 0
    assert runs["loop"].overlap_fraction is None
    assert runs["scan"].overlap_fraction is None


@pytest.mark.parametrize(
    "strategy,backend", [("colrel", "pallas"), ("colrel_fused", "pallas_fused")]
)
def test_kernel_check_is_per_round_on_the_sim_path(strategy, backend):
    spec = dataclasses.replace(
        TINY, name="tiny_kernel_check", strategy=strategy, check_backend=backend
    )
    result = harness.run_scenario(spec, engines=("loop",))
    check = result["kernel_check"]
    assert check["mode"] == "per_round" and check["allclose"] is True
    assert check["backend"] == backend
    assert check["max_abs_diff"] < 1e-6
    assert check["first_round_max_abs_diff"] < 1e-6
    assert check["horizon_max_abs_diff"] >= 0.0
    assert f"scan_{backend}" in result["runs"]


def test_kernel_check_catches_a_kernel_off_by_one_part_in_a_thousand(monkeypatch):
    """A kernel that is wrong by 0.1% in one round's relay mix raises."""
    from repro.kernels import relay_mix

    exact = relay_mix.relay_mix_2d

    def skewed(A, delta, **kw):
        return exact(A, delta, **kw) * 1.001

    monkeypatch.setattr(relay_mix, "relay_mix_2d", skewed)
    spec = dataclasses.replace(
        TINY, name="tiny_bad_kernel", strategy="colrel", check_backend="pallas"
    )
    with pytest.raises(AssertionError, match="per_round"):
        harness.run_scenario(spec, engines=("loop",))


def _skewed_scan_engine(when):
    """An EpochScanEngine whose final params are 0.1% off whenever
    ``when()`` holds — a fault that only the scan program shows."""

    class Skewed(harness.EpochScanEngine):
        def run_schedule(self, *args, **kwargs):
            params, *rest = super().run_schedule(*args, **kwargs)
            if when():
                params = jax.tree.map(lambda x: x * 1.001, params)
            return (params, *rest)

    return Skewed


def test_kernel_check_gates_the_compiled_scan_program(monkeypatch):
    """A kernel backend that is right round by round but wrong inside the
    compiled scan program fails the first-round comparison."""
    monkeypatch.setattr(harness, "EpochScanEngine", _skewed_scan_engine(lambda: True))
    spec = dataclasses.replace(
        TINY, name="tiny_bad_scan", strategy="colrel", check_backend="pallas"
    )
    with pytest.raises(AssertionError, match="first_round"):
        harness.run_scenario(spec, engines=("loop",))


def _at_highest():
    return str(jax.config.jax_default_matmul_precision) == "highest"


@pytest.mark.parametrize("fault", ["none", "timed", "gated"])
def test_pinned_gates_rerun_the_engines_at_highest_precision(monkeypatch, fault):
    """Off the CPU the gates hold re-runs at "highest" precision bitwise and
    only record the timed runs' drift: a fault in the timed scan program is
    recorded, one in the gated program raises."""
    monkeypatch.setattr(harness, "_pinned", lambda: True)
    if fault != "none":
        when = _at_highest if fault == "gated" else lambda: not _at_highest()
        monkeypatch.setattr(harness, "EpochScanEngine", _skewed_scan_engine(when))
    spec = dataclasses.replace(TINY, name=f"tiny_pinned_{fault}")
    if fault == "gated":
        with pytest.raises(AssertionError, match="scan engine diverged"):
            harness.run_scenario(spec)
        return
    result = harness.run_scenario(spec)
    check = result["engine_check"]
    assert result["bitwise_match"] is True
    assert check["gate_precision"] == "highest"
    assert set(check["timed_max_abs_diff"]) == {"scan", "pipelined"}
    assert check["timed_max_abs_diff"]["pipelined"] == 0.0
    assert (check["timed_max_abs_diff"]["scan"] > 0.0) == (fault == "timed")
    assert result["runs"]["scan"].trace_count <= 2  # the re-runs are apart


TINY_CORR = dataclasses.replace(
    TINY,
    name="tiny_corr_test",
    fading="corr_uplink",
    drift="static",
    corr_length=0.5,
)


def test_harness_correlated_scenario_bitwise_identical():
    """Jointly-sampled (adj, p) through every engine: the fused paths must
    still reproduce the loop bit-for-bit."""
    result = harness.run_scenario(TINY_CORR)
    assert result["bitwise_match"] is True
    assert result["runs"]["loop"].trace_count == 1
    assert result["runs"]["scan"].trace_count <= 2
    assert result["runs"]["pipelined"].trace_count <= 2


def test_mesh_step_bitwise_and_trace_bound_under_correlated_schedule():
    """Satellite: the mesh round step (build_scan_round_step) benched under
    a correlated multi-epoch schedule — per-epoch scan dispatches, bitwise
    equal to the per-round mesh step, and trace_count ≤ 2 (fixed coherence
    time ⇒ fixed scan length; at most a shorter final remainder epoch)."""
    spec = dataclasses.replace(TINY_CORR, name="tiny_mesh_test", step="mesh")
    result = harness.run_scenario(spec)
    runs = result["runs"]
    assert result["bitwise_match"] is True
    assert runs["loop"].trace_count == 1
    assert runs["scan"].trace_count <= 2
    assert runs["scan"].dispatches == spec.rounds // spec.adj_every
    assert runs["loop"].dispatches == spec.rounds
    # the τ-fused mesh step: same per-epoch dispatch grid as scan, overlap
    # measured, and still bit-identical (checked above for all engines)
    assert runs["pipelined"].trace_count <= 2
    assert runs["pipelined"].dispatches == runs["scan"].dispatches
    assert 0.0 <= runs["pipelined"].overlap_fraction <= 1.0


# ---------------------------------------------------------- report + gate


def _engine_run(rps):
    return harness.EngineRun(
        engine="x",
        wall_s=TINY.rounds / rps,
        compile_s=0.5,
        rounds_per_sec=rps,
        trace_count=1,
        dispatches=8,
        final_loss=1.0,
    )


def _fake_result():
    return {
        "runs": {"loop": _engine_run(100.0), "scan": _engine_run(500.0)},
        "speedup": 5.0,
        "bitwise_match": True,
    }


def test_report_schema_and_roundtrip(tmp_path):
    rep = report_lib.make_report(TINY, _fake_result())
    assert rep["schema_version"] == report_lib.SCHEMA_VERSION
    assert rep["scenario"] == "tiny_test"
    # the spec lands verbatim, with tuples as JSON-round-trippable lists
    assert rep["spec"] == {
        k: list(v) if isinstance(v, tuple) else v
        for k, v in dataclasses.asdict(TINY).items()
    }
    assert rep["spec"]["engines"] == list(TINY.engines)
    # the devices the run measured, as JAX reports them
    devices = jax.devices()
    assert rep["device"] == {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
    }
    assert set(rep["engines"]) == {"loop", "scan"}
    path = report_lib.write_report(rep, tmp_path)
    assert path.name == "BENCH_tiny_test.json"
    assert report_lib.load_report(path) == rep


def test_load_report_rejects_wrong_schema(tmp_path):
    path = tmp_path / "BENCH_x.json"
    path.write_text(json.dumps({"schema_version": 999, "scenario": "x"}))
    with pytest.raises(ValueError, match="schema_version"):
        report_lib.load_report(path)


def test_gate_passes_against_itself_and_catches_regressions():
    base = report_lib.make_report(TINY, _fake_result())
    assert report_lib.check_regression(base, base) == []

    # >2x rounds/sec regression on one engine
    slow = json.loads(json.dumps(base))
    slow["engines"]["scan"]["rounds_per_sec"] /= 3.0
    fails = report_lib.check_regression(slow, base, factor=2.0)
    assert any("scan" in f and "regressed" in f for f in fails)
    # within 2x: no failure
    ok = json.loads(json.dumps(base))
    ok["engines"]["scan"]["rounds_per_sec"] /= 1.5
    assert report_lib.check_regression(ok, base, factor=2.0) == []

    # retracing engine
    traced = json.loads(json.dumps(base))
    traced["engines"]["scan"]["trace_count"] = 7
    assert any("trace_count" in f for f in report_lib.check_regression(traced, base))

    # lost bit-identity
    diverged = json.loads(json.dumps(base))
    diverged["bitwise_match"] = False
    assert any(
        "bit-identical" in f for f in report_lib.check_regression(diverged, base)
    )

    # collapsed speedup
    flat = json.loads(json.dumps(base))
    flat["speedup_rounds_per_sec"] = 1.0
    assert any("speedup" in f for f in report_lib.check_regression(flat, base))

    # mismatched scenario
    other = json.loads(json.dumps(base))
    other["scenario"] = "something_else"
    assert any("mismatch" in f for f in report_lib.check_regression(other, base))


def test_cli_list_and_tiny_run(tmp_path, capsys):
    from repro.bench import run as run_cli

    assert run_cli.main(["--list"]) == 0
    out = capsys.readouterr().out
    assert "bench_smoke" in out and "fig5_500" in out


def test_sample_sweep_scenarios_registered():
    for name, n in [("sample_sweep_smoke", 256), ("sample_sweep_n1e3", 1_000),
                    ("sample_sweep_n1e4", 10_000)]:
        spec = scenarios.get_scenario(name)
        assert spec.n_clients == n
        assert spec.relay_backend == "segment" and spec.policy == "sparse"
        assert spec.topology == "geometric" and spec.sampling == "fixed_k"
    smoke = scenarios.get_scenario("sample_sweep_smoke")
    assert smoke.check_backend == "einsum"  # parity gate built in


def test_spec_validation_for_sampling_and_segment():
    base = scenarios.get_scenario("sample_sweep_smoke")
    with pytest.raises(ValueError, match="sim path only"):
        dataclasses.replace(base, step="mesh")  # sampling check fires first
    with pytest.raises(ValueError, match="single-host"):
        dataclasses.replace(base, sampling="none", step="mesh")
    with pytest.raises(ValueError, match="sparse"):
        dataclasses.replace(base, policy="adaptive")
    with pytest.raises(ValueError, match="sampling"):
        dataclasses.replace(base, sampling="importance")
    with pytest.raises(ValueError, match="sample_k"):
        dataclasses.replace(base, sample_k=0)
    with pytest.raises(ValueError, match="geo_degree"):
        dataclasses.replace(base, geo_degree=0.0)
    # sampling requires the sim step path (mask handoff lives there)
    dense = scenarios.get_scenario("bench_smoke")
    with pytest.raises(ValueError, match="sim"):
        dataclasses.replace(dense, sampling="uniform", sample_rate=0.5,
                            step="mesh")


def test_sample_sweep_smoke_bundle_builds_sparse_stack():
    from repro import channels
    from repro.core import relay as relay_lib

    spec = dataclasses.replace(
        scenarios.get_scenario("sample_sweep_smoke"),
        n_clients=32, n_train=64, rounds=3, sample_k=8,
    )
    bundle = scenarios.build(spec)
    adj = bundle.base_adjacency()
    assert adj.shape == (32, 32)
    assert bundle.base_adjacency() is adj  # memoized, built once
    sched = bundle.make_schedule()
    pol = bundle.make_policy()
    assert isinstance(pol, channels.SparseOptAlpha)
    states = list(sched.rounds(3))
    assert all(s.active is not None and s.n_active <= 8 for s in states)
    A = pol.relay_matrix(states[0])
    assert isinstance(A, relay_lib.EdgeRelay)
