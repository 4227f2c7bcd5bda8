"""Schema-versioned ``BENCH_<scenario>.json`` reports + the CI perf gate.

Report schema (``schema_version`` 1)::

    {
      "schema_version": 1,
      "scenario": "<name>",
      "description": "...",
      "created_unix": 1234567890,
      "jax_version": "0.9.0",
      "backend": "cpu",
      "device": {"platform": "cpu", "kind": "cpu", "count": 1},
      "spec": { ...ScenarioSpec fields... },
      "engines": {
        "loop": {"wall_s": ..., "compile_s": ..., "rounds_per_sec": ...,
                 "trace_count": ..., "dispatches": ..., "final_loss": ...,
                 "overlap_fraction": null, "host_prep_s": null,
                 "host_wait_s": null},
        "scan": { ... },
        "pipelined": { ...incl. the measured overlap metrics... }
      },
      "speedup_rounds_per_sec": 6.2,
      "speedups_vs_loop": {"scan": 6.2, "pipelined": 7.4},
      "bitwise_match": true,
      "telemetry": {            // --trace runs only; null otherwise
        "pipelined": {"wall_s": ..., "phases": {"solve": ..., ...},
                      "attributed_fraction": ..., "counters": {...},
                      "events": ..., "dropped": ...},
        ...
      }
    }

The ``device`` block, the overlap metrics, ``speedups_vs_loop``,
``model_params``, ``kernel_check``, ``shard_check``, ``engine_check``,
``async_check``, ``ttac`` and the ``telemetry`` block are additive v1
fields (older readers ignore them; older reports read back with them
absent) — see ``docs/benchmarks.md`` for the field-by-field reading guide
and ``docs/observability.md`` for the telemetry block.  ``device`` names what
the run measured: the platform, ``device_kind`` and count of
``jax.devices()``.  ``model_params`` is the model's total parameter count D
(the x-axis of the relay D-sweep); ``kernel_check`` records the mandatory
pallas-vs-reference parity pass (backend, tolerances, measured max |Δ|,
kernel throughput) for scenarios with ``check_backend`` set.
``shard_check`` (shard scenarios only, whose ``spec.devices`` records the
mesh size) is the multi-device gate: sharded engines bitwise-identical to
each other, allclose to the single-device loop at the recorded tolerance
(``max_abs_diff`` is the measured divergence — see docs/distributed.md).
``engine_check`` (runs off the CPU, whose gates hold re-runs at
``gate_precision`` "highest") holds each timed engine's max |Δ| from the
timed loop, recorded and not gated.
``async_check`` (delayed async scenarios) records the mandatory delay-0
parity gate — the async engine with the delay stripped is bitwise-identical
to the loop; ``ttac`` (scenarios with ``ttac_target_loss`` set) is the
per-engine time-to-accuracy block: first round / derived second at which
the training loss reached the target.

The gate (:func:`check_regression`) compares per-engine ``rounds_per_sec``
against a checked-in baseline report and fails when throughput regresses by
more than ``factor`` (default 2×: generous enough to absorb CI-runner noise,
tight enough to catch a lost fusion or an accidental per-round sync).  It
also re-asserts the qualitative invariants the baseline recorded:
``bitwise_match`` and the scan-beats-loop speedup staying within the same
``factor`` of the baseline's.
"""
from __future__ import annotations

import dataclasses
import json
import pathlib
import time

import jax

from repro.bench.harness import EngineRun
from repro.bench.scenarios import ScenarioSpec

SCHEMA_VERSION = 1


def device_info() -> dict:
    """The devices a run measured, as JAX reports them."""
    devices = jax.devices()
    return {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
    }


def make_report(spec: ScenarioSpec, result: dict) -> dict:
    """Assemble the JSON payload from a :func:`run_scenario` result."""
    runs: dict[str, EngineRun] = result["runs"]
    telemetry = {
        name: run.telemetry for name, run in runs.items() if run.telemetry is not None
    }
    return {
        "schema_version": SCHEMA_VERSION,
        "scenario": spec.name,
        "description": spec.description,
        "created_unix": int(time.time()),
        "jax_version": jax.__version__,
        "backend": jax.default_backend(),
        "device": device_info(),
        # tuples (e.g. spec.engines) become lists so the payload is exactly
        # what a JSON round trip reads back
        "spec": {
            k: list(v) if isinstance(v, tuple) else v
            for k, v in dataclasses.asdict(spec).items()
        },
        "engines": {name: run.as_dict() for name, run in runs.items()},
        "speedup_rounds_per_sec": result["speedup"],
        "speedups_vs_loop": result.get("speedups", {}),
        "bitwise_match": result["bitwise_match"],
        "model_params": result.get("model_params"),
        "kernel_check": result.get("kernel_check"),
        "shard_check": result.get("shard_check"),
        "engine_check": result.get("engine_check"),
        "async_check": result.get("async_check"),
        "ttac": result.get("ttac"),
        "telemetry": telemetry or None,
    }


def report_path(out_dir, scenario: str) -> pathlib.Path:
    return pathlib.Path(out_dir) / f"BENCH_{scenario}.json"


def write_report(report: dict, out_dir=".") -> pathlib.Path:
    path = report_path(out_dir, report["scenario"])
    path.parent.mkdir(parents=True, exist_ok=True)
    path.write_text(json.dumps(report, indent=2, sort_keys=True) + "\n")
    return path


def load_report(path) -> dict:
    with open(path) as f:
        report = json.load(f)
    version = report.get("schema_version")
    if version != SCHEMA_VERSION:
        raise ValueError(f"{path}: schema_version {version!r} != {SCHEMA_VERSION}")
    return report


def check_regression(report: dict, baseline: dict, *, factor: float = 2.0) -> list[str]:
    """Compare a fresh report against a baseline; returns failure strings
    (empty ⇒ gate passes).  Only engines present in both are compared."""
    failures = []
    if report.get("scenario") != baseline.get("scenario"):
        failures.append(
            f"scenario mismatch: report {report.get('scenario')!r} vs "
            f"baseline {baseline.get('scenario')!r}"
        )
        return failures
    for name, base in baseline.get("engines", {}).items():
        cur = report.get("engines", {}).get(name)
        if cur is None:
            failures.append(f"engine {name!r} missing from report")
            continue
        base_rps, cur_rps = base["rounds_per_sec"], cur["rounds_per_sec"]
        if cur_rps * factor < base_rps:
            failures.append(
                f"{name}: rounds/sec regressed >{factor:g}x "
                f"({cur_rps:.1f} vs baseline {base_rps:.1f})"
            )
        if cur["trace_count"] > base["trace_count"]:
            failures.append(
                f"{name}: trace_count grew ({cur['trace_count']} vs "
                f"baseline {base['trace_count']}) — the engine retraces"
            )
    if baseline.get("bitwise_match") and report.get("bitwise_match") is False:
        failures.append("scan engine no longer bit-identical to the loop")
    base_speedup = baseline.get("speedup_rounds_per_sec")
    cur_speedup = report.get("speedup_rounds_per_sec")
    if base_speedup and cur_speedup and cur_speedup * factor < base_speedup:
        failures.append(
            f"scan-over-loop speedup collapsed: {cur_speedup:.2f}x vs "
            f"baseline {base_speedup:.2f}x"
        )
    return failures
