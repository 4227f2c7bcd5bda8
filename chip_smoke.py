"""Smoke run of the ColRel round on a TPU, through the benchmark's own path.

    python chip_smoke.py               # one chip
    python chip_smoke.py --four-chips  # the sharded engine on four chips

One chip: the paper's §V setting (ResNet-20 at full width, D = 272,282, on
CIFAR-shaped data with n = 10 clients) built from the ``resnet20_cifar``
scenario and run by ``repro.bench.harness.run_scenario`` — the loop, scan
and pipelined engines under the loop ≡ scan ≡ pipelined gate (held, off
the CPU, by re-runs at "highest" matmul precision), then the kernel check on
the ``pallas`` and ``pallas_fused`` relay backends against the einsum
reference.  It also compiles the pipelined chunk step with a
kernel backend and requires a Mosaic kernel (``tpu_custom_call``) in it, so
no interpreted kernel runs on the chip.

Four chips (``--four-chips``, this phase only): the client-sharded engine
(``build_sharded_scan_round_step`` + ``ShardedScanEngine``) on a 4-device
``("clients",)`` mesh in ``mesh8_ring_churn``'s setting, n = 8 clients (two
per chip) under rotating churn and correlated shadowing, with the gather and
the ring exchange, each held to the harness's shard gate against the
single-device loop.

Every line but the last is a human-readable record.  The last line is one
JSON object, ``{"ok": true, "device": {...}}``, printed only when every
phase passed.  Without a TPU the script exits non-zero and prints no result.
It runs in one process: the chip belongs to the process that touched JAX.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import pathlib
import sys
import time

import numpy as np


def _require(ok: bool, what: str) -> None:
    if not ok:
        raise SystemExit(f"chip_smoke: FAILED: {what}")


def _losses_fall(name: str, losses) -> None:
    losses = np.asarray(losses, np.float64)
    first, last = float(losses[0]), float(losses[-1])
    print(f"  {name:>18}: loss {first:.6f} -> {last:.6f}")
    _require(bool(np.all(np.isfinite(losses))), f"{name}: non-finite loss")
    _require(last < first, f"{name}: loss did not fall ({first} -> {last})")


def _print_runs(result: dict) -> None:
    for name, run in result["runs"].items():
        print(
            f"  {name:>18}: traces {run.trace_count}  compile "
            f"{run.compile_s:.3f}s  {run.rounds_per_sec:.3f} rounds/s  "
            f"dispatches {run.dispatches}"
        )


def _print_engine_check(result: dict) -> None:
    check = result["engine_check"]
    _require(check is not None, "the gates did not run apart from the timed runs")
    print(f"  gates held at {check['gate_precision']} matmul precision")
    for name, diff in sorted(check["timed_max_abs_diff"].items()):
        print(f"  timed {name} vs timed loop: max |Δ| {diff:.3e} (not gated)")


def resnet20_spec():
    """The paper's §V setting at its own widths: n = 10 clients, a CIFAR
    per-client batch of 32, two local steps, 32 rounds over 8-round channel
    epochs."""
    from repro.bench.scenarios import get_scenario

    return dataclasses.replace(
        get_scenario("resnet20_cifar"),
        name="chip_smoke_resnet20",
        n_clients=10,
        rounds=32,
        local_steps=2,
        local_batch=32,
        n_train=5000,
        chunk=8,
        check_backend="pallas",
    )


def kernel_in_pipelined_step(spec) -> int:
    """Compile the pipelined engine's chunk step on ``spec.check_backend``
    and return how many Mosaic kernels (``tpu_custom_call``) it holds."""
    import jax
    import jax.numpy as jnp

    from repro.bench.scenarios import build
    from repro.fl.engine import PipelinedScanEngine

    kspec = dataclasses.replace(
        spec, relay_backend=spec.check_backend, check_backend="none"
    )
    bundle = build(kspec)
    sim = bundle.make_sim()
    engine = PipelinedScanEngine(sim, chunk=kspec.chunk)
    params = bundle.init_fn(jax.random.key(kspec.seed))
    loader = bundle.make_loader()
    batches = jax.tree.map(
        lambda *xs: jnp.asarray(np.stack(xs)),
        *[loader.round_batch(kspec.local_steps, kspec.local_batch)
          for _ in range(kspec.chunk)],
    )
    n = kspec.n_clients
    text = (
        engine._chunk_fn.lower(
            jax.random.key(kspec.seed + 1),
            params,
            sim.init_server_state(params),
            batches,
            jnp.ones((kspec.chunk,), bool),
            jnp.full((n, n), 1.0 / n, jnp.float32),
            jnp.asarray(bundle.base_p(), jnp.float32),
            kspec.lr,
            None,
        )
        .compile()
        .as_text()
    )
    return text.count("tpu_custom_call")


def one_chip_phase() -> None:
    from repro.bench.harness import run_scenario

    spec = resnet20_spec()
    t0 = time.perf_counter()
    result = run_scenario(spec)
    D = result["model_params"]
    print(
        f"resnet20: D = {D:,}  n = {spec.n_clients}  local batch = "
        f"{spec.local_batch}  local steps = {spec.local_steps}  rounds = "
        f"{spec.rounds}  ({time.perf_counter() - t0:.1f}s)"
    )
    _require(D == 272_282, f"ResNet-20 width {D} != 272,282")
    _print_runs(result)
    for name in ("loop", "scan", "pipelined"):
        _losses_fall(name, result["runs"][name].losses)
    traces = {n: result["runs"][n].trace_count for n in ("loop", "scan", "pipelined")}
    _require(all(t == 1 for t in traces.values()), f"an engine retraced: {traces}")
    # the harness has raised unless the gated runs are bitwise-identical
    print(f"  bitwise_match (loop == scan == pipelined): {result['bitwise_match']}")
    _print_engine_check(result)
    checks = [result["kernel_check"]]

    # the fused kernel: a second harness pass, against the loop reference
    fused = run_scenario(
        dataclasses.replace(spec, check_backend="pallas_fused"), engines=("loop",)
    )
    _print_runs(fused)
    checks.append(fused["kernel_check"])
    for check in checks:
        _require(check is not None and check["allclose"], f"kernel check: {check}")
        print(
            f"  kernel check [{check['backend']} vs {check['reference_backend']}]: "
            f"per round max |Δ| {check['max_abs_diff']:.3e}, compiled scan "
            f"after round 1 {check['first_round_max_abs_diff']:.3e} (atol "
            f"{check['atol']:g}, rtol {check['rtol']:g}); timed run after the "
            f"whole horizon {check['horizon_max_abs_diff']:.3e} (not gated)"
        )

    kernels = kernel_in_pipelined_step(spec)
    print(f"  pipelined chunk step [{spec.check_backend}]: {kernels} tpu_custom_call")
    _require(kernels > 0, "no Mosaic kernel in the compiled kernel-backend step")


def four_chip_phase() -> None:
    import jax

    from repro.bench.harness import run_scenario
    from repro.bench.scenarios import build, get_scenario
    from repro.fl.distributed import build_sharded_scan_round_step
    from repro.fl.engine import ShardedScanEngine
    from repro.launch.mesh import make_client_mesh

    _require(len(jax.devices()) >= 4, f"--four-chips needs 4 devices: {jax.devices()}")
    for exchange in ("gather", "ring"):
        # mesh8_ring_churn's setting (n = 8, rotating churn, correlated
        # shadowing) on four chips: two clients per chip
        spec = dataclasses.replace(
            get_scenario("mesh8_ring_churn"),
            name=f"chip_smoke_mesh4_{exchange}",
            devices=4,
            exchange=exchange,
        )
        if exchange == "gather":
            # where one staged batch lives: each device holds its own
            # clients' block (dim 1 of a (rounds, n, T, b, ...) leaf)
            bundle = build(spec)
            mesh = make_client_mesh(4)
            engine = ShardedScanEngine(
                build_sharded_scan_round_step(
                    bundle.loss_fn,
                    n_clients=spec.n_clients,
                    local_steps=spec.local_steps,
                    mesh=mesh,
                ),
                mesh=mesh,
            )
            loader = bundle.make_loader()
            host = jax.tree.map(
                lambda *xs: np.stack(xs),
                *[loader.round_batch(spec.local_steps, spec.local_batch)
                  for _ in range(2)],
            )
            staged = engine.place(host)["inputs"]
            blocks = {}
            for shard in staged.addressable_shards:
                clients = shard.index[1]
                print(
                    f"  staged inputs {staged.shape}: device {shard.device.id} "
                    f"holds clients {clients.start}:{clients.stop} "
                    f"{shard.data.shape}"
                )
                blocks[shard.device.id] = clients
            m = spec.n_clients // 4
            _require(
                len(blocks) == 4
                and sorted((s.start, s.stop) for s in blocks.values())
                == [(i * m, (i + 1) * m) for i in range(4)],
                f"staged batch is not split by clients over 4 devices: {blocks}",
            )
        result = run_scenario(spec)
        check = result["shard_check"]
        print(
            f"mesh4 [{exchange}]: D = {result['model_params']:,}  n = "
            f"{spec.n_clients}  local batch = {spec.local_batch}  rounds = "
            f"{spec.rounds}"
        )
        _print_runs(result)
        for name in ("loop", "scan", "pipelined"):
            _losses_fall(name, result["runs"][name].losses)
        _require(check is not None and check["allclose"], f"shard check: {check}")
        print(
            f"  shard check [{exchange}]: max |Δ| vs single-device loop "
            f"{check['max_abs_diff']:.3e} (atol {check['atol']:g}, rtol "
            f"{check['rtol']:g}); scan == pipelined bitwise: "
            f"{check['bitwise_among_sharded']}"
        )
        _print_engine_check(result)
        for name in ("scan", "pipelined"):
            count = result["runs"][name].trace_count
            _require(count == 1, f"{exchange}/{name}: trace_count {count} != 1")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description=__doc__, formatter_class=argparse.RawDescriptionHelpFormatter
    )
    ap.add_argument(
        "--four-chips",
        action="store_true",
        help="run only the client-sharded engine on a 4-chip host",
    )
    args = ap.parse_args(argv)

    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(
            f"chip_smoke: no TPU found (JAX sees {devices[0].platform} "
            f"devices); this check runs on the chip only",
            file=sys.stderr,
        )
        return 1
    sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent / "src"))
    try:
        from repro.bench.report import device_info
        from repro.launch.compile_cache import use_compile_cache
    except ImportError as e:
        print(f"chip_smoke: the repro package is not beside this script: {e}",
              file=sys.stderr)
        return 1
    print(f"compile cache: {use_compile_cache()}")
    device = device_info()
    print(f"device: {device['kind']} x{device['count']} (jax {jax.__version__})")
    t0 = time.perf_counter()
    if args.four_chips:
        four_chip_phase()
    else:
        one_chip_phase()
    print(f"all phases passed in {time.perf_counter() - t0:.1f}s")
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
