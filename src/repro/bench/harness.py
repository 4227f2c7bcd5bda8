"""Timing harness: one scenario, every engine, cold + warm runs.

Per engine the harness runs the scenario twice on one simulator instance:
the **cold** run pays tracing + XLA compilation, the **warm** run is
steady-state throughput.  Reported quantities:

  wall_s          warm-run wall clock for all ``spec.rounds`` rounds
  compile_s       cold wall minus warm wall (the one-time tracing+compile
                  cost the scan engines amortize over the whole horizon)
  rounds_per_sec  spec.rounds / wall_s — the headline engine throughput
  trace_count     compiles observed across both runs (the no-retrace
                  invariant: 1 for the loop step, ≤ 2 for the scan engines)

The ``pipelined`` engine additionally reports its host/device overlap
(warm run): ``host_prep_s`` (worker-thread staging time), ``host_wait_s``
(how long the consumer actually blocked on staged work) and
``overlap_fraction = 1 - wait/prep`` — the share of host work hidden
behind device execution.

Fairness: the per-round batch stream is pre-generated once (host numpy) and
replayed identically to every run of every engine, and each run builds a
fresh schedule / policy / loader from the same seeds — so all engines
consume bit-identical data, τ randomness and relay matrices, and the harness
can (and does) assert their final parameters match bit-for-bit.

``spec.step = "mesh"`` swaps the execution path under measurement: instead
of ``FLSimulator`` / :class:`EpochScanEngine`, the engines are the
production mesh round steps — per-round :func:`build_round_step` ("loop"),
one :func:`build_scan_round_step` dispatch per channel epoch ("scan"), or
one τ-fused :func:`build_fused_scan_round_step` dispatch per epoch with the
host side prefetched ("pipelined").  Same fairness contract, same bitwise
assertion.

``spec.step = "shard"`` measures the **multi-device** path: "loop" stays
the single-device per-round reference, while "scan" / "pipelined" run the
`shard_map` step (:func:`build_sharded_scan_round_step`) through
:class:`~repro.fl.engine.ShardedScanEngine` across a forced host mesh of
``spec.devices`` devices — serial vs prefetched staging, with staged epochs
``device_put`` directly into their sharded layout.  The bitwise assertion
becomes the *shard gate*: sharded engines bitwise-identical to each other,
allclose (1e-5) to the loop — the measured max |Δ| lands in the report's
``shard_check`` block (see docs/distributed.md for why the loop comparison
is a tolerance, not bitwise).
"""
from __future__ import annotations

import contextlib
import dataclasses
import pathlib
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.bench.scenarios import ScenarioBundle, ScenarioSpec, build
from repro.channels.scheduler import SegmentPrefetcher
from repro.core.aggregation import ServerOpt
from repro.fl.distributed import (
    build_fused_scan_round_step,
    build_round_step,
    build_scan_round_step,
    build_sharded_scan_round_step,
)
from repro.fl.async_engine import AsyncRoundEngine
from repro.fl.engine import (
    EpochScanEngine,
    PipelinedScanEngine,
    ShardedScanEngine,
    run_rounds_loop,
)
from repro.launch.mesh import make_client_mesh
from repro.obs import (
    NULL_TRACER,
    Tracer,
    phase_attribution,
    write_chrome_trace,
    write_jsonl,
)
from repro.optim.sgd import ClientOpt
from repro.utils import tree_size

# tolerance of the mandatory kernel parity check (run_scenario): one round on
# the kernel backend must match the same round on the reference backend to
# f32 accumulation accuracy (the shard gate reuses it over the horizon)
KERNEL_CHECK_RTOL = 1e-5
KERNEL_CHECK_ATOL = 1e-5


def _pinned() -> bool:
    """Whether the gates run apart from the timed runs, at
    ``jax.default_matmul_precision("highest")``.  On the CPU f32 contracts
    exactly and the timed runs are the gated runs.  A TPU contracts f32 in
    one bf16 pass by default, and two XLA programs holding the same round
    round it differently: ResNet-20's loop and scan finish 2.15e-3 apart
    after 32 rounds on a TPU v5e, and bitwise-identical under "highest"."""
    return jax.devices()[0].platform != "cpu"


def _gate_precision():
    """The context every gate's runs execute in (see :func:`_pinned`)."""
    if _pinned():
        return jax.default_matmul_precision("highest")
    return contextlib.nullcontext()


@dataclasses.dataclass
class EngineRun:
    """One engine's measurements on one scenario.

    ``dispatches`` counts compiled round-engine calls only (loop: one step
    call per round; scan: one chunk scan per ⌈len/chunk⌉ per epoch;
    pipelined: identical chunk count, but each dispatch also covers the τ
    draws) — separate τ-sampling calls and H2D transfers are excluded on
    all sides.

    The ``host_*`` / ``overlap_fraction`` fields are the pipelined engine's
    prefetcher measurements (warm run); ``None`` for engines without a
    prefetcher.
    """

    engine: str
    wall_s: float
    compile_s: float
    rounds_per_sec: float
    trace_count: int
    dispatches: int
    final_loss: float
    overlap_fraction: float | None = None
    steady_overlap_fraction: float | None = None
    host_prep_s: float | None = None
    host_wait_s: float | None = None
    chunks_staged: int | None = None
    # traced-pass artifacts (``trace_dir`` runs only): the Chrome trace on
    # disk and the per-phase attribution summary.  The traced pass is a
    # *third* run — its spans and fences (every engine but the pipelined
    # one) cost time (observer effect), so the perf numbers above always
    # come from the untraced warm run.
    trace_path: str | None = None
    telemetry: dict | None = None
    # the warm run's per-round loss trajectory (host floats) — consumed by
    # the time-to-accuracy block (run_scenario), not serialized per engine
    losses: list | None = None

    def as_dict(self) -> dict:
        d = dataclasses.asdict(self)
        # the telemetry block is aggregated once at the report's top level
        # (make_report), not duplicated per engine entry; the loss
        # trajectory is distilled into the ttac block
        d.pop("telemetry")
        d.pop("losses")
        return d


def _pregenerate_batches(bundle: ScenarioBundle) -> list:
    """Materialize the full per-round batch stream once (numpy), replayed
    identically to every engine run."""
    spec = bundle.spec
    loader = bundle.make_loader()
    return [
        loader.round_batch(spec.local_steps, spec.local_batch)
        for _ in range(spec.rounds)
    ]


def _run_once(bundle: ScenarioBundle, engine, batches: list, tracer=None):
    """One full pass over the scenario; returns (wall_s, metrics, params).
    ``tracer`` threads telemetry through every layer of the pass (schedule
    instants, policy solve spans, engine dispatch/fence spans)."""
    spec = bundle.spec
    schedule = bundle.make_schedule()
    policy = bundle.make_policy(tracer=tracer)
    if tracer is not None:
        schedule.tracer = tracer
    params = bundle.init_fn(jax.random.key(spec.seed))
    fused = isinstance(
        engine, (EpochScanEngine, PipelinedScanEngine, AsyncRoundEngine)
    )
    sim = engine.sim if fused else engine
    server_state = sim.init_server_state(params)
    key = jax.random.key(spec.seed + 1)
    stream = iter(batches)
    t0 = time.perf_counter()
    if fused:
        params, server_state, metrics, _ = engine.run_schedule(
            key,
            params,
            server_state,
            schedule=schedule,
            rounds=spec.rounds,
            next_batch=lambda: next(stream),
            lr=spec.lr,
            policy=policy,
        )
    else:
        params, server_state, metrics, _ = run_rounds_loop(
            engine,
            key,
            params,
            server_state,
            schedule=schedule,
            rounds=spec.rounds,
            next_batch=lambda: next(stream),
            lr=spec.lr,
            policy=policy,
            tracer=tracer,
        )
    if tracer is not None:
        # the trailing drain belongs to the device phase too
        with tracer.span("run.finalize", cat="device", track="device"):
            jax.block_until_ready(params)
    else:
        jax.block_until_ready(params)
    return time.perf_counter() - t0, metrics, params


def _finish_trace(tracer: Tracer, trace_dir, scenario: str, engine: str):
    """Export a traced pass (Chrome trace + JSONL) and distill its telemetry
    block: per-phase attribution plus counters.  ``attributed_fraction`` is
    the share of the trace's wall span covered by phase spans — the rest is
    untraced host glue."""
    trace_dir = pathlib.Path(trace_dir)
    path = trace_dir / f"TRACE_{scenario}_{engine}.json"
    write_chrome_trace(tracer, path)
    write_jsonl(tracer, path.with_suffix(".jsonl"))
    phases = phase_attribution(tracer.events)
    wall = tracer.wall_seconds()
    telemetry = {
        "wall_s": wall,
        "phases": phases,
        "attributed_fraction": sum(phases.values()) / wall if wall > 0 else 0.0,
        "counters": dict(tracer.counters),
        "events": len(tracer.events),
        "dropped": tracer.dropped,
    }
    return str(path), telemetry


class _MeshStep:
    """The jitted mesh round steps with trace counting — the bench analogue
    of ``FLSimulator.trace_count`` for ``repro.fl.distributed``.  The
    counters increment at trace time only (python side of the jit)."""

    def __init__(self, bundle: ScenarioBundle):
        spec = bundle.spec
        self.trace_count = 0
        kw = dict(
            n_clients=spec.n_clients,
            local_steps=spec.local_steps,
            relay_mode="fused",
            relay_backend=spec.relay_backend,
            block_d=spec.block_d,
            client_opt=ClientOpt(kind="sgd", weight_decay=1e-4),
            server_opt=ServerOpt(),
        )
        round_fn = build_round_step(bundle.loss_fn, **kw)
        scan_fn = build_scan_round_step(bundle.loss_fn, **kw)
        fused_fn = build_fused_scan_round_step(bundle.loss_fn, **kw)

        def counted_round(params, ss, batch, tau, lr, A):
            self.trace_count += 1
            return round_fn(params, ss, batch, tau, lr, A)

        def counted_scan(params, ss, batches, taus, lr, A):
            self.trace_count += 1
            return scan_fn(params, ss, batches, taus, lr, A)

        def counted_fused(key, params, ss, batches, p, lr, A):
            self.trace_count += 1
            return fused_fn(key, params, ss, batches, p, lr, A)

        self.round = jax.jit(counted_round)
        self.scan = jax.jit(counted_scan)
        self.fused = jax.jit(counted_fused)


def _run_mesh_once(
    bundle: ScenarioBundle, step: _MeshStep, name: str, batches: list, tracer=None
):
    """One full mesh-path pass; returns (wall_s, losses, params, n_segments,
    prefetch_stats).  Walks ``schedule.segments()`` exactly like
    ``EpochScanEngine.run_schedule``: one OPT-α solve and one τ block per
    epoch, with the τ key chain advanced once per round so every engine
    consumes identical randomness.  The ``pipelined`` engine stages whole
    segments through a :class:`SegmentPrefetcher` and dispatches the τ-fused
    epoch scan — the key chain advances on device, identically.  ``tracer``
    adds the same span set as the sim path (stage/dispatch/device)."""
    spec = bundle.spec
    schedule = bundle.make_schedule()
    policy = bundle.make_policy(tracer=tracer)
    tr = NULL_TRACER if tracer is None else tracer
    if tracer is not None:
        schedule.tracer = tracer
    if policy is None:
        raise ValueError("the mesh round step needs a relay policy")
    params = bundle.init_fn(jax.random.key(spec.seed))
    server_state = None
    key = jax.random.key(spec.seed + 1)
    stream = iter(batches)
    losses = []
    n_segments = 0
    prefetch_stats = None
    t0 = time.perf_counter()
    if name == "pipelined":
        # chunk=spec.rounds ⇒ one staged item per segment: the mesh scan
        # path dispatches whole epochs, so the pipelined variant must too
        # for the dispatch counts to be comparable
        prefetcher = SegmentPrefetcher(
            schedule,
            spec.rounds,
            chunk=spec.rounds,
            next_batch=lambda: next(stream),
            policy=policy,
            tracer=tracer,
        )
        try:
            for item in prefetcher:
                seg = item.segment
                if seg.active is not None:
                    raise ValueError("mesh bench path does not drive churn masks")
                n_segments += 1
                A = jnp.asarray(item.A, jnp.float32)
                p = jnp.asarray(seg.p, jnp.float32)
                # item.batches is already device-resident (staged transfer)
                if tr.enabled:
                    with tr.span(
                        "mesh.fused", cat="dispatch", epoch=seg.epoch_id
                    ):
                        key, params, server_state, seg_losses = step.fused(
                            key, params, server_state, item.batches, p, spec.lr, A
                        )
                else:
                    key, params, server_state, seg_losses = step.fused(
                        key, params, server_state, item.batches, p, spec.lr, A
                    )
                prefetcher.note_inflight(seg_losses)
                if tr.enabled:
                    with tr.span(
                        "mesh.device",
                        cat="device",
                        track="device",
                        epoch=seg.epoch_id,
                    ):
                        jax.block_until_ready(seg_losses)
                losses.append(seg_losses)
        finally:
            prefetcher.close()
        prefetch_stats = prefetcher.stats
    else:
        for seg in schedule.segments(spec.rounds):
            if seg.active is not None:
                raise ValueError("mesh bench path does not drive churn masks")
            n_segments += 1
            A = jnp.asarray(policy.relay_matrix(seg.state), jnp.float32)
            p = jnp.asarray(seg.p, jnp.float32)
            taus = []
            for _ in range(seg.n_rounds):
                key, sub = jax.random.split(key)
                taus.append(jax.random.bernoulli(sub, p).astype(jnp.float32))
            seg_batches = [next(stream) for _ in range(seg.n_rounds)]
            if name == "loop":
                for r in range(seg.n_rounds):
                    if tr.enabled:
                        with tr.span("mesh.stage", cat="stage", epoch=seg.epoch_id):
                            batch = jax.tree.map(jnp.asarray, seg_batches[r])
                        with tr.span(
                            "mesh.round", cat="dispatch", epoch=seg.epoch_id
                        ):
                            params, server_state, loss = step.round(
                                params, server_state, batch, taus[r], spec.lr, A
                            )
                        with tr.span(
                            "mesh.sync", cat="device", track="device"
                        ):
                            losses.append(float(loss))
                        continue
                    batch = jax.tree.map(jnp.asarray, seg_batches[r])
                    params, server_state, loss = step.round(
                        params, server_state, batch, taus[r], spec.lr, A
                    )
                    # the per-round host sync every loop driver models (see
                    # run_rounds_loop) — without it async dispatch pipelines
                    # the round calls and the loop baseline measures the
                    # wrong thing
                    losses.append(float(loss))
            else:
                if tr.enabled:
                    with tr.span("mesh.stage", cat="stage", epoch=seg.epoch_id):
                        stacked = jax.tree.map(
                            lambda *xs: jnp.asarray(np.stack(xs)), *seg_batches
                        )
                    with tr.span("mesh.scan", cat="dispatch", epoch=seg.epoch_id):
                        params, server_state, seg_losses = step.scan(
                            params, server_state, stacked, jnp.stack(taus), spec.lr, A
                        )
                    with tr.span(
                        "mesh.device", cat="device", track="device"
                    ):
                        jax.block_until_ready(seg_losses)
                else:
                    stacked = jax.tree.map(
                        lambda *xs: jnp.asarray(np.stack(xs)), *seg_batches
                    )
                    params, server_state, seg_losses = step.scan(
                        params, server_state, stacked, jnp.stack(taus), spec.lr, A
                    )
                losses.append(seg_losses)
    if tr.enabled:
        with tr.span("run.finalize", cat="device", track="device"):
            jax.block_until_ready(params)
    else:
        jax.block_until_ready(params)
    wall = time.perf_counter() - t0
    losses = jnp.asarray(losses) if name == "loop" else jnp.concatenate(losses)
    return wall, losses, params, n_segments, prefetch_stats


def _run_mesh_engine(bundle: ScenarioBundle, name: str, batches: list, trace_dir=None):
    """Cold + warm mesh-path pass; mirrors :func:`run_engine`."""
    spec = bundle.spec
    if name not in ("loop", "scan", "pipelined"):
        raise ValueError(f"unknown engine: {name!r}")
    step = _MeshStep(bundle)
    cold_s, _, _, _, _ = _run_mesh_once(bundle, step, name, batches)
    warm = _run_mesh_once(bundle, step, name, batches)
    warm_s, losses, params, n_segments, overlap = warm
    trace_path = telemetry = None
    if trace_dir is not None:
        tracer = Tracer()
        _run_mesh_once(bundle, step, name, batches, tracer=tracer)
        trace_path, telemetry = _finish_trace(tracer, trace_dir, spec.name, name)
    dispatches = spec.rounds if name == "loop" else n_segments
    run = EngineRun(
        engine=name,
        wall_s=warm_s,
        compile_s=max(0.0, cold_s - warm_s),
        rounds_per_sec=spec.rounds / warm_s,
        trace_count=step.trace_count,
        dispatches=dispatches,
        final_loss=float(losses[-1]),
        losses=np.asarray(losses, np.float64).tolist(),
        overlap_fraction=None if overlap is None else overlap.overlap_fraction,
        steady_overlap_fraction=(
            None if overlap is None else overlap.steady_overlap_fraction
        ),
        host_prep_s=None if overlap is None else overlap.prep_s,
        host_wait_s=None if overlap is None else overlap.wait_s,
        chunks_staged=None if overlap is None else overlap.chunks_staged,
        trace_path=trace_path,
        telemetry=telemetry,
    )
    return run, params


class _ShardStep:
    """The single-device per-round reference for the shard path — the loop
    driver the sharded engines are gated against.  Unlike :class:`_MeshStep`
    it threads the churn mask (shard scenarios may rotate cohorts), so the
    trajectory is the reference for churned epochs too."""

    def __init__(self, bundle: ScenarioBundle):
        spec = bundle.spec
        self.trace_count = 0
        round_fn = build_round_step(
            bundle.loss_fn,
            n_clients=spec.n_clients,
            local_steps=spec.local_steps,
            relay_mode="fused",
            relay_backend=spec.relay_backend,
            block_d=spec.block_d,
            client_opt=ClientOpt(kind="sgd", weight_decay=1e-4),
            server_opt=ServerOpt(),
        )

        def counted_round(params, ss, batch, tau, lr, A, active):
            self.trace_count += 1
            return round_fn(params, ss, batch, tau, lr, A, active=active)

        self.round = jax.jit(counted_round)


def _shard_mesh(spec: ScenarioSpec):
    """The host mesh a shard scenario runs on: ``spec.devices`` devices on
    one axis — the client axis in clients mode, the model axis in D mode.
    Raises (with the XLA_FLAGS hint) when the host presents fewer devices."""
    axis = "clients" if spec.shard == "clients" else "model"
    return make_client_mesh(spec.devices, axis=axis)


def _run_shard_once(bundle: ScenarioBundle, ex, name: str, batches: list, tracer=None):
    """One full shard-path pass; returns (wall_s, losses, params, dispatches,
    prefetch_stats).  The loop reference draws τ host-side with exactly the
    sharded step's op order (split, then ``Bernoulli(p)`` on the subkey), so
    every engine consumes identical randomness; churn masks flow from the
    schedule segments on both sides."""
    spec = bundle.spec
    schedule = bundle.make_schedule()
    policy = bundle.make_policy(tracer=tracer)
    tr = NULL_TRACER if tracer is None else tracer
    if tracer is not None:
        schedule.tracer = tracer
    if policy is None:
        raise ValueError("the sharded round step needs a relay policy")
    params = bundle.init_fn(jax.random.key(spec.seed))
    server_state = None
    key = jax.random.key(spec.seed + 1)
    stream = iter(batches)
    t0 = time.perf_counter()
    if name == "loop":
        losses = []
        for seg in schedule.segments(spec.rounds):
            A = jnp.asarray(policy.relay_matrix(seg.state), jnp.float32)
            p = jnp.asarray(seg.p, jnp.float32)
            active = (
                None
                if seg.active is None
                else jnp.asarray(seg.active, jnp.float32)
            )
            for _ in range(seg.n_rounds):
                key, sub = jax.random.split(key)
                tau = jax.random.bernoulli(sub, p).astype(jnp.float32)
                if tr.enabled:
                    with tr.span("shard.stage", cat="stage", epoch=seg.epoch_id):
                        batch = jax.tree.map(jnp.asarray, next(stream))
                    with tr.span(
                        "shard.round", cat="dispatch", epoch=seg.epoch_id
                    ):
                        params, server_state, loss = ex.round(
                            params, server_state, batch, tau, spec.lr, A, active
                        )
                    with tr.span("shard.sync", cat="device", track="device"):
                        losses.append(float(loss))
                    continue
                batch = jax.tree.map(jnp.asarray, next(stream))
                params, server_state, loss = ex.round(
                    params, server_state, batch, tau, spec.lr, A, active
                )
                # the per-round host sync every loop driver models
                losses.append(float(loss))
        losses = jnp.asarray(losses)
        dispatches = spec.rounds
        prefetch_stats = None
    else:
        prev = ex.tracer
        if tracer is not None:
            ex.tracer = tracer
        try:
            params, server_state, metrics, key = ex.run_schedule(
                key,
                params,
                server_state,
                schedule=schedule,
                rounds=spec.rounds,
                next_batch=lambda: next(stream),
                lr=spec.lr,
                policy=policy,
            )
        finally:
            ex.tracer = prev
        losses = metrics["loss"]
        dispatches = ex.dispatches
        prefetch_stats = ex.prefetch_stats
    if tr.enabled:
        with tr.span("run.finalize", cat="device", track="device"):
            jax.block_until_ready(params)
    else:
        jax.block_until_ready(params)
    wall = time.perf_counter() - t0
    return wall, losses, params, dispatches, prefetch_stats


def _run_shard_engine(bundle: ScenarioBundle, name: str, batches: list, trace_dir=None):
    """Cold + warm shard-path pass; mirrors :func:`_run_mesh_engine`.  The
    ``loop`` engine is the single-device reference; ``scan`` and
    ``pipelined`` run the `shard_map` step through
    :class:`~repro.fl.engine.ShardedScanEngine` (serial vs prefetched
    staging — the prefetched variant ``device_put``s each staged epoch
    directly into its sharded layout)."""
    spec = bundle.spec
    if name not in ("loop", "scan", "pipelined"):
        raise ValueError(f"unknown engine: {name!r}")
    if name == "loop":
        ex = _ShardStep(bundle)
    else:
        mesh = _shard_mesh(spec)
        step_fn = build_sharded_scan_round_step(
            bundle.loss_fn,
            n_clients=spec.n_clients,
            local_steps=spec.local_steps,
            mesh=mesh,
            shard=spec.shard,
            exchange=spec.exchange,
            relay_mode="fused",
            relay_backend=spec.relay_backend,
            block_d=spec.block_d,
            client_opt=ClientOpt(kind="sgd", weight_decay=1e-4),
            server_opt=ServerOpt(),
        )
        ex = ShardedScanEngine(
            step_fn,
            mesh=mesh,
            shard=spec.shard,
            prefetch="serial" if name == "scan" else "inline",
        )
    cold_s, _, _, _, _ = _run_shard_once(bundle, ex, name, batches)
    warm_s, losses, params, dispatches, overlap = _run_shard_once(
        bundle, ex, name, batches
    )
    trace_path = telemetry = None
    if trace_dir is not None:
        tracer = Tracer()
        _run_shard_once(bundle, ex, name, batches, tracer=tracer)
        trace_path, telemetry = _finish_trace(tracer, trace_dir, spec.name, name)
    run = EngineRun(
        engine=name,
        wall_s=warm_s,
        compile_s=max(0.0, cold_s - warm_s),
        rounds_per_sec=spec.rounds / warm_s,
        trace_count=ex.trace_count,
        dispatches=dispatches,
        final_loss=float(losses[-1]),
        losses=np.asarray(losses, np.float64).tolist(),
        overlap_fraction=None if overlap is None else overlap.overlap_fraction,
        steady_overlap_fraction=(
            None if overlap is None else overlap.steady_overlap_fraction
        ),
        host_prep_s=None if overlap is None else overlap.prep_s,
        host_wait_s=None if overlap is None else overlap.wait_s,
        chunks_staged=None if overlap is None else overlap.chunks_staged,
        trace_path=trace_path,
        telemetry=telemetry,
    )
    return run, params


def run_engine(bundle: ScenarioBundle, name: str, batches: list, trace_dir=None):
    """Cold + warm pass of one engine; returns (EngineRun, final params).

    ``trace_dir`` adds a third, *traced* pass on the already-compiled engine
    and writes ``TRACE_<scenario>_<engine>.json`` (+ ``.jsonl``) there.  The
    traced pass fences the device per dispatch (every engine but the
    pipelined one), so its wall time is not the warm measurement — the ``wall_s``/``overlap_fraction`` numbers always
    come from the untraced warm run."""
    spec = bundle.spec
    if spec.step == "mesh":
        return _run_mesh_engine(bundle, name, batches, trace_dir)
    if spec.step == "shard":
        return _run_shard_engine(bundle, name, batches, trace_dir)
    if spec.step != "sim":
        raise ValueError(f"unknown step: {spec.step!r}")
    sim = bundle.make_sim()
    if name in ("scan", "pipelined"):
        cls = EpochScanEngine if name == "scan" else PipelinedScanEngine
        engine = cls(sim, chunk=spec.chunk)
        dispatches = sum(
            -(-seg.n_rounds // spec.chunk)
            for seg in bundle.make_schedule().segments(spec.rounds)
        )
    elif name == "async":
        # each engine run replays the same delay stream (fresh process,
        # same seed); reset=True inside run_schedule makes cold and warm
        # passes identical.  Like the loop, dispatch granularity is one
        # aggregation per round.
        engine = AsyncRoundEngine(
            sim,
            delays=bundle.make_delays(),
            staleness_decay=spec.staleness_decay,
            buffer_k=spec.buffer_k,
            block_d=spec.block_d,
        )
        dispatches = spec.rounds
    elif name == "loop":
        engine = sim
        dispatches = spec.rounds
    else:
        raise ValueError(f"unknown engine: {name!r}")
    cold_s, _, _ = _run_once(bundle, engine, batches)
    warm_s, metrics, params = _run_once(bundle, engine, batches)
    trace_count = engine.trace_count  # engine == sim on the loop path
    overlap = getattr(engine, "prefetch_stats", None)  # warm run's stats
    trace_path = telemetry = None
    if trace_dir is not None:
        tracer = Tracer()
        if name in ("scan", "pipelined", "async"):
            engine.tracer = tracer
        try:
            _run_once(bundle, engine, batches, tracer=tracer)
        finally:
            if name in ("scan", "pipelined", "async"):
                engine.tracer = NULL_TRACER
        trace_path, telemetry = _finish_trace(tracer, trace_dir, spec.name, name)
    run = EngineRun(
        engine=name,
        wall_s=warm_s,
        compile_s=max(0.0, cold_s - warm_s),
        rounds_per_sec=spec.rounds / warm_s,
        trace_count=trace_count,
        dispatches=dispatches,
        final_loss=float(metrics["loss"][-1]),
        losses=np.asarray(metrics["loss"], np.float64).tolist(),
        overlap_fraction=None if overlap is None else overlap.overlap_fraction,
        steady_overlap_fraction=(
            None if overlap is None else overlap.steady_overlap_fraction
        ),
        host_prep_s=None if overlap is None else overlap.prep_s,
        host_wait_s=None if overlap is None else overlap.wait_s,
        chunks_staged=None if overlap is None else overlap.chunks_staged,
        trace_path=trace_path,
        telemetry=telemetry,
    )
    return run, params


def _same(tree_a, tree_b) -> bool:
    """Bitwise equality of two parameter pytrees."""
    a, b = jax.tree.leaves(tree_a), jax.tree.leaves(tree_b)
    return len(a) == len(b) and all(
        np.array_equal(np.asarray(x), np.asarray(y)) for x, y in zip(a, b)
    )


def _compare(
    tree_ref, tree_got, *, rtol=KERNEL_CHECK_RTOL, atol=KERNEL_CHECK_ATOL
) -> tuple[float, bool]:
    """(max |Δ| over all leaves, allclose at the given tolerance — the
    kernel check's by default)."""
    a, b = jax.tree.leaves(tree_ref), jax.tree.leaves(tree_got)
    pairs = [
        (np.asarray(x, np.float64), np.asarray(y, np.float64))
        for x, y in zip(a, b)
    ]
    max_abs_diff = max(
        (float(np.max(np.abs(x - y))) for x, y in pairs), default=0.0
    )
    ok = len(a) == len(b) and all(
        np.allclose(x, y, rtol=rtol, atol=atol) for x, y in pairs
    )
    return max_abs_diff, ok


def _kernel_parity(
    bundle: ScenarioBundle, kbundle: ScenarioBundle, batches: list
) -> dict:
    """The kernel check on the sim path: one walk of the reference loop
    (:func:`run_rounds_loop`) feeds two comparisons at the kernel tolerance.

    * ``per_round``: before every round the clients' (n, D) delta buffer is
      computed once from the round's params and batch, and both backends'
      PS increment take it with the round's τ, relay matrix and churn mask.
    * ``first_round``: the scan engine on the kernel backend, the compiled
      program the timed ``scan_<backend>`` run executes, runs the first round;
      its params must match the walk's after that round.

    Whole trajectories are not compared: a model trained near its stability
    edge (ResNet-20) turns a last-bit difference in one round into 1e-3
    within a few rounds.  Returns ``{comparison: (max |Δ|, allclose)}``."""
    spec = bundle.spec
    ref_sim, k_sim = bundle.make_sim(), kbundle.make_sim()
    local = jax.jit(lambda p, b: ref_sim.local_updates(p, b, spec.lr)[0])
    ref_inc = jax.jit(ref_sim.aggregator.flat_fn)
    k_inc = jax.jit(k_sim.aggregator.flat_fn)
    per_round, after_first = [], []

    def compare_increments(state, sub, params, batch, A):
        buf = local(params, batch)
        inc = []
        for fn, sim in ((ref_inc, ref_sim), (k_inc, k_sim)):
            tau, A_round, active = sim.round_inputs(
                sub, A=A, p=state.p, active=state.active
            )
            inc.append(fn(tau, buf, A_round, active))
        per_round.append(_compare(*inc))

    def fresh(bundle):
        """The run's starting state and its batch stream, as _run_once
        builds them."""
        params = bundle.init_fn(jax.random.key(spec.seed))
        stream = iter(batches)
        return dict(
            key=jax.random.key(spec.seed + 1),
            params=params,
            server_state=ref_sim.init_server_state(params),
            schedule=bundle.make_schedule(),
            policy=bundle.make_policy(),
            next_batch=lambda: next(stream),
            lr=spec.lr,
        )

    run_rounds_loop(
        ref_sim,
        rounds=spec.rounds,
        before_round=compare_increments,
        on_round=lambda r, params: after_first or after_first.append(params),
        **fresh(bundle),
    )
    kparams, *_ = EpochScanEngine(k_sim, chunk=spec.chunk).run_schedule(
        rounds=1, **fresh(kbundle)
    )
    return {
        "per_round": (
            max(d for d, _ in per_round),
            all(ok for _, ok in per_round),
        ),
        "first_round": _compare(after_first[0], kparams),
    }


def run_scenario(
    spec: ScenarioSpec | str,
    *,
    engines=None,
    check_bitwise: bool = True,
    trace_dir=None,
) -> dict:
    """Run ``spec`` under every engine (default: ``spec.engines``); returns
    ``{"runs": {name: EngineRun}, "speedup": float | None,
    "speedups": {name: float}, "bitwise_match": bool | None,
    "model_params": int, "kernel_check": dict | None,
    "shard_check": dict | None, "engine_check": dict | None,
    "async_check": dict | None, "ttac": dict | None}``.

    The ``async`` engine (``spec.engines`` includes it) joins the bitwise
    gate only at ``spec.delay == "none"`` — a delayed run diverges from the
    loop *by design*.  A delayed scenario instead gets the **async parity
    gate** (``async_check``): the async engine re-runs with the delay
    stripped and its final parameters must be bitwise-identical to the
    loop's (the staleness-weighting unbiasedness regression; the re-run is
    recorded in ``runs`` as ``async_delay0``).  A mismatch raises.

    ``spec.ttac_target_loss > 0`` adds the ``ttac`` block: per engine, the
    first round (and derived wall-clock second) at which the warm run's
    training loss reaches the target — the async-vs-synchronous
    time-to-accuracy comparison.

    On the shard path (``spec.step == "shard"``) the bitwise gate is
    replaced by the **shard gate** (``shard_check``): the sharded engines
    must be bitwise-identical to *each other*, and allclose to the
    single-device loop at the kernel-check tolerance (the measured
    ``max_abs_diff`` is recorded).  Either violation raises.

    ``speedups[name]`` is that engine's rounds/sec over the loop's (absent
    unless the loop ran); ``speedup`` remains the scan/loop headline for
    schema continuity.  ``bitwise_match`` asserts every fused engine's final
    parameters are bit-identical to the per-round reference — a benchmark
    whose fast path diverges from the reference is measuring the wrong
    thing, so a mismatch raises.

    Off the CPU every gate runs apart from the timed runs, at
    ``jax.default_matmul_precision("highest")`` (:func:`_pinned` says why):
    each gated engine runs once more there, and the gates hold those runs to
    the same bitwise / 1e-5 bars.  The timed runs' drift from the timed
    loop is recorded in the ``engine_check`` block, not gated.

    ``spec.check_backend != "none"`` adds the **mandatory kernel parity
    check** — a mismatch raises, never degrades to a warning.  On the sim
    path (:func:`_kernel_parity`) it is per round, both backends' PS
    increment on each round's delta buffer along the reference trajectory,
    plus the kernel backend's compiled scan program over the first round.
    On the shard path, where the kernel runs only inside the sharded epoch
    scan, the final parameters of the whole horizon are compared.  Either
    way the scan engine runs the whole horizon on the kernel backend (same
    batches, same randomness): recorded in ``runs`` as ``scan_<backend>``
    (so its throughput lands in the report and the speedup table), with its
    final drift from the reference as ``horizon_max_abs_diff``, and kept out
    of the bitwise gate, which is reference-backend-only by design.
    """
    if isinstance(spec, str):
        from repro.bench.scenarios import get_scenario

        spec = get_scenario(spec)
    if engines is None:
        engines = spec.engines
    bundle = build(spec)
    model_params = tree_size(bundle.init_fn(jax.random.key(spec.seed)))
    batches = _pregenerate_batches(bundle)
    runs: dict[str, EngineRun] = {}
    finals = {}
    for name in engines:
        runs[name], finals[name] = run_engine(bundle, name, batches, trace_dir)
    gate_finals: dict = {}

    def gate_final(name, b=bundle, timed=None):
        """``name``'s final params as the gates compare them: the timed
        run's own, or where the gates are pinned (:func:`_pinned`) those of
        a re-run at the gate precision."""
        timed = finals[name] if timed is None else timed
        if not _pinned():
            return timed
        key = (b.spec.name, b.spec.relay_backend, name)
        if key not in gate_finals:
            with _gate_precision():
                gate_finals[key] = run_engine(b, name, batches)[1]
        return gate_finals[key]

    kernel_check = None
    if spec.check_backend != "none" and finals:
        kspec = dataclasses.replace(
            spec, relay_backend=spec.check_backend, check_backend="none"
        )
        kbundle = build(kspec)
        kname = f"scan_{spec.check_backend}"
        krun, kfinal = run_engine(kbundle, "scan", batches)
        ref_name = "loop" if "loop" in finals else sorted(finals)[0]
        horizon_diff, _ = _compare(finals[ref_name], kfinal)
        if spec.step == "sim":
            with _gate_precision():
                checks = _kernel_parity(bundle, kbundle, batches)
        else:
            checks = {
                "horizon": _compare(
                    gate_final(ref_name),
                    gate_final("scan", kbundle, timed=kfinal),
                )
            }
        for mode, (diff, ok) in checks.items():
            if not ok:
                raise AssertionError(
                    f"{spec.name}: {spec.check_backend} backend diverged from "
                    f"the {spec.relay_backend} reference ({mode}: max |Δ| = "
                    f"{diff:.3e} > atol {KERNEL_CHECK_ATOL:g} / rtol "
                    f"{KERNEL_CHECK_RTOL:g})"
                )
        runs[kname] = dataclasses.replace(krun, engine=kname)
        mode = next(iter(checks))
        kernel_check = {
            "backend": spec.check_backend,
            "reference_backend": spec.relay_backend,
            "engine": "scan",
            "mode": mode,
            "allclose": True,
            "rtol": KERNEL_CHECK_RTOL,
            "atol": KERNEL_CHECK_ATOL,
            "max_abs_diff": checks[mode][0],
            "first_round_max_abs_diff": checks.get("first_round", (None,))[0],
            "horizon_max_abs_diff": horizon_diff,
            "rounds_per_sec": krun.rounds_per_sec,
        }
    async_check = None
    if "async" in finals and spec.delay != "none" and "loop" in finals:
        # the mandatory async parity gate: strip the delay (and the buffer
        # cap — freshest-k at k < n drops clients even when all arrive
        # fresh) and the engine must reproduce the loop bit-for-bit (same
        # batches, same τ chain) — proof the staleness weighting degrades
        # to OPT-α exactly in the synchronous limit
        dspec = dataclasses.replace(spec, delay="none", buffer_k=0)
        with _gate_precision():
            arun, afinal = run_engine(build(dspec), "async", batches)
        if not _same(gate_final("loop"), afinal):
            raise AssertionError(
                f"{spec.name}: the async engine at delay=0 diverged bitwise "
                "from the per-round loop — the staleness weighting broke "
                "the synchronous-limit contract"
            )
        runs["async_delay0"] = dataclasses.replace(arun, engine="async_delay0")
        async_check = {
            "reference": "loop",
            "bitwise": True,
            "recorded_delay": spec.delay,
            "rounds_per_sec": arun.rounds_per_sec,
        }
    ttac = None
    if spec.ttac_target_loss > 0:
        ttac = {"target_loss": spec.ttac_target_loss, "engines": {}}
        for name, run in runs.items():
            if run.losses is None:
                continue
            arr = np.asarray(run.losses)
            hit = np.nonzero(arr <= spec.ttac_target_loss)[0]
            reached = bool(hit.size)
            rounds_to = int(hit[0]) + 1 if reached else None
            ttac["engines"][name] = {
                "reached": reached,
                "rounds_to_target": rounds_to,
                "seconds_to_target": (
                    rounds_to / run.rounds_per_sec if reached else None
                ),
            }
    speedups = {}
    if "loop" in runs:
        speedups = {
            name: runs[name].rounds_per_sec / runs["loop"].rounds_per_sec
            for name in runs
            if name != "loop"
        }
    speedup = speedups.get("scan")
    bitwise = None
    shard_check = None
    engine_check = None
    if check_bitwise and "loop" in finals and len(finals) > 1:
        if spec.step == "shard":
            # The shard gate: sharded engines must agree *bitwise among
            # themselves* (same program, same collectives); against the
            # single-device loop the bar is the documented f32 tolerance —
            # XLA compiles the m-client local scan differently than the
            # n-client program (gather mode), and the ring additionally
            # reassociates the relay accumulation (docs/distributed.md).
            sharded = sorted(k for k in finals if k != "loop")
            for name in sharded[1:]:
                if not _same(gate_final(sharded[0]), gate_final(name)):
                    raise AssertionError(
                        f"{spec.name}: sharded engines {sharded[0]} and "
                        f"{name} diverged bitwise from each other"
                    )
            max_abs_diff, ok = _compare(
                gate_final("loop"), gate_final(sharded[0])
            )
            if not ok:
                raise AssertionError(
                    f"{spec.name}: sharded engines diverged from the "
                    f"single-device loop (max |Δ| = {max_abs_diff:.3e} > "
                    f"atol {KERNEL_CHECK_ATOL:g} / rtol {KERNEL_CHECK_RTOL:g})"
                )
            shard_check = {
                "shard": spec.shard,
                "exchange": spec.exchange,
                "devices": spec.devices,
                "reference": "loop",
                "allclose": True,
                "bitwise_among_sharded": len(sharded) > 1,
                "rtol": KERNEL_CHECK_RTOL,
                "atol": KERNEL_CHECK_ATOL,
                "max_abs_diff": max_abs_diff,
            }
        else:
            for name in finals:
                if name == "loop":
                    continue
                if name == "async" and spec.delay != "none":
                    # a delayed async run diverges from the loop by design;
                    # its gate is the delay-0 re-run above (async_check)
                    continue
                bitwise = _same(gate_final("loop"), gate_final(name))
                if not bitwise:
                    raise AssertionError(
                        f"{spec.name}: {name} engine diverged bitwise from "
                        "the per-round reference"
                    )
        if _pinned():
            # what the gates do not hold the timed runs to: their drift
            # from the timed loop at the default precision
            engine_check = {
                "gate_precision": "highest",
                "reference": "loop",
                "timed_max_abs_diff": {
                    name: _compare(finals["loop"], final)[0]
                    for name, final in finals.items()
                    if name != "loop"
                    and not (name == "async" and spec.delay != "none")
                },
            }
    return {
        "runs": runs,
        "speedup": speedup,
        "speedups": speedups,
        "bitwise_match": bitwise,
        "model_params": model_params,
        "kernel_check": kernel_check,
        "shard_check": shard_check,
        "engine_check": engine_check,
        "async_check": async_check,
        "ttac": ttac,
    }
