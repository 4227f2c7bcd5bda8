"""One run of one cell: set-up, a measured window, the check, the result.

Set-up builds the cell's ``ScenarioSpec`` bundle (schedule, OPT-alpha
policy, live ``FederatedLoader``, simulator), makes the weights on the
device in one jitted call from the seed, and hands them to one
``ContinuousTrainer`` on the pipelined engine.  That trainer runs the
cell's first three rounds as bursts of one round each (these are the
rounds the check compares), then as many rounds as bring it to a chunk
boundary, then one whole burst; every burst runs the one compiled chunk
program the window uses, and the last one warms what joins a burst's
chunks.

The window drives the same trainer closed-loop, in bursts of the mix's
length, until the first burst boundary after ``seconds``.  With
``trace`` the profiler records a steady stretch of it for the per-layer
metrics.  After the window the peak device memory is read, the trainer is
freed, and the plain reference (``reference.py``) replays the first three
rounds from the same seed.  Every OPT-alpha solve the trainer used is held
to its problem (``opt_alpha_ref.py``), and every round's uplink mask, in
set-up and window, to the reference's draw from the round key chain.

The replay runs where ``reference.replay_device`` puts it:

* on the host's CPU where the model's loss runs a convolution.
  ResNet-20 replays there: the TPU compiler takes about 19 minutes over
  its ``HIGHEST`` convolutions, and the CPU replays its three rounds in
  seconds;
* on the cell's first device otherwise, for a model of matmuls whose
  replay at published widths would not fit the run budget on the host.
  It holds about ``5 * 4 * D`` bytes (float32) plus one local step's
  activations (``reference.py``).  The peak memory is read before the
  check, so the replay never enters ``peak_hbm_gib``; the chip's bytes in
  use before the check are printed on standard error, to show that the
  trainer's state was freed.

Every run prints, on standard error before its ``check`` lines, the host
seconds of its phases, which sum to the seconds from the start of
``run.py`` to that line (``wall``):

    phases: weights=... checked=... warm=... window=... trace_stop=... read=... check=... wall=...

``weights``: imports, TPU init, the bundle, data and the weights on the
device; ``checked``: the checked rounds, which hold the chunk program's
compile or its load from the cache; ``warm``: the rounds up to the chunk
boundary and the warm burst; ``window``: the measured window, less
``trace_stop``: the profiler's stop, which collects and writes the
device trace (0 in an untraced run); ``read``: the peak memory, the
trace's reduction and the per-layer metrics, and freeing the trainer;
``check``: the reference's init, compile and replay, the comparison and
the OPT-alpha numbers.  A traced run also prints how many device op
events its trace holds.

The run budget: a run has 360 s to end, and a run still going then is
stopped.  Size a new cell so that its slowest run, traced and with a
cold compile cache, ends within 300 s, and its untraced runs within
180 s.  A traced run drives the chip for at least the set-up's rounds
(``CHECKED_ROUNDS``, the rounds up to the chunk boundary, one burst) and
then ``max(seconds, 1 + TRACED_BURSTS bursts)``: the profiler starts
after the window's first burst and stops ``TRACED_BURSTS`` bursts later.
The stop writes every device op event it recorded, and the reduction
reads each.
"""
from __future__ import annotations

import dataclasses
import functools
import gc
import json
import math
import pathlib
import shutil
import sys
import tempfile
import time

import numpy as np

from chipbench import cells, opt_alpha_ref, reference, trace_reduce

CHECKED_ROUNDS = 3
TRACED_BURSTS = 2
SEED_SPACE = 2**31 - 2**10


class NoChip(RuntimeError):
    """JAX finds no TPU, or fewer chips than the cell asks for."""


def spec_seed(seed: int) -> int:
    """Any whole number (the driver's exceed 32 bits) to a seed that every
    generator of the program takes."""
    return int(np.random.SeedSequence(seed % 2**64).generate_state(1)[0]) % SEED_SPACE


def load_peaks(root) -> dict:
    path = pathlib.Path(root) / cells.BENCH_DIR / "peaks.json"
    return json.loads(path.read_text())["kinds"]


def check_devices(cell, peaks, require_tpu: bool):
    import jax

    devices = jax.devices()
    if require_tpu:
        if devices[0].platform != "tpu":
            raise NoChip(f"no TPU: JAX runs on {devices[0].platform}")
        if devices[0].device_kind not in peaks:
            raise NoChip(f"no peaks for device kind {devices[0].device_kind!r}")
    if len(devices) < cell.chips:
        raise NoChip(f"the cell asks for {cell.chips} chips, JAX sees {len(devices)}")
    return devices


class Feed:
    """The live loader as the trainer's ``next_batch``; keeps the first
    rounds' batches for the check."""

    def __init__(self, loader, steps, batch, keep):
        self.loader, self.steps, self.batch, self.keep = loader, steps, batch, keep
        self.kept: list = []

    def __call__(self):
        out = self.loader.round_batch(self.steps, self.batch)
        if len(self.kept) < self.keep:
            self.kept.append(out)
        return out


class Policy:
    """The OPT-alpha policy as the trainer sees it; keeps every solve it
    hands out (``solves``: relay weights, uplink marginals, D2D adjacency,
    cohort) for the check."""

    def __init__(self, inner):
        self.inner = inner
        self.solves: list = []

    def relay_matrix(self, state):
        A = self.inner.relay_matrix(state)
        active = None if state.active is None else np.array(state.active)
        self.solves.append(
            {"A": np.array(A), "p": np.array(state.p), "adj": np.array(state.adj),
             "active": active}
        )
        return A


class Schedule:
    """The channel schedule as the trainer sees it; keeps each round's
    uplink marginals and cohort (``channel``) for the check."""

    def __init__(self, inner):
        self.inner = inner
        self.channel: list = []

    def segments(self, n_rounds):
        for seg in self.inner.segments(n_rounds):
            active = None if seg.active is None else np.array(seg.active)
            self.channel += [{"p": np.array(seg.p), "active": active}] * seg.n_rounds
            yield seg

    def __getattr__(self, name):
        return getattr(self.inner, name)


@dataclasses.dataclass
class Prepared:
    """A trainer after set-up, with what the check needs."""

    trainer: object
    spec: object
    seed: int
    feed: Feed
    policy: Policy
    schedule: Schedule
    tracer: object
    # on the host: "p0", "after", "losses", "delta_norms" of the checked
    # rounds; "taus" and "all_losses" of every round the trainer ran
    program: dict
    # host seconds (perf_counter) when the weights were on the device and
    # when the checked rounds had run
    marks: dict


def build_spec(cell, seed: int):
    from repro.bench.scenarios import ScenarioSpec

    fields = dict(cell.config["spec"])
    fields.update(cell.traffic["spec"])
    return ScenarioSpec(
        name=cell.name, seed=seed, engines=("pipelined",), rounds=0, **fields
    )


def record(program: dict, metrics: dict) -> None:
    """Keep the uplink masks and losses of rounds the trainer ran."""
    program["taus"] += list(np.asarray(metrics["tau"], np.float64))
    program["all_losses"] += list(np.asarray(metrics["loss"], np.float64))


def prepare(cell, seed: int, *, traced: bool = False) -> Prepared:
    """Set-up: the trainer with its weights made from ``seed`` on the
    device, driven through the checked rounds, up to a chunk boundary and
    through one whole burst."""
    import jax

    from repro.bench.scenarios import build
    from repro.launch.train import ContinuousTrainer
    from repro.obs import Tracer

    s = spec_seed(seed)
    spec = build_spec(cell, s)
    bundle = build(spec)
    tracer = Tracer() if traced else None
    policy = Policy(bundle.make_policy(tracer=tracer))
    schedule = Schedule(bundle.make_schedule())
    feed = Feed(bundle.make_loader(), spec.local_steps, spec.local_batch, CHECKED_ROUNDS)
    burst = int(cell.traffic["burst_rounds"])
    trainer = ContinuousTrainer(
        bundle.make_sim(),
        schedule=schedule,
        next_batch=feed,
        lr=spec.lr,
        policy=policy,
        engine="pipelined",
        chunk=spec.chunk,
        publish_every=burst,
    )
    params = jax.jit(bundle.init_fn)(jax.random.key(s))
    trainer.init(params, jax.random.key(s + 1))
    program = {"p0": jax.device_get(params), "after": [], "taus": [], "all_losses": []}
    del params
    marks = {"weights": time.perf_counter()}
    delta_norms = []
    for _ in range(CHECKED_ROUNDS):
        m = trainer.run(1)
        record(program, m)
        delta_norms.append(float(m["delta_norm"][0]))
        program["after"].append(
            jax.device_get(trainer.params) if len(program["after"]) != 1 else None
        )
    program["losses"] = np.asarray(program["all_losses"])
    program["delta_norms"] = np.asarray(delta_norms)
    marks["checked"] = time.perf_counter()
    realign = (-CHECKED_ROUNDS) % spec.chunk
    if realign:
        record(program, trainer.run(realign))
    # one whole burst, shaped as the window's: its chunks are carried and
    # staged as there, and joined by programs of their own
    record(program, trainer.run(burst))
    if traced:
        trace_reduce.mark()
    return Prepared(trainer, spec, s, feed, policy, schedule, tracer, program, marks)


def check(cell, prep: Prepared, *, control: bool = False) -> dict:
    """Replay the checked rounds through the plain float32 reference, on
    the device ``reference.replay_device`` picks, and compare what the trainer produced with it: ``{"program": numbers}``.
    Every OPT-alpha solve the trainer used is held to its problem, and
    every round's uplink mask to the reference's draw.  With ``control``
    also the reference in bfloat16 on the chip, in the program's place:
    ``"control_bf16"``."""
    import jax
    import jax.numpy as jnp

    model = cell.model()
    cfg = cell.config
    s = prep.seed
    program = prep.program
    channel = prep.schedule.channel[: len(program["taus"])]
    cpu = jax.devices("cpu")[0]
    chip = jax.devices()[0]
    where = reference.replay_device(model, cfg, prep.feed.kept[0])
    in_use = (chip.memory_stats() or {}).get("bytes_in_use", "not reported")
    print(f"chip bytes in use before the check: {in_use}", file=sys.stderr)
    with jax.default_device(cpu):
        taus = reference.draw_taus(jax.random.key(s + 1), [c["p"] for c in channel])
    with jax.default_device(where):
        p0 = jax.device_get(jax.jit(functools.partial(model.init, cfg=cfg))(jax.random.key(s)))
    # each checked round ran as a burst of its own: one solve each
    rounds = [
        {"batch": b, "tau": t, "A": solve["A"], "active": c["active"]}
        for b, t, solve, c in zip(prep.feed.kept, taus, prep.policy.solves, channel)
    ][:CHECKED_ROUNDS]
    run = functools.partial(
        reference.run_rounds, model, p0, rounds,
        lr=prep.spec.lr, wd=cfg["client_weight_decay"],
    )
    ref = dict(run(device=where), p0=p0)
    numbers = reference.compare(program, ref)
    numbers.update(opt_alpha_ref.solve_numbers(prep.policy.solves))
    # every round's uplink mask against the reference's draw
    numbers["tau_mismatch"] = sum(
        not np.array_equal(t * (1.0 if c["active"] is None else c["active"]), pt)
        for t, c, pt in zip(taus, channel, program["taus"])
    ) + abs(len(taus) - len(program["taus"]))
    numbers["nonfinite_rounds"] = int(np.sum(~np.isfinite(program["all_losses"])))
    out = {"program": numbers}
    if control:
        # a bfloat16 program starts from its own rounding of the weights:
        # its change is measured from there
        out["control_bf16"] = reference.compare(run(dtype=jnp.bfloat16), ref)
    return out


def use_compile_cache() -> None:
    """The program's persistent compile cache, with every program kept, so
    that only a checkout's first run compiles."""
    import jax

    from repro.launch.compile_cache import use_compile_cache as program_cache

    program_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)


def _burst_wait(trainer) -> float:
    stats = trainer._engine.prefetch_stats
    return 0.0 if stats is None else float(stats.wait_s)


def run(root, workload: str, seed: int, seconds: float, trace: bool, t_start: float,
        *, require_tpu: bool = True) -> dict:
    """One run; returns the result line's object."""
    cell = cells.resolve(root, workload)
    peaks = load_peaks(root)
    devices = check_devices(cell, peaks, require_tpu)
    peak = peaks.get(devices[0].device_kind)

    prep = prepare(cell, seed, traced=trace)
    trainer = prep.trainer
    ends: list = []  # (host seconds, round, staging wait) after each burst
    tracing = {"dir": None, "done": not trace, "from": None, "stop_s": 0.0}

    def stop():
        now = time.perf_counter()
        ends.append((now, trainer.round, _burst_wait(trainer)))
        if trace:
            _advance_trace(tracing, len(ends), trainer.round)
        return now - t_window >= seconds and tracing["done"]

    compiles = _count_compiles()
    round0 = trainer.round
    t_window = time.perf_counter()
    setup_s = t_window - t_start
    metrics = trainer.run(10**15, stop=stop)
    t_read = time.perf_counter()
    compiles["on"] = False
    t_last, round_last, _ = ends[-1]
    rounds = round_last - round0
    failed = int(np.sum(~np.isfinite(np.asarray(metrics["loss"]))))
    record(prep.program, metrics)
    peak_bytes = peak_memory(devices[0])

    result_metrics = {}
    summary = None
    if not trace:
        result_metrics["rounds_per_s"] = rounds / (t_last - t_window)
        result_metrics["peak_hbm_gib"] = peak_bytes / 2**30
        result_metrics["setup_s"] = setup_s
    else:
        summary = _read_trace(tracing["dir"])
        art = _artifacts(cell, peak, summary, tracing, prep, ends, t_window, round0)
        for m in cell.per_layer:
            value = cell.reader(m["name"]).read(art)
            if value is not None:
                result_metrics[m["name"]] = value

    units = {m["name"]: m["unit"] for m in cell.end_to_end + cell.per_layer}
    # free the program's device state before the reference runs
    del trainer, metrics
    prep.trainer = None
    gc.collect()
    t_check = time.perf_counter()
    numbers = check(cell, prep)["program"]
    for name, value in numbers.items():
        print(f"reading {name}: {value!r}", file=sys.stderr)
    limits = cell.limits["limits"]
    checks = {k: {"value": numbers[k], "limit": limits[k]} for k in limits}
    correct = bool(
        failed == 0
        and all(math.isfinite(c["value"]) and c["value"] <= c["limit"] for c in checks.values())
    )
    t_end = time.perf_counter()
    result = {
        "correct": correct,
        "attempted": int(rounds),
        "failed": failed,
        "metrics": {k: {"value": v, "unit": units[k]} for k, v in result_metrics.items()},
        "device": {
            "platform": devices[0].platform,
            "kind": devices[0].device_kind,
            "count": len(devices),
            "memory_peak_bytes": peak_bytes,
        },
    }
    if trace and summary is not None:
        result["device"]["busy_s"] = summary["busy_s"]
        result["device"]["window_s"] = summary["window_s"]
        result["breakdown"] = summary["breakdown"]
    result["checks"] = checks
    print(f"traces and compiles in the window: {compiles['n']}", file=sys.stderr)
    bursts = np.diff([t_window] + [t for t, _, _ in ends])
    print(f"burst seconds: {' '.join(f'{b:.3f}' for b in bursts)}", file=sys.stderr)
    bounds = {
        "weights": (t_start, prep.marks["weights"]),
        "checked": (prep.marks["weights"], prep.marks["checked"]),
        "warm": (prep.marks["checked"], t_window),
        "window": (t_window, t_read - tracing["stop_s"]),
        "trace_stop": (0.0, tracing["stop_s"]),
        "read": (t_read, t_check),
        "check": (t_check, t_end),
        "wall": (t_start, t_end),
    }
    print("phases: " + " ".join(f"{k}={b - a:.3f}" for k, (a, b) in bounds.items()),
          file=sys.stderr)
    return result


def peak_memory(device) -> int:
    """The device's peak bytes in use so far (0 where it reports none)."""
    return int((device.memory_stats() or {}).get("peak_bytes_in_use", 0))


def _count_compiles() -> dict:
    """Count the traces and backend compiles (a compile-cache hit is a
    trace alone) from now until ``["on"]`` is cleared."""
    import jax

    state = {"n": 0, "on": True}
    events = (
        "/jax/core/compile/jaxpr_trace_duration",
        "/jax/core/compile/backend_compile_duration",
    )

    def listen(event, duration_secs, **_):
        if state["on"] and event in events:
            state["n"] += 1

    jax.monitoring.register_event_duration_secs_listener(listen)
    return state


def _advance_trace(tracing: dict, bursts: int, round_now: int) -> None:
    """Between bursts: start the profiler after the window's first burst,
    mark each burst boundary on the device, stop after ``TRACED_BURSTS``
    bursts.  Host tracing stays off: on a TPU host it records millions of
    runtime events a second and starves the loader."""
    import jax

    if tracing["done"]:
        return
    if bursts == 1:
        tracing["dir"] = tempfile.mkdtemp(prefix="chipbench_trace_")
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        options.host_tracer_level = 0
        jax.profiler.start_trace(tracing["dir"], profiler_options=options)
        tracing["from"] = round_now
    trace_reduce.mark()
    if bursts == 1 + TRACED_BURSTS:
        t0 = time.perf_counter()
        jax.profiler.stop_trace()
        tracing["stop_s"] = time.perf_counter() - t0
        tracing["to"] = round_now
        tracing["done"] = True


def _read_trace(trace_dir):
    try:
        paths = sorted(pathlib.Path(trace_dir).rglob("*.xplane.pb"))
        if not paths:
            return None
        trace = trace_reduce.from_xplane(paths[-1])
        events = sum(len(v) for v in trace["ops"].values())
        print(f"trace: {events} device op events, {paths[-1].stat().st_size} bytes",
              file=sys.stderr)
        return trace_reduce.reduce(trace)
    finally:
        shutil.rmtree(trace_dir, ignore_errors=True)


def _artifacts(cell, peak, summary, tracing, prep, ends, t_window, round0) -> dict:
    """What the per-layer readers read."""
    model = cell.model()
    spec = prep.spec
    lo_ns = int(t_window * 1e9)
    hi_ns = int(ends[-1][0] * 1e9)
    spans = [
        sp for sp in prep.tracer.spans if sp.t0_ns >= lo_ns and sp.t1_ns <= hi_ns
    ]
    return {
        "cell": cell,
        "peak": peak,
        "trace": summary,
        "traced_rounds": tracing.get("to", 0) - (tracing["from"] or 0),
        "window_rounds": ends[-1][1] - round0,
        "window_s": ends[-1][0] - t_window,
        "spans": spans,
        "stage_wait_s": sum(w for _, _, w in ends),
        "flops_per_example": model.flops_per_example(cell.config),
        "n_clients": spec.n_clients,
        "active_clients": spec.sample_k if spec.sampling == "fixed_k" else spec.n_clients,
        "local_steps": spec.local_steps,
        "local_batch": spec.local_batch,
    }


def print_result(result: dict) -> None:
    for name, c in result["checks"].items():
        print(f"check {name}: {c['value']!r} (limit {c['limit']!r})", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
