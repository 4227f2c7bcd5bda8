"""Telemetry subsystem (ISSUE 6): tracer invariants, exporter round-trips,
and non-perturbation.

  * **tracer semantics** — nesting depth and buffer ordering (spans record
    at exit, children before ancestors), per-thread depth isolation, the
    bounded buffer's drop accounting, counter accumulation;
  * **thread safety** — worker-thread spans interleave with main-thread
    spans without corrupting either timeline;
  * **null path** — ``NULL_TRACER`` records nothing, and running any engine
    with tracing off is bitwise identical to running it uninstrumented
    (tracing must be a pure observer);
  * **export round-trips** — a fake-clock trace exports to golden Chrome
    trace-event JSON, and both exporters load back into the same phase
    attribution;
  * **bench integration** — a traced scenario run emits a Perfetto-loadable
    trace with the main / prefetcher / device tracks and a telemetry block
    whose phase attribution is sane.
"""
import json
import threading

import jax
import numpy as np
import pytest

from repro import channels
from repro.core import topology
from repro.fl.engine import EpochScanEngine, PipelinedScanEngine, run_rounds_loop
from repro.fl.simulator import FLSimulator
from repro.obs import (
    NULL_TRACER,
    NullTracer,
    Tracer,
    chrome_trace,
    load_trace_file,
    phase_attribution,
    phase_attribution_loaded,
    write_chrome_trace,
    write_jsonl,
)
from repro.obs.summary import format_summary, main as summary_main


def _fake_clock(start=1_000, step=10):
    """Deterministic ns clock: start, start+step, start+2*step, ..."""
    state = {"t": start - step}

    def clock():
        state["t"] += step
        return state["t"]

    return clock


# ------------------------------------------------------------------ tracer


def test_span_nesting_depth_and_exit_order():
    tr = Tracer(clock=_fake_clock())
    with tr.span("outer", cat="dispatch"):
        with tr.span("inner", cat="solve"):
            pass
        with tr.span("inner2", cat="solve"):
            pass
    # spans record at exit: children first, ancestor last
    assert [s.name for s in tr.spans] == ["inner", "inner2", "outer"]
    assert [s.depth for s in tr.spans] == [1, 1, 0]
    outer = tr.spans[-1]
    for child in tr.spans[:-1]:
        assert outer.t0_ns <= child.t0_ns and child.t1_ns <= outer.t1_ns
    # depth resets after the stack unwinds
    with tr.span("later"):
        pass
    assert tr.spans[-1].depth == 0


def test_span_records_attrs_and_fake_clock_durations():
    tr = Tracer(clock=_fake_clock(start=1000, step=10))
    # t_start consumed tick 1000; span start 1010, end 1020
    with tr.span("s", cat="stage", epoch=3, rounds=8):
        pass
    (s,) = tr.spans
    assert (s.t0_ns, s.t1_ns, s.dur_ns) == (1010, 1020, 10)
    assert s.attrs == {"epoch": 3, "rounds": 8}
    tr.instant("mark", cat="schedule", epoch=4)
    (i,) = tr.instants
    assert i.t_ns == 1030 and i.attrs == {"epoch": 4}


def test_counters_accumulate_ints_and_floats():
    tr = Tracer()
    tr.count("hits")
    tr.count("hits")
    tr.count("hits", 3)
    tr.count("prep_s", 0.25)
    tr.count("prep_s", 0.5)
    assert tr.counters["hits"] == 5
    assert tr.counters["prep_s"] == pytest.approx(0.75)


def test_bounded_buffer_drops_and_counts():
    tr = Tracer(max_events=3, clock=_fake_clock())
    for k in range(5):
        with tr.span(f"s{k}"):
            pass
    assert len(tr.events) == 3
    assert tr.dropped == 2
    # counters are aggregates, not events: unaffected by the bound
    tr.count("still_counts")
    assert tr.counters["still_counts"] == 1
    with pytest.raises(ValueError):
        Tracer(max_events=0)


def test_exception_inside_span_still_records_and_unwinds_depth():
    tr = Tracer(clock=_fake_clock())
    with pytest.raises(RuntimeError):
        with tr.span("doomed"):
            raise RuntimeError("boom")
    assert [s.name for s in tr.spans] == ["doomed"]
    with tr.span("after"):
        pass
    assert tr.spans[-1].depth == 0


def test_worker_thread_spans_are_thread_safe_and_tracked():
    tr = Tracer()
    barrier = threading.Barrier(3)
    n_each = 200

    def work(label):
        barrier.wait()
        for _ in range(n_each):
            with tr.span(label, cat="stage", track="prefetcher"):
                with tr.span(label + ".inner", cat="h2d", track="prefetcher"):
                    pass

    threads = [
        threading.Thread(target=work, args=(f"w{k}",), name=f"worker-{k}")
        for k in range(2)
    ]
    for t in threads:
        t.start()
    barrier.wait()
    for _ in range(n_each):
        with tr.span("main", cat="dispatch"):
            pass
    for t in threads:
        t.join()
    assert len(tr.events) == 5 * n_each  # nothing lost under contention
    # per-thread depth isolation: main spans never inherit worker nesting
    assert all(s.depth == 0 for s in tr.spans if s.name == "main")
    assert all(s.depth == 1 for s in tr.spans if s.name.endswith(".inner"))
    # thread names captured for the track mapping
    tids = {s.tid for s in tr.spans}
    assert len(tids) == 3
    assert set(tr.thread_names) == tids


def test_add_span_ends_now_at_the_current_depth():
    tr = Tracer(clock=_fake_clock(start=1_000, step=10))  # t_start_ns 1000
    with tr.span("dispatch", cat="dispatch"):  # enters at 1010
        tr.add_span("compile.backend", cat="compile", dur_ns=4, fun="f")  # now 1020
    inner, outer = tr.spans
    assert (inner.name, inner.cat, inner.t0_ns, inner.t1_ns) == (
        "compile.backend",
        "compile",
        1016,
        1020,
    )
    assert inner.depth == 1 and outer.depth == 0 and inner.attrs == {"fun": "f"}


def test_null_tracer_is_inert():
    nt = NullTracer()
    assert nt.enabled is False and NULL_TRACER.enabled is False
    with nt.span("x", cat="solve", epoch=1) as s:
        assert s is not None
    assert nt.instant("x") is None
    assert nt.count("x") is None
    assert nt.add_span("x", cat="compile", dur_ns=5) is None
    # the disabled span is one shared constant — no per-call allocation
    assert nt.span("a") is nt.span("b") is NULL_TRACER.span("c")


# --------------------------------------------------------------- exporters


def _golden_tracer():
    """A fixed two-track trace off the fake clock (main + prefetcher)."""
    tr = Tracer(clock=_fake_clock(start=1_000, step=1_000))
    with tr.span("opt_alpha.solve", cat="solve", n_active=6):
        pass
    with tr.span("pipelined.chunk", cat="dispatch", epoch=0):
        pass
    with tr.span("prefetch.stage", cat="stage", track="prefetcher", epoch=1):
        pass
    tr.instant("segment", cat="schedule", epoch=1)
    tr.count("opt_alpha.solves", 1)
    return tr


def test_chrome_trace_golden_structure():
    doc = chrome_trace(_golden_tracer())
    events = doc["traceEvents"]
    meta = [e for e in events if e["ph"] == "M"]
    xs = [e for e in events if e["ph"] == "X"]
    insts = [e for e in events if e["ph"] == "i"]
    # track metadata: the process plus one thread_name per track
    assert {m["name"] for m in meta} == {"process_name", "thread_name"}
    names = [m["args"]["name"] for m in meta if m["name"] == "thread_name"]
    assert names == ["main", "prefetcher"]
    # golden values: fake clock ticks 1000ns apart, exported in µs relative
    # to the tracer's start tick
    assert [(e["name"], e["ts"], e["dur"]) for e in xs] == [
        ("opt_alpha.solve", 1.0, 1.0),
        ("pipelined.chunk", 3.0, 1.0),
        ("prefetch.stage", 5.0, 1.0),
    ]
    assert xs[0]["args"] == {"n_active": 6}
    assert [(e["name"], e["ts"]) for e in insts] == [("segment", 7.0)]
    assert doc["repro"] == {
        "counters": {"opt_alpha.solves": 1},
        "dropped": 0,
        "n_tracks": 2,
    }


def test_export_round_trip_both_formats(tmp_path):
    tr = _golden_tracer()
    chrome = write_chrome_trace(tr, tmp_path / "t.json")
    jsonl = write_jsonl(tr, tmp_path / "t.jsonl")
    live = phase_attribution(tr.events)
    for path in (chrome, jsonl):
        loaded = load_trace_file(path)
        assert [s["name"] for s in loaded["spans"]] == [
            "opt_alpha.solve",
            "pipelined.chunk",
            "prefetch.stage",
        ]
        assert loaded["tracks"] == ["main", "prefetcher"]
        assert loaded["counters"] == {"opt_alpha.solves": 1}
        assert loaded["dropped"] == 0
        loaded_attr = phase_attribution_loaded(loaded["spans"])
        assert loaded_attr == pytest.approx(live)
    # and the summary CLI renders both without error
    out = format_summary(str(chrome), load_trace_file(chrome))
    assert "OPT-α solve" in out and "2 tracks" in out
    assert summary_main([str(chrome), str(jsonl)]) == 0


def test_phase_attribution_skips_same_category_nesting(tmp_path):
    tr = Tracer(clock=_fake_clock(step=100))
    with tr.span("outer", cat="dispatch"):
        with tr.span("inner", cat="dispatch"):  # same cat: already billed
            pass
        with tr.span("other", cat="stage"):  # cross cat: billed separately
            pass
    attr = phase_attribution(tr.events)
    outer = [s for s in tr.spans if s.name == "outer"][0]
    other = [s for s in tr.spans if s.name == "other"][0]
    assert attr["dispatch"] == pytest.approx(outer.dur_ns / 1e9)
    assert attr["stage"] == pytest.approx(other.dur_ns / 1e9)
    # loaded-back attribution applies the same pruning
    loaded = load_trace_file(write_chrome_trace(tr, tmp_path / "prune.json"))
    assert phase_attribution_loaded(loaded["spans"]) == pytest.approx(attr)


# ----------------------------------------- non-perturbation (bitwise) ----


def _quad_loss(params, batch):
    diff = params["x"][None, :] - batch["c"]
    return 0.5 * jax.numpy.mean(jax.numpy.sum(diff**2, axis=-1))


def _batch_stream(n, T=2, b=4, dim=4, seed=0):
    rng = np.random.default_rng(seed)

    def next_batch():
        return {"c": rng.standard_normal((n, T, b, dim)).astype(np.float32)}

    return next_batch


def _drift_schedule(n=6, seed=0):
    link = channels.MarkovLinkProcess(
        topology.ring(n, 2), p_up_to_down=0.4, p_down_to_up=0.6, seed=seed
    )
    drift = channels.PiecewiseConstantDrift(
        np.linspace(0.2, 0.9, n), hold=1, low=0.1, high=0.9, seed=seed + 1
    )
    return channels.TimeVaryingChannel(
        link_process=link, p_process=drift, adj_every=3, p_every=4
    )


def _run_traced(engine_name, tracer, n=6, rounds=12, chunk=4, seed=0):
    sim = FLSimulator(_quad_loss, n_clients=n, strategy="colrel_fused")
    params = {"x": jax.numpy.ones((4,))}
    server_state = sim.init_server_state(params)
    key = jax.random.key(seed)
    schedule = _drift_schedule(n, seed)
    if tracer is not None:
        schedule.tracer = tracer
    policy = channels.AdaptiveOptAlpha(sweeps=10, tracer=tracer)
    next_batch = _batch_stream(n, seed=seed)
    if engine_name == "loop":
        return run_rounds_loop(
            sim,
            key,
            params,
            server_state,
            schedule=schedule,
            rounds=rounds,
            next_batch=next_batch,
            lr=0.1,
            policy=policy,
            tracer=tracer,
        )
    cls = EpochScanEngine if engine_name == "scan" else PipelinedScanEngine
    engine = cls(sim, chunk=chunk, tracer=tracer)
    return engine.run_schedule(
        key,
        params,
        server_state,
        schedule=schedule,
        rounds=rounds,
        next_batch=next_batch,
        lr=0.1,
        policy=policy,
    )


def _tree_equal(a, b) -> bool:
    la, lb = jax.tree.leaves(a), jax.tree.leaves(b)
    return len(la) == len(lb) and all(
        np.array_equal(np.asarray(x), np.asarray(y)) for x, y in zip(la, lb)
    )


@pytest.mark.parametrize("engine_name", ["loop", "scan", "pipelined"])
def test_tracing_is_a_pure_observer(engine_name):
    """Every engine: tracing off (tracer=None) and tracing on produce
    bitwise-identical trajectories — spans, counters and fences must never
    leak into the math or the RNG stream."""
    bp, bs, bm, bk = _run_traced(engine_name, None)
    tracer = Tracer()
    tp, ts, tm, tk = _run_traced(engine_name, tracer)
    assert _tree_equal(bp, tp)
    assert _tree_equal(bs, ts)
    assert _tree_equal(bm, tm)
    assert np.array_equal(jax.random.key_data(bk), jax.random.key_data(tk))
    # and the traced run actually observed something at every layer
    cats = {s.cat for s in tracer.spans}
    assert {"solve", "dispatch"} <= cats
    assert tracer.counters["opt_alpha.solves"] > 0
    if engine_name != "loop":
        # the fused engines walk segments(); the loop driver walks rounds()
        assert any(i.name == "segment" for i in tracer.instants)
    # the loop and scan engines fence each dispatch; the pipelined engine
    # does not, so that its trace shows the pipeline as it runs untraced
    assert ("device" in cats) is (engine_name != "pipelined")
    if engine_name == "pipelined":
        assert "stage" in cats and "h2d" in cats
        # one staged chunk per dispatch, folded onto the counters at close
        n = tracer.counters["pipelined.dispatches"]
        assert n > 0
        assert tracer.counters["prefetch.chunks"] == n
        assert tracer.counters["prefetch.chunks_staged"] == n
        # staging spans land on the logical prefetcher track
        assert {"prefetcher"} <= {s.track for s in tracer.spans if s.track}


def test_traced_pipelined_run_has_no_fence_and_joins_chunk_spans(monkeypatch):
    """The pipelined engine never waits on the device when traced: no
    ``device`` span, no ``block_until_ready``.  Each chunk's stage, h2d and
    dispatch spans carry one ``chunk`` id, in that order on the host."""
    waits = []
    wait = jax.block_until_ready
    monkeypatch.setattr(jax, "block_until_ready", lambda x: waits.append(1) or wait(x))
    tracer = Tracer()
    _run_traced("pipelined", tracer, rounds=14)
    assert waits == []
    spans = tracer.spans
    assert not [s for s in spans if s.cat == "device" or s.name == "pipelined.device"]
    by_chunk: dict = {}
    for s in spans:
        if s.cat in ("stage", "h2d", "dispatch"):
            by_chunk.setdefault(s.attrs["chunk"], {})[s.cat] = s
    n = tracer.counters["pipelined.dispatches"]
    assert sorted(by_chunk) == list(range(n))
    for parts in by_chunk.values():
        assert set(parts) == {"stage", "h2d", "dispatch"}
        assert parts["stage"].t1_ns <= parts["h2d"].t0_ns
        assert parts["h2d"].t1_ns <= parts["dispatch"].t0_ns
        assert parts["stage"].attrs["epoch"] == parts["dispatch"].attrs["epoch"]


def _run_pipelined(engine, rounds):
    params = {"x": jax.numpy.ones((4,))}
    return engine.run_schedule(
        jax.random.key(0),
        params,
        engine.sim.init_server_state(params),
        schedule=_drift_schedule(),
        rounds=rounds,
        next_batch=_batch_stream(6),
        lr=0.1,
        policy=channels.AdaptiveOptAlpha(sweeps=10),
    )


def _conv_loss(params, batch):
    y = jax.lax.conv_general_dilated(
        batch["c"][:, None, :], params["x"][None, None, :], (1,), "SAME"
    )
    return _quad_loss(params, batch) + 0.5 * jax.numpy.mean(y**2)


@pytest.mark.parametrize(
    "loss, client_map", [(_quad_loss, "vmap"), (_conv_loss, "sequential")]
)
def test_chunk_dispatch_span_says_how_clients_are_mapped(loss, client_map):
    """Each traced chunk dispatch carries the client map its round was
    traced with: sequential for a convolutional loss, vmap otherwise."""
    tracer = Tracer()
    sim = FLSimulator(loss, n_clients=6, strategy="colrel_fused")
    _run_pipelined(PipelinedScanEngine(sim, chunk=4, tracer=tracer), 8)
    dispatches = [s for s in tracer.spans if s.name == "pipelined.chunk"]
    n = tracer.counters["pipelined.dispatches"]
    assert len(dispatches) == n > 1
    assert [s.attrs["client_map"] for s in dispatches] == [client_map] * n
    assert sim.client_map == client_map


def test_compiles_inside_a_traced_run_are_spans_and_counted():
    """A traced engine's first run records its chunk program's trace and
    compile; a steady run of the same shapes adds none; a forced retrace
    adds again.  The listener is gone once no traced run is open."""
    from repro.fl import compile_watch

    tracer = Tracer()
    sim = FLSimulator(_quad_loss, n_clients=6, strategy="colrel_fused")
    engine = PipelinedScanEngine(sim, chunk=4, tracer=tracer)

    def compiles_after_a_run():
        _run_pipelined(engine, 8)
        return tracer.counters.get(compile_watch.COUNTER, 0)

    first = compiles_after_a_run()
    assert first > 0
    spans = [s for s in tracer.spans if s.cat == "compile"]
    assert len(spans) == first
    assert {s.name for s in spans} == {"compile.trace", "compile.backend"}
    assert any("_chunk_impl" in s.attrs["fun"] for s in spans)
    assert all(s.dur_ns >= 0 for s in spans)
    assert compiles_after_a_run() == first  # steady: nothing compiles
    jax.clear_caches()
    assert compiles_after_a_run() > first  # forced retrace
    assert compile_watch._watching == {}
    # an untraced engine opens no watch
    _run_pipelined(PipelinedScanEngine(sim, chunk=4), 4)
    assert compile_watch._watching == {}


def test_chunk_program_names_its_layers():
    """The compiled chunk program's op metadata carries the round's layer
    scopes, which a device trace reports as each op's name-scope path.  Two
    leaves, so that the ravel is a real concatenation and not a bitcast."""

    def loss(params, batch):
        return _quad_loss(params, batch) + jax.numpy.sum(params["y"] ** 2)

    sim = FLSimulator(loss, n_clients=6, strategy="colrel_fused")
    engine = PipelinedScanEngine(sim, chunk=4)
    params = {"x": jax.numpy.ones((4,)), "y": jax.numpy.ones((3,))}
    lowered = engine._chunk_fn.lower(
        jax.random.key(0),
        params,
        sim.init_server_state(params),
        {"c": np.zeros((4, 6, 2, 4, 4), np.float32)},
        np.ones(4, bool),
        jax.numpy.eye(6),
        jax.numpy.ones(6),
        0.1,
        None,
    )
    hlo = lowered.compile().as_text()
    for scope in ("local_train", "ravel", "aggregate", "server_update"):
        assert f"/{scope}/" in hlo, scope


def test_trainer_records_fetch_and_control_spans_per_burst():
    from repro.launch.train import ContinuousTrainer

    trainer = ContinuousTrainer(
        FLSimulator(_quad_loss, n_clients=6, strategy="colrel_fused"),
        schedule=_drift_schedule(),
        next_batch=_batch_stream(6),
        lr=0.1,
        policy=channels.AdaptiveOptAlpha(sweeps=10),
        engine="pipelined",
        chunk=4,
        publish_every=4,
    )
    assert trainer.tracer is NULL_TRACER
    tracer = Tracer()
    trainer.tracer = tracer  # follows to the engine
    assert trainer._engine.tracer is tracer
    trainer.init({"x": jax.numpy.ones((4,))}, jax.random.key(1))
    stops = []
    trainer.run(12, stop=lambda: stops.append(1) and False)
    names = [s.name for s in tracer.spans if s.cat in ("fetch", "control")]
    assert names == ["trainer.fetch", "trainer.publish", "trainer.stop"] * 3
    assert len(stops) == 3
    fetches = [s for s in tracer.spans if s.name == "trainer.fetch"]
    assert [s.attrs["rounds"] for s in fetches] == [4, 4, 4]
    # the last burst's fetch follows its dispatches
    dispatch_end = max(s.t1_ns for s in tracer.spans if s.cat == "dispatch")
    assert fetches[-1].t0_ns >= dispatch_end


def test_null_tracer_default_records_nothing_anywhere():
    """The default (no tracer passed) wires NULL_TRACER end to end: same
    trajectory, and nothing to flush."""
    pol = channels.AdaptiveOptAlpha(sweeps=10)
    assert pol.tracer is NULL_TRACER
    engine = PipelinedScanEngine(
        FLSimulator(_quad_loss, n_clients=6, strategy="colrel_fused"), chunk=4
    )
    assert engine.tracer is NULL_TRACER


# ------------------------------------------------------- bench integration


def test_traced_bench_scenario_end_to_end(tmp_path):
    """A traced scenario run: the pipelined trace carries the three logical
    tracks, the report telemetry block's attribution is sane, and the trace
    file loads back (Perfetto-compatible structure)."""
    from repro.bench import harness, report as report_lib
    from repro.bench.scenarios import ScenarioSpec

    spec = ScenarioSpec(
        name="obs_tiny",
        description="telemetry integration fixture",
        n_clients=4,
        rounds=12,
        local_steps=1,
        local_batch=4,
        dim=8,
        width=4,
        n_train=64,
        adj_every=4,
        p_every=4,
        chunk=4,
        opt_method="bisect",
        opt_sweeps=10,
        warm_sweeps=5,
    )
    result = harness.run_scenario(
        spec,
        engines=("loop", "pipelined"),
        trace_dir=tmp_path,
    )
    rep = report_lib.make_report(spec, result)
    for name in ("loop", "pipelined"):
        run = result["runs"][name]
        assert run.trace_path is not None
        tele = rep["telemetry"][name]
        assert tele is run.telemetry
        # attribution sums to a meaningful share of the traced wall, and
        # same-category pruning keeps it from exceeding it
        assert 0.3 < tele["attributed_fraction"] <= 1.05
        assert tele["dropped"] == 0 and tele["events"] > 0
        # as_dict keeps the engines block JSON-light (telemetry lives once
        # at the report top level)
        assert "telemetry" not in run.as_dict()
        assert run.as_dict()["trace_path"] == run.trace_path
    pipe = result["runs"]["pipelined"]
    loaded = load_trace_file(pipe.trace_path)
    assert {"main", "prefetcher", "device"} <= set(loaded["tracks"])
    assert loaded["counters"]["pipelined.dispatches"] == pipe.dispatches
    # pipelined extras recorded from the untraced warm run
    assert pipe.chunks_staged == pipe.dispatches
    assert 0.0 <= pipe.steady_overlap_fraction <= 1.0
    # the report is valid JSON including the telemetry block
    path = report_lib.write_report(rep, tmp_path)
    assert json.loads(path.read_text())["telemetry"]["pipelined"]["phases"]
