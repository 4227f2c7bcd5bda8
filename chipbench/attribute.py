"""One traced run of a cell with the program's spans on the device clock.

    python3 chipbench/attribute.py --workload resnet20_n10.fig5 --seed 7 \
        --seconds 20 [--profile 1] [--fixture PATH]

Set-up and window as ``run.py`` runs them (``harness.prepare``, then the
trainer closed-loop in bursts), except that the trainer and its engine
record into the run's ``obs`` Tracer too (the harness gives it to the
OPT-alpha policy only).  With ``--profile 1`` the profiler records the
window's second and third bursts as in ``run.py --trace 1``, and each mark
between them is timed on the host (``trace_align.timed_mark``), so that
the spans land on the device clock.  The last line of standard output is
one JSON object: ``rounds_per_s`` of the window without the profiler and,
with it, every per-layer metric (those of ``BENCHMARK.json`` and those that read
``trace_align``'s results), with the clock offset, the idle split, the
busy time by layer scope and the idle gaps over 10 ms on standard error.
With ``--profile 0`` the tracer records and nothing else differs from an
untraced run: what the spans cost.  No correctness check is made.
The benchmark's own work at each burst boundary (the burst wait, the
timed mark, the compile count) is recorded as a ``bench.mark`` span of
category ``mark``, inside the trainer's ``trainer.stop``: idle under it is
the benchmark's, and lands in the remainder, not in the burst edge.
``--fixture`` writes the window's spans, the marks' host intervals and
the device ops from the window's start to 50 ms into its first chunk
program, with their scopes.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gzip  # noqa: E402
import json  # noqa: E402
import pathlib  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parents[1]
NEW_METRICS = (
    "local_train_ms_per_round", "ravel_ms_per_round", "idle_staging_ms_per_round",
    "idle_burst_edge_ms_per_round", "idle_unattributed_ms_per_round", "window_compiles",
)
FIXTURE_NS = 50e6
MARK = "mark"  # category of the benchmark's own work at a burst boundary


def _compile_count(tracer):
    """The program's ``compile.events`` total, or None where the program
    keeps no such counter."""
    try:
        from repro.fl import compile_watch
    except ImportError:
        return None
    return tracer.counters.get(compile_watch.COUNTER, 0)


def run(root, workload, seed, seconds, profile, t_start, *, fixture=None, require_tpu=True):
    import jax

    from chipbench import cells, harness, trace_align, trace_reduce

    cell = cells.resolve(root, workload)
    peaks = harness.load_peaks(root)
    devices = harness.check_devices(cell, peaks, require_tpu)
    peak = peaks.get(devices[0].device_kind)
    prep = harness.prepare(cell, seed, traced=True)
    trainer = prep.trainer
    tracer = prep.tracer
    trainer.tracer = tracer
    ends: list = []
    tracing = {"dir": None, "done": not profile, "from": None, "hosts": [], "compiles": []}

    def advance(bursts):
        if tracing["done"]:
            return
        if bursts == 1:
            tracing["dir"] = tempfile.mkdtemp(prefix="chipbench_attr_")
            options = jax.profiler.ProfileOptions()
            options.python_tracer_level = 0
            options.host_tracer_level = 0
            jax.profiler.start_trace(tracing["dir"], profiler_options=options)
            tracing["from"] = trainer.round
        tracing["hosts"].append(trace_align.timed_mark())
        tracing["compiles"].append(_compile_count(tracer))
        if bursts == 1 + harness.TRACED_BURSTS:
            jax.profiler.stop_trace()
            tracing["to"] = trainer.round
            tracing["done"] = True

    def stop():
        now = time.perf_counter()
        with tracer.span("bench.mark", cat=MARK):
            ends.append((now, trainer.round, harness._burst_wait(trainer)))
            advance(len(ends))
        return now - t_window >= seconds and tracing["done"]

    round0 = trainer.round
    t_window = time.perf_counter()
    trainer.run(10**15, stop=stop)
    t_last, round_last, _ = ends[-1]
    metrics = {}
    if not profile:  # the profiler's own stop and the marks cost time
        metrics["rounds_per_s"] = (round_last - round0) / (t_last - t_window)
    out = {"metrics": metrics, "device": {"platform": devices[0].platform,
                                          "kind": devices[0].device_kind}}
    if profile:
        try:
            path = sorted(pathlib.Path(tracing["dir"]).rglob("*.xplane.pb"))[-1]
            trace = trace_align.from_xplane(path)
        finally:
            shutil.rmtree(tracing["dir"], ignore_errors=True)
        summary = trace_reduce.reduce(trace_align.three_fields(trace))
        art = harness._artifacts(cell, peak, summary, tracing, prep, ends, t_window, round0)
        found = trace_align.attribute(trace, tracing["hosts"], tracer.spans)
        first, last = tracing["compiles"][0], tracing["compiles"][-1]
        art["window_compiles"] = None if first is None else last - first
        if found is not None:
            art["scopes"] = found["scopes"]
            if found["idle_by_span"] is not None:
                art["idle_buckets"] = trace_align.idle_buckets(found["idle_by_span"])
            _report(found, art, summary)
        names = [m["name"] for m in cell.per_layer] + list(NEW_METRICS)
        for name in names:
            value = cell.reader(name).read(art)
            if value is not None:
                metrics[name] = value
        out["device"]["busy_s"] = summary["busy_s"] if summary else None
        out["device"]["window_s"] = summary["window_s"] if summary else None
        if fixture and found is not None:
            _write_fixture(fixture, trace, found, tracing, tracer.spans)
    return out


def _report(found, art, summary):
    rounds = art["traced_rounds"]
    err = sys.stderr
    marks, hosts = found["marks"]
    bounds = ", ".join(f"[{lo:.0f}, {hi:.0f}]" for lo, hi in found["bounds"])
    print(f"{marks} mark runs on {found['plane']}, {hosts} timed on the host; "
          f"offset bounds (ns) {bounds}", file=err)
    if found["offset_ns"] is None:
        print("no clock offset: the marks do not pair up or their bounds do not meet",
              file=err)
    else:
        print(f"clock offset {found['offset_ns']:.0f} ns, uncertainty "
              f"{found['uncertainty_ns']:.0f} ns", file=err)
    idle_ms = 1e3 * found["idle_s"] / rounds
    print(f"window {found['window_s']:.6f} s, {rounds} rounds, idle {idle_ms:.4f} ms/round, "
          f"{1e3 * found['idle_in_program_s'] / rounds:.4f} of it inside program runs",
          file=err)
    if found["idle_by_span"] is not None:
        b = art["idle_buckets"]
        parts = {"staging": b["staging"], "burst_edge": b["burst_edge"],
                 "unattributed": b["unattributed"], **b["remainder"]}
        for name, sec in parts.items():
            print(f"idle under {name}: {1e3 * sec / rounds:.4f} ms/round", file=err)
        total = sum(parts.values())
        print(f"idle parts sum {1e3 * total / rounds:.4f} ms/round "
              f"(idle {idle_ms:.4f})", file=err)
        for start, length, under in found["gaps"]:
            named = ", ".join(f"{n} [{c}] {t / 1e6:.3f} ms" for n, c, t in under)
            print(f"gap at +{start / 1e9:.4f} s, {length / 1e6:.3f} ms: {named}", file=err)
    busy = sum(found["scopes"].values())
    for scope, sec in sorted(found["scopes"].items(), key=lambda kv: -kv[1]):
        print(f"scope {scope}: {sec:.6f} s, {100 * sec / busy:.3f}% of busy", file=err)
    if summary:
        print(f"busy (reduce) {summary['busy_s']:.6f} s, by scopes {busy:.6f} s", file=err)


def _write_fixture(path, trace, found, tracing, spans):
    """The window's spans, the marks' host intervals, every program run,
    and the ops from the window's start to ``FIXTURE_NS`` into its first
    chunk program run, with their scopes: the burst's first idle gap and
    the start of its first chunk."""
    from chipbench import trace_align

    plane = found["plane"]
    modules = trace["modules"][plane]
    runs = trace_align.mark_runs(modules)
    lo, hi = runs[0][1], runs[-1][0]
    chunk = min(s for name, s, d in modules if "_chunk_impl" in name and s >= lo)
    ops = [op for op in trace["ops"][plane] if lo <= op[1] < chunk + FIXTURE_NS]
    c = found["offset_ns"]
    if c is not None:  # the window's spans, and a second around it
        spans = [s for s in spans if s.t1_ns > lo + c - 1e9 and s.t0_ns < hi + c + 1e9]
    doc = {
        "ops": {plane: ops},
        "modules": {plane: modules},
        "marks_host": tracing["hosts"],
        "spans": [[s.name, s.cat, s.t0_ns, s.t1_ns, s.depth, s.attrs] for s in spans],
        "traced_rounds": tracing["to"] - tracing["from"],
    }
    with gzip.open(path, "wt") as f:
        json.dump(doc, f)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--profile", type=int, choices=(0, 1), default=1)
    ap.add_argument("--fixture", default=None)
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT / "src"), str(ROOT)]
    from chipbench import harness

    harness.use_compile_cache()
    try:
        out = run(ROOT, args.workload, args.seed, args.seconds, bool(args.profile), T_START,
                  fixture=args.fixture)
    except harness.NoChip as e:
        print(f"chipbench: {e}", file=sys.stderr)
        return 3
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
