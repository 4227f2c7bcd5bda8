"""Reduce a profiler trace to device busy time, per-op time and idle gaps.

The traced stretch is marked on the device itself: between bursts the
benchmark dispatches a tiny program, ``jit_chipbench_mark``, which runs
once the burst's work is done (host tracing stays off, as on a TPU host
it records millions of runtime events a second and starves the loader).
The window runs from the end of the first mark to the start of the last.

``from_xplane`` reads the ``.xplane.pb`` that ``jax.profiler`` writes into
a small plain form, which is also what ``testdata/`` keeps:

    {"ops":     {plane: [[op name, start_ns, dur_ns], ...]},
     "modules": {plane: [[program name, start_ns, dur_ns], ...]}}

``ops`` is each TPU plane's ``XLA Ops`` line, ``modules`` its ``XLA
Modules`` line (one event per program run, the marks among them).
``reduce`` works on that form alone.
"""
from __future__ import annotations

import re
from collections import defaultdict

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
MARK = "chipbench_mark"
MAJOR_NS = 10_000
TOP = 10

# the device trace names an op by its HLO text, "%name = shape op(...)"
HLO_NAME = re.compile(r"^%?([\w.\-]+)\s*=")


def op_name(text: str) -> str:
    m = HLO_NAME.match(text)
    return m.group(1) if m else text[:200]


def mark() -> None:
    """Run the mark program on the device and wait for it."""
    import jax
    import jax.numpy as jnp

    jax.block_until_ready(_mark_program()(jnp.zeros((), jnp.int32)))


_MARK_FN: list = []


def _mark_program():
    if not _MARK_FN:
        import jax

        def chipbench_mark(x):
            return x + 1

        _MARK_FN.append(jax.jit(chipbench_mark))
    return _MARK_FN[0]


def from_xplane(path) -> dict:
    from jax.profiler import ProfileData

    out: dict = {"ops": {}, "modules": {}}
    for plane in ProfileData.from_file(str(path)).planes:
        if not DEVICE_PLANE.match(plane.name):
            continue
        for line in plane.lines:
            if line.name == OPS_LINE:
                key, name = "ops", op_name
            elif line.name == MODULES_LINE:
                key, name = "modules", str
            else:
                continue
            out[key][plane.name] = [
                [name(e.name), float(e.start_ns), float(e.duration_ns)] for e in line.events
            ]
    return out


def union(intervals) -> list:
    """Merge ``(start, end)`` intervals into disjoint sorted ones."""
    merged: list = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            if e > merged[-1][1]:
                merged[-1][1] = e
        else:
            merged.append([s, e])
    return merged


def self_times(events) -> list:
    """``[name, start, end, self]`` for ``(name, start, end)`` ops of one
    line, where an op that holds others (a while loop and its body) keeps
    only the time none of its children covers."""
    evs = sorted(events, key=lambda e: (e[1], e[1] - e[2]))
    out = [[name, s, e, e - s] for name, s, e in evs]
    stack: list = []  # indices of the open ops, outermost first
    for i, (_, s, e, _) in enumerate(out):
        while stack and out[stack[-1]][2] < e:
            stack.pop()
        if stack:
            out[stack[-1]][3] -= e - s
        stack.append(i)
    return out


def _gap_name(programs, start, end) -> str:
    """What the device waited for in an idle gap, from the program runs
    around it: the host's turn between bursts (fetching a burst's result
    and deciding to go on), staging a burst's first chunk with nothing in
    flight, staging between two programs, or a pause inside one."""
    if any(p[1] < start and p[2] > end for p in programs):
        return "inside a program"
    # the marks, and programs that do work: not the host's sub-microsecond
    # conversions of a segment's inputs
    major = [p for p in programs if MARK in p[0] or p[2] - p[1] > MAJOR_NS]
    after = [p for p in major if p[1] >= end]
    before = [p for p in major if p[2] <= start]
    if not after or MARK in after[0][0]:
        return "after a burst: result fetch and stop check"
    if before and MARK in before[-1][0]:
        return "burst start: first chunk staged with nothing in flight"
    return "between programs: staging not hidden"


def reduce(trace: dict) -> dict | None:
    """Busy and window seconds (averaged over the device planes), per-op
    count, seconds and self seconds, and the top device ops (by self time)
    and idle gaps; ``None`` without two marks or without a device op
    between them."""
    busy_ns, window_ns, planes = 0.0, 0.0, 0
    ops: dict = defaultdict(lambda: [0, 0.0, 0.0])  # count, seconds, self seconds
    gaps = []
    for plane, modules in trace["modules"].items():
        marks = sorted((s, s + d) for name, s, d in modules if MARK in name)
        if len(marks) < 2:
            continue
        lo, hi = marks[0][1], marks[-1][0]
        clipped = [
            (name, max(s, lo), min(s + d, hi))
            for name, s, d in trace["ops"].get(plane, [])
            if s + d > lo and s < hi
        ]
        if not clipped:
            continue
        planes += 1
        window_ns += hi - lo
        for name, s, e, own in self_times(clipped):
            ops[name][0] += 1
            ops[name][1] += (e - s) / 1e9
            ops[name][2] += own / 1e9
        merged = union([(s, e) for _, s, e in clipped])
        busy_ns += sum(e - s for s, e in merged)
        programs = sorted(((name, s, s + d) for name, s, d in modules), key=lambda p: p[1])
        edges = [lo] + [x for iv in merged for x in iv] + [hi]
        for i in range(0, len(edges), 2):
            if edges[i + 1] > edges[i]:
                gaps.append((edges[i + 1] - edges[i], edges[i], edges[i + 1], programs))
    if planes == 0:
        return None
    gaps.sort(key=lambda g: -g[0])
    top_ops = sorted(ops.items(), key=lambda kv: -kv[1][2])[:TOP]
    return {
        "busy_s": busy_ns / planes / 1e9,
        "window_s": window_ns / planes / 1e9,
        "ops": {k: tuple(v) for k, v in ops.items()},
        "breakdown": {
            "device_ops": [[name, own] for name, (_, _, own) in top_ops],
            "idle_gaps": [[_gap_name(p, s, e), g / 1e9] for g, s, e, p in gaps[:TOP]],
        },
    }
