"""The program's host spans on the device trace's clock: device time by
layer, and every idle nanosecond of the device to the host work behind it.

Host tracing stays off on the TPU host (``trace_reduce``), so the device
trace holds no host event.  The clocks are joined by the benchmark's mark
program instead: the host reads ``time.perf_counter_ns`` just before a
mark is dispatched and just after it is ready (``timed_mark``), and the
mark's run on the device has to fall inside that interval.  Each mark so
bounds the offset ``host = device + offset``; ``clock_offset`` intersects
the bounds of every mark of the trace, and the width of the intersection
is the offset's uncertainty.  Past ``MAX_UNCERTAINTY_NS`` nothing is
placed: the span-based numbers read ``None``.

``from_xplane`` reads the profiler's ``.xplane.pb`` as
``trace_reduce.from_xplane`` does, but on the device's own clock, with
each op's name-scope path (the HLO ``op_name`` metadata, which the
profiler keeps as the ``tf_op`` stat of the op's event metadata, e.g.
``jit(_chunk_impl)/while/body/closed_call/local_train/vmap()/sub:``) as a
fourth field; ``three_fields`` gives back the form ``trace_reduce.reduce``
reads.  The round step names its layers with ``jax.named_scope``
(``fl/simulator.py``): ``SCOPES``.  ``attribute`` turns a trace, the
marks' host intervals and the program's spans into what the per-layer
readers read.
"""
from __future__ import annotations

import pathlib
import re
import time
from collections import defaultdict

from chipbench import trace_reduce

SCOPES = ("local_train", "ravel", "aggregate", "server_update")
NO_SCOPE = "none"
# the profiler's stat that holds an op's HLO op_name (its name-scope path)
SCOPE_STAT = "tf_op"
MAX_UNCERTAINTY_NS = 1_000_000
# idle under these span categories is staging (the prefetcher's stage and
# h2d, the OPT-alpha solve inside it) or the burst's edge (the trainer's
# result fetch, publish and stop poll); idle under a dispatch or compile
# span, or any other (such as the benchmark's own ``mark`` work inside the
# trainer's stop poll), is the remainder
STAGING = ("stage", "h2d", "solve")
BURST_EDGE = ("fetch", "control")
GAP_NS = 10_000_000  # idle gaps named one by one on standard error

_SCOPE = re.compile(r"(?:^|/)(" + "|".join(SCOPES) + r")(?=[/:]|$)")


def scope_of(path: str) -> str:
    """The innermost of ``SCOPES`` among the components of a name-scope
    path, or ``NO_SCOPE``."""
    found = _SCOPE.findall(path or "")
    return found[-1] if found else NO_SCOPE


def _xspace_class():
    """A message class for the part of the profiler's ``XSpace`` proto
    (``tsl/profiler/protobuf/xplane.proto``, field numbers as there) that
    holds each plane's event and stat metadata; ``jax.profiler``'s
    ``ProfileData`` gives an event's own stats but not its metadata's.
    Lines and events are skipped as unknown fields."""
    from google.protobuf import descriptor_pb2, descriptor_pool, message_factory

    F = descriptor_pb2.FieldDescriptorProto
    one, many = F.LABEL_OPTIONAL, F.LABEL_REPEATED
    fd = descriptor_pb2.FileDescriptorProto(
        name="chipbench_xspace.proto", package="chipbench_xspace", syntax="proto3")
    messages = {  # name: [(field, number, label, type or message)]
        "XStat": [("metadata_id", 1, one, F.TYPE_INT64), ("str_value", 5, one, F.TYPE_STRING),
                  ("ref_value", 7, one, F.TYPE_UINT64)],
        "XStatMetadata": [("id", 1, one, F.TYPE_INT64), ("name", 2, one, F.TYPE_STRING)],
        "XEventMetadata": [("id", 1, one, F.TYPE_INT64), ("name", 2, one, F.TYPE_STRING),
                           ("stats", 5, many, "XStat")],
        # the proto's two maps, in their wire form: repeated key/value entries
        "EventMetadataEntry": [("key", 1, one, F.TYPE_INT64),
                               ("value", 2, one, "XEventMetadata")],
        "StatMetadataEntry": [("key", 1, one, F.TYPE_INT64), ("value", 2, one, "XStatMetadata")],
        "XPlane": [("name", 2, one, F.TYPE_STRING),
                   ("event_metadata", 4, many, "EventMetadataEntry"),
                   ("stat_metadata", 5, many, "StatMetadataEntry")],
        "XSpace": [("planes", 1, many, "XPlane")],
    }
    for name, fields in messages.items():
        m = fd.message_type.add(name=name)
        for field, number, label, kind in fields:
            f = m.field.add(name=field, number=number, label=label)
            if isinstance(kind, str):
                f.type, f.type_name = F.TYPE_MESSAGE, f".chipbench_xspace.{kind}"
            else:
                f.type = kind
    pool = descriptor_pool.DescriptorPool()
    pool.Add(fd)
    return message_factory.GetMessageClass(pool.FindMessageTypeByName("chipbench_xspace.XSpace"))


def scope_paths(path) -> dict:
    """``{plane: {event name: name-scope path}}`` from the ``SCOPE_STAT``
    stat of each device plane's event metadata (an event's name is its
    metadata's)."""
    space = _xspace_class().FromString(pathlib.Path(path).read_bytes())
    out: dict = {}
    for plane in space.planes:
        if not trace_reduce.DEVICE_PLANE.match(plane.name):
            continue
        names = {e.key: e.value.name for e in plane.stat_metadata}
        wanted = {k for k, v in names.items() if v == SCOPE_STAT}
        table = out.setdefault(plane.name, {})
        for entry in plane.event_metadata:
            meta = entry.value
            for stat in meta.stats:
                if stat.metadata_id in wanted:
                    # a repeated string is interned: a reference to the stat
                    # metadata that carries it as its name
                    table[meta.name] = stat.str_value or names.get(stat.ref_value, "")
    return out


def _device_times(e) -> tuple:
    """An event's start and duration (ns) on the device's own clock, where
    the profiler keeps them (``device_offset_ps``, ``device_duration_ps``);
    else as ``ProfileData`` gives them.  ``ProfileData`` moves each program
    run onto the host's clock by an anchor of its own, and on a TPU v5e an
    anchor can be off by milliseconds (seen: 4.6 and 7.8 ms on one mark of
    a window), which no constant offset from the marks can absorb; the
    device's clock runs steadily through the window."""
    start = duration = None
    for name, value in e.stats:
        if name == "device_offset_ps":
            start = float(value) / 1e3
        elif name == "device_duration_ps":
            duration = float(value) / 1e3
    if start is None or duration is None:
        return float(e.start_ns), float(e.duration_ns)
    return start, duration


def from_xplane(path) -> dict:
    """``trace_reduce.from_xplane``'s form on the device's own clock
    (``_device_times``), each op with its name-scope path as a fourth
    field (``""`` where its metadata has none)."""
    from jax.profiler import ProfileData

    scopes = scope_paths(path)
    out: dict = {"ops": {}, "modules": {}}
    for plane in ProfileData.from_file(str(path)).planes:
        if not trace_reduce.DEVICE_PLANE.match(plane.name):
            continue
        table = scopes.get(plane.name, {})
        for line in plane.lines:
            if line.name == trace_reduce.OPS_LINE:
                out["ops"][plane.name] = [
                    [trace_reduce.op_name(e.name), *_device_times(e), table.get(e.name, "")]
                    for e in line.events
                ]
            elif line.name == trace_reduce.MODULES_LINE:
                out["modules"][plane.name] = [
                    [str(e.name), *_device_times(e)] for e in line.events
                ]
    return out


def three_fields(trace: dict) -> dict:
    """The trace as ``trace_reduce.reduce`` reads it: ops without scopes."""
    return {
        "ops": {p: [op[:3] for op in ops] for p, ops in trace["ops"].items()},
        "modules": trace["modules"],
    }


def timed_mark() -> tuple:
    """``trace_reduce.mark`` with the host's ``perf_counter_ns`` just before
    the mark program is dispatched and just after its result is ready."""
    import jax
    import jax.numpy as jnp

    x = jax.block_until_ready(jnp.zeros((), jnp.int32))
    t0 = time.perf_counter_ns()
    jax.block_until_ready(trace_reduce._mark_program()(x))
    return t0, time.perf_counter_ns()


def mark_runs(modules) -> list:
    """``(start, end)`` of each run of the mark program, in order."""
    return sorted((s, s + d) for name, s, d in modules if trace_reduce.MARK in name)


def clock_offset(runs, hosts) -> dict | None:
    """The offset ``host = device + offset`` from marks whose device runs
    (``runs``) and host intervals (``hosts``) pair up in order: the middle
    of the intersection of the bounds ``host0 - start <= offset <= host1 -
    end``, and its width as ``uncertainty_ns``.  ``None`` when the counts
    differ or the bounds do not meet."""
    if not runs or len(runs) != len(hosts):
        return None
    lo = max(h0 - s for (s, _), (h0, _) in zip(runs, hosts))
    hi = min(h1 - e for (_, e), (_, h1) in zip(runs, hosts))
    if hi < lo:
        return None
    return {"offset_ns": (lo + hi) / 2, "uncertainty_ns": hi - lo}


def idle_intervals(ops, lo, hi) -> list:
    """The stretches of ``[lo, hi]`` in which no op runs, in order."""
    clipped = [(max(op[1], lo), min(op[1] + op[2], hi)) for op in ops
               if op[1] + op[2] > lo and op[1] < hi]
    edges = [lo] + [x for iv in trace_reduce.union(clipped) for x in iv] + [hi]
    return [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
            if edges[i + 1] > edges[i]]


def _innermost(spans) -> list:
    """``(start, end, span)`` pieces that cover the spans' union, each
    labelled with the innermost span open over it: the one that started
    last (of two that started together, the shorter)."""
    cuts = sorted({t for s in spans for t in (s["t0"], s["t1"])})
    pieces = []
    for a, b in zip(cuts, cuts[1:]):
        open_ = [s for s in spans if s["t0"] <= a and s["t1"] >= b]
        if open_:
            pieces.append((a, b, max(open_, key=lambda s: (s["t0"], -s["t1"]))))
    return pieces


def attribute_idle(idle, spans) -> dict:
    """Split every idle nanosecond to the innermost span open over it:
    ``{category: ns}``, with ``None`` for idle under no span.  ``spans``
    are dicts with ``t0``, ``t1`` (on the device clock) and ``cat``; the
    parts add up to the idle time exactly."""
    pieces = _innermost(spans)
    out: dict = defaultdict(float)
    j = 0
    for s, e in idle:
        t = s
        while t < e:
            while j < len(pieces) and pieces[j][1] <= t:
                j += 1
            if j == len(pieces) or pieces[j][0] >= e:
                out[None] += e - t
                break
            a, b, span = pieces[j]
            if a > t:
                out[None] += a - t
                t = a
                continue
            end = min(b, e)
            out[span["cat"]] += end - t
            t = end
    return dict(out)


def gap_spans(idle, spans, min_ns=GAP_NS) -> list:
    """Each idle gap of at least ``min_ns``: ``(start, length, [(span
    name, category, ns of the gap under it as innermost)])``."""
    out = []
    pieces = _innermost(spans)
    for s, e in idle:
        if e - s < min_ns:
            continue
        under: dict = defaultdict(float)
        for a, b, span in pieces:
            if b > s and a < e:
                under[(span["name"], span["cat"])] += min(b, e) - max(a, s)
        covered = sum(under.values())
        if e - s > covered:
            under[("(no span)", None)] += e - s - covered
        out.append((s, e - s, sorted(((n, c, t) for (n, c), t in under.items()),
                                     key=lambda x: -x[2])))
    return out


def scope_times(ops, lo, hi) -> dict:
    """Self time (ns) of the ops in ``[lo, hi]`` by layer scope."""
    clipped = [(scope_of(op[3] if len(op) > 3 else ""), max(op[1], lo),
                min(op[1] + op[2], hi)) for op in ops if op[1] + op[2] > lo and op[1] < hi]
    out: dict = defaultdict(float)
    for scope, _, _, own in trace_reduce.self_times(clipped):
        out[scope] += own
    return dict(out)


def _overlap(a, b) -> float:
    """Length of the intersection of two sorted lists of disjoint
    intervals."""
    total, j = 0.0, 0
    for s, e in a:
        while j < len(b) and b[j][1] <= s:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            total += min(e, b[k][1]) - max(s, b[k][0])
            k += 1
    return total


def attribute(trace: dict, hosts, spans) -> dict | None:
    """What the span and scope readers read, from the first device plane
    with two marks: the clock offset and its uncertainty (``None`` where
    the marks do not pair up with ``hosts`` or their bounds do not meet;
    ``bounds`` gives each mark's), the window (between the first mark's
    end and the last mark's start), idle seconds by span category (``None``
    without an offset within ``MAX_UNCERTAINTY_NS``), the idle seconds that
    fall inside a program's run (gaps between its ops: the device's own,
    whatever host span is open), busy seconds by scope, and the idle gaps of
    at least ``GAP_NS`` with the spans under them.  ``spans`` are the
    program's ``obs`` span events, on the host clock."""
    for plane, modules in sorted(trace["modules"].items()):
        runs = mark_runs(modules)
        if len(runs) < 2:
            continue
        offset = clock_offset(runs, hosts)
        lo, hi = runs[0][1], runs[-1][0]
        ops = trace["ops"].get(plane, [])
        idle = idle_intervals(ops, lo, hi)
        placed = gaps = None
        if offset is not None and offset["uncertainty_ns"] <= MAX_UNCERTAINTY_NS:
            c = offset["offset_ns"]
            on_device = [
                {"t0": sp.t0_ns - c, "t1": sp.t1_ns - c, "cat": sp.cat, "name": sp.name}
                for sp in spans if sp.t1_ns - c > lo and sp.t0_ns - c < hi
            ]
            placed = {k: v / 1e9 for k, v in attribute_idle(idle, on_device).items()}
            gaps = [(s - lo, n, under) for s, n, under in gap_spans(idle, on_device)]
        programs = trace_reduce.union(
            [(s, s + d) for name, s, d in modules if trace_reduce.MARK not in name])
        return {
            "plane": plane,
            "offset_ns": None if offset is None else offset["offset_ns"],
            "uncertainty_ns": None if offset is None else offset["uncertainty_ns"],
            "bounds": [(h0 - s, h1 - e) for (s, e), (h0, h1) in zip(runs, hosts)],
            "marks": (len(runs), len(hosts)),
            "window_s": (hi - lo) / 1e9,
            "idle_s": sum(e - s for s, e in idle) / 1e9,
            "idle_in_program_s": _overlap(idle, programs) / 1e9,
            "idle_by_span": placed,
            "scopes": {k: v / 1e9 for k, v in scope_times(ops, lo, hi).items()},
            "gaps": gaps,
        }
    return None


def idle_buckets(idle_by_span: dict) -> dict:
    """Idle seconds by span category folded into ``staging``,
    ``burst_edge``, ``unattributed`` and the remainder, each category of
    which is kept under its own name."""
    out = {"staging": 0.0, "burst_edge": 0.0, "unattributed": 0.0, "remainder": {}}
    for cat, sec in idle_by_span.items():
        if cat is None:
            out["unattributed"] += sec
        elif cat in STAGING:
            out["staging"] += sec
        elif cat in BURST_EDGE:
            out["burst_edge"] += sec
        else:
            out["remainder"][cat] = out["remainder"].get(cat, 0.0) + sec
    return out
