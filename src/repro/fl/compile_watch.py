"""Compiles as telemetry: JAX's trace and backend-compile events recorded
into an engine's :class:`~repro.obs.Tracer`.

While an engine with an enabled tracer runs (``watching_compiles`` on its
``run_schedule``), each ``/jax/core/compile/jaxpr_trace_duration`` and
``/jax/core/compile/backend_compile_duration`` event JAX reports becomes a
span of category ``compile`` (``compile.trace`` / ``compile.backend``, attr
``fun``: the function JAX traced or compiled) that ends when JAX reports it
and lasts the duration JAX measured, and adds one to the tracer's
``compile.events`` counter.  A steady run adds none; a retrace inside a
burst shows as a span on the timeline, nested in the dispatch that caused
it.

One ``jax.monitoring`` listener serves every watching tracer and is
registered only while a watch is open.  Events are process-wide: two
engines traced at once in two threads see each other's compiles.  Lives
here, not in :mod:`repro.obs`, which stays stdlib-only.
"""
from __future__ import annotations

import contextlib
import functools
import threading

import jax

SPAN_NAMES = {
    "/jax/core/compile/jaxpr_trace_duration": "compile.trace",
    "/jax/core/compile/backend_compile_duration": "compile.backend",
}
COUNTER = "compile.events"

_lock = threading.Lock()
_watching: dict[int, list] = {}  # id(tracer) -> [tracer, open watches]


def _on_event(event: str, duration_secs: float, **kwargs) -> None:
    name = SPAN_NAMES.get(event)
    if name is None:
        return
    with _lock:
        tracers = [tracer for tracer, _ in _watching.values()]
    fun = str(kwargs.get("fun_name", ""))
    for tracer in tracers:
        tracer.add_span(name, cat="compile", dur_ns=int(duration_secs * 1e9), fun=fun)
        tracer.count(COUNTER)


@contextlib.contextmanager
def watch_compiles(tracer):
    """Record the process's compiles into ``tracer`` while open (nestable;
    a no-op for a disabled tracer)."""
    if not tracer.enabled:
        yield
        return
    with _lock:
        if not _watching:
            jax.monitoring.register_event_duration_secs_listener(_on_event)
        entry = _watching.setdefault(id(tracer), [tracer, 0])
        entry[1] += 1
    try:
        yield
    finally:
        with _lock:
            entry[1] -= 1
            if entry[1] == 0:
                del _watching[id(tracer)]
                if not _watching:
                    jax.monitoring.unregister_event_duration_listener(_on_event)


def watching_compiles(run):
    """Decorate an engine method so that it runs under
    ``watch_compiles(self.tracer)``."""

    @functools.wraps(run)
    def wrapped(self, *args, **kwargs):
        with watch_compiles(self.tracer):
            return run(self, *args, **kwargs)

    return wrapped
