"""Compile-cache tracking over ``jax.monitoring``'s public events.

jax records a duration event every time it traces a jitted function to
a jaxpr (``/jax/core/compile/jaxpr_trace_duration``) and every time it
asks the backend for an executable
(``/jax/core/compile/backend_compile_duration`` — fired on in-memory
jit-cache MISSES only; a hit in the persistent on-disk cache still
fires, with the retrieval time as its duration). Two services ride on
them:

1. ``install()`` (idempotent, called at ``import observability``):
   process-lifetime listeners that count every executable build into
   ``jit.xla_compiles`` and keep the events' seconds: ``jit.trace_s``
   (tracing to a jaxpr), ``jit.lower_s`` (jaxpr to an MLIR module) and
   ``jit.backend_compile_s`` (the backend's compile, or the retrieval
   when the persistent cache holds the executable), with the persistent
   cache's ``jit.cache_hits`` and ``jit.cache_misses`` (a miss is counted
   where jax writes the new entry, so one below the cache's size and
   time thresholds is not). A production run answers "how many
   recompiles, and how long did they take?" from ``dump()`` alone. A
   function traced inside another's trace fires its own trace event, so
   ``jit.trace_s`` can count the same wall time twice.

2. ``count_compiles()`` / ``count_traces()`` context managers yielding
   a CALLABLE count (``with count_compiles() as c: ...; assert c() ==
   0``); the count object also carries ``.seconds``, the summed event
   durations — the compile time chip_smoke.py reports.

Per-FUNCTION compile/cache-hit accounting lives in
``paddle_tpu.jit.StaticFunction`` (calls / probes / graph breaks /
specializations / XLA executable counts) and is published into the
registry at snapshot time by the collector in ``observability``.
"""
from __future__ import annotations

import threading
from contextlib import contextmanager

from jax import monitoring

from . import metrics as _met

BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
JAXPR_TRACE_EVENT = "/jax/core/compile/jaxpr_trace_duration"
JAXPR_TO_MLIR_EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"
CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"
CACHE_MISS_EVENT = "/jax/compilation_cache/cache_misses"

_install_lock = threading.Lock()
_installed = False


class _Count:
    """Callable current-count plus the summed event seconds."""

    __slots__ = ("n", "seconds")

    def __init__(self):
        self.n = 0
        self.seconds = 0.0

    def __call__(self) -> int:
        return self.n


def install() -> None:
    """Register the ``jit.*`` compile listeners once."""
    global _installed
    with _install_lock:
        if _installed:
            return
        reg = _met.REGISTRY
        compiles = reg.counter("jit.xla_compiles")
        seconds = {
            JAXPR_TRACE_EVENT: reg.counter("jit.trace_s"),
            JAXPR_TO_MLIR_EVENT: reg.counter("jit.lower_s"),
            BACKEND_COMPILE_EVENT: reg.counter("jit.backend_compile_s")}
        plain = {CACHE_HIT_EVENT: reg.counter("jit.cache_hits"),
                 CACHE_MISS_EVENT: reg.counter("jit.cache_misses")}

        def on_duration(event, duration_secs, **_kw):
            total = seconds.get(event)
            if total is not None:
                total.inc(duration_secs)
                if event == BACKEND_COMPILE_EVENT:
                    compiles.inc()

        def on_event(event, **_kw):
            count = plain.get(event)
            if count is not None:
                count.inc()

        monitoring.register_event_duration_secs_listener(on_duration)
        monitoring.register_event_listener(on_event)
        _installed = True


@contextmanager
def _count_event(name):
    count = _Count()

    def on_duration(event, duration_secs, **_kw):
        if event == name:
            count.n += 1
            count.seconds += duration_secs

    monitoring.register_event_duration_secs_listener(on_duration)
    try:
        yield count
    finally:
        monitoring.unregister_event_duration_listener(on_duration)


def count_compiles():
    """Count XLA executable builds (in-memory jit-cache misses) within
    the context; yields a callable returning the count."""
    return _count_event(BACKEND_COMPILE_EVENT)


def count_traces():
    """Count jit traces (tracing-cache misses) within the context;
    yields a callable returning the count."""
    return _count_event(JAXPR_TRACE_EVENT)
