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
   ``jit.trace_s`` can count the same wall time twice. Beside each
   duration jax also records the event's time span (``time.time()`` at
   its start and end, and ``fun_name``): ``jit.compile_wall_s`` is the
   length of the UNION of those spans, so nested and repeated traces
   count once and it is the wall time the three sums are an upper bound
   of; ``programs()`` is the same seconds by the program they built.

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

import bisect
import threading
from contextlib import contextmanager

from jax import monitoring

from . import metrics as _met

BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
JAXPR_TRACE_EVENT = "/jax/core/compile/jaxpr_trace_duration"
JAXPR_TO_MLIR_EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"
CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"
CACHE_MISS_EVENT = "/jax/compilation_cache/cache_misses"

_STAGES = {JAXPR_TRACE_EVENT: "trace_s", JAXPR_TO_MLIR_EVENT: "lower_s",
           BACKEND_COMPILE_EVENT: "backend_compile_s"}
#: names ``programs()`` keeps; later ones are summed under ``_other``
_MAX_PROGRAMS = 512
#: intervals ``_SpanUnion`` keeps apart before it joins the two oldest
_MAX_INTERVALS = 256

_install_lock = threading.Lock()
_installed = False


class _SpanUnion:
    """The length of the union of time spans, kept as they arrive (a span
    arrives at its end, so an enclosing one after those it encloses).

    ``_ivs`` holds disjoint ``[start, end, seconds counted inside]``, in
    order. Over ``_MAX_INTERVALS`` the two oldest become one that keeps
    their counted seconds and forgets where the gap between them lay: a
    later span that covers it whole still adds exactly what was not
    counted, one that cuts into it is credited with all of the overlap, so
    the length can fall short of the union and never passes it."""

    def __init__(self):
        self._ivs = []

    def add(self, start, end):
        """Seconds the union grew by."""
        ivs = self._ivs
        lo = hi = bisect.bisect_left(ivs, start, key=lambda iv: iv[1])
        counted = inside = 0.0
        while hi < len(ivs) and ivs[hi][0] <= end:
            s, e, seconds = ivs[hi]
            inside += seconds
            counted += seconds if start <= s and e <= end \
                else min(e, end) - max(s, start)
            hi += 1
        grown = max(0.0, (end - start) - counted)
        if hi > lo:
            start, end = min(start, ivs[lo][0]), max(end, ivs[hi - 1][1])
        ivs[lo:hi] = [[start, end, inside + grown]]
        if len(ivs) > _MAX_INTERVALS:
            a, b = ivs[0], ivs[1]
            ivs[:2] = [[a[0], b[1], a[2] + b[2]]]
        return grown


class _CompileSpans:
    """What the span listener keeps: the union of the compile spans and
    the table of programs behind ``programs()``."""

    def __init__(self):
        self._lock = threading.Lock()
        self._union = _SpanUnion()
        self._programs = {}
        self._hits_unclaimed = {}       # by the compiling thread

    def cache_hit(self):
        """The persistent cache served an executable: the event fires
        inside the compile's span, which ends later on the same thread and
        says whose."""
        ident = threading.get_ident()
        with self._lock:
            self._hits_unclaimed[ident] = \
                self._hits_unclaimed.get(ident, 0) + 1

    def add(self, stage, start, end, fun_name):
        """One span of ``stage``; returns the seconds the union grew by."""
        with self._lock:
            entry = self._entry(fun_name)
            entry[stage] += end - start
            if entry["first"] is None:
                entry["first"] = start
            entry["last"] = end
            if stage == "backend_compile_s":
                entry["builds"] += 1
                entry["cache_hits"] += self._hits_unclaimed.pop(
                    threading.get_ident(), 0)
            return self._union.add(start, end)

    def _entry(self, fun_name):
        # jax names the trace ``f``, the lowering and compile ``jit(f)``
        name = str(fun_name)
        if name.startswith("jit(") and name.endswith(")"):
            name = name[4:-1]
        if name not in self._programs \
                and len(self._programs) >= _MAX_PROGRAMS:
            name = "_other"
        return self._programs.setdefault(name, {
            "builds": 0, "trace_s": 0.0, "lower_s": 0.0,
            "backend_compile_s": 0.0, "cache_hits": 0,
            "first": None, "last": None})

    def table(self):
        with self._lock:
            return {name: dict(e) for name, e in self._programs.items()}

    def clear(self):
        with self._lock:
            self._union = _SpanUnion()
            self._programs.clear()
            self._hits_unclaimed.clear()


_SPANS = _CompileSpans()


def programs():
    """{program: {"builds" (executables the backend was asked for),
    "trace_s", "lower_s", "backend_compile_s", "cache_hits" (builds the
    persistent cache served), "first", "last" (``time.time()`` at the
    start of its first span and the end of its last)}}, by the name jax
    gives the jitted function (eager helpers such as
    ``convert_element_type`` are programs too). At most ``_MAX_PROGRAMS``
    names; what comes later is summed under ``_other``. A copy."""
    return _SPANS.table()


def reset():
    """Forget the spans and the table, as ``obs.reset()`` zeroes
    ``jit.compile_wall_s``: the two say the same seconds."""
    _SPANS.clear()


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

        wall = reg.counter("jit.compile_wall_s")

        def on_event(event, **_kw):
            count = plain.get(event)
            if count is not None:
                count.inc()
                if event == CACHE_HIT_EVENT and _met._ENABLED:
                    _SPANS.cache_hit()

        def on_span(event, start, end, fun_name="?", **_kw):
            stage = _STAGES.get(event)
            if stage is not None and _met._ENABLED:
                wall.inc(_SPANS.add(stage, start, end, fun_name))

        monitoring.register_event_duration_secs_listener(on_duration)
        monitoring.register_event_listener(on_event)
        monitoring.register_event_time_span_listener(on_span)
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
