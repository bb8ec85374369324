"""paddle.profiler equivalent.

Reference: python/paddle/profiler/profiler.py:358 (scheduler windows,
chrome-tracing export, statistics tables) over the C++ HostTracer/CUPTI
CudaTracer (fluid/platform/profiler/).

TPU-native: device timelines come from jax.profiler (XLA/TPU xprof trace);
``RecordEvent`` spans are ``jax.profiler.TraceAnnotation``s, so they sit in
that same trace, on its host plane and clock. While a ``Profiler`` records,
the spans are also kept by this module for its chrome-tracing JSON export.
"""
from __future__ import annotations

import contextlib
import json
import os
import threading
import time
from dataclasses import dataclass, field
from enum import Enum
from typing import Callable, Dict, List, Optional

import jax

from paddle_tpu import native as _native
from paddle_tpu.core import dispatch as _dispatch


class ProfilerTarget(Enum):
    CPU = 0
    GPU = 1
    CUSTOM_DEVICE = 2
    TPU = 3


class ProfilerState(Enum):
    CLOSED = 0
    READY = 1
    RECORD = 2
    RECORD_AND_RETURN = 3


@dataclass
class _Span:
    name: str
    start_us: float
    end_us: float = 0.0
    tid: int = 0
    args: Optional[dict] = None


class _HostTracer:
    def __init__(self):
        self.spans: List[_Span] = []
        self._lock = threading.Lock()
        self.enabled = False

    def add(self, span):
        with self._lock:
            self.spans.append(span)

    def clear(self):
        with self._lock:
            self.spans = []


_TRACER = _HostTracer()


class RecordEvent:
    """Span marker (reference platform::RecordEvent).

    Every span is a ``jax.profiler.TraceAnnotation``: while a
    ``jax.profiler`` trace runs it lands on the ``/host:CPU`` plane, on
    the calling thread's line and the profiler's clock, beside the device
    planes; keyword attributes become the event's stats. Without a
    running trace that costs a flag test.

    While a :class:`Profiler` records, the span also goes to the native
    C++ tracer ring (native/src/tracer.cc, HostTracer analog) when the
    native runtime is built, the Python-side buffer otherwise, and from
    there into the chrome-tracing export.
    """

    def __init__(self, name: str, event_type=None, **attrs):
        self.name = name
        self._attrs = attrs
        self._annotation = None
        self._t0 = None
        self._native = False

    def begin(self):
        self._annotation = jax.profiler.TraceAnnotation(self.name,
                                                        **self._attrs)
        self._annotation.__enter__()
        # Availability is only probed while the tracer is enabled, so the
        # common profiler-off hot path never triggers the native build.
        if _TRACER.enabled:
            self._native = _native.available()
            if self._native:
                _native.tracer_begin(self.name)
        self._t0 = time.perf_counter_ns() / 1e3

    def end(self):
        if self._t0 is not None:
            if self._native:
                # always pop the native span stack once begin() pushed,
                # even if the tracer was disabled mid-span — an unmatched
                # entry would corrupt later spans on this thread
                _native.tracer_end()
                self._native = False
            elif _TRACER.enabled:
                _TRACER.add(_Span(self.name, self._t0,
                                  time.perf_counter_ns() / 1e3,
                                  threading.get_ident() % 100000))
        self._t0 = None
        if self._annotation is not None:
            self._annotation.__exit__(None, None, None)
            self._annotation = None

    def __enter__(self):
        self.begin()
        return self

    def __exit__(self, *exc):
        self.end()
        return False


def make_scheduler(closed=0, ready=0, record=1, repeat=0, skip_first=0):
    """reference profiler.make_scheduler window FSM."""
    total = closed + ready + record

    def scheduler(step: int):
        if step < skip_first:
            return ProfilerState.CLOSED
        s = step - skip_first
        if repeat and s >= repeat * total:
            return ProfilerState.CLOSED
        pos = s % total
        if pos < closed:
            return ProfilerState.CLOSED
        if pos < closed + ready:
            return ProfilerState.READY
        if pos == total - 1:
            return ProfilerState.RECORD_AND_RETURN
        return ProfilerState.RECORD

    return scheduler


def export_chrome_tracing(dir_name: str, worker_name: str = None):
    def handler(prof: "Profiler"):
        os.makedirs(dir_name, exist_ok=True)
        path = os.path.join(
            dir_name, f"{worker_name or 'worker'}_{int(time.time())}.json")
        prof._export_chrome(path)
        print(f"[profiler] chrome trace written to {path}")
    return handler


class Profiler:
    """reference profiler.py:358 surface."""

    def __init__(self, targets=None, scheduler=None, on_trace_ready=None,
                 timer_only=False, record_shapes=False, profile_memory=False,
                 with_flops=False):
        self.scheduler = scheduler if callable(scheduler) else (
            make_scheduler(record=scheduler[1] - scheduler[0],
                           closed=scheduler[0])
            if isinstance(scheduler, (tuple, list)) else None)
        self.on_trace_ready = on_trace_ready
        self.timer_only = timer_only
        self.step_num = 0
        self.current_state = ProfilerState.CLOSED
        self._op_unhook = None
        self._xprof_dir = None
        self._step_info = _StepInfo()

    # ---- lifecycle ----
    def start(self):
        self.current_state = ProfilerState.RECORD if self.scheduler is None \
            else self.scheduler(self.step_num)
        if not self.timer_only:
            _TRACER.enabled = True
            _TRACER.clear()
            if _native.available():
                _native.tracer_clear()
                _native.tracer_enable(True)
            self._hook_ops()
            try:
                self._xprof_dir = os.environ.get(
                    "PADDLE_TPU_XPROF_DIR", "/tmp/paddle_tpu_xprof")
                if jax.default_backend() == "tpu":
                    jax.profiler.start_trace(self._xprof_dir)
            except Exception:
                self._xprof_dir = None
        self._step_t0 = time.perf_counter()

    def stop(self):
        if not self.timer_only:
            _TRACER.enabled = False
            if _native.available():
                _native.tracer_enable(False)
            if self._op_unhook:
                self._op_unhook()
                self._op_unhook = None
            if self._xprof_dir is not None:
                try:
                    jax.profiler.stop_trace()
                except Exception:
                    pass
        if self.on_trace_ready is not None:
            self.on_trace_ready(self)

    def step(self, num_samples: Optional[int] = None):
        now = time.perf_counter()
        self._step_info.add(now - self._step_t0, num_samples)
        self._step_t0 = now
        self.step_num += 1
        if self.scheduler is not None:
            self.current_state = self.scheduler(self.step_num)

    def step_info(self, unit=None):
        return self._step_info.summary()

    def __enter__(self):
        self.start()
        return self

    def __exit__(self, *exc):
        self.stop()
        return False

    # ---- op-level spans ----
    def _hook_ops(self):
        def cb(name, outs):
            if _TRACER.enabled:
                t = time.perf_counter_ns() / 1e3
                _TRACER.add(_Span(f"op::{name}", t, t + 1))
        self._op_unhook = _dispatch.add_op_observer(cb)

    # ---- export / stats ----
    def _all_spans(self):
        """Python-buffer spans + native-tracer spans, unified."""
        spans = list(_TRACER.spans)
        for name, start, dur, tid in _native.tracer_spans():
            spans.append(_Span(name, start, start + dur, tid))
        return spans

    def _export_chrome(self, path):
        events = []
        for s in self._all_spans():
            events.append({
                "name": s.name, "ph": "X", "ts": s.start_us,
                "dur": max(s.end_us - s.start_us, 0.001),
                "pid": 0, "tid": s.tid,
            })
        events.extend(self._metric_counter_events())
        with open(path, "w") as f:
            json.dump({"traceEvents": events}, f)

    @staticmethod
    def _metric_counter_events():
        """The observability registry snapshot as chrome-tracing counter
        ('ph':'C') events, so tokens/s, queue depth, compile counts etc.
        land in the SAME trace as the host spans (the reference's
        statistic tables riding its chrome export)."""
        from paddle_tpu.observability import metrics as _met
        events = []
        ts = time.perf_counter_ns() / 1e3
        for d in _met.REGISTRY.snapshot():
            name = d["name"]
            if d["labels"]:
                lab = ",".join(f"{k}={v}"
                               for k, v in sorted(d["labels"].items()))
                name = f"{name}{{{lab}}}"
            if d["type"] == "histogram":
                args = {"count": d["count"], "sum": d["sum"]}
                if "p50" in d:
                    args["p50"] = d["p50"]
                    args["p99"] = d["p99"]
            else:
                args = {"value": d["value"]}
            events.append({"name": f"metric::{name}", "ph": "C",
                           "ts": ts, "pid": 0, "args": args})
        return events

    def export(self, path, format="json"):
        self._export_chrome(path)

    def summary(self, sorted_by=None, op_detail=True, thread_sep=False,
                time_unit="ms"):
        agg: Dict[str, List[float]] = {}
        for s in self._all_spans():
            agg.setdefault(s.name, []).append(s.end_us - s.start_us)
        lines = [f"{'name':<40}{'calls':>8}{'total(us)':>12}"]
        for name, durs in sorted(agg.items(),
                                 key=lambda kv: -sum(kv[1]))[:40]:
            lines.append(f"{name:<40}{len(durs):>8}{sum(durs):>12.1f}")
        table = "\n".join(lines)
        print(table)
        return table


class _StepInfo:
    def __init__(self):
        self.times = []
        self.samples = []

    def add(self, dt, n):
        self.times.append(dt)
        if n:
            self.samples.append(n)

    def summary(self):
        if not self.times:
            return ""
        import numpy as np
        avg = float(np.mean(self.times))
        ips = (float(np.mean(self.samples)) / avg) if self.samples else 0
        return f"avg_step {avg*1e3:.2f} ms, ips {ips:.1f} samples/s"


@contextlib.contextmanager
def profile(*args, **kwargs):
    p = Profiler(*args, **kwargs)
    p.start()
    try:
        yield p
    finally:
        p.stop()


def load_profiler_result(path):
    with open(path) as f:
        return json.load(f)
