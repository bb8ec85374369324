"""paddle_tpu.observability — unified telemetry layer.

The reference framework ships profiler statistics tables and device
tracers; this subsystem is their quantitative complement: a
process-global metrics registry every framework layer records into
(training step time / samples/s / MFU, pipeline bubble fraction,
serving queue depth and tokens/s, dataloader fetch wait, collective
bytes, eager op dispatches, jit compile/cache events), with JSON-lines
and Prometheus-text exporters and a one-call ``dump()`` snapshot.

Quick use::

    import paddle_tpu.observability as obs
    ... run training / serving ...
    snap = obs.dump()                       # list of metric dicts
    print(obs.to_prometheus())              # scrape format
    with obs.count_compiles() as compiles:  # compile-cache tracking
        step(...)
    assert compiles() == 0

Off switch: ``PADDLE_TPU_METRICS=off`` (env) or ``obs.disable()``.
Instrumented hot paths guard on one module-global bool, so the
disabled cost is a single branch (asserted by
tests/test_observability.py's micro-benchmark).
"""
from __future__ import annotations

import json as _json
import time as _time

from . import metrics as _metrics
from . import catalog  # noqa: F401
from . import server  # noqa: F401
from . import training  # noqa: F401
from .metrics import (  # noqa: F401
    REGISTRY, Counter, Gauge, Histogram, Registry,
    enable, disable, enabled,
)
from .compile_tracker import (  # noqa: F401
    count_compiles, count_traces, install as _install_compile_hook,
    programs as compiled_programs, reset as _reset_compile_spans,
)
from .server import MetricsServer  # noqa: F401
from .snapshots import (  # noqa: F401
    Snapshot, SnapshotDelta, delta, window,
)


def take_snapshot() -> Snapshot:
    """Indexed read-side view of the live registry (snapshots.py)."""
    return Snapshot.take()


def counter(name, **labels):
    return REGISTRY.counter(name, **labels)


def gauge(name, **labels):
    return REGISTRY.gauge(name, **labels)


def histogram(name, **labels):
    return REGISTRY.histogram(name, **labels)


def to_prometheus() -> str:
    return REGISTRY.to_prometheus()


def to_jsonl() -> str:
    return REGISTRY.to_jsonl()


def reset() -> None:
    REGISTRY.reset()
    _reset_compile_spans()


def dump(path=None, format: str = "json"):
    """Snapshot the registry. Returns the snapshot list; when `path`
    is given also writes it there — format 'json' (one document),
    'jsonl' (one line per metric) or 'prom' (Prometheus text)."""
    snap = REGISTRY.snapshot()
    if path is not None:
        if format == "prom":
            text = to_prometheus()
        elif format == "jsonl":
            text = to_jsonl()
        else:
            text = _json.dumps({"ts": _time.time(), "metrics": snap},
                               indent=1, sort_keys=True)
        with open(path, "w") as f:
            f.write(text)
    return snap


def compile_report():
    """Per-StaticFunction jit-cache stats: calls, probes, graph breaks,
    specializations, XLA executables (the reference's sot
    introspection, quantified)."""
    out = []
    from paddle_tpu import jit as _jit
    for sf in list(_jit._static_functions):
        name = getattr(sf._fn, "__qualname__", str(sf._fn))
        calls = probes = breaks = specs = execs = 0
        fallbacks = 0
        for e in sf._cache.values():
            probes += e["probes"]
            breaks += e["breaks"]
            specs += len(e["specs"])
            fallbacks += 1 if e["fallback"] else 0
            for s in e["specs"]:
                calls += s.hits
                j = s.jitted
                if j is not None:
                    try:
                        execs += j._cache_size()
                    except Exception:
                        pass
        out.append({"function": name, "cache_hits": calls,
                    "eager_probes": probes, "graph_breaks": breaks,
                    "specializations": specs, "xla_executables": execs,
                    "eager_fallbacks": fallbacks})
    return out


def _jit_collector(reg):
    """Publish aggregate jit-cache state as gauges at snapshot time."""
    rep = compile_report()
    reg.gauge("jit.static_functions").set(len(rep))
    reg.gauge("jit.specializations").set(
        sum(r["specializations"] for r in rep))
    reg.gauge("jit.xla_executables").set(
        sum(r["xla_executables"] for r in rep))
    reg.gauge("jit.graph_breaks").set(
        sum(r["graph_breaks"] for r in rep))


REGISTRY.register_collector(_jit_collector)
_install_compile_hook()
