"""Training-loop instrumentation helpers.

One funnel — ``record_step(dt_s, samples=, tokens=)`` — shared by the
hapi trainer, the fleet pipeline facade, and user loops: it feeds the
step-time histogram, the samples/s / tokens/s gauges, and (when the
model's arithmetic cost is configured) the achieved-MFU gauge.
"""
from __future__ import annotations

from typing import Optional

from . import metrics as _met
from paddle_tpu.cost_model import attached_chip_spec as _attached_chip_spec

_flops_per_token: Optional[float] = None
#: None = the attached device's bf16 peak, looked up by device_kind on
#: the first MFU computation (an unknown device raises there)
_peak_flops: Optional[float] = None


def configure(flops_per_token: Optional[float] = None,
              peak_flops: Optional[float] = None) -> None:
    """Declare the model's cost so record_step can derive MFU.
    flops_per_token: the caller's own count of its model's operations
    a trained token;
    peak_flops: accelerator peak (default: the attached device's, via
    cost_model.attached_chip_spec)."""
    global _flops_per_token, _peak_flops
    if flops_per_token is not None:
        _flops_per_token = float(flops_per_token)
    if peak_flops is not None:
        _peak_flops = float(peak_flops)


def record_step(dt_s: float, samples: Optional[int] = None,
                tokens: Optional[int] = None) -> None:
    """Record one optimizer step: wall time, throughput, MFU."""
    global _peak_flops
    if not _met._ENABLED:
        return
    r = _met.REGISTRY
    r.counter("train.steps").inc()
    r.histogram("train.step_time_s").observe(dt_s)
    if samples:
        r.counter("train.samples").inc(samples)
        if dt_s > 0:
            r.gauge("train.samples_per_s").set(samples / dt_s)
    if tokens:
        r.counter("train.tokens").inc(tokens)
        if dt_s > 0:
            r.gauge("train.tokens_per_s").set(tokens / dt_s)
            if _flops_per_token:
                if _peak_flops is None:
                    _peak_flops = _attached_chip_spec()["flops"]
                r.gauge("train.mfu").set(
                    (tokens / dt_s) * _flops_per_token / _peak_flops)
