"""From the profiler's ``.xplane.pb`` to the numbers the metrics read.

Layout of a trace on this runtime (jax 0.9.0, libtpu 0.0.34, ``TPU v5
lite``; looked at by hand with ``tests/record_trace.py``):

* one plane per chip, ``/device:TPU:<n>``. Its line ``XLA Modules`` holds one
  event per execution of a program, named ``jit_<function>(<fingerprint>)``;
  its line ``XLA Ops`` one event per HLO operation, named by the instruction's
  text (``%fusion.3 = bf16[24,16,128]{...} fusion(...)``). Operation events
  carry no module name, so an operation belongs to the module event that
  contains its start. A ``while`` operation spans its body's operations on
  the same line.
* the plane ``/host:CPU`` holds the benchmark's own
  ``jax.profiler.TraceAnnotation`` spans (``bench.window``, ``bench.step``,
  ``bench.submit``, ``bench.wait``, ``bench.feed``) on the line of the thread
  that opened them (on the line ``python`` when the Python tracer is on; the
  benchmark turns it off, it records every call).
* host and device timestamps share an origin to within a millisecond or two
  (in the recorded trace the device leads the host by about 1.5 ms). The
  window is the host's ``bench.window`` span and device events are clipped to
  it, so shares of a window of seconds are good to a part in a thousand; a
  gap is attributed to the host span open at its middle, which is only
  meaningful for gaps of some milliseconds.

Everything is in seconds. Nothing here touches a device.
"""
import glob
import os
import re

WINDOW_SPAN = "bench.window"
HOST_SPANS = ("bench.step", "bench.submit", "bench.poll", "bench.feed",
              "bench.wait")
NO_SPAN = "_no_bench_span_"
#: operations that only contain others on the same line
_CONTAINERS = ("while", "conditional", "call")
COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
               "collective-permute")

_OP = re.compile(r"^%?(?P<name>[^\s=]+)\s*=\s*(?P<type>\(?[a-z0-9]+\[[0-9,]*\])?")


def find_xplane(logdir):
    """The one ``.xplane.pb`` under a ``jax.profiler.start_trace`` directory,
    or None."""
    found = sorted(glob.glob(os.path.join(
        logdir, "plugins", "profile", "*", "*.xplane.pb")))
    return found[-1] if found else None


def op_key(text):
    """``%multiply_reduce_fusion.7 = f32[24,1024,16]{...} fusion(...)`` ->
    ``multiply_reduce_fusion f32[24,1024,16]``: the instruction's name without
    its number, and the type of its result (of a tuple, the first)."""
    m = _OP.match(text)
    if not m:
        return text.split(" ")[0].lstrip("%")
    name = re.sub(r"[.\d]+$", "", m.group("name"))
    typ = (m.group("type") or "").lstrip("(")
    return f"{name} {typ}".strip()


def op_kind(text):
    """The HLO opcode family by the instruction's name: ``all-gather-start.3``
    -> ``all-gather-start``."""
    return re.sub(r"[.\d]+$", "", text.split(" ")[0].lstrip("%"))


def _union(intervals):
    """Merged, sorted, non-overlapping copy of [(start, end), ...]."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _clip(events, lo, hi):
    """(name, start, end) clipped to [lo, hi]; what falls outside is
    dropped."""
    out = []
    for name, s, e in events:
        s, e = max(s, lo), min(e, hi)
        if e > s:
            out.append((name, s, e))
    return out


def _line_events(line):
    return [(ev.name, ev.start_ns * 1e-9, (ev.start_ns + ev.duration_ns) * 1e-9)
            for ev in line.events]


def reduce_file(path):
    """Reads the trace and returns ``reduce_planes`` of it."""
    import jax
    data = jax.profiler.ProfileData.from_file(path)
    devices, spans = {}, []
    for plane in data.planes:
        m = re.fullmatch(r"/device:TPU:(\d+)", plane.name)
        if m:
            lines = {line.name: _line_events(line) for line in plane.lines
                     if line.name in ("XLA Modules", "XLA Ops")}
            devices[int(m.group(1))] = lines
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                spans += [ev for ev in _line_events(line)
                          if ev[0] == WINDOW_SPAN or ev[0] in HOST_SPANS]
    return reduce_planes(devices, spans)


def layout(path):
    """{plane: {line: events}}: what to look at when a trace reduces to
    nothing."""
    import jax
    if not path:
        return None
    data = jax.profiler.ProfileData.from_file(path)
    return {plane.name: {line.name: sum(1 for _ in line.events)
                         for line in plane.lines} for plane in data.planes}


def reduce_planes(devices, spans):
    """``devices``: {ordinal: {"XLA Modules": [(name, start, end)],
    "XLA Ops": [...]}}; ``spans``: the host's benchmark spans, same form.

    Returns None where the trace holds no window span or no device plane;
    else a dict: ``window_s``; ``busy_s`` (mean over devices of the union of
    operation intervals inside the window); per device under ``devices``:
    ``busy_s``, ``idle_share``, ``module_s`` {function: seconds},
    ``module_runs`` {function: count}, ``op_s`` {op_key: [seconds, count]},
    ``kind_s`` {opcode family: seconds}, ``gaps`` {span: seconds}."""
    windows = [(s, e) for name, s, e in spans if name == WINDOW_SPAN]
    if not windows or not devices:
        return None
    lo, hi = windows[0]
    host = sorted((s, e, name) for name, s, e in spans if name in HOST_SPANS)
    per_device = {}
    for ordinal, lines in sorted(devices.items()):
        ops = _clip(lines.get("XLA Ops", ()), lo, hi)
        modules = _clip(lines.get("XLA Modules", ()), lo, hi)
        busy = _union([(s, e) for _n, s, e in ops])
        busy_s = sum(e - s for s, e in busy)
        module_s, module_runs = {}, {}
        for name, s, e in modules:
            fn = re.sub(r"\(\d+\)$", "", name)
            module_s[fn] = module_s.get(fn, 0.0) + (e - s)
            module_runs[fn] = module_runs.get(fn, 0) + 1
        op_s, kind_s = {}, {}
        for text, s, e in ops:
            kind = op_kind(text)
            if kind in _CONTAINERS:
                continue
            key = op_key(text)
            ent = op_s.setdefault(key, [0.0, 0])
            ent[0] += e - s
            ent[1] += 1
            kind_s[kind] = kind_s.get(kind, 0.0) + (e - s)
        gaps = {}
        edges = [lo] + [t for iv in busy for t in iv] + [hi]
        for gs, ge in zip(edges[0::2], edges[1::2]):
            if ge > gs:
                span = _span_at(host, (gs + ge) / 2)
                gaps[span] = gaps.get(span, 0.0) + (ge - gs)
        per_device[ordinal] = {
            "busy_s": busy_s, "idle_share": 1.0 - busy_s / (hi - lo),
            "module_s": module_s, "module_runs": module_runs,
            "op_s": op_s, "kind_s": kind_s, "gaps": gaps}
    return {"window_s": hi - lo,
            "busy_s": sum(d["busy_s"] for d in per_device.values())
            / len(per_device),
            "devices": per_device}


def _span_at(host, t):
    """Name of the innermost benchmark span open at time ``t``."""
    best = None
    for s, e, name in host:
        if s > t:
            break
        if e >= t and (best is None or s >= best[0]):
            best = (s, name)
    return best[1] if best else NO_SPAN


def breakdown(reduced, top=10):
    """The contract's optional ``breakdown``, of device 0."""
    dev = reduced["devices"][min(reduced["devices"])]
    ops = sorted(dev["op_s"].items(), key=lambda kv: -kv[1][0])[:top]
    gaps = sorted(dev["gaps"].items(), key=lambda kv: -kv[1])[:top]
    return {"device_ops": [[f"{k} x{n}", s] for k, (s, n) in ops],
            "idle_gaps": [[k, s] for k, s in gaps]}
