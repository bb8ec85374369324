#!/usr/bin/env python3
"""The ledger benchmark's one command.

    python benchmarks/ledger/run.py --workload <name> --seed <n> \
        --seconds <s> --trace <0|1>

One process, one cell of ``BENCHMARK.json``. It refuses (non-zero exit, no
result line) anything but a TPU whose ``device_kind`` is in ``peaks.py`` with
at least the cell's chips. It then hands the cell to its loop
(``loops/<loop>.py``, named by the traffic file), which builds the system
under test from the seed, checks it against the plain reference, warms the
cell's own shapes, and measures for ``--seconds``. The last line of stdout is
the contract's JSON object: the cell's end-to-end metrics with ``--trace 0``,
its per-layer metrics (each read by ``layer_metrics/<name>.py``) with
``--trace 1``. README.md beside this file says how a later PR adds to it.
"""
import time

_T0 = time.perf_counter()   # process start, as near as Python lets us see it

import argparse  # noqa: E402
import contextlib  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from byname import HERE, ROOT, load_json, load_module, resolve  # noqa: E402

if ROOT not in sys.path:
    sys.path.insert(0, ROOT)    # the repository is not installed

#: jax.monitoring's event for one executable built (or fetched from the
#: persistent cache); none may fire inside the window
_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class Context:
    """What a loop gets: the cell's data, the devices, and the window."""

    def __init__(self, cell, config, traffic, seed, seconds, trace, devices,
                 peaks):
        import jax
        self.cell, self.config, self.traffic = cell, config, traffic
        # folded so that numpy's and jax's 32-bit seeds both take it
        self.seed = int(seed) % (2 ** 31 - 1)
        self.seconds = float(seconds)
        self.trace = bool(trace)
        self.devices = devices
        self.on_chip = devices[0].platform == "tpu"
        self.peaks = peaks
        self.arch = load_module("arch", config["arch"])
        self.span = jax.profiler.TraceAnnotation
        self.resolve = resolve
        self.setup_s = None
        self.window_s = None
        self.compiles_in_window = None
        self.trace_from_s = None      # window-relative start of the trace
        self.trace_dir = os.path.join(ROOT, ".ledger_trace", cell["name"])
        self._t0 = None
        self._traced = None
        self.memory_seen = 0          # most bytes seen on one chip

    def note(self, *words):
        """To stderr, stamped with the seconds since the process started, so
        that a run's log shows where its set-up went."""
        print(f"[ledger {time.perf_counter() - _T0:7.2f}s]", *words,
              file=sys.stderr, flush=True)

    @contextlib.contextmanager
    def window(self):
        """The measured window. Set-up ends where it starts; the loop calls
        ``tick`` between its steps and ``close`` when its last work is done
        (the window's length is taken there, before the trace is written)."""
        import jax
        compiles = []

        def on_event(event, _secs, **_kw):
            if event == _COMPILE_EVENT:
                compiles.append(event)

        jax.monitoring.register_event_duration_secs_listener(on_event)
        self._t0 = time.perf_counter()
        self.setup_s = self._t0 - _T0
        try:
            yield self
        finally:
            self.close()
            jax.monitoring.unregister_event_duration_listener(on_event)
            self.compiles_in_window = len(compiles)

    def elapsed(self):
        return time.perf_counter() - self._t0

    def tick(self):
        """Called by the loop between its steps: samples the chips' memory
        and, with ``--trace 1``, starts the profiler for the window's last
        ``trace_seconds`` (starting it costs some tens of milliseconds,
        stopping it seconds, so it stops after the window)."""
        self.memory_seen = max(self.memory_seen, memory_now(self.devices))
        if self.trace and self._traced is None and self.elapsed() >= \
                self.seconds - float(self.traffic.get("trace_seconds", 3)):
            import jax
            shutil.rmtree(self.trace_dir, ignore_errors=True)
            options = jax.profiler.ProfileOptions()
            options.python_tracer_level = 0   # else every Python call
            options.enable_hlo_proto = False
            jax.profiler.start_trace(self.trace_dir,
                                     profiler_options=options)
            self._traced = self.span("bench.window")
            self._traced.__enter__()
            self.trace_from_s = self.elapsed()

    def close(self):
        if self.window_s is not None:
            return
        self.window_s = self.elapsed()
        if self._traced is not None:
            import jax
            self._traced.__exit__(None, None, None)
            jax.profiler.stop_trace()


def find_cell(bench, name):
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        sys.exit(f"ledger: no workload {name!r} in BENCHMARK.json; "
                 f"known: {sorted(cells)}")
    cell = cells[name]
    cfg_entry = next(c for c in bench["configs"] if c["name"] == cell["config"])
    config = load_json(ROOT, cfg_entry["file"])
    traffic = load_json(HERE, "traffic", cell["traffic"] + ".json")
    return cell, config, traffic


def metrics_of(bench, section, cell):
    return [m for m in bench[section]
            if "workloads" not in m or cell["name"] in m["workloads"]]


def memory_now(devices):
    """Bytes held on the fullest chip right now. On this runtime live arrays
    are counted under ``in_use`` and a running program's temporaries under
    ``reserved`` (PERF.md, PR 21); ``peak_bytes_in_use`` alone misses the
    temporaries, so the window samples the sum while its programs run."""
    held = [0]
    for d in devices:
        stats = d.memory_stats() or {}
        held.append(stats.get("bytes_in_use", 0)
                    + stats.get("bytes_reserved", 0))
    return max(held)


def main(argv=None, _test_override=None):
    """``_test_override`` is for ``tests/`` alone and cannot be reached from
    the command line: {"allow_cpu": True, "config": {...}, "traffic": {...}}
    patches sizes down to what a CPU runs. The command itself always refuses
    a CPU."""
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    test = _test_override or {}

    bench = load_json(ROOT, "BENCHMARK.json")
    cell, config, traffic = find_cell(bench, args.workload)
    config = _patched(config, test.get("config"))
    traffic = _patched(traffic, test.get("traffic"))

    import jax
    from peaks import peaks_for
    devices = jax.devices()
    platform, kind = devices[0].platform, devices[0].device_kind
    if test.get("allow_cpu"):
        peaks = peaks_for("TPU v5 lite")
    else:
        if jax.default_backend() != "tpu":
            sys.exit(f"ledger: the backend is {jax.default_backend()!r}, "
                     "not 'tpu'; this benchmark only runs on the chip")
        peaks = peaks_for(kind)          # raises for an unknown kind
        # every program, however small, comes from the cache after a
        # checkout's first run
        if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
            jax.config.update("jax_compilation_cache_dir",
                              os.path.join(ROOT, ".jax_cache"))
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    if len(devices) < cell["chips"]:
        sys.exit(f"ledger: {cell['name']} needs {cell['chips']} chips, "
                 f"{len(devices)} are attached")
    used = devices[:cell["chips"]]

    ctx = Context(cell, config, traffic, args.seed, args.seconds, args.trace,
                  used, peaks)
    ctx.note(f"{len(devices)} x {kind} attached")
    loop = load_module("loops", traffic["loop"])
    result = loop.run(ctx)

    checks = dict(result["checks"])
    checks["no_compile_in_window"] = ctx.compiles_in_window == 0
    for name, ok in sorted(checks.items()):
        ctx.note(f"check {name}: {'ok' if ok else 'FAILED'}")
    stats = used[0].memory_stats() or {}
    ctx.note("chip 0 memory: sampled in the window", ctx.memory_seen,
             "peak in use", stats.get("peak_bytes_in_use"), "peak reserved",
             stats.get("peak_bytes_reserved"))
    device = {"platform": platform, "kind": kind, "count": len(devices),
              "memory_peak_bytes": ctx.memory_seen}
    out = {"correct": all(checks.values()),
           "attempted": result["attempted"], "failed": result["failed"],
           "metrics": {}, "device": device, "checks": checks,
           "window_s": ctx.window_s}

    if not args.trace:
        values = dict(result["metrics"], setup_s=ctx.setup_s)
        for m in metrics_of(bench, "end_to_end", cell):
            out["metrics"][m["name"]] = {"value": values[m["name"]],
                                         "unit": m["unit"]}
    else:
        import trace_reduce
        path = trace_reduce.find_xplane(ctx.trace_dir)
        reduced = trace_reduce.reduce_file(path) if path else None
        if reduced is None and not test.get("allow_cpu"):
            sys.exit(f"ledger: the trace {path} holds no device plane or no "
                     f"bench.window span: {trace_reduce.layout(path)}")
        shutil.rmtree(ctx.trace_dir, ignore_errors=True)
        if reduced is not None:
            device["busy_s"] = reduced["busy_s"]
            device["window_s"] = reduced["window_s"]
            out["breakdown"] = trace_reduce.breakdown(reduced)
        counts = dict(result["counts"], trace_from_s=ctx.trace_from_s,
                      window_s=ctx.window_s, chips=cell["chips"],
                      metrics=result["metrics"])
        for m in metrics_of(bench, "per_layer", cell):
            reader = load_module("layer_metrics", m["name"])
            value = reader.read(reduced, counts, config, peaks)
            if value is not None:
                out["metrics"][m["name"]] = {"value": value,
                                             "unit": m["unit"]}
    print(json.dumps(out), flush=True)
    return out


def _patched(data, patch):
    if not patch:
        return data
    out = dict(data)
    for key, value in patch.items():
        if isinstance(value, dict) and isinstance(out.get(key), dict):
            out[key] = _patched(out[key], value)
        else:
            out[key] = value
    return out


if __name__ == "__main__":
    main()
