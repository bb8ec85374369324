"""Records the small trace kept beside the tests (``recorded.xplane.pb``).

Run once on the chip (``chiprun -- python benchmarks/ledger/tests/record_trace.py``);
it writes ``chiprun_out/recorded.xplane.pb`` and prints the trace's layout
(planes, lines, the stats one event carries), which is what
``trace_reduce.py`` was written against. Two jitted programs (``alpha``,
``beta``) run three times each under the benchmark's host spans, with a
sleep between them so that there are idle gaps to attribute.
"""
import glob
import os
import shutil
import sys
import time

import jax
import jax.numpy as jnp


def main():
    out = "chiprun_out"
    os.makedirs(out, exist_ok=True)
    print("env JAX_COMPILATION_CACHE_DIR =",
          os.environ.get("JAX_COMPILATION_CACHE_DIR"))
    dev = jax.devices()[0]
    print("device", dev.platform, dev.device_kind, len(jax.devices()))
    print("memory_stats", dev.memory_stats())

    @jax.jit
    def alpha(x):
        for _ in range(4):
            x = jnp.tanh(x @ x)
        return x

    @jax.jit
    def beta(x):
        return jnp.sum(x.astype(jnp.float32) ** 2, axis=0)

    x = jnp.ones((2048, 2048), jnp.bfloat16) * 0.01
    jax.block_until_ready((alpha(x), beta(x)))
    logdir = os.path.join(out, "_trace")
    shutil.rmtree(logdir, ignore_errors=True)
    jax.profiler.start_trace(logdir)
    with jax.profiler.TraceAnnotation("bench.window"):
        for _ in range(3):
            with jax.profiler.TraceAnnotation("bench.step"):
                jax.block_until_ready(alpha(x))
            with jax.profiler.TraceAnnotation("bench.feed"):
                time.sleep(0.003)
            with jax.profiler.TraceAnnotation("bench.poll"):
                jax.block_until_ready(beta(x))
    jax.profiler.stop_trace()
    path = glob.glob(os.path.join(logdir, "plugins/profile/*/*.xplane.pb"))[0]
    shutil.copy(path, os.path.join(out, "recorded.xplane.pb"))
    print("trace bytes", os.path.getsize(path))
    shutil.rmtree(logdir, ignore_errors=True)

    data = jax.profiler.ProfileData.from_file(
        os.path.join(out, "recorded.xplane.pb"))
    for plane in data.planes:
        lines = list(plane.lines)
        print(f"PLANE {plane.name!r}: {len(lines)} lines")
        for line in lines:
            events = list(line.events)
            print(f"  LINE {line.name!r}: {len(events)} events")
            for ev in events[:6]:
                stats = {k: v for k, v in ev.stats}
                print(f"    {ev.name!r} start_ns={ev.start_ns} "
                      f"dur_ns={ev.duration_ns} stats={stats}")
    sys.stdout.flush()


if __name__ == "__main__":
    main()
