"""Device time of collective operations on device 0 over the traced window,
in %: all-gather, all-reduce, reduce-scatter, all-to-all and
collective-permute events, their ``-start``/``-done`` halves included.

An UPPER bound on what is exposed: a collective that runs while a matmul
runs is counted in full. Exposed time (no compute on the device meanwhile)
is under Open questions in PERF.md."""
from trace_reduce import COLLECTIVES


def read(reduced, counts, config, peaks):
    if reduced is None:
        return None
    dev = reduced["devices"][min(reduced["devices"])]
    total = sum(s for kind, s in dev["kind_s"].items()
                if any(c in kind for c in COLLECTIVES))
    return 100.0 * total / reduced["window_s"]
