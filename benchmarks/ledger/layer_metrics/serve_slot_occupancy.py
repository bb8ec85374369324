"""Mean of running slots over slots, in %, sampled by the loop after every
``step()`` of the counted window (in a traced run up to where the profiler
starts: starting it stalls the loop and requests pile up)."""
import numpy as np


def read(reduced, counts, config, peaks):
    if not counts.get("samples"):
        return None
    until = counts.get("trace_from_s") or float("inf")
    running = [r for t, r, _v in counts["samples"] if t < until]
    return 100.0 * float(np.mean(running)) / counts["slots"] \
        if running else None
