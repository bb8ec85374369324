"""Roofline share of one decode step, in %: the bytes a step must read
(``arch.decode_step_bytes``: every weight once plus the VALID cached tokens
of the running slots, at the cache's dtype) over the published bytes/s,
over the device time of one step. A step's time is the decode block's
module time in the trace over its runs and over ``decode_block`` steps.
Memory-bound by construction: at a batch of a few tens of tokens the
operations take a small part of the time the bytes do."""
import jax.numpy as jnp
import numpy as np

from byname import load_module


def read(reduced, counts, config, peaks):
    if reduced is None or not counts.get("samples"):
        return None
    serve = config["build"]["serve"]
    dev = reduced["devices"][min(reduced["devices"])]
    runs = dev["module_runs"].get(serve["decode_module"])
    if not runs:
        return None
    step_s = dev["module_s"][serve["decode_module"]] / runs \
        / serve["session_kwargs"]["decode_block"]
    start = counts.get("trace_from_s") or 0.0
    valid = [v for t, _running, v in counts["samples"] if t >= start]
    if not valid:
        return None
    arch = load_module("arch", config["arch"])
    need = arch.decode_step_bytes(
        config["sizes"], float(np.mean(valid)),
        jnp.dtype(serve["weights_dtype"]).itemsize,
        jnp.dtype(serve["cache_dtype"]).itemsize)
    return 100.0 * need / peaks["bytes_per_s"] / step_s
