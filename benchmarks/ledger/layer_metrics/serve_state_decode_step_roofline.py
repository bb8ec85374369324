"""Roofline share of one decode step of a model that keeps recurrent state
beside its KV, in %: the bytes a step must move (``arch.decode_step_bytes``:
every weight once, the running slots' recurrent state read AND written, their
VALID cached tokens read, each at its dtype) over the published bytes/s,
over the device time of one step. A step's time is the decode block's module
time in the trace over its runs and over ``decode_block`` steps; running
slots and valid tokens are the loop's samples in the traced span.
Memory-bound by construction: the state has no reuse, and at a few hundred
rows the matmuls sit at the chip's ridge, not over it. None where the
architecture keeps no such state (``arch.state_bytes_per_slot``) or the
trace holds no decode block."""
import jax.numpy as jnp
import numpy as np

from byname import load_module


def read(reduced, counts, config, peaks):
    if reduced is None or not counts.get("samples"):
        return None
    arch = load_module("arch", config["arch"])
    if not hasattr(arch, "state_bytes_per_slot"):
        return None
    serve = config["build"]["serve"]
    dev = reduced["devices"][min(reduced["devices"])]
    runs = dev["module_runs"].get(serve["decode_module"])
    if not runs:
        return None
    step_s = dev["module_s"][serve["decode_module"]] / runs \
        / serve["session_kwargs"]["decode_block"]
    start = counts.get("trace_from_s") or 0.0
    seen = [(r, v) for t, r, v in counts["samples"] if t >= start]
    if not seen:
        return None
    running, valid = np.mean(seen, axis=0)
    need = arch.decode_step_bytes(
        config["sizes"], float(running), float(valid),
        jnp.dtype(serve["weights_dtype"]).itemsize,
        jnp.dtype(serve["cache_dtype"]).itemsize,
        jnp.dtype(serve["state_dtype"]).itemsize)
    return 100.0 * need / peaks["bytes_per_s"] / step_s
