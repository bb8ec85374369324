"""Roofline share of one forward pass of a block-diffusion dispatch, in %:
the bytes the dispatch's passes must read (``arch.forward_bytes`` at
``slots x block_length`` new positions, the traced window's mean valid
cached tokens and the experts a layer's pass reached by the program's own
count, ``moe.experts_touched`` over ``moe.layer_passes``, cumulative:
every denoising pass with the head, the commit pass without it) over the
published bytes/s, over the device time of the dispatch (the block
program's module time in the trace over its runs).
The same share for the mean pass and for the whole dispatch. Memory-bound
by construction: a pass computes a few tens of tokens an expert at most.
None where the trace holds no such program (another configuration, or a
program from before the mode existed)."""
import jax.numpy as jnp
import numpy as np
import paddle_tpu.observability as obs

from byname import load_module


def read(reduced, counts, config, peaks):
    if reduced is None or not counts.get("samples"):
        return None
    serve = config["build"]["serve"]
    module = serve.get("block_module")
    dev = reduced["devices"][min(reduced["devices"])]
    runs = dev["module_runs"].get(module) if module else None
    if not runs:
        return None
    dispatch_s = dev["module_s"][module] / runs
    start = counts.get("trace_from_s") or 0.0
    valid = [v for t, _running, v in counts["samples"] if t >= start]
    if not valid:
        return None
    arch = load_module("arch", config["arch"])
    lane_tokens = counts["slots"] * config["generation"]["block_length"]
    sizes = (jnp.dtype(serve["weights_dtype"]).itemsize,
             jnp.dtype(serve["cache_dtype"]).itemsize)
    layer_passes = obs.counter("moe.layer_passes").value
    touched = obs.counter("moe.experts_touched").value / layer_passes \
        if layer_passes else None
    need = sum(arch.forward_bytes(config, lane_tokens, float(np.mean(valid)),
                                  *sizes, head=head, touched=touched)
               for head in [True] * (counts["passes"] - 1) + [False])
    return 100.0 * need / peaks["bytes_per_s"] / dispatch_s
