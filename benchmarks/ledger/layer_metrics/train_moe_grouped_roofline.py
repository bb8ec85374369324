"""Compute-roofline share of the expert layer's grouped products, in %: the
operations the six products of a layer-step require (``arch.grouped_flops``:
forward, rows' cotangent and weights' cotangent of gate|up and of down, at
the run's mean count of pairs routed to the experts held) over the published
FLOP/s, over the device time of the operations the trace names after the
configuration's ``build.train.grouped_ops`` (the Pallas calls' own names,
``gmm`` and ``tgmm``). Layer-steps in the trace: a layer-step runs the
weights' cotangent (``grouped_weights_op``) exactly twice, and nothing
recomputes it. A forward product that remat runs again costs time here and
adds no required operation, so the share reads lower for it.
None where the trace holds no such operation (another configuration, or a
program from before the kernels had a backward pass)."""
from byname import load_module


def grouped_ops(reduced, config):
    """(seconds of the grouped products on device 0, layer-steps traced);
    (0.0, 0) where there are none."""
    train = config["build"].get("train", {})
    names = train.get("grouped_ops")
    if reduced is None or not names:
        return 0.0, 0
    dev = reduced["devices"][min(reduced["devices"])]
    seconds, twice = 0.0, 0
    for key, (s, calls) in dev["op_s"].items():
        name = key.split(" ")[0]
        if name in names:
            seconds += s
            if name == train.get("grouped_weights_op"):
                twice += calls
    return seconds, twice / 2


def read(reduced, counts, config, peaks):
    seconds, layer_steps = grouped_ops(reduced, config)
    pairs = counts.get("held_pairs_per_layer_step")
    if not seconds or not layer_steps or not pairs:
        return None
    arch = load_module("arch", config["arch"])
    need = layer_steps * arch.grouped_flops(config["sizes"], pairs)
    return 100.0 * need / peaks["flops_per_s"] / seconds
