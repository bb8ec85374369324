"""Device time of the expert layer's grouped products (the operations the
trace names after ``build.train.grouped_ops``:
``train_moe_grouped_roofline.grouped_ops``) over the device's busy time in
the traced window, in %. None where the trace holds no such operation."""
from byname import load_module


def read(reduced, counts, config, peaks):
    seconds, _layer_steps = load_module(
        "layer_metrics", "train_moe_grouped_roofline").grouped_ops(
            reduced, config)
    if not seconds:
        return None
    dev = reduced["devices"][min(reduced["devices"])]
    return 100.0 * seconds / dev["busy_s"]
