"""Device time of the prefill scan (the operations the trace names after
``build.serve.scan_scope`` and the copies into and out of the kernel's layout
beside them: ``serve_scan_kernel_roofline.scan_ops``) over the device's busy
time in the traced window, in %. None where the trace holds no such
operation."""
from byname import load_module


def read(reduced, counts, config, peaks):
    ops = load_module("layer_metrics",
                      "serve_scan_kernel_roofline").scan_ops(reduced, config)
    seconds = sum(s for _b, _p, s, _n in ops)
    if not seconds:
        return None
    dev = reduced["devices"][min(reduced["devices"])]
    return 100.0 * seconds / dev["busy_s"]
