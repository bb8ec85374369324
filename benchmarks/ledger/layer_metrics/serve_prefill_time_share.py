"""Device time under the admit program (a b=1 prefill written into its
slot) over the device's busy time in the traced window, in %."""


def read(reduced, counts, config, peaks):
    if reduced is None:
        return None
    dev = reduced["devices"][min(reduced["devices"])]
    admit = dev["module_s"].get(config["build"]["serve"]["admit_module"], 0.0)
    return 100.0 * admit / dev["busy_s"] if dev["busy_s"] else None
