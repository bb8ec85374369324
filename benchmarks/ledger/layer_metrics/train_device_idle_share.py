"""Share of the traced window in which no operation ran on device 0, in %
(1 - union of operation intervals / window)."""


def read(reduced, counts, config, peaks):
    if reduced is None:
        return None
    return 100.0 * reduced["devices"][min(reduced["devices"])]["idle_share"]
