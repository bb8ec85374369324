"""99th percentile of (submit time - due time) over the counted requests:
how late the benchmark's own generator ran. The loop submits between
``step()`` calls, so this is about one decode block by design; more means
the generator, not the server, was starved."""
import numpy as np


def read(reduced, counts, config, peaks):
    late = counts.get("lateness_s")
    return float(np.percentile(late, 99)) if late else None
