"""Median of the host's own time in one ``session.step()``, in ms: the
step's wall time less the seconds it waited in its fetch (the program's
``serving.step_host_s`` histogram, observed by the session itself). An
upper bound on the idle time a step's host work can cause: work the host
does while the device still runs a block costs nothing.

Read from the live registry of this process, so it is cumulative: the
window's steps are over nine tenths of the observations (the reference
check, warm-up and ramp add a few tens), and a median does not see them.
None where the program has no such histogram."""
import paddle_tpu.observability as obs


def read(reduced, counts, config, peaks):
    p50 = obs.histogram("serving.step_host_s").percentile(0.5)
    return None if p50 is None else 1e3 * p50
