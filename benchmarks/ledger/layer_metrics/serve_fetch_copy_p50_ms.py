"""Median over the cycles that dispatched a decode block of the seconds the
fetch spent AFTER the block was done, in ms (the program's
``serving.cycle_part_s{part=fetch_copy}`` histogram): ``jax.device_get`` of
outputs that ``block_until_ready`` has already waited for, their copies to
the host started before the wait. The chip has nothing of the session's to
run meanwhile: every such second is also in ``serve_starved_p50_ms``, and it
is the part of the fetch that ``serve_step_host_p50_ms`` subtracts with the
wait.

Read from the live registry of this process (cumulative; a median does not
see the few tens of warm-up and ramp cycles). None where the program has no
such histogram."""
import paddle_tpu.observability as obs


def read(reduced, counts, config, peaks):
    p50 = obs.histogram("serving.cycle_part_s",
                        part="fetch_copy").percentile(0.5)
    return None if p50 is None else 1e3 * p50
