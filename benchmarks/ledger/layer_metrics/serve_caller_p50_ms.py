"""Median over the cycles that dispatched a decode block of the seconds
between a ``session.step()``'s return and the next one's entry while the
session held work, in ms (the program's ``serving.cycle_part_s{part=caller}``
histogram): the loop's own bookkeeping around the step, which the session
cannot see into and only times from outside. ``step()`` is synchronous, so
the chip is idle through all of it: every such second is also in
``serve_starved_p50_ms``.

Read from the live registry of this process (cumulative; a median does not
see the few tens of warm-up and ramp cycles). None where the program has no
such histogram."""
import paddle_tpu.observability as obs


def read(reduced, counts, config, peaks):
    p50 = obs.histogram("serving.cycle_part_s",
                        part="caller").percentile(0.5)
    return None if p50 is None else 1e3 * p50
