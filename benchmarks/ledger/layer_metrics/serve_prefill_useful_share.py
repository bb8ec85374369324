"""Share of the prefilled positions that held a prompt token, in %:
``serving.prefill_tokens`` over ``serving.prefill_padded_tokens`` (the
bucket every admitted prompt was padded to), the session's own counters.

Cumulative over the process (live registry): a ratio of counts in which
the window's admissions are over nine tenths. None where the program does
not count padded tokens."""
import paddle_tpu.observability as obs


def read(reduced, counts, config, peaks):
    padded = obs.counter("serving.prefill_padded_tokens").value
    if not padded:
        return None
    return 100.0 * obs.counter("serving.prefill_tokens").value / padded
