"""Median over requests of (first token on the host - admit program
dispatched), in s: the program's ``serving.first_token_hold_s`` histogram.
The admit's token stays on the device until the next drain, so this is
the prefill plus the whole decode block dispatched after it. Part two of
a time to first token inside the session.

Cumulative over the process (live registry); a median over requests of
which the window's are over nine tenths. In no ``per_layer`` entry yet
(PERF.md, Open questions, row 0)."""
import paddle_tpu.observability as obs


def read(reduced, counts, config, peaks):
    return obs.histogram("serving.first_token_hold_s").percentile(0.5)
