"""Median over requests of (admit program dispatched - submitted), in s:
the program's ``serving.queue_wait_s`` histogram, stamped by the session.
Part one of a time to first token inside the session.

Cumulative over the process (live registry); a median over requests of
which the window's are over nine tenths. In no ``per_layer`` entry yet: it
moves a time to first token, which is not an end-to-end metric of this
benchmark (PERF.md, Open questions, row 0)."""
import paddle_tpu.observability as obs


def read(reduced, counts, config, peaks):
    return obs.histogram("serving.queue_wait_s").percentile(0.5)
