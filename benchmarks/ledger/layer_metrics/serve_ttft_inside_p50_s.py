"""Median over requests of (first token on the host - submitted), in s: the
program's ``serving.ttft_s`` histogram: queue wait plus first-token hold,
the part of a time to first token that passes inside the session. What
the loop adds outside (a due request is submitted when ``step()``
returns) is ``gen_lateness_p99_s``'s.

Cumulative over the process (live registry); a median over requests of
which the window's are over nine tenths. In no ``per_layer`` entry yet
(PERF.md, Open questions, row 0)."""
import paddle_tpu.observability as obs


def read(reduced, counts, config, peaks):
    return obs.histogram("serving.ttft_s").percentile(0.5)
