"""Share of the dispatched decode lane-steps whose token a request took, in
%: (``serving.decode_tokens`` - ``serving.first_tokens``) over
``serving.decode_lane_steps``, all three the session's own counters. A
lane-step is one slot in one decode step; it is wasted when the slot is
empty or its request is past its budget (retirement waits for the block's
end). First tokens come from the admit program and are taken out.

Cumulative over the process (live registry): the window is over nine
tenths of the lane-steps, the reference check and warm-up the rest. None
where the program does not count lane-steps."""
import paddle_tpu.observability as obs


def read(reduced, counts, config, peaks):
    lanes = obs.counter("serving.decode_lane_steps").value
    if not lanes:
        return None
    taken = obs.counter("serving.decode_tokens").value \
        - obs.counter("serving.first_tokens").value
    return 100.0 * taken / lanes
