"""Share of the routed (token, expert) pairs that went to the experts held
here, in %: ``moe.held_assignments`` over ``moe.assignments`` (the program's
counters, ticked once after the window from the assignments the train state
sums on the device). ``num_local_experts / num_experts`` (25 in the cell) is
even; it says how far ``train_mfu``'s expected count of the experts'
operations is from the pairs the grouped products really multiplied. Neither
direction is better: under 25 the router has shed the held experts' work (and
the step reads faster for it), which is why ``BENCHMARK.json`` calls higher
better; the loop's check ``held_share_in_band`` holds it on both sides.

Cumulative over the process (live registry). None where the program counts
no held assignments (a program from before the counter existed)."""
import paddle_tpu.observability as obs


def read(reduced, counts, config, peaks):
    pairs = obs.counter("moe.assignments").value
    held = obs.counter("moe.held_assignments").value
    if not pairs or not held:
        return None
    return 100.0 * held / pairs
