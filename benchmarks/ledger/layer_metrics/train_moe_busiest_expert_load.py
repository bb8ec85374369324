"""Load of a step's busiest expert against an even split, mean over the
expert layers and the steps of the training run: the router's width
(``sizes.num_experts``) x ``moe.busiest_expert_assignments`` over
``moe.assignments`` (the program's counters, ticked once after the window from
what the train state sums on the device every step: each layer's busiest
expert's pairs OF THAT STEP, and all pairs). 1.0 is even; a step's longest
group in the grouped products is this many times the mean.

Cumulative over the process (live registry). None where the program counts
no assignments."""
import paddle_tpu.observability as obs


def read(reduced, counts, config, peaks):
    pairs = obs.counter("moe.assignments").value
    if not pairs or "num_experts" not in config.get("sizes", {}):
        return None
    return config["sizes"]["num_experts"] \
        * obs.counter("moe.busiest_expert_assignments").value / pairs
