"""Load of the busiest expert against an even split, mean over the expert
layers and passes of the block-diffusion dispatches: ``num_experts`` x
``moe.busiest_expert_assignments`` over ``moe.assignments`` (the program's
counters, summed on the device over the stepping lanes of every pass). 1.0
is even; the grouped product's longest group is this many times the mean.

Cumulative over the process (live registry). None where the program
counts no assignments."""
import paddle_tpu.observability as obs


def read(reduced, counts, config, peaks):
    pairs = obs.counter("moe.assignments").value
    if not pairs or "num_experts" not in config:
        return None
    return config["num_experts"] \
        * obs.counter("moe.busiest_expert_assignments").value / pairs
