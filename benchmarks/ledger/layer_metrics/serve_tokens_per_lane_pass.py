"""Tokens a request took per lane and forward pass of the block-diffusion
dispatches: ``serving.decode_tokens`` over ``serving.block_lane_passes``
(max_slots x (denoising_steps + 1) a dispatch), the session's own
counters. At most block_length / (denoising_steps + 1); less where lanes
were empty, a first block held prompt tokens or a last block passed the
budget.

Cumulative over the process (live registry): the window is over nine
tenths of the passes. None where the program counts no lane-passes."""
import paddle_tpu.observability as obs


def read(reduced, counts, config, peaks):
    passes = obs.counter("serving.block_lane_passes").value
    if not passes:
        return None
    return obs.counter("serving.decode_tokens").value / passes
