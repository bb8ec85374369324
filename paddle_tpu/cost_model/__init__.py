"""paddle.cost_model equivalent (reference: python/paddle/cost_model —
CostModel.profile_measure + the static_op_benchmark.json table backing
the auto-parallel planner).

TPU-native: instead of a pre-measured per-op latency JSON, costs come
from (a) an analytic roofline over published TPU peak numbers
(MXU flops, HBM bandwidth, ICI bandwidth) for planning without
hardware, and (b) `profile_measure`, which times a jitted callable on
the attached device — the measured path the reference gets from its
benchmark table."""
from .cost_model import (  # noqa: F401
    CostModel, DEVICE_KIND_TO_CHIP, TPU_SPECS, OpCost,
    attached_chip_spec, mfu, spec_for_device_kind)

__all__ = ["CostModel", "DEVICE_KIND_TO_CHIP", "TPU_SPECS", "OpCost",
           "attached_chip_spec", "mfu", "spec_for_device_kind"]
