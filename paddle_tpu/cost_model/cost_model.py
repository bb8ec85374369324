"""Analytic + measured cost model (reference:
python/paddle/cost_model/cost_model.py:33 and the planner usage in
distributed/auto_parallel/static/cost/)."""
from __future__ import annotations

import time
from dataclasses import dataclass
from typing import Dict, Optional, Sequence

import numpy as np

# Published per-chip peaks (bf16 matmul FLOP/s, HBM B/s, ICI B/s per
# link), keyed by nickname for planning WITHOUT hardware
# (distributed/planner.py). Source of every row: the Google Cloud TPU
# documentation's system-architecture page of that generation
# (cloud.google.com/tpu/docs/{v4,v5e,v5p,v6e}); v5e: 197 TFLOP/s bf16,
# 819 GB/s HBM.
TPU_SPECS: Dict[str, Dict[str, float]] = {
    "v4":  {"flops": 275e12, "hbm_bw": 1.2e12,  "ici_bw": 50e9},
    "v5e": {"flops": 197e12, "hbm_bw": 0.82e12, "ici_bw": 50e9},
    "v5p": {"flops": 459e12, "hbm_bw": 2.76e12, "ici_bw": 100e9},
    "v6e": {"flops": 918e12, "hbm_bw": 1.64e12, "ici_bw": 100e9},
}

#: ``jax.devices()[0].device_kind`` -> TPU_SPECS row, for anything that
#: divides a MEASUREMENT by a peak. Only strings read off a real chip
#: belong here ("TPU v5 lite": the v5e chip tool, PR 21); a kind that
#: is missing is an error, never a default.
DEVICE_KIND_TO_CHIP: Dict[str, str] = {
    "TPU v5 lite": "v5e",
}


def spec_for_device_kind(device_kind: str) -> Dict[str, float]:
    """Peak spec of the chip jax reports as ``device_kind``; raises
    KeyError for a kind the table does not hold."""
    if device_kind not in DEVICE_KIND_TO_CHIP:
        raise KeyError(
            f"no peak spec for device_kind {device_kind!r}; known: "
            f"{sorted(DEVICE_KIND_TO_CHIP)}. Add the string the chip "
            "reports to cost_model.DEVICE_KIND_TO_CHIP with its source.")
    return TPU_SPECS[DEVICE_KIND_TO_CHIP[device_kind]]


def attached_chip_spec() -> Dict[str, float]:
    """Peak spec of the attached accelerator (creates the backend)."""
    import jax
    return spec_for_device_kind(jax.devices()[0].device_kind)


def mfu(tokens_per_s: float, flops_per_token: float,
        peak_flops: float) -> float:
    """Achieved model-flops utilization against a bf16 peak FLOP/s
    (``attached_chip_spec()["flops"]`` for a measurement)."""
    return tokens_per_s * flops_per_token / peak_flops


@dataclass
class OpCost:
    """Cost estimate for one op (reference: auto_parallel cost items:
    comp_cost / comm_cost entries)."""
    flops: float = 0.0
    bytes_accessed: float = 0.0
    comm_bytes: float = 0.0
    time_s: float = 0.0

    def __add__(self, other: "OpCost") -> "OpCost":
        return OpCost(self.flops + other.flops,
                      self.bytes_accessed + other.bytes_accessed,
                      self.comm_bytes + other.comm_bytes,
                      self.time_s + other.time_s)


class CostModel:
    def __init__(self, chip: str = "v5p"):
        if chip not in TPU_SPECS:
            raise ValueError(f"unknown chip {chip!r}; one of "
                             f"{sorted(TPU_SPECS)}")
        self.chip = chip
        self.spec = TPU_SPECS[chip]

    # ----------------------------------------------------- analytic path
    def matmul_cost(self, m: int, n: int, k: int, dtype_bytes: int = 2,
                    batch: int = 1) -> OpCost:
        flops = 2.0 * batch * m * n * k
        byts = dtype_bytes * batch * (m * k + k * n + m * n)
        return self._finish(OpCost(flops=flops, bytes_accessed=byts))

    def elementwise_cost(self, numel: int, n_operands: int = 2,
                         dtype_bytes: int = 2) -> OpCost:
        byts = dtype_bytes * numel * (n_operands + 1)
        return self._finish(OpCost(flops=numel, bytes_accessed=byts))

    def attention_cost(self, batch: int, heads: int, seq: int,
                       head_dim: int, dtype_bytes: int = 2,
                       flash: bool = True) -> OpCost:
        flops = 4.0 * batch * heads * seq * seq * head_dim
        io = dtype_bytes * batch * heads * seq * head_dim * 4
        if not flash:                       # materialized S/P matrices
            io += dtype_bytes * batch * heads * seq * seq * 2
        return self._finish(OpCost(flops=flops, bytes_accessed=io))

    def collective_cost(self, kind: str, bytes_per_rank: float,
                        n_ranks: int) -> OpCost:
        """Ring-model cost over ICI (scaling-book formulation):
        all_reduce moves 2(n-1)/n, all_gather / reduce_scatter
        (n-1)/n, all_to_all (n-1)/n of the payload per link."""
        if n_ranks <= 1:
            return OpCost()
        factor = {"all_reduce": 2.0, "all_gather": 1.0,
                  "reduce_scatter": 1.0, "all_to_all": 1.0,
                  "ppermute": 1.0, "send_recv": 1.0}[kind]
        wire = factor * (n_ranks - 1) / n_ranks * bytes_per_rank
        c = OpCost(comm_bytes=wire)
        c.time_s = wire / self.spec["ici_bw"]
        return c

    def _finish(self, c: OpCost) -> OpCost:
        """Roofline: time = max(compute, memory) (+comm handled by
        collective_cost)."""
        c.time_s = max(c.flops / self.spec["flops"],
                       c.bytes_accessed / self.spec["hbm_bw"])
        return c

    def roofline_intensity(self) -> float:
        """FLOP/byte at the compute/memory ridge point."""
        return self.spec["flops"] / self.spec["hbm_bw"]

    # ----------------------------------------------------- measured path
    def profile_measure(self, fn, args: Sequence, steps: int = 10,
                        warmup: int = 3) -> float:
        """Wall-clock a jitted callable on the attached device
        (reference CostModel.profile_measure over a Program; here over
        a jax-compiled function). Returns seconds/step."""
        import jax
        compiled = jax.jit(fn)
        for _ in range(warmup):
            out = compiled(*args)
        jax.block_until_ready(out)
        t0 = time.perf_counter()
        for _ in range(steps):
            out = compiled(*args)
        jax.block_until_ready(out)
        return (time.perf_counter() - t0) / steps

    # ------------------------------------------------ model-level helper
    @staticmethod
    def train_flops(n_params: float, layers: int, hidden: int, seq: int,
                    batch_tokens: float) -> float:
        """fwd+bwd transformer FLOPs: 6/param/token + the attention
        quadratic term — the single home of this formula (used by
        transformer_step_cost and the distributed planner)."""
        return (6.0 * n_params + 12.0 * layers * hidden * seq) \
            * batch_tokens

    def transformer_step_cost(self, n_params: float, batch_tokens: float,
                              hidden: int, layers: int, seq: int,
                              n_chips: int = 1, dp: int = 1, tp: int = 1,
                              dtype_bytes: int = 2) -> OpCost:
        """End-to-end train-step estimate with DP grad all_reduce and TP
        activation collectives — the planner's objective function."""
        flops = self.train_flops(n_params, layers, hidden, seq,
                                 batch_tokens)
        cost = OpCost(flops=flops,
                      bytes_accessed=dtype_bytes * n_params * 3)
        cost = self._finish(cost)
        if dp > 1:
            cost = cost + self.collective_cost(
                "all_reduce", dtype_bytes * n_params / tp, dp)
        if tp > 1:
            per_layer = dtype_bytes * batch_tokens * hidden
            cost = cost + self.collective_cost(
                "all_reduce", 2 * layers * per_layer / dp, tp)
        return cost
