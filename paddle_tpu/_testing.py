"""Test environment helper: pin a fresh process to the CPU backend.

Tests, the CPU-mesh dry run and spawned worker scripts all call
``force_cpu`` before their first jax operation so they never reach for
an accelerator (a chip belongs to one process at a time).
"""
from __future__ import annotations

import os


def force_cpu(num_devices: int | None = None) -> None:
    """Env + jax config for a CPU-only process. Must run before the
    first jax operation; num_devices > 1 adds the virtual-device XLA
    flag (only effective if jax hasn't created a backend yet)."""
    os.environ["JAX_PLATFORMS"] = "cpu"
    if num_devices and num_devices > 1:
        flags = os.environ.get("XLA_FLAGS", "")
        if "xla_force_host_platform_device_count" not in flags:
            os.environ["XLA_FLAGS"] = (
                flags +
                f" --xla_force_host_platform_device_count={num_devices}"
            ).strip()
    import jax

    jax.config.update("jax_platforms", "cpu")
