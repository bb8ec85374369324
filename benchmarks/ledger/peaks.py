"""Published peaks of the chips this benchmark may run on, keyed by
``device_kind`` as JAX reports it. The benchmark's own copy: the program's
table (``paddle_tpu.cost_model``) may change, the yardstick may not.

A device that is not in the table is an error, never a default.
"""

PEAKS = {
    # Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, 16 GB of HBM
    # at 819 GB/s per chip.
    "TPU v5 lite": {"flops_per_s": 197e12, "bytes_per_s": 819e9,
                    "memory_bytes": 16e9,
                    "source": "Google Cloud TPU v5e documentation"},
}


def peaks_for(device_kind):
    if device_kind not in PEAKS:
        raise KeyError(f"no published peaks for device_kind {device_kind!r}; "
                       f"known: {sorted(PEAKS)}")
    return PEAKS[device_kind]
