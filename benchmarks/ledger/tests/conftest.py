"""Tests of the ledger benchmark; run by hand, on the CPU:

    python -m pytest benchmarks/ledger/tests -q

They are not under ``tests/``, so the repository's tier-1 count neither gains
nor loses by them."""
import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=4")

LEDGER = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(os.path.dirname(LEDGER))
for p in (ROOT, LEDGER):
    if p not in sys.path:
        sys.path.insert(0, p)
