"""Test config: force a CPU backend with 8 virtual devices so collective /
sharding semantics are testable without TPU hardware (the reference's
gloo-on-CPU "fake cluster" trick, SURVEY §4.2).
"""
import os

os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    _flags = (_flags + " --xla_force_host_platform_device_count=8")
if "xla_cpu_enable_concurrency_optimized_scheduler" not in _flags:
    # the concurrency-optimized CPU thunk scheduler issues data-
    # independent collectives in divergent per-device orders; with the
    # manual-tp zero-bubble pipelines (explicit collectives inside
    # cond-gated phases) that deadlocks the rendezvous (round 5 —
    # models/gpt_manual_tp.py). Sequential thunk scheduling restores
    # the uniform issue order. TPU is unaffected (per-core program
    # order is always uniform).
    _flags = (_flags
              + " --xla_cpu_enable_concurrency_optimized_scheduler=false")
os.environ["XLA_FLAGS"] = _flags.strip()

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

import numpy as np  # noqa: E402
import pytest  # noqa: E402

# tests/ top level carries only test modules plus these two helpers.
# One-off measurement probes (the `_*.py` scripts that used to pollute
# the tests dir and its grep results) do not belong here: a chip script
# of one PR goes to the git-ignored _chip_scratch/; this guard keeps
# it that way.
_ALLOWED_NON_TEST = {"conftest.py", "op_test.py"}
_strays = sorted(
    f for f in os.listdir(os.path.dirname(os.path.abspath(__file__)))
    if f.endswith(".py") and not f.startswith("test_")
    and f not in _ALLOWED_NON_TEST)
if _strays:
    raise RuntimeError(
        "non-test modules at tests/ top level: %s — one-off probe "
        "scripts do not belong under tests/" % ", ".join(_strays))


@pytest.fixture(autouse=True)
def _seeded():
    import paddle_tpu
    paddle_tpu.seed(2024)
    np.random.seed(2024)
    yield


@pytest.fixture(autouse=True)
def _chaos_hygiene(request):
    """Fault-injection hygiene for `chaos`-marked tests (pytest.ini):
    installed rules and the arming env var never leak into later
    tests — a leaked persistent rule would fail every serving test
    after it."""
    yield
    if request.node.get_closest_marker("chaos") is not None:
        from paddle_tpu import _chaos
        _chaos.clear()
        os.environ.pop(_chaos.ENV, None)


# one log per session (pid-suffixed: concurrent sessions/users must not
# clobber each other's 'first leaker' diagnostic or hit foreign-owned
# /tmp files in fixture teardown)
DIRTY_STATE_LOG = f"/tmp/jax_dirty_state.{os.getpid()}.log"


@pytest.fixture(autouse=True, scope="session")
def _fresh_dirty_state_log():
    try:
        os.remove(DIRTY_STATE_LOG)
    except OSError:
        pass
    yield


@pytest.fixture(autouse=True)
def _jax_global_state_hygiene(request):
    """Record the FIRST test that leaves process-global jax state dirty
    (leaked disable_jit / trace context / x64): such a leak silently
    degrades every later test — the executable-count perf gate caught
    one as an order-dependent failure. Diagnostic log only; the leaker
    is fixed at the source."""
    yield
    from jax._src import core as _jcore
    dirty = []
    if jax.config.jax_disable_jit:
        dirty.append("jax_disable_jit")
    if jax.config.jax_enable_x64:
        dirty.append("jax_enable_x64")
    try:
        if not _jcore.trace_state_clean():
            dirty.append("trace_state")
    except Exception:
        pass
    if dirty:
        with open(DIRTY_STATE_LOG, "a") as f:
            f.write(f"{request.node.nodeid}: {dirty}\n")
