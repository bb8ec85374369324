"""The one home of JAX's persistent compilation cache placement.

``JAX_COMPILATION_CACHE_DIR`` set: nothing is done here — jax reads
the variable itself, so whoever runs the program places the cache.
Unset: the cache lives at ``<checkout>/.jax_cache``, a path derived
from this package's location (the directory is part of the cache key's
stability: a path made from a pid, a tempdir or the time never hits).

chip_smoke.py, which compiles the big programs, calls :func:`enable`
once before its first jit; the benchmark's ``benchmarks/ledger/run.py``
places its cache by the same rule itself, and nothing else in the tree
touches ``jax_compilation_cache_dir``.
"""
from __future__ import annotations

import os

_DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    ".jax_cache")


def enable() -> str:
    """Place the cache (see module docstring); returns the directory
    jax will use."""
    import jax
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", _DEFAULT_DIR)
    return jax.config.jax_compilation_cache_dir


def entry_count(directory: str) -> int:
    """Files currently in the cache directory (0 when absent)."""
    if not os.path.isdir(directory):
        return 0
    return sum(len(files) for _root, _dirs, files in os.walk(directory))
