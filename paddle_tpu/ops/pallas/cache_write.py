"""The cache write of ``inference/decode._cache_attention`` (Pallas TPU).

A step's new keys and values ``[B, s, Hkv, D]`` go into the fixed-capacity
cache ``[B, C, Hkv, D]`` at each slot's own offset. As XLA has it, a
``dynamic_update_slice`` batched over its start index is a scatter, and the
TPU pipeline expands that scatter into a ``while`` over the slots: three or
four launches for every row. Here a layer's write is one program:

- A slot's ``s`` rows are one contiguous span of the buffer, so each slot is
  one copy from HBM to HBM (``make_async_copy``), for K and for V. All
  ``2 * B`` copies are started before any is waited for. Nothing passes
  through VMEM: no budget, and no gate on ``s`` or the shape.
- The buffers are aliased to the outputs (``input_output_aliases``): the
  rows are written in place and nothing else of the cache is touched or
  moved.
- The offset is ``clip(len, 0, C - s)``, ``dynamic_update_slice``'s own
  clamp: a lane that a decode block steps past its budget up to the
  capacity writes where it wrote before.
- A copy does no arithmetic, so any dtype the cache has; the caller casts
  the new rows to it.

``interpret=True`` runs the same program through the Pallas interpreter
(tests/test_cache_write_kernel.py).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(lens_ref, kn_hbm, vn_hbm, _k_in, _v_in, k_hbm, v_hbm, sems):
    nslots, s = kn_hbm.shape[:2]
    capacity = k_hbm.shape[1]

    def copies(b):
        rows = pl.ds(jnp.clip(lens_ref[b], 0, capacity - s), s)
        return (pltpu.make_async_copy(kn_hbm.at[b], k_hbm.at[b, rows],
                                      sems.at[0]),
                pltpu.make_async_copy(vn_hbm.at[b], v_hbm.at[b, rows],
                                      sems.at[1]))

    def start(b, _):
        for cp in copies(b):
            cp.start()

    def wait(b, _):
        # every copy of a buffer moves the same bytes, so they share one
        # semaphore: B waits take what B copies signalled
        for cp in copies(b):
            cp.wait()

    lax.fori_loop(0, nslots, start, None)
    lax.fori_loop(0, nslots, wait, None)


def write_rows(kbuf, vbuf, kn, vn, lens, *, interpret=False):
    """kbuf/vbuf: [B, C, Hkv, D]; kn/vn: [B, s, Hkv, D] in the buffers'
    dtypes, s <= C; lens: [B]. Returns (kbuf', vbuf') with slot b's rows
    ``p .. p + s - 1`` replaced by the new ones, ``p = clip(lens[b], 0,
    C - s)``, in place where the caller gives the buffers up."""
    if kn.shape[1] > kbuf.shape[1]:
        raise ValueError(f"write_rows: {kn.shape[1]} new positions into a "
                         f"cache of {kbuf.shape[1]}")
    if (kn.dtype, vn.dtype) != (kbuf.dtype, vbuf.dtype):
        raise ValueError(
            "write_rows: new rows in the buffers' dtypes, got "
            f"{kn.dtype}/{vn.dtype} for {kbuf.dtype}/{vbuf.dtype}")
    hbm = pl.BlockSpec(memory_space=pl.ANY)
    return pl.pallas_call(
        _kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(),
            in_specs=[hbm, hbm, hbm, hbm],
            out_specs=[hbm, hbm],
            scratch_shapes=[pltpu.SemaphoreType.DMA((2,))]),
        out_shape=[jax.ShapeDtypeStruct(kbuf.shape, kbuf.dtype),
                   jax.ShapeDtypeStruct(vbuf.shape, vbuf.dtype)],
        # operands count from the prefetched lens: 3, 4 are the buffers
        input_output_aliases={3: 0, 4: 1},
        interpret=interpret,
    )(lens.astype(jnp.int32), kn, vn, kbuf, vbuf)
