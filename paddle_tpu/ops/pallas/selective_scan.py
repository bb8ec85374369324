"""The selective scan of a Mamba-1 mixer over a prefill (Pallas TPU).

Per sequence, over S positions of I channels with N states a channel::

    h_t = exp(delta_t (x) A) * h_{t-1} + (delta_t * x_t) (x) B_t
    y_t = h_t C_t + D * x_t

``x``, ``delta`` [B, S, I]; ``B``, ``C`` [B, S, N]; ``A`` [N, I] (negative);
``D`` [I]; ``h_in`` [B, N, I] -> ``y`` [B, S, I], ``h_out`` [B, N, I]; float32
throughout. State-major: a TPU tiles an array's last two dims, so the
channels lie along the lanes and a state is a row.

The recurrence is over time and nothing else, so XLA has two poor forms for
it: a ``lax.scan`` of S steps, each a handful of operations on a few
registers' worth of data, and an ``associative_scan`` that materialises
``[S, I, N]`` several times over. Here a layer's scan is one program:

- The channels are cut into tiles of 1,024: eight rows of 128 lanes, one
  vector register. A position's ``x`` and ``delta`` of a tile are a register
  each; the tile's state is N registers, carried in registers through a
  chunk of time and in a VMEM scratch from one chunk to the next.
- The grid is (sequence, tile, chunk of time), time innermost and
  sequential. A chunk's ``x`` and ``delta`` come in and its ``y`` goes out
  through the pipeline while the chunk before it is computed.
- ``B_t`` and ``C_t`` are the same for every channel: they are read as
  scalars from SMEM and broadcast by the multiply.
- ``exp`` is taken once a (position, channel, state); nothing of shape
  [S, I, N] exists anywhere.

A position with ``delta_t = 0`` leaves the state as it was (``exp(0) = 1``,
``0 * x_t = 0``): that is how a caller passes over padding.

``interpret=True`` runs the same program through the Pallas interpreter
(tests/test_selective_scan_kernel.py). ``sequential`` is the same arithmetic
as a ``lax.scan`` over time, for every backend but the TPU.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128
#: rows of 128 channels in a tile: one float32 register
TILE_ROWS = 8
#: positions in a chunk of time
CHUNK = 128


def sequential(x, delta, bm, cm, a, d, h_in):
    """The scan as a ``lax.scan`` over time; shapes as in the module's
    docstring."""
    def step(h, t):
        x_t, d_t, b_t, c_t = t
        h = jnp.exp(d_t[:, None, :] * a) * h \
            + (d_t * x_t)[:, None, :] * b_t[:, :, None]
        return h, jnp.sum(h * c_t[:, :, None], axis=1) + d * x_t

    h, y = lax.scan(step, h_in,
                    [jnp.swapaxes(t, 0, 1) for t in (x, delta, bm, cm)])
    return jnp.swapaxes(y, 0, 1), h


def tiling(s, inner):
    """(positions a chunk, rows of 128 channels a tile) for a scan of ``s``
    positions over ``inner`` channels; raises for a shape the kernel cannot
    take."""
    if inner % LANES:
        raise ValueError(f"selective_scan: {inner} channels are not whole "
                         f"rows of {LANES} lanes")
    rows = inner // LANES
    # a block's second-minor dim: a multiple of eight, or all of it
    return math.gcd(s, CHUNK), TILE_ROWS if rows % TILE_ROWS == 0 else rows


def _kernel(b_ref, c_ref, x_ref, dt_ref, a_ref, d_ref, h0_ref, y_ref,
            hout_ref, h_scr, *, n, ts):
    chunk = pl.program_id(2)

    @pl.when(chunk == 0)
    def _():
        h_scr[...] = h0_ref[0]

    skip = d_ref[...]

    def step(t, h):
        x_t, d_t = x_ref[0, t], dt_ref[0, t]
        dx = d_t * x_t
        y = skip * x_t
        out = []
        for i in range(n):
            h_i = jnp.exp(d_t * a_ref[i]) * h[i] + dx * b_ref[t * n + i]
            y = y + h_i * c_ref[t * n + i]
            out.append(h_i)
        y_ref[0, t] = y
        return tuple(out)

    h = lax.fori_loop(0, ts, step, tuple(h_scr[i] for i in range(n)))
    for i in range(n):
        h_scr[i] = h[i]

    @pl.when(chunk == pl.num_programs(2) - 1)
    def _():
        hout_ref[0] = h_scr[...]


def selective_scan(x, delta, bm, cm, a, d, h_in, *, interpret=False):
    """The scan as one Pallas program; shapes as in the module's
    docstring. Returns (y [B, S, I], h_out [B, N, I])."""
    b, s, inner = x.shape
    n = a.shape[0]
    ts, tr = tiling(s, inner)
    rows = inner // LANES
    if any(t.dtype != jnp.float32 for t in (x, delta, bm, cm, a, d, h_in)):
        raise ValueError("selective_scan: float32 throughout")
    chunks = s // ts

    def tiled(t):                       # [..., I] -> [..., rows, 128]
        return t.reshape(*t.shape[:-1], rows, LANES)

    smem = pl.BlockSpec((ts * n,), lambda i, j, k: (i * chunks + k,),
                        memory_space=pltpu.SMEM)
    seq = pl.BlockSpec((1, ts, tr, LANES), lambda i, j, k: (i, k, j, 0))
    state = pl.BlockSpec((1, n, tr, LANES), lambda i, j, k: (i, 0, j, 0))
    y, h_out = pl.pallas_call(
        functools.partial(_kernel, n=n, ts=ts),
        grid=(b, rows // tr, chunks),
        in_specs=[smem, smem, seq, seq,
                  pl.BlockSpec((n, tr, LANES), lambda i, j, k: (0, j, 0)),
                  pl.BlockSpec((tr, LANES), lambda i, j, k: (j, 0)),
                  state],
        out_specs=[seq, state],
        out_shape=[jax.ShapeDtypeStruct((b, s, rows, LANES), jnp.float32),
                   jax.ShapeDtypeStruct((b, n, rows, LANES), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((n, tr, LANES), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        name="selective_scan",
        interpret=interpret,
    )(bm.reshape(-1), cm.reshape(-1), tiled(x), tiled(delta), tiled(a),
      tiled(d), tiled(h_in))
    return y.reshape(b, s, inner), h_out.reshape(b, n, inner)
