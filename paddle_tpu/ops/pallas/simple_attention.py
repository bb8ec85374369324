"""Monolithic Pallas attention for short sequences (TPU).

Motivation (probes on an earlier v5e, in git history before PR 30): at
S<=1024 a whole (batch, head) slice — q/k/v [S,D] plus the full [S,S]
score matrix — fits in VMEM (~7 MB of the ~16 MB/core), so the streaming-softmax machinery of
the general flash kernel (jax.experimental.pallas.ops.tpu.flash_attention)
buys nothing and its multi-block pipeline costs ~20 us/program of
overhead. This kernel does the whole slice in ONE program per (b, h):
scores on the MXU, softmax in VMEM, no inter-block streaming.

Every timing quoted in this file comes from an earlier chip record
(deleted in PR 21): not measured on the current code. On the current
chip the kernel compiles and matches its float32 reference, fwd and bwd,
at [4,16,1024,128] (chip_smoke.py).

Reference being replaced: phi/kernels/gpu/flash_attn_kernel.cu:587 (the
short-sequence path of the CUDA flash wrapper).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

NEG_INF = -1e30


def _pl():
    from jax.experimental import pallas as pl
    return pl


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, *, sm_scale, causal, bh):
    # bh heads per program: amortizes grid overhead (0.56 vs 0.76
    # ms/layer at bh=2 on an earlier v5e; the probe is in git history
    # before PR 30)
    for hh in range(bh):
        q = q_ref[0, hh].astype(jnp.float32)        # [S, D]
        k = k_ref[0, hh].astype(jnp.float32)
        v = v_ref[0, hh]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * sm_scale   # [S, S]
        if causal:
            sq = s.shape[0]
            iq = jax.lax.broadcasted_iota(jnp.int32, (sq, sq), 0)
            ik = jax.lax.broadcasted_iota(jnp.int32, (sq, sq), 1)
            s = jnp.where(iq >= ik, s, NEG_INF)
        m = jnp.max(s, axis=-1, keepdims=True)
        p = jnp.exp(s - m)
        l = jnp.sum(p, axis=-1, keepdims=True)
        p = (p / l).astype(v.dtype)
        o = jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        o_ref[0, hh] = o.astype(o_ref.dtype)


def _bwd_kernel(q_ref, k_ref, v_ref, do_ref, dq_ref, dk_ref, dv_ref, *,
                sm_scale, causal):
    q = q_ref[0, 0].astype(jnp.float32)
    k = k_ref[0, 0].astype(jnp.float32)
    v = v_ref[0, 0].astype(jnp.float32)
    do = do_ref[0, 0].astype(jnp.float32)
    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32) * sm_scale
    if causal:
        sq = s.shape[0]
        iq = jax.lax.broadcasted_iota(jnp.int32, (sq, sq), 0)
        ik = jax.lax.broadcasted_iota(jnp.int32, (sq, sq), 1)
        s = jnp.where(iq >= ik, s, NEG_INF)
    m = jnp.max(s, axis=-1, keepdims=True)
    e = jnp.exp(s - m)
    l = jnp.sum(e, axis=-1, keepdims=True)
    p = e / l                                           # [S, S]
    # dv = p^T @ do
    dv = jax.lax.dot_general(
        p, do, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    # dp = do @ v^T ; softmax vjp: ds = p * (dp - rowsum(dp * p))
    dp = jax.lax.dot_general(
        do, v, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)
    delta = jnp.sum(dp * p, axis=-1, keepdims=True)
    ds = p * (dp - delta) * sm_scale
    dq = jax.lax.dot_general(
        ds, k, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    dk = jax.lax.dot_general(
        ds, q, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    dq_ref[0, 0] = dq.astype(dq_ref.dtype)
    dk_ref[0, 0] = dk.astype(dk_ref.dtype)
    dv_ref[0, 0] = dv.astype(dv_ref.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def simple_attention(q, k, v, sm_scale, causal=True, interpret=False):
    """q/k/v: [B, H, S, D] -> [B, H, S, D]."""
    return _fwd(q, k, v, sm_scale, causal, interpret)[0]


def _fwd_block_h(s, d, h, dtype):
    """Heads per fwd program. bh=2 wins standalone (0.56 vs 0.76
    ms/layer) but LOSES ~4% end-to-end inside the remat train step
    (VMEM pressure vs XLA scheduling; both read on an earlier chip by
    scripts in git history before PR 30), so stay at 1."""
    return 1


def _fwd(q, k, v, sm_scale, causal, interpret):
    pl = _pl()
    b, h, s, d = q.shape
    bh = _fwd_block_h(s, d, h, q.dtype)
    blk = pl.BlockSpec((1, bh, s, d), lambda i, j: (i, j, 0, 0))
    out = pl.pallas_call(
        functools.partial(_fwd_kernel, sm_scale=sm_scale, causal=causal,
                          bh=bh),
        grid=(b, h // bh),
        in_specs=[blk, blk, blk],
        out_specs=blk,
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        interpret=interpret,
    )(q, k, v)
    return out, (q, k, v)


def _bwd(sm_scale, causal, interpret, res, do):
    pl = _pl()
    q, k, v = res
    b, h, s, d = q.shape
    blk = pl.BlockSpec((1, 1, s, d), lambda i, j: (i, j, 0, 0))
    dq, dk, dv = pl.pallas_call(
        functools.partial(_bwd_kernel, sm_scale=sm_scale, causal=causal),
        grid=(b, h),
        in_specs=[blk, blk, blk, blk],
        out_specs=[blk, blk, blk],
        out_shape=[jax.ShapeDtypeStruct(q.shape, q.dtype)] * 3,
        interpret=interpret,
    )(q, k, v, do)
    return dq, dk, dv


simple_attention.defvjp(_fwd, _bwd)


def supported(q_shape, dtype, vmem_budget=12 * 2 ** 20):
    """Whole-slice VMEM feasibility: q/k/v/o [S,D] + scores [S,S] f32
    (x2 for fwd+recompute headroom)."""
    b, h, s, d = q_shape
    if d % 128 != 0 and d != 64:
        return False
    if s % 128 != 0:
        return False
    itemsize = 2 if dtype in (jnp.bfloat16, jnp.float16) else 4
    need = 4 * s * d * itemsize + 2 * s * s * 4
    return need <= vmem_budget


def attention_bhsd(q, k, v, causal=True, scale=None, interpret=False):
    """Convenience: [B,H,S,D] layout with defaulted scale."""
    d = q.shape[-1]
    sm = scale if scale is not None else 1.0 / math.sqrt(d)
    return simple_attention(q, k, v, sm, causal, interpret)
