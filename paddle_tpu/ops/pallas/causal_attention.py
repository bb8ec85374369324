"""Causal-skip monolithic attention (Pallas TPU, one program per (b,h)).

Combines the two effects measured on v5e:
- monolithic grid (b, h): whole q/k/v slice resident in VMEM, ~64
  programs, so the ~20 us/program TPU grid overhead stays amortized
  (why simple_attention beats the library flash kernel at S<=1024);
- STATIC causal skipping: the q dim is split into nq blocks unrolled in
  Python; q-block i computes one [bq, (i+1)*bq] score strip (a single
  dot + a single softmax — no online-softmax rescale chain, which is
  what made a fori_loop flash variant lose), so the strictly-upper
  triangle blocks are never computed. MAC fraction = (nq+1)/(2*nq)
  (62.5% at nq=4) vs the full-S^2 monolithic kernel.

fwd saves (o, lse); bwd uses delta = rowsum(do * o) per strip and
accumulates dk/dv into f32 VMEM refs at static offsets.

MEASURED OUTCOME (v5e, D128, bf16): shape-dependent.
- S=1024 (B8): LOSES to the full-S^2 simple_attention kernel — 48.7k
  tok/s e2e at nq=4, 49.1k at nq=2, vs 50.6k for simple. A dynamic
  fori_loop online-softmax variant was worse still (44.3k), and a
  q-block-grid flash variant worst (43.9k; ~20us/program grid
  overhead). At short S the kernel is VPU/VMEM-bound, not MAC-bound.
- S=2048 (B4, nq=8): WINS 1.8x over the q-block kernel (4.33 vs 7.85
  ms/layer fwd+bwd; 41.3k -> 43.8k tok/s e2e) — at long S attention
  MACs dominate and skipping the upper triangle pays.
Dispatch (flash_attention_maybe): simple first where it fits
(S<=1024), then this kernel for causal longer-S, then q-block.

Every timing quoted in this file comes from an earlier chip record
(deleted in PR 21): not measured on the current code. On the current
chip the kernel compiles and matches its float32 reference, fwd and bwd,
at [4,8,2048,128] (chip_smoke.py).

Reference being replaced: phi/kernels/gpu/flash_attn_kernel.cu:587
(causal path of the CUDA flash-attention v2 wrapper).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax

NEG_INF = -1e30


def _pl():
    from jax.experimental import pallas as pl
    return pl


_NQ = 2   # preferred (fewest, biggest strips); _pick_nq may raise it


def _vmem_need(s, d, nq, itemsize):
    """bwd residency: q/k/v/o/do native + dk/dv f32 + p/dp strips f32."""
    bq = s // nq
    return (5 * s * d * itemsize + 2 * s * d * 4
            + 2 * bq * s * 4 + 8 * s * 4)


def _pick_nq(s, d, itemsize, vmem_budget=11 * 2 ** 20):
    """Smallest nq (widest strips -> best MXU shapes) whose bwd
    working set fits VMEM. At S=1024 this is 2; at S=2048 the [bq, S]
    f32 strips force nq=8."""
    for nq in (_NQ, 4, 8, 16):
        if s % (nq * 128) == 0 and _vmem_need(s, d, nq, itemsize) \
                <= vmem_budget:
            return nq
    return None


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, l_ref, *, sm_scale, bq, nq):
    for qb in range(nq):
        kw = (qb + 1) * bq                       # strip width (static)
        q = q_ref[0, 0, qb * bq:(qb + 1) * bq, :].astype(jnp.float32)
        k = k_ref[0, 0, :kw, :].astype(jnp.float32)
        v = v_ref[0, 0, :kw, :]
        s = lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * sm_scale  # [bq, kw]
        iq = lax.broadcasted_iota(jnp.int32, (bq, kw), 0) + qb * bq
        ik = lax.broadcasted_iota(jnp.int32, (bq, kw), 1)
        s = jnp.where(iq >= ik, s, NEG_INF)
        m = jnp.max(s, axis=-1)
        p = jnp.exp(s - m[:, None])
        l = jnp.sum(p, axis=-1)
        o = lax.dot_general(
            (p / l[:, None]).astype(v.dtype), v,
            (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        o_ref[0, 0, qb * bq:(qb + 1) * bq, :] = o.astype(o_ref.dtype)
        l_ref[0, 0, :, qb * bq:(qb + 1) * bq] = jnp.broadcast_to(
            (m + jnp.log(l))[None, :], (8, bq))


def _bwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, do_ref,
                dq_ref, dk_ref, dv_ref, *, sm_scale, bq, nq):
    dk_ref[0, 0] = jnp.zeros_like(dk_ref[0, 0])
    dv_ref[0, 0] = jnp.zeros_like(dv_ref[0, 0])
    for qb in range(nq):
        kw = (qb + 1) * bq
        sl = slice(qb * bq, (qb + 1) * bq)
        q = q_ref[0, 0, sl, :].astype(jnp.float32)
        do = do_ref[0, 0, sl, :].astype(jnp.float32)
        o = o_ref[0, 0, sl, :].astype(jnp.float32)
        lse = lse_ref[0, 0, 0, sl]
        k = k_ref[0, 0, :kw, :].astype(jnp.float32)
        v = v_ref[0, 0, :kw, :].astype(jnp.float32)
        delta = jnp.sum(do * o, axis=-1)
        s = lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * sm_scale
        iq = lax.broadcasted_iota(jnp.int32, (bq, kw), 0) + qb * bq
        ik = lax.broadcasted_iota(jnp.int32, (bq, kw), 1)
        s = jnp.where(iq >= ik, s, NEG_INF)
        p = jnp.exp(s - lse[:, None])                     # [bq, kw]
        dv_blk = lax.dot_general(
            p, do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)           # [kw, D]
        dp = lax.dot_general(
            do, v, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        ds = p * (dp - delta[:, None]) * sm_scale
        dq = lax.dot_general(
            ds, k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dk_blk = lax.dot_general(
            ds, q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)           # [kw, D]
        dq_ref[0, 0, sl, :] = dq.astype(dq_ref.dtype)
        dk_ref[0, 0, :kw, :] += dk_blk
        dv_ref[0, 0, :kw, :] += dv_blk


def supported(q_shape, dtype, vmem_budget=11 * 2 ** 20):
    b, h, s, d = q_shape
    if d % 128 != 0 and d != 64:
        return False
    itemsize = 2 if dtype in (jnp.bfloat16, jnp.float16) else 4
    return _pick_nq(s, d, itemsize, vmem_budget) is not None


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def causal_attention(q, k, v, sm_scale, interpret=False):
    """q/k/v: [B, H, S, D] -> [B, H, S, D]; causal only."""
    return _fwd(q, k, v, sm_scale, interpret)[0]


def _require_nq(s, d, dtype):
    itemsize = 2 if dtype in (jnp.bfloat16, jnp.float16) else 4
    nq = _pick_nq(s, d, itemsize)
    if nq is None:
        raise ValueError(
            f"causal_attention: shape (S={s}, D={d}, {dtype}) exceeds "
            "the VMEM budget — check supported() before calling")
    return nq


def _fwd(q, k, v, sm_scale, interpret):
    pl = _pl()
    b, h, s, d = q.shape
    nq = _require_nq(s, d, q.dtype)
    bq = s // nq
    blk = pl.BlockSpec((1, 1, s, d), lambda i, j: (i, j, 0, 0))
    lblk = pl.BlockSpec((1, 1, 8, s), lambda i, j: (i, j, 0, 0))
    o, lse = pl.pallas_call(
        functools.partial(_fwd_kernel, sm_scale=sm_scale, bq=bq, nq=nq),
        grid=(b, h),
        in_specs=[blk, blk, blk],
        out_specs=[blk, lblk],
        out_shape=[jax.ShapeDtypeStruct(q.shape, q.dtype),
                   jax.ShapeDtypeStruct((b, h, 8, s), jnp.float32)],
        interpret=interpret,
    )(q, k, v)
    return o, (q, k, v, o, lse)


def _bwd(sm_scale, interpret, res, do):
    pl = _pl()
    q, k, v, o, lse = res
    b, h, s, d = q.shape
    nq = _require_nq(s, d, q.dtype)
    bq = s // nq
    blk = pl.BlockSpec((1, 1, s, d), lambda i, j: (i, j, 0, 0))
    lblk = pl.BlockSpec((1, 1, 8, s), lambda i, j: (i, j, 0, 0))
    dq, dk, dv = pl.pallas_call(
        functools.partial(_bwd_kernel, sm_scale=sm_scale, bq=bq, nq=nq),
        grid=(b, h),
        in_specs=[blk, blk, blk, blk, lblk, blk],
        out_specs=[blk, blk, blk],
        out_shape=[jax.ShapeDtypeStruct(q.shape, q.dtype),
                   jax.ShapeDtypeStruct(k.shape, jnp.float32),
                   jax.ShapeDtypeStruct(v.shape, jnp.float32)],
        interpret=interpret,
    )(q, k, v, o, lse, do)
    return dq, dk.astype(k.dtype), dv.astype(v.dtype)


causal_attention.defvjp(_fwd, _bwd)


def attention_bhsd(q, k, v, causal=True, scale=None, interpret=False):
    assert causal, "causal_attention is causal-only"
    d = q.shape[-1]
    sm = scale if scale is not None else 1.0 / math.sqrt(d)
    return causal_attention(q, k, v, sm, interpret)


# ---------------------------------------------------------------------
# Hybrid (round 4): causal-skip strips FORWARD, monolithic BACKWARD.
#
# The strip forward does ~(nq+1)/(2*nq) of the full-matrix MXU+VPU work
# (62.5% at nq=4); the backward reuses simple_attention's monolithic
# kernel with residuals (q, k, v) ONLY — no lse/o saves, byte-identical
# backward liveness to the e2e-proven 'simple' path (the round-3
# full-causal kernel's extra residuals were the OOM suspect, NOTES).
# ---------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def causal_fwd_attention(q, k, v, sm_scale, interpret=False):
    """q/k/v: [B, H, S, D] -> [B, H, S, D]; causal only."""
    return _fwd_light(q, k, v, sm_scale, interpret)[0]


def hybrid_supported(q_shape, dtype):
    """Feasibility = strip FORWARD fits AND the monolithic BACKWARD
    fits (simple_attention's full-S^2 budget): gating on the forward
    alone would accept long-S shapes whose backward blows VMEM."""
    from paddle_tpu.ops.pallas import simple_attention as sak
    return supported(q_shape, dtype) and sak.supported(q_shape, dtype)


def _fwd_light(q, k, v, sm_scale, interpret):
    o, (q_, k_, v_, _o, _lse) = _fwd(q, k, v, sm_scale, interpret)
    return o, (q_, k_, v_)


def _bwd_light(sm_scale, interpret, res, do):
    from paddle_tpu.ops.pallas import simple_attention as sak
    return sak._bwd(sm_scale, True, interpret, res, do)


causal_fwd_attention.defvjp(_fwd_light, _bwd_light)


def attention_bhsd_hybrid(q, k, v, causal=True, scale=None,
                          interpret=False):
    assert causal, "causal_fwd_attention is causal-only"
    if not hybrid_supported(q.shape, q.dtype):
        raise ValueError(
            f"hybrid attention unsupported for shape {q.shape} "
            f"{q.dtype}: the monolithic backward must also fit VMEM "
            "(check hybrid_supported() before calling)")
    d = q.shape[-1]
    sm = scale if scale is not None else 1.0 / math.sqrt(d)
    return causal_fwd_attention(q, k, v, sm, interpret)
