"""q×kv double-blocked causal flash attention (Pallas TPU).

The long-context rung of the attention-kernel ladder (ROADMAP item 2).
The in-tree monolithic kernels keep whole [S,D] slices (and [S,S] or
[bq,S] score strips) resident in VMEM, which caps them at S<=2048; the
causal-skip negative result was measured in the VPU-bound short-S
regime.  This kernel targets the MAC-bound S>=2048 regime:

- fwd grid (b, h, q-block, kv-block) with kv innermost: one [bq, bkv]
  score tile at a time, online-softmax state (m, l, acc) carried in
  f32 VMEM scratch across the kv dimension — VMEM residency is
  O(bq*bkv + (bq+bkv)*D), independent of S, so the S-cap is lifted
  entirely.
- STATIC causal block-skipping: for q-block qi only kv-blocks
  0..last_ki(qi) = ((qi+1)*bq-1)//bkv do work.  Skipped iterations are
  guarded by pl.when (no MXU/VPU work) AND their kv index map clamps to
  last_ki(qi), so the pipeline re-fetches the block already resident —
  strictly-above-diagonal kv blocks never issue a DMA.  The diagonal
  mask itself is applied only on straddling tiles (lax.cond), so
  fully-below-diagonal tiles skip the VPU masking work too.
- fwd saves (o, lse); bwd is the flash-v2 two-kernel split: a dq kernel
  (same grid/skip as fwd, dq accumulated in f32 VMEM scratch) and a
  dk/dv kernel (grid (b, h, kv-block, q-block), q innermost, skipping
  q-blocks strictly left of the diagonal, dk/dv accumulated in f32
  VMEM scratch and written once at the last q-block).

Block sizes (bq, bkv) are autotunable (ops/pallas/autotune.py measures
the `block_candidates` variants and persists the winner); the default
picks the largest of 512/256/128 dividing the sequence, so ragged
sequences that are multiples of 128 but not of the preferred block
still lower (e.g. S=640 -> 128).

interpret=True runs the same kernels through the Pallas interpreter so
CPU tier-1 tests exercise the identical code path
(tests/test_blocked_flash.py).

Reference being replaced: phi/kernels/gpu/flash_attn_kernel.cu:587
(the tiled flash-attention v2 path proper).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax

NEG_INF = -1e30

#: preferred block edges, largest first (MXU-friendly multiples of 128)
_BLOCKS = (512, 256, 128)


def _pl():
    from jax.experimental import pallas as pl
    return pl


def _pltpu():
    from jax.experimental.pallas import tpu as pltpu
    return pltpu


def _pick_block(n: int):
    for b in _BLOCKS:
        if n % b == 0:
            return b
    return None


def _blocks_for(sq: int, skv: int, block_q=None, block_kv=None):
    bq = block_q if block_q is not None else _pick_block(sq)
    bkv = block_kv if block_kv is not None else _pick_block(skv)
    if bq is None or bkv is None or sq % bq or skv % bkv:
        raise ValueError(
            f"blocked_flash: no block sizes for S={sq}, Skv={skv} "
            f"(got bq={block_q}, bkv={block_kv}; sequence lengths must "
            "be multiples of 128 and of any explicit block size)")
    return bq, bkv


def block_candidates(sq: int, skv: int):
    """(bq, bkv) variants worth measuring for this problem, preferred
    first — the autotuner times each as a separate candidate."""
    combos = [(512, 512), (256, 512), (512, 1024)]
    out = [(bq, bkv) for bq, bkv in combos
           if sq % bq == 0 and skv % bkv == 0]
    if not out:
        bq, bkv = _pick_block(sq), _pick_block(skv)
        if bq is not None and bkv is not None:
            out = [(bq, bkv)]
    return out


def supported(q_shape, skv, dtype, causal=True):
    """Shape gate ([B,H,S,D] + kv length).  No VMEM-derived S cap: the
    working set is O(block^2 + block*D) by construction."""
    b, h, s, d = q_shape
    if dtype not in (jnp.bfloat16, jnp.float16, jnp.float32):
        return False
    if d % 128 != 0 and d != 64:
        return False
    if s % 128 != 0 or skv % 128 != 0:
        return False
    if causal and s != skv:
        return False                # causal cross-attn: not this kernel
    return _pick_block(s) is not None and _pick_block(skv) is not None


def _compiler_params():
    """(b, h, q) are parallel (megacore may split them); kv / inner q
    are 'arbitrary' — scratch accumulators carry state across them."""
    return _pltpu().CompilerParams(
        dimension_semantics=("parallel", "parallel", "parallel",
                             "arbitrary"))


def _masked_tile(s, q0, k0, bq, bkv):
    """Causal mask for a tile whose global top-left is (q0, k0).  Only
    invoked (via lax.cond) when the tile straddles the diagonal."""
    iq = lax.broadcasted_iota(jnp.int32, (bq, bkv), 0) + q0
    ik = lax.broadcasted_iota(jnp.int32, (bq, bkv), 1) + k0
    return jnp.where(iq >= ik, s, NEG_INF)


def _maybe_mask(s, qi, ki, bq, bkv):
    q0 = qi * bq
    k0 = ki * bkv
    return lax.cond(q0 >= k0 + bkv - 1,          # tile fully allowed
                    lambda t: t,
                    lambda t: _masked_tile(t, q0, k0, bq, bkv), s)


# ----------------------------------------------------------- forward

def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, m_scr, l_scr,
                acc_scr, *, sm_scale, causal, bq, bkv, nkv):
    pl = _pl()
    qi = pl.program_id(2)
    ki = pl.program_id(3)
    last = ((qi + 1) * bq - 1) // bkv if causal else nkv - 1

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    @pl.when(ki <= last)
    def _step():
        q = q_ref[0, 0].astype(jnp.float32)            # [bq, D]
        k = k_ref[0, 0].astype(jnp.float32)            # [bkv, D]
        v = v_ref[0, 0]                                # [bkv, D] native
        s = lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * sm_scale
        if causal:
            s = _maybe_mask(s, qi, ki, bq, bkv)
        m_prev = m_scr[...]                            # [bq, 128]
        l_prev = l_scr[...]
        m_new = jnp.maximum(
            m_prev, jnp.broadcast_to(jnp.max(s, axis=-1)[:, None],
                                     m_prev.shape))
        alpha = jnp.exp(m_prev[:, :1] - m_new[:, :1])  # [bq, 1]
        p = jnp.exp(s - m_new[:, :1])                  # [bq, bkv]
        l_new = alpha * l_prev[:, :1] \
            + jnp.sum(p, axis=-1)[:, None]
        m_scr[...] = m_new
        l_scr[...] = jnp.broadcast_to(l_new, l_scr.shape)
        acc_scr[...] = acc_scr[...] * alpha + lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(ki == last)
    def _final():
        l = l_scr[:, :1]
        o_ref[0, 0] = (acc_scr[...] / l).astype(o_ref.dtype)
        lse = m_scr[:, :1] + jnp.log(l)                # [bq, 1]
        lse_ref[0, 0] = jnp.broadcast_to(
            lse.reshape(1, -1), lse_ref.shape[2:])


def _kv_index_map(causal, bq, bkv):
    if causal:
        # clamp skipped kv blocks to the last valid one: consecutive
        # identical indices -> the pipeline issues no new DMA
        return lambda ib, ih, qi, ki: (
            ib, ih, jnp.minimum(ki, ((qi + 1) * bq - 1) // bkv), 0)
    return lambda ib, ih, qi, ki: (ib, ih, ki, 0)


def _fwd(q, k, v, sm_scale, causal, interpret, bq, bkv):
    pl = _pl()
    pltpu = _pltpu()
    b, h, sq, d = q.shape
    skv = k.shape[2]
    nq, nkv = sq // bq, skv // bkv
    qspec = pl.BlockSpec((1, 1, bq, d),
                         lambda ib, ih, qi, ki: (ib, ih, qi, 0))
    kvspec = pl.BlockSpec((1, 1, bkv, d), _kv_index_map(causal, bq, bkv))
    lspec = pl.BlockSpec((1, 1, 8, bq),
                         lambda ib, ih, qi, ki: (ib, ih, 0, qi))
    o, lse = pl.pallas_call(
        functools.partial(_fwd_kernel, sm_scale=sm_scale, causal=causal,
                          bq=bq, bkv=bkv, nkv=nkv),
        grid=(b, h, nq, nkv),
        in_specs=[qspec, kvspec, kvspec],
        out_specs=[qspec, lspec],
        out_shape=[jax.ShapeDtypeStruct(q.shape, q.dtype),
                   jax.ShapeDtypeStruct((b, h, 8, sq), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((bq, 128), jnp.float32),
                        pltpu.VMEM((bq, 128), jnp.float32),
                        pltpu.VMEM((bq, d), jnp.float32)],
        interpret=interpret,
        compiler_params=_compiler_params(),
    )(q, k, v)
    return o, lse


# ----------------------------------------------------------- backward

def _bwd_dq_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, do_ref, dq_ref,
                   delta_scr, dq_scr, *, sm_scale, causal, bq, bkv, nkv):
    pl = _pl()
    qi = pl.program_id(2)
    ki = pl.program_id(3)
    last = ((qi + 1) * bq - 1) // bkv if causal else nkv - 1

    @pl.when(ki == 0)
    def _init():
        do = do_ref[0, 0].astype(jnp.float32)
        o = o_ref[0, 0].astype(jnp.float32)
        delta_scr[...] = jnp.broadcast_to(
            jnp.sum(do * o, axis=-1)[:, None], delta_scr.shape)
        dq_scr[...] = jnp.zeros_like(dq_scr)

    @pl.when(ki <= last)
    def _step():
        q = q_ref[0, 0].astype(jnp.float32)
        k = k_ref[0, 0].astype(jnp.float32)
        v = v_ref[0, 0].astype(jnp.float32)
        do = do_ref[0, 0].astype(jnp.float32)
        lse = lse_ref[0, 0, 0, :]                      # [bq]
        s = lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * sm_scale
        if causal:
            s = _maybe_mask(s, qi, ki, bq, bkv)
        p = jnp.exp(s - lse[:, None])                  # [bq, bkv]
        dp = lax.dot_general(
            do, v, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        ds = p * (dp - delta_scr[:, :1]) * sm_scale
        dq_scr[...] += lax.dot_general(
            ds, k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(ki == last)
    def _final():
        dq_ref[0, 0] = dq_scr[...].astype(dq_ref.dtype)


def _bwd_dkv_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, do_ref,
                    dk_ref, dv_ref, dk_scr, dv_scr, *, sm_scale, causal,
                    bq, bkv, nq):
    pl = _pl()
    ki = pl.program_id(2)
    qi = pl.program_id(3)
    first = (ki * bkv) // bq if causal else 0

    @pl.when(qi == 0)
    def _init():
        dk_scr[...] = jnp.zeros_like(dk_scr)
        dv_scr[...] = jnp.zeros_like(dv_scr)

    @pl.when(qi >= first)
    def _step():
        q = q_ref[0, 0].astype(jnp.float32)            # [bq, D]
        k = k_ref[0, 0].astype(jnp.float32)            # [bkv, D]
        v = v_ref[0, 0].astype(jnp.float32)
        o = o_ref[0, 0].astype(jnp.float32)
        do = do_ref[0, 0].astype(jnp.float32)
        lse = lse_ref[0, 0, 0, :]                      # [bq]
        s = lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * sm_scale
        if causal:
            s = _maybe_mask(s, qi, ki, bq, bkv)
        p = jnp.exp(s - lse[:, None])                  # [bq, bkv]
        dv_scr[...] += lax.dot_general(
            p, do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)        # [bkv, D]
        dp = lax.dot_general(
            do, v, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)        # [bq, bkv]
        delta = jnp.sum(do * o, axis=-1)
        ds = p * (dp - delta[:, None]) * sm_scale
        dk_scr[...] += lax.dot_general(
            ds, q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)        # [bkv, D]

    @pl.when(qi == nq - 1)
    def _final():
        dk_ref[0, 0] = dk_scr[...]
        dv_ref[0, 0] = dv_scr[...]


def _bwd_dq(q, k, v, o, lse, do, sm_scale, causal, interpret, bq, bkv):
    pl = _pl()
    pltpu = _pltpu()
    b, h, sq, d = q.shape
    skv = k.shape[2]
    nq, nkv = sq // bq, skv // bkv
    qspec = pl.BlockSpec((1, 1, bq, d),
                         lambda ib, ih, qi, ki: (ib, ih, qi, 0))
    kvspec = pl.BlockSpec((1, 1, bkv, d), _kv_index_map(causal, bq, bkv))
    lspec = pl.BlockSpec((1, 1, 8, bq),
                         lambda ib, ih, qi, ki: (ib, ih, 0, qi))
    return pl.pallas_call(
        functools.partial(_bwd_dq_kernel, sm_scale=sm_scale,
                          causal=causal, bq=bq, bkv=bkv, nkv=nkv),
        grid=(b, h, nq, nkv),
        in_specs=[qspec, kvspec, kvspec, qspec, lspec, qspec],
        out_specs=qspec,
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        scratch_shapes=[pltpu.VMEM((bq, 128), jnp.float32),
                        pltpu.VMEM((bq, d), jnp.float32)],
        interpret=interpret,
        compiler_params=_compiler_params(),
    )(q, k, v, o, lse, do)


def _bwd_dkv(q, k, v, o, lse, do, sm_scale, causal, interpret, bq, bkv):
    pl = _pl()
    pltpu = _pltpu()
    b, h, sq, d = q.shape
    skv = k.shape[2]
    nq, nkv = sq // bq, skv // bkv
    if causal:
        # clamp skipped leading q blocks to the first valid one: no
        # DMA is issued for tiles strictly left of the diagonal
        def q_idx(ib, ih, ki, qi):
            return (ib, ih, jnp.maximum(qi, (ki * bkv) // bq), 0)
    else:
        def q_idx(ib, ih, ki, qi):
            return (ib, ih, qi, 0)
    qspec = pl.BlockSpec((1, 1, bq, d), q_idx)
    kvspec = pl.BlockSpec((1, 1, bkv, d),
                          lambda ib, ih, ki, qi: (ib, ih, ki, 0))
    lspec = pl.BlockSpec(
        (1, 1, 8, bq),
        (lambda ib, ih, ki, qi: (ib, ih, 0,
                                 jnp.maximum(qi, (ki * bkv) // bq)))
        if causal else (lambda ib, ih, ki, qi: (ib, ih, 0, qi)))
    dk, dv = pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, sm_scale=sm_scale,
                          causal=causal, bq=bq, bkv=bkv, nq=nq),
        grid=(b, h, nkv, nq),
        in_specs=[qspec, kvspec, kvspec, qspec, lspec, qspec],
        out_specs=[kvspec, kvspec],
        out_shape=[jax.ShapeDtypeStruct(k.shape, jnp.float32),
                   jax.ShapeDtypeStruct(v.shape, jnp.float32)],
        scratch_shapes=[pltpu.VMEM((bkv, d), jnp.float32),
                        pltpu.VMEM((bkv, d), jnp.float32)],
        interpret=interpret,
        compiler_params=_compiler_params(),
    )(q, k, v, o, lse, do)
    return dk, dv


# ------------------------------------------------------- public entry

@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def blocked_flash(q, k, v, sm_scale, causal=True, interpret=False,
                  block_q=None, block_kv=None):
    """q/k/v: [B, H, S, D] -> [B, H, S, D]."""
    return _fwd_rule(q, k, v, sm_scale, causal, interpret,
                     block_q, block_kv)[0]


def _fwd_rule(q, k, v, sm_scale, causal, interpret, block_q, block_kv):
    bq, bkv = _blocks_for(q.shape[2], k.shape[2], block_q, block_kv)
    o, lse = _fwd(q, k, v, sm_scale, causal, interpret, bq, bkv)
    return o, (q, k, v, o, lse)


def _bwd_rule(sm_scale, causal, interpret, block_q, block_kv, res, do):
    q, k, v, o, lse = res
    bq, bkv = _blocks_for(q.shape[2], k.shape[2], block_q, block_kv)
    dq = _bwd_dq(q, k, v, o, lse, do, sm_scale, causal, interpret,
                 bq, bkv)
    dk, dv = _bwd_dkv(q, k, v, o, lse, do, sm_scale, causal, interpret,
                      bq, bkv)
    return dq, dk.astype(k.dtype), dv.astype(v.dtype)


blocked_flash.defvjp(_fwd_rule, _bwd_rule)


def attention_bhsd(q, k, v, causal=True, scale=None, interpret=False,
                   block_q=None, block_kv=None):
    """Convenience: [B,H,S,D] layout with defaulted scale."""
    d = q.shape[-1]
    sm = scale if scale is not None else 1.0 / math.sqrt(d)
    return blocked_flash(q, k, v, sm, causal, interpret,
                         block_q, block_kv)
