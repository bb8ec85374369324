"""q×kv double-blocked causal flash attention (Pallas TPU).

The long-context rung of the attention-kernel ladder (ROADMAP item 2).
The in-tree monolithic kernels keep whole [S,D] slices (and [S,S] or
[bq,S] score strips) resident in VMEM, which caps them at S<=2048; the
causal-skip negative result was measured in the VPU-bound short-S
regime.  This kernel targets the MAC-bound S>=2048 regime:

- fwd grid (b, h, tile): a slice's [bq, bkv] score tiles one at a
  time, a q block's kv blocks in turn; online-softmax state (m, l, acc)
  carried in f32 VMEM scratch across them — VMEM residency is
  O(bq*bkv + (bq+bkv)*D), independent of S, so the S-cap is lifted
  entirely.
- STATIC causal block-skipping: for q-block qi only kv-blocks
  0..last_ki(qi) = ((qi+1)*bq-1)//bkv do work, and only they are grid
  steps: `_tile_tables` lists the (q block, kv block) pairs with work,
  the tables are prefetched to SMEM, and index maps and kernels read a
  step's blocks from them — strictly-above-diagonal tiles cost no DMA
  and no step.  The diagonal mask itself is applied only on straddling
  tiles (a second kernel body under pl.when), so fully-below-diagonal
  tiles skip the VPU masking work too.
- fwd saves (o, lse); bwd is the flash-v2 two-kernel split: a dq kernel
  (same walk as fwd, dq accumulated in f32 VMEM scratch) and a dk/dv
  kernel (a kv block's q blocks in turn, from the first at or under the
  diagonal; dk/dv accumulated in f32 VMEM scratch and written once at
  the last q-block; its score tile is computed transposed, [bkv, bq]).

What the products run in (PR 35): every dot_general of the three kernels
takes its operands in the dtype of the arrays the kernel was given and
accumulates in float32.  bf16 (and float16) arrays multiply natively:
p and ds are rounded once, to that dtype, at their product; float32
arrays keep float32 operands, which Mosaic multiplies in one bf16 pass
at the default precision (measured: the upcasts the kernels used to
carry changed no result and no time).  Everything between the products
is float32: the scores, the mask, m, l, alpha, exp2, lse, delta, and the
acc / dq / dk / dv scratch.  No flag chooses: the dtype decides.

What the tile's VPU work is shaped by (measured in PR 35, PERF.md §6):
the row statistics live as [bq, 128] arrays with equal lanes and are
widened by register (`_lanes`), never as a [bq, 1] column broadcast over
the tile (that goes through the XLU, and was the forward's longest
part); exp(x * sm_scale) is exp2(x * sm_scale * log2(e)), one multiply;
sm_scale reaches dq and dk once, at their store; dq keeps lse and delta
as columns made once a q block, dk/dv reads both along lanes (delta
from one XLA pass over do and o).

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
import numpy as np
from jax import lax

NEG_INF = -1e30
LOG2E = math.log2(math.e)

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
    """(b, h) are parallel (megacore may split them); the walk over a
    slice's tiles is 'arbitrary' — scratch accumulators carry state
    across it."""
    return _pltpu().CompilerParams(
        dimension_semantics=("parallel", "parallel", "arbitrary"))


def _tile_tables(nq, nkv, bq, bkv, causal, kv_major=False):
    """(q block, kv block) of every tile that has work, as two int32
    tables in the order the grid walks them: a q block's kv blocks in
    turn (``kv_major``: a kv block's q blocks).  STATIC causal
    block-skipping: a tile strictly above the diagonal is no grid step
    at all (as a step that only skipped its body it still cost 0.4-0.5
    us, 47 % of the steps at equal blocks; PERF.md section 6, PR 35)."""
    if kv_major:
        tiles = [(qi, ki) for ki in range(nkv)
                 for qi in range((ki * bkv) // bq if causal else 0, nq)]
    else:
        tiles = [(qi, ki) for qi in range(nq)
                 for ki in range(min(nkv, ((qi + 1) * bq - 1) // bkv + 1)
                                 if causal else nkv)]
    qs, ks = zip(*tiles)
    return np.asarray(qs, np.int32), np.asarray(ks, np.int32)


def _grid_spec(b, h, tables, in_specs, out_specs, scratch_shapes):
    """Grid (b, h, tile) with the two tile tables prefetched to SMEM; an
    index map and a kernel read a step's blocks from them."""
    return _pltpu().PrefetchScalarGridSpec(
        num_scalar_prefetch=2, grid=(b, h, tables[0].shape[0]),
        in_specs=in_specs, out_specs=out_specs,
        scratch_shapes=scratch_shapes)


def _q_block(d, bq):
    pl = _pl()
    return pl.BlockSpec((1, 1, bq, d),
                        lambda ib, ih, t, qt, kt: (ib, ih, qt[t], 0))


def _kv_block(d, bkv):
    pl = _pl()
    return pl.BlockSpec((1, 1, bkv, d),
                        lambda ib, ih, t, qt, kt: (ib, ih, kt[t], 0))


def _row_block(bq):
    """lse / delta, [B, H, 8, S] float32: a q block's row statistics
    along lanes."""
    pl = _pl()
    return pl.BlockSpec((1, 1, 8, bq),
                        lambda ib, ih, t, qt, kt: (ib, ih, 0, qt[t]))


def _causal_mask(s, q0, k0, q_axis=0):
    """Causal mask for a score tile whose first query is q0 and first
    key k0; queries run along ``q_axis`` (1: the tile is transposed)."""
    iq = lax.broadcasted_iota(jnp.int32, s.shape, q_axis) + q0
    ik = lax.broadcasted_iota(jnp.int32, s.shape, 1 - q_axis) + k0
    return jnp.where(iq >= ik, s, NEG_INF)


def _on_tile(step, causal, qi, ki, bq, bkv):
    """Run ``step(masked)``: masked on the tiles that straddle the
    diagonal, unmasked (no VPU masking work) below it.  Two bodies under
    pl.when, not a lax.cond inside one: a cond in the middle of the
    chain made Mosaic store the score tile before it and load it
    after."""
    pl = _pl()
    if not causal:
        step(False)
        return
    straddles = qi * bq < ki * bkv + bkv - 1
    pl.when(straddles)(lambda: step(True))
    pl.when(jnp.logical_not(straddles))(lambda: step(False))


def _lanes(x, n):
    """A [r, 128] array whose lanes are equal, as [r, n]: by reusing its
    registers, where a [r, 1] column broadcast goes through the XLU."""
    if n <= 128:
        return x[:, :n]
    return _pltpu().repeat(x, n // 128, axis=1)


# ----------------------------------------------------------- forward

def _fwd_kernel(qi_tab, ki_tab, q_ref, k_ref, v_ref, o_ref, lse_ref,
                m_scr, l_scr, acc_scr, *, sm_scale, causal, bq, bkv, nkv):
    pl = _pl()
    qi = qi_tab[pl.program_id(2)]
    ki = ki_tab[pl.program_id(2)]
    last = ((qi + 1) * bq - 1) // bkv if causal else nkv - 1
    c = sm_scale * LOG2E           # exp(x * sm_scale) = exp2(x * c)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    def _step(masked):
        q = q_ref[0, 0]                                # [bq, D]
        k = k_ref[0, 0]                                # [bkv, D]
        v = v_ref[0, 0]                                # [bkv, D]
        s = lax.dot_general(                           # unscaled
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        if masked:
            s = _causal_mask(s, qi * bq, ki * bkv)
        m_prev = m_scr[...]                            # [bq, 128]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1)[:, None])
        alpha = jnp.exp2((m_prev - m_new) * c)         # [bq, 128]
        p = jnp.exp2((s - _lanes(m_new, bkv)) * c)     # [bq, bkv]
        l_scr[...] = alpha * l_scr[...] + jnp.sum(p, axis=-1)[:, None]
        m_scr[...] = m_new
        acc_scr[...] = acc_scr[...] * _lanes(alpha, acc_scr.shape[1]) \
            + lax.dot_general(
                p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)

    _on_tile(_step, causal, qi, ki, bq, bkv)

    @pl.when(ki == last)
    def _final():
        l = l_scr[:, :1]
        o_ref[0, 0] = (acc_scr[...] / l).astype(o_ref.dtype)
        lse = m_scr[:, :1] * sm_scale + jnp.log(l)     # [bq, 1]
        lse_ref[0, 0] = jnp.broadcast_to(
            lse.reshape(1, -1), lse_ref.shape[2:])


def _fwd(q, k, v, sm_scale, causal, interpret, bq, bkv):
    pl = _pl()
    pltpu = _pltpu()
    b, h, sq, d = q.shape
    nq, nkv = sq // bq, k.shape[2] // bkv
    tables = _tile_tables(nq, nkv, bq, bkv, causal)
    o, lse = pl.pallas_call(
        functools.partial(_fwd_kernel, sm_scale=sm_scale, causal=causal,
                          bq=bq, bkv=bkv, nkv=nkv),
        grid_spec=_grid_spec(
            b, h, tables,
            in_specs=[_q_block(d, bq), _kv_block(d, bkv),
                      _kv_block(d, bkv)],
            out_specs=[_q_block(d, bq), _row_block(bq)],
            scratch_shapes=[pltpu.VMEM((bq, 128), jnp.float32),
                            pltpu.VMEM((bq, 128), jnp.float32),
                            pltpu.VMEM((bq, d), jnp.float32)]),
        out_shape=[jax.ShapeDtypeStruct(q.shape, q.dtype),
                   jax.ShapeDtypeStruct((b, h, 8, sq), jnp.float32)],
        interpret=interpret,
        compiler_params=_compiler_params(),
    )(*tables, q, k, v)
    return o, lse


# ----------------------------------------------------------- backward

def _bwd_dq_kernel(qi_tab, ki_tab, q_ref, k_ref, v_ref, o_ref, lse_ref,
                   do_ref, dq_ref, lse_scr, delta_scr, dq_scr, *,
                   sm_scale, causal, bq, bkv, nkv):
    pl = _pl()
    qi = qi_tab[pl.program_id(2)]
    ki = ki_tab[pl.program_id(2)]
    last = ((qi + 1) * bq - 1) // bkv if causal else nkv - 1
    c = sm_scale * LOG2E

    @pl.when(ki == 0)
    def _init():
        # once a q block, not once a tile: the row statistics as
        # [bq, 128] columns with equal lanes (lse comes along lanes)
        lse = lse_ref[0, 0, 0, :]                      # [bq]
        lse_scr[...] = jnp.broadcast_to(
            lse[:, None] * LOG2E, lse_scr.shape)
        do = do_ref[0, 0].astype(jnp.float32)
        o = o_ref[0, 0].astype(jnp.float32)
        delta_scr[...] = jnp.broadcast_to(
            jnp.sum(do * o, axis=-1)[:, None], delta_scr.shape)
        dq_scr[...] = jnp.zeros_like(dq_scr)

    def _step(masked):
        q = q_ref[0, 0]
        k = k_ref[0, 0]
        v = v_ref[0, 0]
        do = do_ref[0, 0]
        s = lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        if masked:
            s = _causal_mask(s, qi * bq, ki * bkv)
        p = jnp.exp2(s * c - _lanes(lse_scr[...], bkv))   # [bq, bkv]
        dp = lax.dot_general(
            do, v, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        ds = p * (dp - _lanes(delta_scr[...], bkv))    # sm_scale: _final
        dq_scr[...] += lax.dot_general(
            ds.astype(k.dtype), k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    _on_tile(_step, causal, qi, ki, bq, bkv)

    @pl.when(ki == last)
    def _final():
        dq_ref[0, 0] = (dq_scr[...] * sm_scale).astype(dq_ref.dtype)


def _bwd_dkv_kernel(qi_tab, ki_tab, q_ref, k_ref, v_ref, lse_ref,
                    delta_ref, do_ref, dk_ref, dv_ref, dk_scr, dv_scr, *,
                    sm_scale, causal, bq, bkv, nq):
    """The tile is computed transposed, [bkv, bq]: every product then has
    its contraction on the left operand's lanes (a packed bf16 tile
    contracted over its rows goes through a transpose), and lse and delta
    lie along lanes as they arrive."""
    pl = _pl()
    qi = qi_tab[pl.program_id(2)]
    ki = ki_tab[pl.program_id(2)]
    first = (ki * bkv) // bq if causal else 0
    c = sm_scale * LOG2E

    @pl.when(qi == first)
    def _init():
        dk_scr[...] = jnp.zeros_like(dk_scr)
        dv_scr[...] = jnp.zeros_like(dv_scr)

    def _step(masked):
        q = q_ref[0, 0]                                # [bq, D]
        k = k_ref[0, 0]                                # [bkv, D]
        v = v_ref[0, 0]
        do = do_ref[0, 0]
        lse = lse_ref[0, 0, 0:1, :]                    # [1, bq]
        delta = delta_ref[0, 0, 0:1, :]                # [1, bq]
        st = lax.dot_general(
            k, q, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)        # [bkv, bq]
        if masked:
            st = _causal_mask(st, qi * bq, ki * bkv, q_axis=1)
        pt = jnp.exp2(st * c - lse * LOG2E)            # [bkv, bq]
        dv_scr[...] += lax.dot_general(
            pt.astype(do.dtype), do, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)        # [bkv, D]
        dpt = lax.dot_general(
            v, do, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)        # [bkv, bq]
        dst = pt * (dpt - delta)                       # sm_scale: _final
        dk_scr[...] += lax.dot_general(
            dst.astype(q.dtype), q, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)        # [bkv, D]

    _on_tile(_step, causal, qi, ki, bq, bkv)

    @pl.when(qi == nq - 1)
    def _final():
        dk_ref[0, 0] = dk_scr[...] * sm_scale
        dv_ref[0, 0] = dv_scr[...]


def _bwd_dq(q, k, v, o, lse, do, sm_scale, causal, interpret, bq, bkv):
    pl = _pl()
    pltpu = _pltpu()
    b, h, sq, d = q.shape
    nq, nkv = sq // bq, k.shape[2] // bkv
    tables = _tile_tables(nq, nkv, bq, bkv, causal)
    return pl.pallas_call(
        functools.partial(_bwd_dq_kernel, sm_scale=sm_scale,
                          causal=causal, bq=bq, bkv=bkv, nkv=nkv),
        grid_spec=_grid_spec(
            b, h, tables,
            in_specs=[_q_block(d, bq), _kv_block(d, bkv),
                      _kv_block(d, bkv), _q_block(d, bq), _row_block(bq),
                      _q_block(d, bq)],
            out_specs=_q_block(d, bq),
            scratch_shapes=[pltpu.VMEM((bq, 128), jnp.float32),
                            pltpu.VMEM((bq, 128), jnp.float32),
                            pltpu.VMEM((bq, d), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        interpret=interpret,
        compiler_params=_compiler_params(),
    )(*tables, q, k, v, o, lse, do)


def _bwd_dkv(q, k, v, o, lse, do, sm_scale, causal, interpret, bq, bkv):
    pl = _pl()
    pltpu = _pltpu()
    b, h, sq, d = q.shape
    nq, nkv = sq // bq, k.shape[2] // bkv
    tables = _tile_tables(nq, nkv, bq, bkv, causal, kv_major=True)
    # delta = sum(do * o) a query, along lanes beside lse: one XLA pass
    # over do and o where the kernel turned a column into a row a tile
    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32),
                    axis=-1)
    delta = jnp.broadcast_to(delta[:, :, None, :], lse.shape)
    dk, dv = pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, sm_scale=sm_scale,
                          causal=causal, bq=bq, bkv=bkv, nq=nq),
        grid_spec=_grid_spec(
            b, h, tables,
            in_specs=[_q_block(d, bq), _kv_block(d, bkv),
                      _kv_block(d, bkv), _row_block(bq), _row_block(bq),
                      _q_block(d, bq)],
            out_specs=[_kv_block(d, bkv), _kv_block(d, bkv)],
            scratch_shapes=[pltpu.VMEM((bkv, d), jnp.float32),
                            pltpu.VMEM((bkv, d), jnp.float32)]),
        out_shape=[jax.ShapeDtypeStruct(k.shape, jnp.float32),
                   jax.ShapeDtypeStruct(v.shape, jnp.float32)],
        interpret=interpret,
        compiler_params=_compiler_params(),
    )(*tables, q, k, v, lse, delta, do)
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
    """Convenience: [B,H,S,D] layout with defaulted scale.  Ticks
    ``attn.matmul_operands{kernel=blocked, dtype}`` once a traced call:
    the dtype the kernels' products take their operands in, which is
    the arrays' own."""
    from paddle_tpu.ops.pallas.flash_attention import _count
    d = q.shape[-1]
    sm = scale if scale is not None else 1.0 / math.sqrt(d)
    _count("attn.matmul_operands", kernel="blocked",
           dtype=jnp.dtype(q.dtype).name)
    return blocked_flash(q, k, v, sm, causal, interpret,
                         block_q, block_kv)
