"""Flash attention for TPU (Pallas).

Reference being replaced: phi/kernels/gpu/flash_attn_kernel.cu:587 (CUDA
flash-attention v2 wrapper). TPU-native: the Pallas TPU flash kernel
shipped with JAX (jax.experimental.pallas.ops.tpu.flash_attention) —
blockwise streaming-softmax in VMEM with custom fwd+bwd kernels tuned for
the MXU. This module adapts it to the paddle layout [B, S, H, D] and
applies the shape gating (seq % block == 0, head_dim tile-friendly).
"""
from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp


def supported_shape(bshd, skv, dtype) -> bool:
    """Library-flash shape gate ([B,S,H,D] + kv length); the single
    home for this predicate (autotune.candidates uses it too)."""
    if dtype not in (jnp.float32, jnp.bfloat16):
        return False
    b, s, h, d = bshd
    return s % 128 == 0 and skv % 128 == 0 and d % 64 == 0


def _supported(q, k, v):
    return supported_shape(tuple(q.shape), k.shape[1], q.dtype)


def _gate_reason(q, k):
    """Why the library-flash shape gate rejected ([B,S,H,D] inputs) —
    the label on the attn.dispatch_fallback counter."""
    if q.shape[-1] % 64 != 0:
        return "head_dim"           # not a multiple of the lane width
    if q.shape[1] % 128 != 0 or k.shape[1] % 128 != 0:
        return "seq_len"
    return "dtype"


def _count(metric, **labels):
    """Trace-time dispatch counter (single-branch no-op when telemetry
    is off)."""
    from paddle_tpu import observability as obs
    if obs.enabled():
        obs.counter(metric, **labels).inc()


def flash_attention(q, k, v, causal: bool = False,
                    scale: Optional[float] = None):
    """q/k/v: [B, S, H, D] (paddle flash-attn layout) -> [B, S, H, D]."""
    from jax.experimental.pallas.ops.tpu.flash_attention import (
        BlockSizes, flash_attention as _fa)
    d = q.shape[-1]
    sm_scale = scale if scale is not None else 1.0 / math.sqrt(d)
    # kernel layout is [B, H, S, D]
    qt = jnp.swapaxes(q, 1, 2)
    kt = jnp.swapaxes(k, 1, 2)
    vt = jnp.swapaxes(v, 1, 2)
    s_q, s_k = qt.shape[2], kt.shape[2]
    # tuned on an earlier v5e (probes in git history before PR 30):
    # 512 blocks win over 1024 (VMEM pressure in the dkv/dq kernels);
    # head_dim >= 128 is what keeps the MXU full — the model zoo
    # defaults to 128-dim heads
    bq = min(512, s_q)
    bk = min(512, s_k)
    blk = BlockSizes(
        block_q=bq, block_k_major=bk, block_k=bk, block_b=1,
        block_q_major_dkv=bq, block_k_major_dkv=bk,
        block_k_dkv=bk, block_q_dkv=bq,
        block_k_major_dq=bk, block_k_dq=bk,
        block_q_dq=bq)
    out = _fa(qt, kt, vt, causal=causal, sm_scale=sm_scale,
              block_sizes=blk)
    return jnp.swapaxes(out, 1, 2)


def flash_attention_maybe(q, k, v, causal=False, scale=None):
    """Pallas kernel when on TPU with supported shapes, else None
    (None routes the caller to plain XLA attention — shapes the gates
    reject, e.g. a head dim that is not a multiple of the 64-lane
    width, FALL BACK rather than raise, and the fallback is counted on
    the ``attn.dispatch_fallback`` observability counter). An error
    inside an accepted kernel is an error: nothing here catches it.

    Static chain (ordered by timings from an earlier chip record, deleted
    in PR 21 — not measured on the current code; the autotune table, when
    warm, overrides it): monolithic simple kernel where the whole (b, h)
    slice fits VMEM (S<=1024), causal-skip strip kernel where the
    [S,S] scores no longer fit (S<=2048), q-block kernel for the
    non-causal middle tier, then the q×kv-blocked flash kernel for the
    MAC-bound long-S regime (S>=4096 — VMEM residency O(block^2), no
    S-cap), with the jax library flash kernel as the final tier.

    The blocked kernel's products take their operands in the dtype of
    q, k and v as they are handed over (bf16 arrays multiply natively,
    float32 arrays in float32 operands) and accumulate in float32; a
    traced call of it ticks ``attn.matmul_operands{kernel=blocked,
    dtype}`` beside ``attn.dispatch{kernel=blocked}``."""
    if jax.default_backend() != "tpu":
        return None
    if not _supported(q, k, v):
        _count("attn.dispatch_fallback", reason=_gate_reason(q, k))
        return None
    from paddle_tpu.ops.pallas import autotune
    from paddle_tpu.ops.pallas import blocked_flash as bfk
    from paddle_tpu.ops.pallas import causal_attention as cak
    from paddle_tpu.ops.pallas import simple_attention as sa
    from paddle_tpu.ops.pallas import simple_attention2 as sa2
    # measured winner (runtime autotune cache / first-call timing)
    # takes precedence over the static chain below
    tuned = autotune.decide(q, k, causal)
    if tuned is not None:
        _count("attn.dispatch", kernel=tuned)
        if tuned == "xla":
            return None
        return autotune.run(tuned, q, k, v, causal, scale)
    # Dispatch order (timings from an earlier chip record, deleted in
    # PR 21; not measured on the current code): at S<=1024 the full-S^2
    # monolithic kernel wins (VPU-bound; causal skipping does not
    # pay: 49.1k vs 50.6k tok/s e2e). Where the whole [S,S] score
    # matrix no longer fits (S=2048), the causal-skip strip kernel
    # beats the q-block kernel ~1.8x (4.33 vs 7.85 ms/layer
    # fwd+bwd) because attention MACs dominate at long S.
    bhsd = (q.shape[0], q.shape[2], q.shape[1], q.shape[3])
    if q.shape[1] == k.shape[1] and sa.supported(bhsd, q.dtype):
        qt = jnp.swapaxes(q, 1, 2)
        kt = jnp.swapaxes(k, 1, 2)
        vt = jnp.swapaxes(v, 1, 2)
        _count("attn.dispatch", kernel="simple")
        out = sa.attention_bhsd(qt, kt, vt, causal=causal,
                                scale=scale)
        return jnp.swapaxes(out, 1, 2)
    if causal and q.shape[1] == k.shape[1] \
            and cak.supported(bhsd, q.dtype):
        qt = jnp.swapaxes(q, 1, 2)
        kt = jnp.swapaxes(k, 1, 2)
        vt = jnp.swapaxes(v, 1, 2)
        _count("attn.dispatch", kernel="causal_skip")
        out = cak.attention_bhsd(qt, kt, vt, causal=True,
                                 scale=scale)
        return jnp.swapaxes(out, 1, 2)
    if q.shape[1] == k.shape[1] and sa2.supported(bhsd, q.dtype):
        # middle tier: q streams in blocks, k/v whole in VMEM
        # (3.30 vs 3.64 ms/layer vs library flash at S=2048 on an
        # earlier chip; the probe is in git history before PR 30)
        qt = jnp.swapaxes(q, 1, 2)
        kt = jnp.swapaxes(k, 1, 2)
        vt = jnp.swapaxes(v, 1, 2)
        _count("attn.dispatch", kernel="qblock")
        out = sa2.attention_bhsd(qt, kt, vt, causal=causal,
                                 scale=scale)
        return jnp.swapaxes(out, 1, 2)
    if bfk.supported(bhsd, k.shape[1], q.dtype, causal):
        # long-S tier: every monolithic gate above has rejected
        # (S>=4096 at D128) — q×kv-blocked online-softmax kernel
        # with static causal block-skipping
        qt = jnp.swapaxes(q, 1, 2)
        kt = jnp.swapaxes(k, 1, 2)
        vt = jnp.swapaxes(v, 1, 2)
        _count("attn.dispatch", kernel="blocked")
        out = bfk.attention_bhsd(qt, kt, vt, causal=causal,
                                 scale=scale)
        return jnp.swapaxes(out, 1, 2)
    _count("attn.dispatch", kernel="library_flash")
    return flash_attention(q, k, v, causal=causal, scale=scale)
