"""Attention functionals.

Reference surface: paddle scaled_dot_product_attention +
nn/functional/flash_attention.py:195 (flash_attn CUDA kernel,
phi/kernels/gpu/flash_attn_kernel.cu:587).

TPU-native: a Pallas flash-attention kernel (paddle_tpu/ops/pallas/
flash_attention.py) when running on TPU with supported shapes, otherwise an
XLA attention einsum chain that the compiler fuses. Same [batch, seq, heads,
head_dim] layout as the reference API.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from paddle_tpu.core.dispatch import run_op
from paddle_tpu.core.tensor import Tensor


def _xla_attention(q, k, v, mask=None, causal=False, scale=None,
                   dropout_p=0.0, dropout_key=None):
    """q/k/v: [B, S, H, D] (paddle flash-attn layout)."""
    d = q.shape[-1]
    s = scale if scale is not None else 1.0 / math.sqrt(d)
    orig_dtype = q.dtype
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k) * s
    logits = logits.astype(jnp.float32)
    if causal:
        qlen, klen = logits.shape[-2], logits.shape[-1]
        idx_q = jnp.arange(qlen)[:, None] + (klen - qlen)
        idx_k = jnp.arange(klen)[None, :]
        cmask = idx_q >= idx_k
        logits = jnp.where(cmask, logits, -1e30)
    if mask is not None:
        if mask.dtype == jnp.bool_:
            logits = jnp.where(mask, logits, -1e30)
        else:
            logits = logits + mask.astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1).astype(orig_dtype)
    if dropout_p > 0.0 and dropout_key is not None:
        keep = jax.random.bernoulli(dropout_key, 1.0 - dropout_p,
                                    probs.shape)
        probs = jnp.where(keep, probs / (1.0 - dropout_p), 0.0)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v)


def _maybe_pallas_attention(q, k, v, causal, scale):
    """Use the Pallas flash kernel when on TPU and shapes are tile-friendly
    (None = gated off; a kernel error raises)."""
    if q.dtype not in (jnp.float32, jnp.bfloat16):
        return None
    if jax.default_backend() != "tpu":
        return None
    if q.shape[1] % 128 != 0 or k.shape[1] % 128 != 0:
        return None
    if q.shape[-1] not in (64, 128, 256):
        return None
    from paddle_tpu.ops.pallas.flash_attention import flash_attention
    return flash_attention(q, k, v, causal=causal, scale=scale)


def scaled_dot_product_attention(query, key, value, attn_mask=None,
                                 dropout_p=0.0, is_causal=False,
                                 training=True, name=None):
    """paddle.nn.functional.scaled_dot_product_attention; layout
    [batch, seq, num_heads, head_dim]."""
    from paddle_tpu.core import generator as gen_mod
    drop_key = gen_mod.next_key() if (dropout_p > 0.0 and training) else None
    p = dropout_p if training else 0.0

    def f(q, k, v, *maybe_mask):
        if not maybe_mask and p == 0.0:
            out = _maybe_pallas_attention(q, k, v, is_causal, None)
            if out is not None:
                return out
        return _xla_attention(q, k, v,
                              maybe_mask[0] if maybe_mask else None,
                              causal=is_causal, dropout_p=p,
                              dropout_key=drop_key)
    if attn_mask is not None:
        return run_op("scaled_dot_product_attention", f, query, key, value,
                      attn_mask)
    return run_op("scaled_dot_product_attention", f, query, key, value)


def flash_attention(query, key, value, dropout=0.0, causal=False,
                    return_softmax=False, fixed_seed_offset=None,
                    rng_name="", training=True, name=None):
    """paddle flash_attention API (nn/functional/flash_attention.py:195).
    Returns (out, softmax) tuple like the reference."""
    out = scaled_dot_product_attention(query, key, value, None, dropout,
                                       causal, training)
    return out, None


def flash_attn_unpadded(query, key, value, cu_seqlens_q, cu_seqlens_k,
                        max_seqlen_q, max_seqlen_k, scale=None, dropout=0.0,
                        causal=False, return_softmax=False, training=True,
                        name=None):
    """Varlen flash-attention: emulated by segment-masked attention over the
    packed sequence (TPU prefers packed+masked over ragged)."""
    def f(q, k, v, cu_q, cu_k):
        # q: [total_q, H, D] packed; build segment ids from cu_seqlens
        total_q = q.shape[0]
        pos = jnp.arange(total_q)
        seg_q = jnp.searchsorted(cu_q, pos, side="right") - 1
        total_k = k.shape[0]
        pos_k = jnp.arange(total_k)
        seg_k = jnp.searchsorted(cu_k, pos_k, side="right") - 1
        d = q.shape[-1]
        s = scale if scale is not None else 1.0 / math.sqrt(d)
        logits = jnp.einsum("qhd,khd->hqk", q, k) * s
        mask = seg_q[:, None] == seg_k[None, :]
        if causal:
            off_q = pos - jnp.take(cu_q, seg_q)
            off_k = pos_k - jnp.take(cu_k, seg_k)
            mask = mask & (off_q[:, None] >= off_k[None, :])
        logits = jnp.where(mask[None], logits.astype(jnp.float32), -1e30)
        probs = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
        return jnp.einsum("hqk,khd->qhd", probs, v)
    out = run_op("flash_attn_unpadded", f, query, key, value, cu_seqlens_q,
                 cu_seqlens_k)
    return out, None


def sdp_kernel(*args, **kwargs):  # torch-style context shim
    import contextlib
    return contextlib.nullcontext()
