"""Ring attention over a mesh axis (long-context sequence parallelism).

Reference gap being filled: the reference snapshot has NO ring/Ulysses
attention (SURVEY §2.7 SP row — sep-axis splitting only); this is the
idiomatic TPU upgrade: K/V blocks rotate around the ICI ring via ppermute
while each device keeps its Q shard, with flash-style streaming-softmax
accumulation so memory stays O(S_local).

Use inside shard_map with sequence sharded over `axis_name`:
    out = ring_attention(q, k, v, axis_name='sp', causal=True)
q/k/v: [B, S_local, H, D]; out same shape.
"""
from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax


def _block_attend(q, k, v, scale, mask):
    """One q-block x kv-block pass. Returns (scores_max, exp_sums, out_part)
    in f32 for stable accumulation. q:[B,Sq,H,D] k/v:[B,Sk,H,D]
    mask: [Sq, Sk] bool or None (True = attend)."""
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                        preferred_element_type=jnp.float32) * scale
    if mask is not None:
        logits = jnp.where(mask[None, None], logits, -1e30)
    m = jnp.max(logits, axis=-1)                      # [B,H,Sq]
    p = jnp.exp(logits - m[..., None])
    if mask is not None:
        # rows fully masked: avoid exp(-1e30 - -1e30)=1 garbage
        any_valid = jnp.any(mask, axis=-1)            # [Sq]
        p = jnp.where(any_valid[None, None, :, None], p, 0.0)
        m = jnp.where(any_valid[None, None, :], m, -jnp.inf)
    l = jnp.sum(p, axis=-1)                           # [B,H,Sq]
    o = jnp.einsum("bhqk,bkhd->bqhd", p.astype(v.dtype), v,
                   preferred_element_type=jnp.float32)
    return m, l, o


def ring_attention(q, k, v, axis_name: str, causal: bool = False,
                   scale: Optional[float] = None):
    """Exact attention over the full (ring-distributed) sequence."""
    d = q.shape[-1]
    s = scale if scale is not None else 1.0 / math.sqrt(d)
    n = lax.axis_size(axis_name)
    my_idx = lax.axis_index(axis_name)
    sq = q.shape[1]
    b, _, h, _ = q.shape

    # running flash-softmax state (f32); pvary marks the fresh buffers as
    # device-varying so the scan carry type matches its outputs
    acc = lax.pcast(jnp.zeros((b, sq, h, d), jnp.float32), (axis_name,), to='varying')
    m_run = lax.pcast(jnp.full((b, h, sq), -jnp.inf, jnp.float32),
                      (axis_name,), to='varying')
    l_run = lax.pcast(jnp.zeros((b, h, sq), jnp.float32), (axis_name,), to='varying')

    perm = [(i, (i + 1) % n) for i in range(n)]

    def _mask_for(src):
        if not causal:
            return None
        # global block order: q-block my_idx attends kv-block src iff
        # src <= my_idx; equal block → triangular mask
        iq = lax.broadcasted_iota(jnp.int32, (sq, sq), 0)
        ik = lax.broadcasted_iota(jnp.int32, (sq, sq), 1)
        tri = iq >= ik
        full = jnp.ones((sq, sq), bool)
        empty = jnp.zeros((sq, sq), bool)
        return jnp.where(src < my_idx, full,
                         jnp.where(src == my_idx, tri, empty))

    def _merge(acc, m_run, l_run, k_cur, v_cur, t):
        # k_cur originated on device (my_idx - t) mod n
        src = (my_idx - t) % n
        m_blk, l_blk, o_blk = _block_attend(q, k_cur, v_cur, s,
                                            _mask_for(src))
        m_new = jnp.maximum(m_run, m_blk)
        # guard -inf - -inf
        safe = lambda x, mn: jnp.where(  # noqa: E731
            jnp.isfinite(mn), jnp.exp(x - mn), 0.0)
        alpha = safe(m_run, m_new)                    # rescale old
        beta = safe(m_blk, m_new)                     # rescale new
        l_new = alpha * l_run + beta * l_blk
        acc = acc * jnp.moveaxis(alpha, 1, 2)[..., None] \
            + o_blk * jnp.moveaxis(beta, 1, 2)[..., None]
        return acc, m_new, l_new

    # local block first, then n-1 rotations: permute at the TOP of each
    # scan step so no discarded final rotation is issued
    acc, m_run, l_run = _merge(acc, m_run, l_run, k, v, 0)

    def step(carry, t):
        k_cur, v_cur, acc, m_run, l_run = carry
        k_cur = lax.ppermute(k_cur, axis_name, perm)
        v_cur = lax.ppermute(v_cur, axis_name, perm)
        acc, m_run, l_run = _merge(acc, m_run, l_run, k_cur, v_cur, t)
        return (k_cur, v_cur, acc, m_run, l_run), None

    if n > 1:
        (k_f, v_f, acc, m_run, l_run), _ = lax.scan(
            step, (k, v, acc, m_run, l_run), jnp.arange(1, n))
    denom = jnp.moveaxis(l_run, 1, 2)[..., None]
    out = acc / jnp.maximum(denom, 1e-30)
    return out.astype(q.dtype)


def ring_attention_sharded(q, k, v, mesh, axis_name="sp", causal=False):
    """Convenience: run ring_attention via shard_map on [B, S, H, D] arrays
    sharded along S over `axis_name` (other dims replicated)."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    from jax import shard_map
    spec = P(None, axis_name, None, None)
    fn = shard_map(
        functools.partial(ring_attention, axis_name=axis_name,
                          causal=causal),
        mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec)
    return fn(q, k, v)
