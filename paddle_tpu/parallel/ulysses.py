"""Ulysses (DeepSpeed-style) all-to-all sequence parallelism.

Reference gap being filled: SURVEY §2.7 SP row — the snapshot has no
Ulysses/all-to-all attention; its long-context story is sep-axis
splitting. On TPU the all-to-all rides ICI, making Ulysses the natural
complement to ring attention:

  ring    — K/V rotate around the ring; O(S_local) memory; n-1 hops.
  ulysses — ONE all-to-all reshards [B, S/n, H, D] -> [B, S, H/n, D],
            attention runs *unsharded over sequence* per head-group,
            one all-to-all back. Cheaper when H >= n and S fits HBM;
            exact same math.

Use inside shard_map with sequence sharded over `axis_name`:
    out = ulysses_attention(q, k, v, axis_name='sp', causal=True)
q/k/v: [B, S_local, H, D]; out same shape. Requires H % axis_size == 0.
"""
from __future__ import annotations

import functools
import math
from typing import Optional

import jax.numpy as jnp
from jax import lax


def _full_attention(q, k, v, scale, causal):
    """Dense attention on full-sequence blocks. q/k/v: [B, S, Hl, D]."""
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                        preferred_element_type=jnp.float32) * scale
    if causal:
        sq, sk = q.shape[1], k.shape[1]
        iq = lax.broadcasted_iota(jnp.int32, (sq, sk), 0)
        ik = lax.broadcasted_iota(jnp.int32, (sq, sk), 1)
        logits = jnp.where((iq >= ik)[None, None], logits, -1e30)
    probs = _softmax(logits)
    return jnp.einsum("bhqk,bkhd->bqhd", probs.astype(v.dtype), v)


def _softmax(x):
    m = jnp.max(x, axis=-1, keepdims=True)
    e = jnp.exp(x - m)
    return e / jnp.sum(e, axis=-1, keepdims=True)


def ulysses_attention(q, k, v, axis_name: str, causal: bool = False,
                      scale: Optional[float] = None):
    """Exact attention over the full sequence via head<->seq all-to-all."""
    n = lax.axis_size(axis_name)
    b, s_local, h, d = q.shape
    if h % n != 0:
        raise ValueError(f"ulysses needs heads ({h}) % axis ({n}) == 0")
    sc = scale if scale is not None else 1.0 / math.sqrt(d)
    if n == 1:
        return _full_attention(q, k, v, sc, causal).astype(q.dtype)
    # reshard: gather sequence, scatter heads  [B,S/n,H,D] -> [B,S,H/n,D]
    a2a = functools.partial(lax.all_to_all, axis_name=axis_name,
                            split_axis=2, concat_axis=1, tiled=True)
    qh, kh, vh = a2a(q), a2a(k), a2a(v)
    out = _full_attention(qh, kh, vh, sc, causal)
    # reshard back: scatter sequence, gather heads
    out = lax.all_to_all(out.astype(q.dtype), axis_name=axis_name,
                         split_axis=1, concat_axis=2, tiled=True)
    return out


def ulysses_attention_sharded(q, k, v, mesh, axis_name="sp",
                              causal=False):
    """Convenience: shard_map wrapper for [B, S, H, D] arrays sharded
    along S over `axis_name` (mirrors ring_attention_sharded)."""
    from jax import shard_map
    from jax.sharding import PartitionSpec as P
    spec = P(None, axis_name, None, None)
    fn = shard_map(
        functools.partial(ulysses_attention, axis_name=axis_name,
                          causal=causal),
        mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec)
    return fn(q, k, v)
