"""TPU-native decode/serving path: static KV cache + one compiled step.

Reference being reproduced:
  * masked_multihead_attention decode kernel
    (/root/reference/python/paddle/incubate/nn/functional/masked_multihead_attention.py)
  * block_multihead_attention paged-KV serving attention
    (/root/reference/python/paddle/incubate/nn/functional/block_multihead_attention.py)
  * the serving role of AnalysisPredictor
    (/root/reference/paddle/fluid/inference/api/analysis_predictor.h:105)

TPU-native design. GPU serving pages the KV cache because CUDA kernels can
chase block tables; on TPU every program is compiled with static shapes, so
the idiomatic equivalent is a FIXED-CAPACITY dense cache ``[B, C, Hkv, D]``
plus a per-sequence length counter:

  * the cache is updated in place: ``lax.dynamic_update_slice`` (XLA
    aliases the donated buffer) or, for a batch of slots on a TPU, one
    Pallas program of copies a layer (``_write_kv``) — either way a true
    in-place write in HBM;
  * attention masks columns ``>= length``, so capacity padding never leaks;
  * ONE jitted decode step (embed -> attention against the cache prefix ->
    sample) is reused for every generated token — zero recompiles after
    warmup;
  * prefill runs as a second static program per bucketed prompt length.

`DecodeSession` packages this: it traces the model's cached forward into
pure jax functions (weights passed as inputs, cache donated), and exposes
``generate``.
"""
from __future__ import annotations

import collections
import functools
import math
import time
import weakref
from typing import List, Optional, Sequence

import numpy as np
import jax
import jax.numpy as jnp
from jax import lax

from paddle_tpu.core.tensor import Tensor
from paddle_tpu.core.dispatch import run_op
from paddle_tpu.observability import metrics as _met
from paddle_tpu.observability import server as _obs_server
from paddle_tpu import _chaos
from paddle_tpu.profiler import RecordEvent
from paddle_tpu.inference import admission as _adm
from paddle_tpu.inference.admission import (AdmissionRejected,  # noqa: F401
                                            RequestResult, RequestState,
                                            ServingStepError)

# What a slot of the batched decode step is doing (_masked_step)
_LANE_EMPTY, _LANE_STEPPING, _LANE_PAUSED = 0, 1, 2


# A layer's cache entry is one of three kinds (keys and values, latent
# rows, a recurrent state), and a session asks each the same things, on
# raw arrays inside its traced programs:
#
#   slot(i), cleared()       a fresh request's one-lane slice of slot i
#   put_slot(part, i, n)     that slice written back after a prefill of n
#   prefilling(lens) ..      around a prefill padded past lane b's lens[b]
#     .. prefilled(lens)       positions: only those count
#   stepping(lane) ..        around one decode step of which only the
#     .. stepped(old, active)  stepping lanes' one position counts
#
# ``kind`` names it for the gauges; ``rewindable``: a pass can be taken
# back by putting the length back (generation by diffusion over blocks).

class _ByPosition:
    """The rules of an entry that keeps one row a position: every leaf but
    the last is a [B, C, ...] buffer of rows, the last is ``length`` [B]
    int32, the number of valid positions per sequence. A position that
    does not count is a dead row: it is written past the length the entry
    is left at, and the next position that counts overwrites it."""
    __slots__ = ()
    rewindable = True

    def slot(self, i):
        return type(self)(*(lax.dynamic_slice_in_dim(a, i, 1, 0)
                            for a in self))

    def cleared(self):
        # fresh slot: the valid region restarts at 0
        return self._replace(length=jnp.zeros_like(self.length))

    def put_slot(self, part, i, n):
        return type(self)(
            *(lax.dynamic_update_slice_in_dim(a, p, i, 0)
              for a, p in zip(self[:-1], part[:-1])),
            lax.dynamic_update_index_in_dim(self.length, n, i, 0))

    def prefilling(self, lens):
        return self

    def prefilled(self, lens):
        # the prefill wrote the full padded block: the lengths are the
        # true prompt lengths (decode steps overwrite the padding's rows)
        return self._replace(length=lens)

    def stepping(self, lane):
        # an empty lane is shown at length 0, so attention reads one
        # block of it and not the retired request's whole text; the
        # write lands at position 0 of a slot that the next admit's
        # prefill overwrites from 0. A paused lane is shown as it is:
        # the row the step writes at its length is dead
        return self._replace(
            length=jnp.where(lane == _LANE_EMPTY, 0, self.length))

    def stepped(self, old, active):
        return self._replace(
            length=jnp.where(active, self.length, old.length))


class StaticCache(_ByPosition, collections.namedtuple(
        "StaticCache", ["k", "v", "length"])):
    """Per-layer fixed-capacity KV cache. k/v: [B, C, num_kv_heads,
    head_dim]; length: [B] int32."""
    __slots__ = ()
    kind = "kv"


class LatentCache(_ByPosition, collections.namedtuple(
        "LatentCache", ["c", "k_rope", "length", "take"],
        defaults=(None,))):
    """Per-layer cache of latent attention (MLA): no keys or values of
    heads, only what they are expanded from. c: [B, C, kv_lora_rank], the
    normed latent of each position; k_rope: [B, C, qk_rope_head_dim], its
    rotated key, one for all heads; length: [B] int32. Written through
    ``latent_write`` and read by the model's own attention (on a TPU its
    own kernel, ``decode_attention.latent_decode_attention``), never
    through ``_cache_attention``.
    ``take`` ([B] int32; None: all), as ``RecurrentCache``'s: around a
    prefill, the positions of the call that count in each lane, so that
    the model need not compute far past the prompt; of one call, not a
    leaf the session keeps."""
    __slots__ = ()
    kind = "latent"

    def slot(self, i):
        return LatentCache(*(lax.dynamic_slice_in_dim(a, i, 1, 0)
                             for a in self[:3]))

    def put_slot(self, part, i, n):
        return LatentCache(
            lax.dynamic_update_slice_in_dim(self.c, part.c, i, 0),
            lax.dynamic_update_slice_in_dim(self.k_rope, part.k_rope, i, 0),
            lax.dynamic_update_index_in_dim(self.length, n, i, 0))

    def prefilling(self, lens):
        return self._replace(take=jnp.broadcast_to(
            jnp.asarray(lens, jnp.int32), self.length.shape))

    def prefilled(self, lens):
        return self._replace(length=lens, take=None)


class RecurrentCache(collections.namedtuple(
        "RecurrentCache", ["conv", "ssm", "length", "take"],
        defaults=(None,))):
    """Per-layer state of a recurrent (state-space) mixer, whatever the
    length. conv: [K - 1, B, I], the last inputs of the layer's causal
    convolution; ssm: [B, N, I], the scan state; float32, the channels
    along the last dim. length: [B] int32, positions consumed. A state has
    no dead row, so a position that does not count must not reach it:
    ``take`` ([B] int32; None: all) tells the model how many of a call's
    positions count in each lane, the model holds the others out of its
    update, and nothing is selected or copied afterwards. ``take`` is of
    one call, not a leaf the session keeps."""
    __slots__ = ()
    kind, rewindable = "recurrent", False

    def slot(self, i):
        return RecurrentCache(lax.dynamic_slice_in_dim(self.conv, i, 1, 1),
                              lax.dynamic_slice_in_dim(self.ssm, i, 1, 0),
                              lax.dynamic_slice_in_dim(self.length, i, 1, 0))

    def cleared(self):
        # fresh slot: a zeroed state
        return RecurrentCache(*(jnp.zeros_like(a) for a in self[:3]))

    def put_slot(self, part, i, n):
        return RecurrentCache(
            lax.dynamic_update_slice_in_dim(self.conv, part.conv, i, 1),
            lax.dynamic_update_slice_in_dim(self.ssm, part.ssm, i, 0),
            lax.dynamic_update_index_in_dim(self.length, n, i, 0))

    def prefilling(self, lens):
        return self._replace(take=jnp.broadcast_to(
            jnp.asarray(lens, jnp.int32), self.length.shape))

    def prefilled(self, lens):
        return self._replace(take=None)

    def stepping(self, lane):
        return self._replace(
            take=(lane == _LANE_STEPPING).astype(jnp.int32))

    def stepped(self, old, active):
        return self._replace(take=None)


def init_static_cache(batch_size, capacity, num_kv_heads, head_dim,
                      dtype="float32"):
    """Allocate one layer's fixed-capacity KV cache."""
    _chaos.hit("serving.cache_alloc", batch=batch_size,
               capacity=capacity)
    from paddle_tpu.ops.creation import zeros
    k = zeros([batch_size, capacity, num_kv_heads, head_dim], dtype=dtype)
    v = zeros([batch_size, capacity, num_kv_heads, head_dim], dtype=dtype)
    length = zeros([batch_size], dtype="int32")
    return StaticCache(k, v, length)


def init_latent_cache(batch_size, capacity, kv_lora_rank, rope_dim,
                      dtype="bfloat16"):
    """Allocate one latent-attention layer's fixed-capacity cache."""
    _chaos.hit("serving.cache_alloc", batch=batch_size,
               capacity=capacity)
    from paddle_tpu.ops.creation import zeros
    return LatentCache(
        zeros([batch_size, capacity, kv_lora_rank], dtype=dtype),
        zeros([batch_size, capacity, rope_dim], dtype=dtype),
        zeros([batch_size], dtype="int32"))


def init_recurrent_cache(batch_size, channels, states, window):
    """Allocate one layer's recurrent state: zeros, float32."""
    _chaos.hit("serving.cache_alloc", batch=batch_size, capacity=0)
    from paddle_tpu.ops.creation import zeros
    return RecurrentCache(
        zeros([window, batch_size, channels], dtype="float32"),
        zeros([batch_size, states, channels], dtype="float32"),
        zeros([batch_size], dtype="int32"))


def _entries(caches):
    """A model's cache entries (of Tensors) as entries of raw arrays."""
    return [type(c)(*(None if t is None else t._data for t in c))
            for c in caches]


@functools.partial(jax.jit, static_argnames="interpret")
@jax.named_scope("write_kv")            # a profile names the program after it
def _row_dma(kbuf, vbuf, kn, vn, lens, interpret):
    """The write as a program of its own, traced once a shape and not once
    a layer, as ``_decode_kernel`` is."""
    from paddle_tpu.ops.pallas.cache_write import write_rows
    return write_rows(kbuf, vbuf, kn, vn, lens, interpret=interpret)


@jax.named_scope("write_kv")
def _write_kv(kbuf, vbuf, kn, vn, lens):
    """Write kn/vn [B, s, ...] into kbuf/vbuf [B, C, ...] (keys and values
    [Hkv, D] a row, or a latent entry's rows) at per-seq offsets
    ``clip(lens, 0, C - s)``. One algorithm, and the shape says
    which form is the cheap one: XLA expands a ``dynamic_update_slice``
    batched over its start into a loop over the slots, so on a TPU a batch
    of slots goes through one Pallas program of 2 * B copies in place; one
    slot (every prefill into a session's slot) is one in-place operation
    already, and every other backend keeps the update. So does a buffer of
    rows with no dims of their own ([B, C, W]: a latent entry's): its
    positions lie in the two dims a TPU tiles, and a copy cannot start at a
    row inside a tile (Mosaic refuses the slice)."""
    row_dma = kbuf.shape[0] > 1 and kbuf.ndim > 3 and _kernel_backend()
    if _met._ENABLED:
        _met.REGISTRY.counter(
            "cache.write_dispatch",
            kernel="row_dma" if row_dma else "update_slice").inc()
    if row_dma:
        return _row_dma(kbuf, vbuf, kn, vn, lens,
                        interpret=jax.default_backend() != "tpu")

    def put(buf, new):
        return jax.vmap(
            lambda b, n, l: lax.dynamic_update_slice(
                b, n, (l,) + (0,) * (b.ndim - 1))
        )(buf, new, lens)
    return put(kbuf, kn), put(vbuf, vn)


@jax.named_scope("write_kv")
def latent_write(cbuf, krbuf, c, k_rope, lens):
    """A latent entry's write, for a model's layer to call inside its own
    op: c [B, s, R], k_rope [B, s, r] into the entry's buffers at offsets
    ``clip(lens, 0, C - s)``, cast to the buffers' dtype. Returns (cbuf',
    krbuf'). The update form on every backend (``_write_kv``'s reason: a
    [B, C, W] buffer's positions lie in the two dims a TPU tiles). The rope
    key goes through its [B, r, C] view, where the latent kernel reads it:
    a TPU keeps a [B, C, 64] buffer with the positions in lanes (a 64-wide
    minor dim would pad to 128), and a write in rows would have XLA copy
    the whole buffer from one layout to the other at every step."""
    if _met._ENABLED:
        _met.REGISTRY.counter("cache.write_dispatch",
                              kernel="update_slice").inc()
    c, k_rope = c.astype(cbuf.dtype), k_rope.astype(krbuf.dtype)
    kr_t, kr_new = jnp.swapaxes(krbuf, 1, 2), jnp.swapaxes(k_rope, 1, 2)
    cbuf = jax.vmap(lambda b, n, l: lax.dynamic_update_slice(b, n, (l, 0)))(
        cbuf, c, lens)
    kr_t = jax.vmap(lambda b, n, l: lax.dynamic_update_slice(b, n, (0, l)))(
        kr_t, kr_new, lens)
    return cbuf, jnp.swapaxes(kr_t, 1, 2)


def _attend_einsum(q, kbuf, vbuf, lens, block=None):
    """q [B, s, H, D] against all C columns of the cache, masked to each
    row's valid prefix afterwards: every prefill, and every backend but
    the TPU. With ``block`` = B the prefix is the row's whole block of B
    positions (blocks aligned at absolute multiples of B), so the rows of
    one block see one another in both directions."""
    b, s, h, d = q.shape
    c = kbuf.shape[1]
    hkv = kbuf.shape[2]
    g = h // hkv
    qg = q.reshape(b, s, hkv, g, d).astype(jnp.float32)
    scale = 1.0 / math.sqrt(d)
    logits = jnp.einsum("bskgd,bckd->bkgsc", qg,
                        kbuf.astype(jnp.float32)) * scale
    col = jnp.arange(c)[None, None, None, None, :]
    row = jnp.arange(s)[None, None, None, :, None]
    at = lens[:, None, None, None, None] + row
    valid = col < (at + 1 if block is None else (at // block + 1) * block)
    logits = jnp.where(valid, logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bkgsc,bckd->bskgd", probs,
                     vbuf.astype(jnp.float32))
    return out.reshape(b, s, h, d)


def _kernel_backend():
    """Where the cache is written and read through the Pallas programs."""
    return jax.default_backend() == "tpu"


@functools.partial(jax.jit, static_argnames="interpret")
@jax.named_scope("cache_attention")     # a profile names the kernel after it
def _decode_kernel(q, kbuf, vbuf, lens, interpret):
    """The kernel as a program of its own: the layers of a model call it
    at one shape, so it is traced once a shape and not once a layer (in
    line it costs 60 ms a layer, in every process; XLA inlines the
    calls)."""
    from paddle_tpu.ops.pallas.decode_attention import decode_attention
    return decode_attention(q, kbuf, vbuf, lens, interpret=interpret)


def _attend_decode_kernel(q, kbuf, vbuf, lens):
    """The one-token step on a TPU: the length-aware kernel, which reads
    each slot's valid blocks and nothing else. None where the call is not
    its to take (s > 1, another backend) or its shape gate refuses; a
    refusal is counted, as flash_attention_maybe counts its own."""
    if q.shape[1] != 1 or not _kernel_backend():
        return None
    from paddle_tpu.ops.pallas.decode_attention import gate_reason
    reason = gate_reason(q.shape, kbuf.shape, kbuf.dtype)
    if reason is not None:
        if _met._ENABLED:
            _met.REGISTRY.counter("attn.dispatch_fallback",
                                  reason=reason).inc()
        return None
    if _met._ENABLED:
        _met.REGISTRY.counter("attn.dispatch",
                              kernel="decode_ragged").inc()
    return _decode_kernel(q, kbuf, vbuf, lens,
                          interpret=jax.default_backend() != "tpu")


@jax.named_scope("cache_attention")
def _cache_attention(q, kn, vn, kbuf, vbuf, lens, block=None):
    """Write-then-attend against a fixed-capacity cache.

    q: [B, s, H, D] new queries; kn/vn: [B, s, Hkv, D] new keys/values;
    kbuf/vbuf: [B, C, Hkv, D]; lens: [B] valid lengths BEFORE this call.
    Returns (out [B, s, H, D], kbuf', vbuf', lens + s). GQA is handled by
    grouping the query heads — the cache is never materialized at H heads.
    One algorithm, two regimes: float32 products and sums over each row's
    valid prefix, through the kernel where s == 1 on a TPU.

    ``block`` = B is the block form (generation by diffusion over blocks):
    row i sees column j iff j // B <= (lens + i) // B. One rule serves the
    prefill (lens 0) and a block pass (lens a multiple of B, s = B: every
    row sees all B new columns). A caller whose pass is not a commit keeps
    the buffers and drops the advanced length: the rows written past it
    are dead until the commit pass overwrites them.
    """
    kbuf, vbuf = _write_kv(kbuf, vbuf, kn.astype(kbuf.dtype),
                           vn.astype(vbuf.dtype), lens)
    out = None if block is not None else \
        _attend_decode_kernel(q, kbuf, vbuf, lens)
    if out is None:
        out = _attend_einsum(q, kbuf, vbuf, lens, block)
    return out.astype(q.dtype), kbuf, vbuf, lens + jnp.int32(q.shape[1])


def block_attention(q, kn, vn, block, cache=None):
    """The block form on raw arrays, for a model's layer to call inside
    its own op: q [B, s, H, D], kn/vn [B, s, Hkv, D] under the block mask
    of ``block`` positions. ``cache``: None (the s positions are the whole
    sequence, from 0) or (kbuf, vbuf, lens). Returns (out, cache')."""
    if cache is None:
        lens = jnp.zeros((q.shape[0],), jnp.int32)
        return _attend_einsum(q, kn, vn, lens, block).astype(q.dtype), None
    out, *cache = _cache_attention(q, kn, vn, *cache, block=block)
    return out, tuple(cache)


def check_capacity(length, s_new, capacity):
    """Eager misuse guard: writing past capacity would silently clamp
    (dynamic_update_slice semantics) and corrupt the newest cache slot.
    Lengths are concrete in eager mode — check them; under a trace
    (DecodeSession / user jit) lengths are tracers and this is a no-op,
    so the compiled serving path pays nothing. The eager check costs one
    tiny device sync per step; disable with
    FLAGS_kv_capacity_check=false when an eager loop is latency-bound
    and externally guarded."""
    arr = length._data if isinstance(length, Tensor) else length
    if isinstance(arr, jax.core.Tracer):
        return
    from paddle_tpu.core.flags import get_flag
    if not get_flag("FLAGS_kv_capacity_check"):
        return
    top = int(jax.device_get(jnp.max(arr))) + s_new
    if top > capacity:
        raise ValueError(
            f"KV cache overflow: writing {s_new} token(s) at length "
            f"{top - s_new} exceeds capacity {capacity}")


def cache_attention(q, k_new, v_new, cache: StaticCache, block=None):
    """Eager-op wrapper: attend q against (cache ++ new kv), updating the
    cache in place. Returns (out, new_cache). Not differentiable (serving
    path). ``block``: the block form of ``_cache_attention``."""
    check_capacity(cache.length, q.shape[1], cache.k.shape[1])
    fn = _cache_attention if block is None else \
        functools.partial(_cache_attention, block=int(block))
    out, k2, v2, l2 = run_op(
        "masked_cache_attention", fn, q, k_new, v_new,
        cache.k, cache.v, cache.length, n_outputs=4, differentiable=False)
    return out, StaticCache(k2, v2, l2)


def masked_multihead_attention_impl(x, cache_kv, seq_lens, num_heads,
                                    rotary_theta: Optional[float] = None):
    """Reference masked_multihead_attention semantics on the static cache.

    x: [B, 3*H*D] fused qkv for ONE decode step; cache_kv: [2, B, H, C, D]
    (the reference's cache layout); seq_lens: [B] int32 lengths before this
    step. Returns (out [B, H*D], new cache_kv).
    """
    check_capacity(seq_lens, 1, (cache_kv.shape[3] if hasattr(
        cache_kv, "shape") else cache_kv._data.shape[3]))

    def f(xa, ck, lens):
        b = xa.shape[0]
        h = num_heads
        d = xa.shape[1] // (3 * h)
        qkv = xa.reshape(b, 3, h, d)
        q, k, v = qkv[:, 0], qkv[:, 1], qkv[:, 2]     # [B, H, D]
        if rotary_theta is not None:
            pos = lens.astype(jnp.float32)            # [B]
            inv = 1.0 / (rotary_theta ** (
                jnp.arange(0, d, 2, dtype=jnp.float32) / d))
            freqs = pos[:, None] * inv[None, :]       # [B, D/2]
            cos = jnp.cos(freqs)[:, None, :]
            sin = jnp.sin(freqs)[:, None, :]

            def rot(a):
                a1, a2 = a[..., 0::2], a[..., 1::2]
                o1 = a1 * cos - a2 * sin
                o2 = a2 * cos + a1 * sin
                return jnp.stack([o1, o2], -1).reshape(a.shape)
            q, k = rot(q), rot(k)
        # cache layout [2, B, H, C, D] -> our [B, C, H, D]
        kbuf = jnp.swapaxes(ck[0], 1, 2)
        vbuf = jnp.swapaxes(ck[1], 1, 2)
        out, kbuf, vbuf, _ = _cache_attention(
            q[:, None], k[:, None], v[:, None], kbuf, vbuf, lens)
        new_ck = jnp.stack([jnp.swapaxes(kbuf, 1, 2),
                            jnp.swapaxes(vbuf, 1, 2)])
        return out.reshape(b, h * d), new_ck
    return run_op("masked_multihead_attention", f, x, cache_kv, seq_lens,
                  n_outputs=2, differentiable=False)


@jax.named_scope("sample")
def _sample(logits, key, temperature, top_p, top_k=None):
    """On-device sampling: greedy / temperature / top-k / nucleus."""
    if temperature == 0.0:
        return jnp.argmax(logits, axis=-1).astype(jnp.int32), key
    if top_k is not None and top_k > 0:
        # clamp so over-large configs degrade to no-op filtering instead
        # of a shape error deep inside the compiled step
        kth = lax.top_k(logits,
                        int(min(top_k, logits.shape[-1])))[0][..., -1:]
        logits = jnp.where(logits >= kth, logits, -jnp.inf)
    probs = jax.nn.softmax(logits.astype(jnp.float32) / temperature, -1)
    if top_p is not None and top_p < 1.0:
        sorted_p = jnp.sort(probs, axis=-1)[..., ::-1]
        cum = jnp.cumsum(sorted_p, axis=-1)
        # smallest set whose mass exceeds top_p: keep p >= threshold
        k = jnp.sum(cum - sorted_p < top_p, axis=-1, keepdims=True)
        thresh = jnp.take_along_axis(sorted_p, k - 1, axis=-1)
        probs = jnp.where(probs >= thresh, probs, 0.0)
        probs = probs / jnp.sum(probs, -1, keepdims=True)
    key, sub = jax.random.split(key)
    nxt = jax.random.categorical(sub, jnp.log(jnp.maximum(probs, 1e-30)))
    return nxt.astype(jnp.int32), key


def _collect_model_state(model):
    """Dedup'd parameters + buffers (the jit.StaticFunction state
    discipline) — shared by DecodeSession and the continuous-batching
    session."""
    out, seen = [], set()
    for _, p in model.named_parameters():
        if id(p) not in seen:
            seen.add(id(p))
            out.append(p)
    for _, b in model.named_buffers():
        if id(b) not in seen:
            seen.add(id(b))
            out.append(b)
    return out


def _bind_and_run(model, state_tensors, state_arrays, ids_arr, entries,
                  with_expert_load=None):
    """Rebind traced state into the live model and run its cached
    forward (the jit.StaticFunction discipline, serving-only) over the
    cache ``entries`` (of raw arrays, as is what it returns).
    ``with_expert_load``: a lane mask; the model's
    ``forward_with_expert_load`` is run instead and its load vector
    returned third."""
    import paddle_tpu as paddle
    saved = [t._data for t in state_tensors]
    try:
        for t, a in zip(state_tensors, state_arrays):
            t._data = a
        caches = jax.tree_util.tree_map(lambda a: Tensor._wrap(a, True),
                                        list(entries))
        ids = Tensor._wrap(ids_arr, True)
        with paddle.no_grad():
            if with_expert_load is None:
                logits, caches = model.forward_with_cache(ids, caches)
            else:
                logits, caches, load = model.forward_with_expert_load(
                    ids, caches, with_expert_load)
        if with_expert_load is None:
            return logits._data, _entries(caches)
        return logits._data, _entries(caches), load
    finally:
        for t, s in zip(state_tensors, saved):
            t._data = s


def _default_buckets(max_length):
    b, out = 16, []
    while b < max_length:
        out.append(b)
        b *= 2
    out.append(max_length)
    return out


class _SessionLifecycle:
    """Shared close()/context-manager/finalizer protocol for serving
    sessions: one refcount on the PADDLE_TPU_METRICS_PORT scrape
    endpoint, taken in __init__ (session_started) and released exactly
    once here — the last session closing shuts the server down and
    frees the port."""

    def close(self):
        """Release session-held resources. Idempotent; also runs via
        the context-manager exit and the finalizer."""
        if getattr(self, "_closed", False):
            return
        self._closed = True
        if getattr(self, "_metrics_server", None) is not None:
            self._metrics_server = None
            _obs_server.session_finished()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()

    def __del__(self):
        try:
            self.close()
        except Exception:
            pass


class DecodeSession(_SessionLifecycle):
    """Compiled serving session over a causal-LM Layer.

    The model must implement ``init_cache(batch_size, max_length=C)`` ->
    list[StaticCache] and ``forward_with_cache(ids, caches)`` ->
    (logits, caches); `LlamaForCausalLM` / `GPTForCausalLM` do.

    Two executables total (plus one prefill per prompt bucket): cache
    buffers are donated to the decode step so generation runs at a single
    cache's HBM footprint with zero recompiles after warmup.
    """

    def __init__(self, model, max_length, prefill_buckets=None,
                 temperature=0.0, top_p=None, top_k=None,
                 eos_token_id=None, decode_block=None):
        model.eval()
        self._model = model
        self._max_length = int(max_length)
        self._buckets = sorted(prefill_buckets or
                               _default_buckets(self._max_length))
        self._temperature = float(temperature)
        self._top_p = top_p
        self._top_k = top_k
        self._eos = eos_token_id
        self._buckets = [min(b, self._max_length) for b in self._buckets]
        self._state = self._collect_state()
        # decode_block > 1 selects the SINGLE-PROGRAM multi-token loop:
        # one lax.while_loop program emits a [B, decode_block] token
        # block per dispatch, so decode throughput is independent of
        # host<->device round-trip latency (the per-token dispatch loop
        # pays one host round trip per token). The reference
        # gets the same effect by fusing the whole decode stack into
        # fused_multi_transformer's one-kernel-per-token loop.
        self._decode_block = int(decode_block) if decode_block else None
        # one jitted decode step; cache buffers donated (decode args are
        # (*state, token, key, *cache_leaves) -> caches start at n+2)
        n_state = len(self._state)
        self._decode_jit = jax.jit(
            self._decode_pure,
            donate_argnums=tuple(range(n_state + 2,
                                       n_state + 2 + self._n_cache_leaves)))
        # block program args: (*state, token, key, finished, m,
        # *cache_leaves) -> caches start at n+4
        self._decode_block_jit = jax.jit(
            self._decode_block_pure,
            donate_argnums=tuple(range(n_state + 4,
                                       n_state + 4 + self._n_cache_leaves)))
        self._prefill_jit = jax.jit(self._prefill_pure)
        # pull-based scrape endpoint (PADDLE_TPU_METRICS_PORT): hold
        # one ref for this session's lifetime; close() releases it
        self._metrics_server = _obs_server.session_started()
        self._closed = False

    # -- state plumbing (same discipline as jit.StaticFunction) ---------
    def _collect_state(self):
        return _collect_model_state(self._model)

    @property
    def _n_cache_leaves(self):
        if not hasattr(self, "_cache_leaves_n"):
            c = self._model.init_cache(1, max_length=8)
            self._cache_leaves_n = len(
                jax.tree_util.tree_leaves(_entries(c)))
        return self._cache_leaves_n

    def _run_model(self, state_arrays, ids_arr, cache_arrays, lens=None):
        """The model over the flat cache leaves; ``lens``: the call is a
        prefill padded past each sequence's ``lens`` positions."""
        entries = jax.tree_util.tree_unflatten(self._cache_treedef,
                                               list(cache_arrays))
        if lens is not None:
            entries = [e.prefilling(lens) for e in entries]
        logits, entries = _bind_and_run(self._model, self._state,
                                        state_arrays, ids_arr, entries)
        if lens is not None:
            entries = [e.prefilled(lens) for e in entries]
        return logits, jax.tree_util.tree_leaves(entries)

    def _prefill_pure(self, *flat):
        n = len(self._state)
        state, (ids, lens, key) = flat[:n], flat[n:n + 3]
        cache_arrays = flat[n + 3:]
        # the prompts are padded to the bucket: each cache entry is told
        # the true lengths, by its own rule
        logits, cache_out = self._run_model(state, ids, cache_arrays, lens)
        # last VALID position's logits, per sequence
        b = ids.shape[0]
        last = logits[jnp.arange(b), lens - 1]
        nxt, key = _sample(last, key, self._temperature, self._top_p,
                           self._top_k)
        return nxt, key, cache_out

    def _decode_pure(self, *flat):
        n = len(self._state)
        state, token, key = flat[:n], flat[n], flat[n + 1]
        cache_arrays = flat[n + 2:]
        logits, cache_out = self._run_model(state, token[:, None],
                                            cache_arrays)
        nxt, key = _sample(logits[:, -1], key, self._temperature,
                           self._top_p, self._top_k)
        return nxt, key, cache_out

    def _decode_block_pure(self, *flat):
        """Up to ``decode_block`` decode steps in ONE program: a
        lax.while_loop carrying (token, key, finished, out, caches) that
        exits early when every sequence has emitted eos — the early-exit
        check rides ON DEVICE instead of costing a host sync. ``m``
        (actual steps wanted) is a traced operand, so short final blocks
        reuse the same executable."""
        n = len(self._state)
        state = flat[:n]
        token, key, finished, m = flat[n:n + 4]
        cache_arrays = tuple(flat[n + 4:])
        blk = self._decode_block
        eos = self._eos
        fill = jnp.int32(eos if eos is not None else 0)
        out0 = jnp.full((token.shape[0], blk), fill)

        def cond(carry):
            i, _token, _key, fin, _out, _caches = carry
            live = i < m
            if eos is not None:
                live = live & ~jnp.all(fin)
            return live

        def body(carry):
            i, token, key, fin, out, caches = carry
            logits, cache_out = self._run_model(state, token[:, None],
                                                caches)
            nxt, key = _sample(logits[:, -1], key, self._temperature,
                               self._top_p, self._top_k)
            if eos is not None:
                nxt = jnp.where(fin, jnp.int32(eos), nxt)
                fin = fin | (nxt == eos)
            out = out.at[:, i].set(nxt)
            return (i + 1, nxt, key, fin, out, tuple(cache_out))

        carry = (jnp.int32(0), token, key, finished, out0, cache_arrays)
        _i, token, key, finished, out, cache_arrays = lax.while_loop(
            cond, body, carry)
        return out, token, key, finished, list(cache_arrays)

    # -- public API -----------------------------------------------------
    def generate(self, input_ids, max_new_tokens=16, seed=None):
        """Generate tokens; returns [B, prompt + n_generated] ids.

        seed=None (default) draws the sampling key from the framework's
        global generator — successive calls produce different samples,
        matching the legacy eager path; pass an int for reproducibility.
        Sequences that emit eos_token_id are pinned to eos for the rest
        of the batch (per-sequence finished state); the loop exits early
        once every sequence has finished (checked every 8 steps so the
        device pipeline is not serialized by per-token host syncs)."""
        t0 = time.perf_counter()
        ids = input_ids._data if isinstance(input_ids, Tensor) else \
            jnp.asarray(input_ids)
        ids = ids.astype(jnp.int32)
        b, s = ids.shape
        # every generated token except the last is written into the
        # cache, so occupancy reaches s + max_new_tokens - 1
        if s + max_new_tokens - 1 > self._max_length:
            raise ValueError(
                f"prompt ({s}) + {max_new_tokens} new tokens exceeds the "
                f"cache capacity max_length={self._max_length}")
        bucket = next((k for k in self._buckets if k >= s),
                      self._max_length)
        padded = jnp.pad(ids, ((0, 0), (0, bucket - s)))
        lens = jnp.full((b,), s, jnp.int32)
        caches = _entries(
            self._model.init_cache(b, max_length=self._max_length))
        self._cache_treedef = jax.tree_util.tree_structure(caches)
        cache_arrays = jax.tree_util.tree_leaves(caches)
        state = [t._data for t in self._state]
        if seed is None:
            from paddle_tpu.core import generator as gen_mod
            key = gen_mod.default_generator().next_key()
        else:
            key = jax.random.PRNGKey(seed)

        token, key, cache_arrays = self._prefill_jit(
            *state, padded, lens, key, *cache_arrays)
        finished = jnp.zeros((b,), bool) if self._eos is not None else None
        if finished is not None:
            finished = finished | (token == self._eos)

        if self._decode_block:
            gen = self._generate_blocks(state, token, key, finished,
                                        cache_arrays, b,
                                        max_new_tokens - 1)
            if _met._ENABLED:
                jax.block_until_ready(gen)
            self._record_generate(t0, b, s, int(gen.shape[1]))
            return Tensor._wrap(jnp.concatenate([ids, gen], axis=1),
                                True)

        outs = [token]
        for i in range(max_new_tokens - 1):
            token, key, cache_arrays = self._decode_jit(
                *state, token, key, *cache_arrays)
            if finished is not None:
                # pin finished sequences to eos; update finished state
                token = jnp.where(finished, jnp.int32(self._eos), token)
                finished = finished | (token == self._eos)
            outs.append(token)
            # early exit probed only every 8 steps: keeps dispatch async
            if finished is not None and (i % 8 == 7) and bool(
                    jax.device_get(jnp.all(finished))):
                break
        gen = jnp.stack(outs, axis=1)
        if _met._ENABLED:
            # close the timing window on completion, not dispatch —
            # async futures would report impossible tokens/s
            jax.block_until_ready(gen)
        self._record_generate(t0, b, s, len(outs))
        return Tensor._wrap(jnp.concatenate([ids, gen], axis=1), True)

    @staticmethod
    def _record_generate(t0, batch, prompt_len, n_new):
        if not _met._ENABLED:
            return
        dt = time.perf_counter() - t0
        r = _met.REGISTRY
        r.counter("serving.generate_calls").inc()
        r.counter("serving.prefill_tokens").inc(batch * prompt_len)
        r.counter("serving.decode_tokens").inc(batch * n_new)
        r.histogram("serving.generate_latency_s").observe(dt)

    def _generate_blocks(self, state, token, key, finished, cache_arrays,
                         b, m_total):
        """Drive the single-program block decoder: one dispatch per
        ``decode_block`` tokens (host RTT amortized by the block size);
        a finished batch stops between blocks and back-fills eos, which
        matches the per-step path's eos pinning token-for-token."""
        blk = self._decode_block
        if finished is None:
            finished = jnp.zeros((b,), bool)
        outs = [token[:, None]]
        done = 0
        while done < m_total:
            m = min(blk, m_total - done)
            toks, token, key, finished, cache_arrays = \
                self._decode_block_jit(*state, token, key, finished,
                                       jnp.int32(m), *cache_arrays)
            outs.append(toks[:, :m])
            done += m
            if self._eos is not None and done < m_total and bool(
                    jax.device_get(jnp.all(finished))):
                outs.append(jnp.full((b, m_total - done),
                                     jnp.int32(self._eos)))
                break
        return jnp.concatenate(outs, axis=1)

    def executable_counts(self):
        """(n_prefill_executables, n_decode_executables) — the decode
        count must stay 1 however many tokens are generated. In block
        mode the block program is THE decode executable (the per-step
        one goes unused), so the counts are summed."""
        return (self._prefill_jit._cache_size(),
                self._decode_jit._cache_size()
                + self._decode_block_jit._cache_size())



#: the parts of a session's wall time, from its first step()'s entry to its
#: last one's return (``_CycleAccount``)
_PARTS = ("caller", "no_work", "expire", "admit", "dispatch", "fetch_wait",
          "fetch_copy", "deliver", "other")
#: the parts whose seconds a cycle are also a histogram: those a reader of
#: the benchmark takes a median of. The counters carry every part's sum.
_CYCLE_HIST_PARTS = ("caller", "fetch_copy")


class _CycleAccount:
    """The session's wall clock, partitioned: every second from the first
    ``step()``'s entry to the last one's return is in exactly one of
    ``_PARTS``. ``mark(part, now)`` closes the running part at a clock read
    some span already made and opens the next, so the parts sum to the
    wall.

    ``caller`` is a ``step()``'s return to the next one's entry where the
    session held work (something running or queued), ``no_work`` the same
    gap where it held none (a gap is split at the first ``submit()`` after
    an empty return); ``other`` is what is left of a step beside its
    phases (the gauges, the health report, the recovery path).

    ``starving`` is set from the instant the fetch's wait returned (every
    program the session had enqueued is done) to the return of the next
    successful device call (``fed``): by the session's own books nothing
    of its can be running on the chip then, and the seconds marked
    meanwhile are also ``starved``, part by part. That is a LOWER bound
    on the idle the host causes: the launch latency after the call
    returns is not seen, nor an admit program that ends before the
    dispatch behind it is enqueued. Nothing starves while the session is
    ``idle`` (from a return with no work to the next ``submit()``: nobody
    waits, so ``no_work`` never starves), and ``fetch_wait`` cannot (the
    chip runs the block).

    A cycle runs from a ``step()``'s return to the next one's return: the
    caller's gap, then the step. ``seconds`` and ``starved`` hold the
    running cycle's and are emptied by ``end_cycle``. While metrics are off
    nothing calls ``mark`` and no clock is read."""

    __slots__ = ("part", "t", "starving", "idle", "decoded", "seconds",
                 "starved")

    def __init__(self):
        self.part = self.t = None
        self.starving = True        # nothing is enqueued yet
        self.idle = False
        self.decoded = False        # this cycle dispatched a decode block
        self.seconds = dict.fromkeys(_PARTS, 0.0)
        self.starved = dict.fromkeys(_PARTS, 0.0)

    def mark(self, part, now):
        if self.t is not None:
            self.seconds[self.part] += now - self.t
            if self.starving and not self.idle:
                self.starved[self.part] += now - self.t
        self.part, self.t = part, now

    def fed(self, now=None, decode=False):
        """A device call returned: the chip has work again. ``now`` splits
        the running part there (the dispatch's phase ends at that read
        already and passes none)."""
        if now is not None:
            self.mark(self.part, now)
        self.starving = False
        self.decoded |= decode

    def drained(self, now):
        """The fetch's wait returned: every program the session enqueued
        is done, and the copy of its tokens starts."""
        self.mark("fetch_copy", now)
        self.starving = True

    def submitted(self, now):
        """A request was queued: if the session held none, the rest of
        the gap is the caller's."""
        if self.part == "no_work":
            self.mark("caller", now)
            self.idle = False

    def end_cycle(self, gap):
        """At ``step()``'s return: names the gap that opens (no clock is
        read: the step's span closed at ``t``) and hands out the cycle's
        ``(seconds, starved, decoded)``."""
        out = self.seconds, self.starved, self.decoded
        self.part, self.idle = gap, gap == "no_work"
        self.decoded = False
        self.seconds = dict.fromkeys(_PARTS, 0.0)
        self.starved = dict.fromkeys(_PARTS, 0.0)
        return out

    def forget(self):
        """A step ran with metrics off: the open part has no end that was
        read, so the account starts again at the next timed entry, as a new
        session's does (what that step enqueued or fetched is not known)."""
        self.part = self.t = None
        self.idle = False
        self.starving = True


class _Phase:
    """One timed phase of the serving step: the span (a RecordEvent, so it
    shows in a running jax.profiler trace), its seconds into a histogram
    (if it has one) and its part of the session's ``_CycleAccount`` (if it
    has one: ``part`` runs from the phase's entry, ``other`` from its
    exit), all off the same two clock reads. While metrics are off it does
    nothing and reads no clock; ``t0`` is then None and ``seconds`` 0."""

    __slots__ = ("_span", "_hist", "_account", "_part", "t0", "seconds")

    def __init__(self, span, hist=None, account=None, part=None):
        self._span, self._hist = span, hist
        self._account, self._part = account, part
        self.t0, self.seconds = None, 0.0

    def __enter__(self):
        if _met._ENABLED:
            self._span.begin()
            self.t0 = time.perf_counter()
            if self._account is not None:
                self._account.mark(self._part, self.t0)
        return self

    def __exit__(self, *exc):
        if self.t0 is not None:
            now = time.perf_counter()
            self.seconds = now - self.t0
            self._span.end()
            if self._hist is not None:
                self._hist.observe(self.seconds)
            if self._account is not None:
                self._account.mark("other", now)
        return False


class _Request:
    __slots__ = ("rid", "ids", "plen", "budget", "tokens", "slot",
                 "cached", "t_submit", "t_admit", "t_first", "t_done", "state",
                 "priority", "deadline", "ttft_deadline", "error",
                 "commit_steps")

    def __init__(self, rid, ids, plen, budget, priority=0,
                 deadline_s=None, ttft_deadline_s=None):
        self.rid, self.ids, self.plen = rid, ids, plen
        self.budget = budget
        self.tokens: List[int] = []
        # block diffusion: for each token the denoising pass that fixed it
        self.commit_steps: List[int] = []
        self.slot = None
        self.cached = 0     # positions of the slot's cache that hold tokens
        # lifecycle stamps, all perf_counter instants: submitted, admit
        # program dispatched, first token on the host, terminal
        # transition; the last three only while metrics are on
        self.t_submit = time.perf_counter()
        self.t_admit = self.t_first = self.t_done = None
        self.state = RequestState.QUEUED
        self.priority = int(priority)
        # deadlines are absolute perf_counter instants; None = no bound
        self.deadline = (self.t_submit + deadline_s
                         if deadline_s is not None else None)
        self.ttft_deadline = (self.t_submit + ttft_deadline_s
                              if ttft_deadline_s is not None else None)
        self.error = None

    def deadline_hit(self, now):
        """Total deadline always applies; the TTFT deadline only until
        the first token has been DELIVERED (drained to the host)."""
        if self.deadline is not None and now > self.deadline:
            return True
        return (self.ttft_deadline is not None and not self.tokens
                and now > self.ttft_deadline)

    def timings(self):
        return {"submit": self.t_submit, "admit": self.t_admit,
                "first_token": self.t_first, "done": self.t_done}


class ContinuousBatchingSession(_SessionLifecycle):
    """Continuous batching over the dense fixed-capacity cache: requests
    are admitted into free SLOTS and retired mid-flight while decode
    keeps running for the other slots.

    Reference role being re-designed: block_multihead_attention's paged
    KV cache exists to serve variable-length multi-request batches
    (/root/reference/python/paddle/incubate/nn/functional/
    block_multihead_attention.py) with dynamic insertion. On TPU the
    paged indirection is replaced by the static [slots, capacity] cache
    plus per-slot lengths; the dynamic part is slot management:

      * admit  — ONE executable per prompt bucket: slice the slot's
        cache rows out of the batch, run a b=1 prefill on the padded
        prompt, write the rows back at a TRACED slot index and deposit
        the first sampled token into the batched token vector;
      * decode — ONE executable, always the full slot batch,
        ``decode_block`` steps a dispatch; lanes that do not step are
        masked (their token is passed through and their length stays as
        it was; a lane with no request is shown to the model at length
        0, so attention reads one block of it);
      * retire — host-side: eos or budget exhaustion frees the slot,
        the next queued request is admitted into it on the next step.

    Executable count is bounded by 1 + #prefill_buckets regardless of
    how many requests flow through. Sampling uses one device RNG
    stream; with temperature=0 (default) outputs are bit-identical to
    isolated DecodeSession runs (asserted in
    tests/test_continuous_batching.py).

    A model that counts its experts' load (``EXPERT_LOAD_LEN``,
    ``forward_with_expert_load``, ``count_expert_load``) has it summed on
    the device over a dispatch's steps, over the stepping lanes, and
    ticked at delivery, in either mode.

    ``generation="block_diffusion"`` (a model that generates by diffusion
    over blocks, ``models/sdar_moe.py``; SDAR's
    ``block_diffusion_generate``): the text grows a block of the model's
    ``block_length`` = B positions at a time, blocks aligned at absolute
    multiples of B, under the model's block mask. The model also gives
    ``mask_token_id``.

      * admit  — prefills the prompt's whole blocks, ``(plen // B) * B``
        tokens, and samples nothing; the ``plen % B`` tokens left open
        the first block, already fixed;
      * decode — ONE executable, one dispatch = one block for every
        stepping lane: ``denoising_steps`` = T passes over ``[slots, B]``
        ids whose open positions hold the mask token, each fixing the most
        confident open positions (``remasking``: ``low_confidence_static``
        fixes ``B // T`` a pass, SDAR's ``get_num_transfer_tokens``;
        ``low_confidence_dynamic`` every open position whose confidence is
        over ``confidence_threshold`` where those are at least that many),
        then one commit pass that writes the block's K/V and advances the
        lengths by B. A denoising pass keeps no K/V: its rows lie past the
        length and the commit pass overwrites them. A lane with nothing
        open passes through unchanged, so a request's tokens are those of
        the published b=1 loop;
      * a dispatch yields up to B tokens a lane, delivered in position
        order; what passes the budget is dropped. ``RequestResult.
        commit_steps`` gives for each token the pass that fixed it.

    Departures from the published loop: the mask token's logit is held at
    -inf when sampling; open-ness is a boolean kept beside the ids; only
    open positions are ever fixed; confidences that tie go to the lower
    position.
    """

    def __init__(self, model, max_slots, max_length,
                 prefill_buckets=None, temperature=0.0, top_p=None,
                 top_k=None, eos_token_id=None, seed=0,
                 decode_block=None,
                 max_queue=None, shed_policy="reject_newest",
                 default_deadline_s=None, default_ttft_s=None,
                 step_retries=2, step_backoff_s=0.02,
                 degraded_queue_frac=0.8,
                 generation="autoregressive", denoising_steps=None,
                 remasking="low_confidence_static",
                 confidence_threshold=0.9):
        model.eval()
        self._model = model
        self._slots = int(max_slots)
        self._max_length = int(max_length)
        self._buckets = sorted(
            min(b, self._max_length)
            for b in (prefill_buckets
                      or _default_buckets(self._max_length)))
        self._temperature = float(temperature)
        self._top_p = top_p
        self._top_k = top_k
        self._eos = eos_token_id
        self._state_t = _collect_model_state(model)

        caches = _entries(model.init_cache(self._slots,
                                           max_length=self._max_length))
        self._cache_treedef = jax.tree_util.tree_structure(caches)
        self._cache_arrays = jax.tree_util.tree_leaves(caches)
        if _met._ENABLED:
            for kind in sorted({c.kind for c in caches}):
                _met.REGISTRY.gauge("cache.bytes", kind=kind).set(sum(
                    a.nbytes for c in caches if c.kind == kind
                    for a in jax.tree_util.tree_leaves(c)))
        self._tokens = jnp.zeros((self._slots,), jnp.int32)
        self._key = jax.random.PRNGKey(seed)
        # a mixture of experts counts its own load: on the device beside
        # the pass (forward_with_expert_load gives EXPERT_LOAD_LEN int32
        # sums, added up over a dispatch's passes or steps), on the host
        # at delivery (count_expert_load ticks them)
        self._expert_load_len = getattr(model, "EXPERT_LOAD_LEN", 0)

        n = len(self._state_t)
        nc = len(self._cache_arrays)
        # admit args: (*state, ids, plen, slot, tokens, key, *caches)
        self._admit_jit = jax.jit(
            self._admit_pure,
            donate_argnums=tuple(range(n + 5, n + 5 + nc)))
        # decode_block=k (None: 1) is the one size of a decode dispatch:
        # k masked steps in one lax.while_loop program that emits a
        # [slots, k] token block, so the host round trip is paid once a
        # block. Retirement lags up to k-1 steps (the freed slot's extra
        # decodes are discarded; its cache is reset by the next admission).
        # decode args: (*state, tokens, key, lane, *caches)
        self._decode_block = int(decode_block) if decode_block else 1
        self._decode_blk_jit = jax.jit(
            self._decode_block_pure,
            donate_argnums=tuple(range(n + 3, n + 3 + nc)))

        self._free = list(range(self._slots))
        self._queue: collections.deque = collections.deque()
        self._running: dict = {}          # slot -> _Request
        self._done: dict = {}             # rid -> _Request (undelivered)
        self._next_rid = 0
        self._used_rids: set = set()
        # this step()'s ("admit" | "block" | "blocks", info, array)
        # entries: filled by _admit_one, _dispatch_once and recovery's
        # probes, emptied by _drain_pending in the same step()
        self._pending: List = []
        reg = _met.REGISTRY
        self._h_phase = {
            phase: reg.histogram("serving.step_phase_s", phase=phase)
            for phase in ("admit", "dispatch", "fetch", "deliver")}
        self._h_step = reg.histogram("serving.step_s")
        self._h_step_host = reg.histogram("serving.step_host_s")
        self._fetch_s = 0.0     # seconds this step() waited in its fetch
        # the wall-clock account: the operator's sums a part, and a
        # cycle's seconds for a median over the cycles that decoded
        self._account = _CycleAccount()
        self._c_cycle = {part: reg.counter("serving.cycle_s", part=part)
                         for part in _PARTS}
        self._c_starved = {part: reg.counter("serving.starved_s", part=part)
                           for part in _PARTS
                           if part not in ("no_work", "fetch_wait")}
        self._h_cycle_part = {
            part: reg.histogram("serving.cycle_part_s", part=part)
            for part in _CYCLE_HIST_PARTS}
        self._h_cycle_starved = reg.histogram("serving.cycle_starved_s")
        self._block_length = None
        if generation == "block_diffusion":
            if decode_block:
                raise ValueError(
                    "decode_block is the autoregressive mode's; "
                    "a block-diffusion dispatch is one block")
            self._init_block_diffusion(denoising_steps, remasking,
                                       confidence_threshold)
        elif generation != "autoregressive":
            raise ValueError(f"unknown generation mode {generation!r}")
        # robustness knobs (ISSUE 14): bounded-queue admission control
        # with a pluggable shedding policy, per-request deadline
        # defaults, and the device-step retry envelope
        self._admission = _adm.AdmissionController(
            max_queue=max_queue, policy=shed_policy,
            degraded_queue_frac=degraded_queue_frac)
        self._default_deadline_s = default_deadline_s
        self._default_ttft_s = default_ttft_s
        self._step_retries = max(0, int(step_retries))
        self._step_backoff_s = float(step_backoff_s)
        # readiness: /healthz flips to 503 `degraded` while this
        # session reports queue/slot pressure, so load balancers route
        # away BEFORE the shedding policy has to reject. Registered
        # through a weakref so the module-global provider list never
        # pins an abandoned session alive (close()'s finalizer path
        # must stay reachable).
        wself = weakref.ref(self)

        def _provider():
            s = wself()
            return s._health_report() if s is not None else None
        self._health_unreg = _obs_server.register_health_provider(
            _provider)
        # pull-based scrape endpoint (PADDLE_TPU_METRICS_PORT): hold
        # one ref for this session's lifetime; close() releases it
        self._metrics_server = _obs_server.session_started()
        self._closed = False

    # ---------------- compiled programs ------------------------------
    def _entries(self, cache_arrays):
        return jax.tree_util.tree_unflatten(self._cache_treedef,
                                            list(cache_arrays))

    @staticmethod
    def _slot_slice(entries, slot):
        """A fresh request's one-lane entries of ``slot`` (every slice
        before any clearing: the order the programs have had)."""
        sliced = [e.slot(slot) for e in entries]
        return [e.cleared() for e in sliced]

    @jax.named_scope("admit")
    def _admit_pure(self, *flat):
        n = len(self._state_t)
        state = flat[:n]
        ids, plen, slot, tokens, key = flat[n:n + 5]
        full = self._entries(flat[n + 5:])
        # the prompt is padded to its bucket: plen of the positions count
        fresh = [e.prefilling(plen) for e in self._slot_slice(full, slot)]
        logits, part = _bind_and_run(
            self._model, self._state_t, state, ids, fresh)
        last = logits[0, plen - 1]
        nxt, key = _sample(last[None], key, self._temperature,
                           self._top_p, self._top_k)
        tokens = lax.dynamic_update_index_in_dim(tokens, nxt[0],
                                                 slot, 0)
        full = [e.put_slot(p, slot, plen) for e, p in zip(full, part)]
        return tokens, key, jax.tree_util.tree_leaves(full)

    @jax.named_scope("decode_step")
    def _masked_step(self, state, tok, key, lane, cache_arrays):
        """ONE masked decode step — the single home of the per-slot
        semantics. ``lane`` says what each slot is doing (_LANE_*): only
        a stepping lane takes its new token, and only its one new
        position counts in the cache. Every other lane passes its token
        through and its cache entries keep it where it was, each kind by
        its own rule (``StaticCache.stepping``, ``RecurrentCache``'s)."""
        active = lane == _LANE_STEPPING
        old = self._entries(cache_arrays)
        shown = [e.stepping(lane) for e in old]
        logits, new, *load = _bind_and_run(
            self._model, self._state_t, state, tok[:, None], shown,
            with_expert_load=active if self._expert_load_len else None)
        nxt, key = _sample(logits[:, -1], key, self._temperature,
                           self._top_p, self._top_k)
        nxt = jnp.where(active, nxt, tok)
        fixed = [e.stepped(o, active) for e, o in zip(new, old)]
        return (nxt, key, jax.tree_util.tree_leaves(fixed), *load)

    def _decode_block_pure(self, *flat):
        """`decode_block` batched decode steps in ONE program: a
        while_loop over _masked_step carrying (tokens, key, out,
        caches). A model that counts its experts' load also carries the
        sum of its steps' load vectors, returned after the block's tokens
        in one int32 vector (``[slots * decode_block + EXPERT_LOAD_LEN]``),
        as a block-diffusion dispatch returns it."""
        n = len(self._state_t)
        state = flat[:n]
        tokens, key, lane = flat[n:n + 3]
        cache_arrays = tuple(flat[n + 3:])
        blk = self._decode_block
        out0 = jnp.zeros((self._slots, blk), jnp.int32)

        def body(carry):
            i, tok, key, out, caches, *load = carry
            nxt, key, fixed, *step_load = self._masked_step(
                state, tok, key, lane, caches)
            out = out.at[:, i].set(nxt)
            return (i + 1, nxt, key, out, tuple(fixed),
                    *(a + b for a, b in zip(load, step_load)))

        carry = (jnp.int32(0), tokens, key, out0, cache_arrays,
                 *([jnp.zeros((self._expert_load_len,), jnp.int32)]
                   if self._expert_load_len else []))
        _i, tokens, key, out, cache_arrays, *load = lax.while_loop(
            lambda c: c[0] < blk, body, carry)
        if load:
            out = jnp.concatenate([out.reshape(-1), load[0]])
        return out, tokens, key, list(cache_arrays)

    # ---------------- generation by diffusion over blocks -------------
    def _init_block_diffusion(self, denoising_steps, remasking,
                              confidence_threshold):
        fixed = [type(e).__name__ for e in self._entries(self._cache_arrays)
                 if not e.rewindable]
        if fixed:
            # a denoising pass is taken back by keeping the length; a
            # state that has consumed a position cannot give it back
            raise ValueError(
                'generation="block_diffusion" takes every denoising pass '
                f"back, which a {fixed[0]} cannot do "
                f"({len(fixed)} of the model's cache entries)")
        model = self._model
        blk = int(model.block_length)       # the model's block mask's
        if remasking not in ("low_confidence_static",
                             "low_confidence_dynamic"):
            raise ValueError(f"unknown remasking {remasking!r}")
        steps = int(denoising_steps or blk)
        if not 1 <= steps <= blk:
            raise ValueError("denoising_steps must lie in 1..block_length")
        self._block_length, self._denoising_steps = blk, steps
        self._remasking = remasking
        self._confidence_threshold = float(confidence_threshold)
        self._mask_token_id = int(model.mask_token_id)
        # SDAR's get_num_transfer_tokens: positions fixed by pass t
        self._transfer_counts = [blk // steps + (t < blk % steps)
                                 for t in range(steps)]
        n, nc = len(self._state_t), len(self._cache_arrays)
        # admit args: (*state, ids, plen, slot, *caches)
        self._admit_blk_jit = jax.jit(
            self._admit_block_pure,
            donate_argnums=tuple(range(n + 3, n + 3 + nc)))
        # block args: (*state, ids, open, key, lane, *caches)
        self._block_jit = jax.jit(
            self._block_pure,
            donate_argnums=tuple(range(n + 4, n + 4 + nc)))

    @jax.named_scope("admit")
    def _admit_block_pure(self, *flat):
        """Block mode's admit: a b=1 prefill of the prompt's whole blocks
        under the model's block mask into ``slot``; nothing is sampled
        (the unused head is not computed)."""
        n = len(self._state_t)
        state = flat[:n]
        ids, plen, slot = flat[n:n + 3]
        full = self._entries(flat[n + 3:])
        _logits, part = _bind_and_run(
            self._model, self._state_t, state, ids,
            self._slot_slice(full, slot))
        return jax.tree_util.tree_leaves(
            [e.put_slot(p, slot, plen) for e, p in zip(full, part)])

    def _block_pure(self, *flat):
        """One block for every stepping lane in ONE program: the
        denoising passes (a fori_loop) and the commit pass. Returns the
        block's ids, for each position the pass that fixed it
        (``denoising_steps`` where it was not open) and the expert load,
        packed into one int32 vector so that one transfer fetches them."""
        n = len(self._state_t)
        state = flat[:n]
        ids, is_open, key, lane = flat[n:n + 4]
        cache_arrays = tuple(flat[n + 4:])
        blk, steps = self._block_length, self._denoising_steps
        active = lane == _LANE_STEPPING
        old = self._entries(cache_arrays)
        # an empty lane is shown at length 0, as in _masked_step
        shown = [jnp.where(lane == _LANE_EMPTY, 0, e.length) for e in old]
        counts = jnp.asarray(self._transfer_counts, jnp.int32)

        def run_pass(ids, caches):
            """The model over the block at the lengths shown, whatever
            length an earlier pass left in ``caches``."""
            layers = [e._replace(length=ln)
                      for e, ln in zip(self._entries(caches), shown)]
            logits, cache_out, *load = _bind_and_run(
                self._model, self._state_t, state, ids, layers,
                with_expert_load=active if self._expert_load_len else None)
            return logits, tuple(jax.tree_util.tree_leaves(cache_out)), \
                load[0] if load else jnp.zeros((0,), jnp.int32)

        @jax.named_scope("block_denoise")
        def denoise(t, carry):
            ids, is_open, fixed_at, key, load, caches = carry
            logits, caches, load_t = run_pass(ids, caches)
            vocab = jnp.arange(logits.shape[-1])
            logits = jnp.where(vocab == self._mask_token_id, -jnp.inf,
                               logits.astype(jnp.float32))
            x0, key = _sample(logits, key, self._temperature, self._top_p,
                              self._top_k)
            conf = jnp.exp(
                jnp.take_along_axis(logits, x0[..., None], -1)[..., 0]
                - jax.nn.logsumexp(logits, axis=-1))
            conf = jnp.where(is_open, conf, -jnp.inf)
            rank = jnp.argsort(jnp.argsort(-conf, axis=-1, stable=True),
                               axis=-1)
            move = is_open & (rank < counts[t])
            if self._remasking == "low_confidence_dynamic":
                high = is_open & (conf > self._confidence_threshold)
                enough = jnp.sum(high, -1, keepdims=True) >= counts[t]
                move = jnp.where(enough, high, move)
            move = move & active[:, None]
            return (jnp.where(move, x0, ids), is_open & ~move,
                    jnp.where(move, t, fixed_at), key, load + load_t, caches)

        carry = (ids, is_open, jnp.full(ids.shape, steps, jnp.int32), key,
                 jnp.zeros((self._expert_load_len,), jnp.int32),
                 cache_arrays)
        ids, _open, fixed_at, key, load, caches = lax.fori_loop(
            0, steps, denoise, carry)
        with jax.named_scope("block_commit"):
            _logits, caches, load_c = run_pass(ids, caches)
        fixed = [e._replace(length=jnp.where(active, o.length + blk,
                                             o.length))
                 for e, o in zip(self._entries(caches), old)]
        out = jnp.concatenate([ids.reshape(-1), fixed_at.reshape(-1),
                               load + load_c])
        return out, key, jax.tree_util.tree_leaves(fixed)

    def _dispatch_block(self, state, slots, lane, retries):
        """Block mode's ``_dispatch_once``: the host builds each stepping
        lane's block (the prompt's remainder opens a request's first
        block, every later block is all open) and dispatches it."""
        blk = self._block_length
        passes = self._denoising_steps + 1
        ids = np.full((self._slots, blk), self._mask_token_id, np.int32)
        is_open = np.zeros((self._slots, blk), bool)
        starts = []
        for slot in slots:
            req = self._running[slot]
            held = max(0, min(blk, req.plen - req.cached))
            ids[slot, :held] = req.ids[req.cached:req.cached + held]
            is_open[slot, held:] = True
            starts.append((slot, held))

        def call():
            return self._block_jit(
                *state, jnp.asarray(ids), jnp.asarray(is_open), self._key,
                jnp.asarray(lane), *self._cache_arrays)

        with _Phase(RecordEvent("serving.dispatch", slots=len(slots),
                                passes=passes),
                    self._h_phase["dispatch"], self._account, "dispatch"):
            out, self._key, self._cache_arrays = self._device_call(
                "serving.decode_step", {"slots": slots}, call, retries)
        if _met._ENABLED:
            self._account.fed(decode=True)
            r = _met.REGISTRY
            r.counter("serving.block_dispatches").inc()
            # every lane computes every pass, whoever sits in it
            r.counter("serving.block_lane_passes").inc(self._slots * passes)
            r.counter("serving.block_open_positions").inc(
                int(is_open.sum()))
        for slot in slots:
            self._running[slot].cached += blk
        self._pending.append(("blocks", tuple(starts), out))

    def _deliver_blocks(self, starts, row, now):
        """One fetched block dispatch: each lane's tokens from its first
        open position on, in position order; what its request no longer
        takes (past the budget, after eos) is dropped. Returns (tokens
        delivered, requests whose first token this was)."""
        lanes = self._slots * self._block_length
        shape = (self._slots, self._block_length)
        tokens = row[:lanes].reshape(shape)
        fixed_at = row[lanes:2 * lanes].reshape(shape)
        delivered = first = dropped = 0
        for slot, held in starts:
            for col in range(held, self._block_length):
                req = self._running.get(slot)
                if req is None:
                    dropped += 1
                    continue
                if not req.tokens:
                    first += 1
                    if now is not None:
                        self._stamp_first_token(req, now)
                req.tokens.append(int(tokens[slot, col]))
                req.commit_steps.append(int(fixed_at[slot, col]))
                delivered += 1
                self._maybe_retire(req)
        if _met._ENABLED:
            r = _met.REGISTRY
            if dropped:
                r.counter("serving.block_discarded_tokens").inc(dropped)
        if self._expert_load_len:
            self._model.count_expert_load(row[2 * lanes:])
        return delivered, first

    def generated(self, request_id):
        """Tokens of the request that have reached the host so far; None
        for unknown (or already-delivered) ids."""
        req = self._done.get(request_id)
        if req is not None:
            return len(req.tokens)
        for req in (*self._running.values(), *self._queue):
            if req.rid == request_id:
                return len(req.tokens)
        return None

    # ---------------- host-side slot management ----------------------
    def submit(self, input_ids, max_new_tokens, request_id=None,
               priority=0, deadline_s=None, ttft_deadline_s=None):
        """Queue one request (1D token list/array). Returns its id.

        deadline_s / ttft_deadline_s bound the request's TOTAL and
        time-to-first-token wall time (defaults from the session);
        expiry evicts the request with state TIMED_OUT instead of
        letting it wait forever. With a bounded queue (``max_queue``)
        an overloaded session sheds load: the configured policy either
        raises :class:`AdmissionRejected` here (fast rejection — the
        request never waits) or, under the ``priority`` policy, evicts
        a lower-priority queued request (delivered as REJECTED)."""
        ids = np.asarray(
            input_ids._data if isinstance(input_ids, Tensor)
            else input_ids).reshape(-1).astype(np.int32)
        need = ids.size + max_new_tokens - 1
        if self._block_length:
            # the last block is committed whole
            need = -(-(need + 1) // self._block_length) * self._block_length
        if need > self._max_length:
            raise ValueError(
                f"prompt ({ids.size}) + {max_new_tokens} new tokens "
                f"exceeds the cache capacity {self._max_length}")
        if request_id is not None:
            if request_id in self._used_rids:
                raise ValueError(
                    f"request_id {request_id!r} is already in use")
            rid = request_id
        else:
            while self._next_rid in self._used_rids:
                self._next_rid += 1
            rid = self._next_rid
            self._next_rid += 1
        req = _Request(
            rid, ids, ids.size, max_new_tokens, priority=priority,
            deadline_s=(deadline_s if deadline_s is not None
                        else self._default_deadline_s),
            ttft_deadline_s=(ttft_deadline_s if ttft_deadline_s
                             is not None else self._default_ttft_s))
        try:
            victim = self._admission.admit(self._queue, req,
                                           free_slots=len(self._free))
        except AdmissionRejected:
            # shed-not-collapse: the rejection is the fast path — no
            # rid is consumed, nothing is retained
            if _met._ENABLED:
                _met.REGISTRY.counter("serving.rejected").inc()
            raise
        self._used_rids.add(rid)
        if victim is not None:
            self._finish(victim, RequestState.REJECTED)
        self._queue.append(req)
        if _met._ENABLED:
            self._account.submitted(req.t_submit)
            r = _met.REGISTRY
            r.counter("serving.requests_submitted").inc()
            r.gauge("serving.queue_depth").set(len(self._queue))
            r.gauge("serving.inflight_requests").set(
                len(self._used_rids))
        return rid

    def cancel(self, request_id):
        """Cancel a queued or running request: it transitions to
        CANCELLED, its slot (if any) is freed for the next admission,
        and its partial output is delivered with the next drain.
        Returns True if the request was found in a non-terminal state
        (unknown / already-terminal ids return False)."""
        for req in self._queue:
            if req.rid == request_id:
                self._queue.remove(req)
                self._finish(req, RequestState.CANCELLED)
                return True
        for req in list(self._running.values()):
            if req.rid == request_id:
                self._finish(req, RequestState.CANCELLED)
                return True
        return False

    def status(self, request_id):
        """RequestState of an in-flight or undelivered request; None
        for unknown (or already-delivered) ids."""
        for req in self._queue:
            if req.rid == request_id:
                return req.state
        for req in self._running.values():
            if req.rid == request_id:
                return req.state
        req = self._done.get(request_id)
        return req.state if req is not None else None

    # -------- lifecycle internals (state machine + recovery) ---------
    def _finish(self, req, state, error=None):
        """The single terminal transition: free the slot, record the
        state, park the request for delivery, tick the outcome
        counter. Every exit path — retire, timeout, cancel, shed,
        quarantine — funnels through here."""
        if req.slot is not None:
            self._running.pop(req.slot, None)
            self._free.append(req.slot)
            req.slot = None
        req.state = state
        req.error = error
        self._done[req.rid] = req
        if _met._ENABLED:
            r = _met.REGISTRY
            req.t_done = now = time.perf_counter()
            if state is RequestState.DONE:
                r.counter("serving.requests_completed").inc()
                r.histogram("serving.request_latency_s").observe(
                    now - req.t_submit)
                if req.t_first is not None and len(req.tokens) > 1:
                    r.histogram("serving.tpot_s").observe(
                        (now - req.t_first) / (len(req.tokens) - 1))
            elif state is RequestState.TIMED_OUT:
                r.counter("serving.timed_out").inc()
            elif state is RequestState.CANCELLED:
                r.counter("serving.cancelled").inc()
            elif state is RequestState.REJECTED:
                r.counter("serving.rejected").inc()
            elif state is RequestState.FAILED:
                r.counter("serving.quarantined").inc()

    def _expire_deadlines(self):
        """Evict deadline-exceeded requests (queued AND running) —
        runs at the top of every step, so expiry is honored within one
        step of the deadline instant."""
        if not (self._queue or self._running):
            return
        now = time.perf_counter()
        for req in [r for r in self._queue if r.deadline_hit(now)]:
            self._queue.remove(req)
            self._finish(req, RequestState.TIMED_OUT)
        for req in list(self._running.values()):
            if req.deadline_hit(now):
                self._finish(req, RequestState.TIMED_OUT)

    def _health_report(self):
        """Readiness provider for the /healthz endpoint: a non-empty
        reason list means degraded (503)."""
        if getattr(self, "_closed", False):
            return None
        return self._admission.degraded_reasons(
            len(self._queue), len(self._free))

    def _device_call(self, site, ctx, fn, retries=None):
        """Retry-with-backoff envelope around one device dispatch.
        The chaos hook sits INSIDE the try so injected faults exercise
        the same recovery as real ones. Retrying is safe here because
        a dispatch that raised did not consume its donated buffers —
        the session state the closure captured is still alive."""
        retries = self._step_retries if retries is None else retries
        delay = self._step_backoff_s
        attempt = 0
        while True:
            try:
                _chaos.hit(site, **ctx)
                return fn()
            except Exception:
                if attempt >= retries:
                    raise
                attempt += 1
                if _met._ENABLED:
                    _met.REGISTRY.counter("serving.step_retries").inc()
                if delay > 0:
                    time.sleep(delay)
                    delay *= 2

    def _dispatch_once(self, state, slots, retries=None):
        """One decode dispatch for the given active-slot subset; on
        success the sampled tokens are committed to pending tagged
        with exactly that subset (drains credit only those slots)."""
        lane = np.full((self._slots,), _LANE_EMPTY, np.int8)
        lane[list(self._running)] = _LANE_PAUSED
        lane[list(slots)] = _LANE_STEPPING
        if self._block_length:
            return self._dispatch_block(state, slots, lane, retries)
        steps = self._decode_block

        def call():
            return self._decode_blk_jit(
                *state, self._tokens, self._key, jnp.asarray(lane),
                *self._cache_arrays)

        with _Phase(RecordEvent("serving.dispatch", slots=len(slots)),
                    self._h_phase["dispatch"], self._account, "dispatch"):
            out = self._device_call("serving.decode_step",
                                    {"slots": slots}, call, retries)
        stepped = [self._running[slot] for slot in slots]
        if _met._ENABLED:
            self._account.fed(decode=True)
            r = _met.REGISTRY
            # every lane computes every step, whoever sits in it
            r.counter("serving.decode_lane_steps").inc(self._slots * steps)
            # the share of the cache a step has any reason to read
            r.counter("serving.decode_cache_positions").inc(
                steps * sum(req.cached for req in stepped))
            r.counter("serving.decode_cache_capacity").inc(
                self._slots * self._max_length * steps)
        for req in stepped:
            req.cached += steps
        blk_out, self._tokens, self._key, self._cache_arrays = out
        self._pending.append(("block", slots, blk_out))

    def _probe_slots(self, state, subset):
        """Single-attempt step over a slot subset. A SUCCESSFUL probe
        is a real step — its tokens are committed and delivered — so
        bisection never wastes device work or skips tokens. Returns
        True when the subset still fails."""
        try:
            self._dispatch_once(state, tuple(subset), retries=0)
            return False
        except Exception:
            return True

    def _bisect_poison(self, state, slots, exc):
        """Find the single poison slot by probing halves. Returns the
        slot, or None when every probe succeeded (the fault cleared —
        all slots stepped during recovery). Raises ServingStepError
        when DISJOINT subsets fail: that is a step-wide fault, not a
        poison request, and pretending otherwise would quarantine
        innocent requests one by one."""
        while len(slots) > 1:
            mid = len(slots) // 2
            left, right = slots[:mid], slots[mid:]
            lf = self._probe_slots(state, left)
            rf = self._probe_slots(state, right)
            if lf and rf:
                raise ServingStepError(
                    "decode step fails for disjoint slot subsets "
                    f"{tuple(left)} and {tuple(right)} — failure is "
                    "step-wide, not attributable to one poison "
                    "request") from exc
            if lf:
                slots = left
            elif rf:
                slots = right
            else:
                return None
        return slots[0]

    def _recover_decode(self, state, slots, exc):
        """Persistent step failure (retry budget exhausted): isolate
        the poison request by bisection and fail ONLY it; the session
        and every other in-flight request stay alive. The freed slot
        returns to the pool (its cache region is reset by the next
        admission's prefill)."""
        if len(slots) == 1:
            poison = slots[0]
        else:
            poison = self._bisect_poison(state, list(slots), exc)
            if poison is None:
                return
        req = self._running.get(poison)
        if req is not None:
            self._finish(req, RequestState.FAILED,
                         error=f"{type(exc).__name__}: {exc}")

    def _admit_ready(self):
        state = [t._data for t in self._state_t]
        while self._free and self._queue:
            req = self._queue.popleft()
            slot = self._free.pop()
            req.state = RequestState.PREFILLING
            bucket = next((b for b in self._buckets
                           if b >= self._prefill_len(req)),
                          self._max_length)
            with _Phase(RecordEvent("serving.admit", rid=req.rid,
                                    slot=slot, plen=req.plen,
                                    bucket=bucket),
                        self._h_phase["admit"], self._account, "admit"):
                self._admit_one(state, req, slot, bucket)

    def _prefill_len(self, req):
        """Prompt tokens the admit program prefills: all of them, or in
        block mode the prompt's whole blocks."""
        blk = self._block_length
        return req.plen // blk * blk if blk else req.plen

    def _admit_one(self, state, req, slot, bucket):
        """Pad the prompt to its bucket and dispatch the admit program
        (a b=1 prefill into ``slot``)."""
        plen = self._prefill_len(req)
        padded = jnp.asarray(
            np.pad(req.ids[:plen], (0, bucket - plen))[None])

        def call():
            if self._block_length:
                return self._tokens, self._key, self._admit_blk_jit(
                    *state, padded, jnp.int32(plen), jnp.int32(slot),
                    *self._cache_arrays)
            return self._admit_jit(
                *state, padded, jnp.int32(req.plen),
                jnp.int32(slot), self._tokens, self._key,
                *self._cache_arrays)

        try:
            self._tokens, self._key, self._cache_arrays = \
                self._device_call("serving.admit_step",
                                  {"rid": req.rid, "slot": slot},
                                  call)
        except Exception as e:  # noqa: BLE001
            # the failing request is identified directly here (the
            # admit is b=1): quarantine it, keep admitting others
            self._free.append(slot)
            self._finish(req, RequestState.FAILED,
                         error=f"{type(e).__name__}: {e}")
            return
        req.slot = slot
        req.cached = plen
        req.state = RequestState.DECODING
        self._running[slot] = req
        if _met._ENABLED:
            r = _met.REGISTRY
            r.counter("serving.admits").inc()
            r.counter("serving.prefill_tokens").inc(plen)
            r.counter("serving.prefill_padded_tokens").inc(bucket)
            req.t_admit = time.perf_counter()
            self._account.fed(req.t_admit)
            r.histogram("serving.queue_wait_s").observe(
                req.t_admit - req.t_submit)
        # the admit's sampled token is the request's first output;
        # it stays ON DEVICE and is fetched with this step's drain
        # (an immediate device_get would add one blocking round trip
        # per admission). The tagged entry applies to THIS slot only:
        # the other lanes of the vector hold already-consumed
        # decode tokens. A block-mode admit samples nothing: the first
        # tokens come with the request's first block.
        if not self._block_length:
            self._pending.append(("admit", slot, self._tokens))

    def _maybe_retire(self, req):
        if (len(req.tokens) >= req.budget
                or (self._eos is not None
                    and req.tokens
                    and req.tokens[-1] == self._eos)):
            self._finish(req, RequestState.DONE)

    def _drain_pending(self):
        if not self._pending:
            return
        entries = self._pending
        self._pending = []
        _chaos.hit("serving.drain", n=len(entries))
        arrays = [t for (_k, _s, t) in entries]
        with _Phase(RecordEvent("serving.fetch", entries=len(entries)),
                    self._h_phase["fetch"], self._account,
                    "fetch_wait") as fetch:
            if fetch.t0 is not None:
                # the fetch's two halves: the chip runs the block
                # (fetch_wait), then has nothing of this session's to run
                # while the tokens cross (fetch_copy). The copies are
                # started first, so that the split delays none.
                for a in arrays:
                    a.copy_to_host_async()
                with RecordEvent("serving.fetch_wait"):
                    jax.block_until_ready(arrays)
                self._account.drained(time.perf_counter())
                with RecordEvent("serving.fetch_copy"):
                    fetched = jax.device_get(arrays)
            else:
                fetched = jax.device_get(arrays)
        self._fetch_s += fetch.seconds
        with _Phase(RecordEvent("serving.deliver", entries=len(entries)),
                    self._h_phase["deliver"], self._account,
                    "deliver") as deliver:
            self._deliver(entries, fetched, deliver.t0)

    def _deliver(self, entries, fetched, now):
        """Append the fetched tokens to their requests and retire those
        at their budget or eos. ``now`` is when the tokens reached the
        host (None while metrics are off)."""
        delivered = first = 0
        for (kind, ainfo, _t), row in zip(entries, fetched):
            # ainfo: the admitted slot ("admit") or the tuple of slots
            # active AT DISPATCH ("block") — only those lanes
            # carry live tokens; slots evicted (cancel/timeout/
            # quarantine) between dispatch and drain are skipped, and
            # recovery probes over subsets credit exactly their subset
            row = np.asarray(row)
            if kind == "blocks":
                n_tok, n_first = self._deliver_blocks(ainfo, row, now)
                delivered += n_tok
                first += n_first
                continue
            if kind == "admit":
                req = self._running.get(ainfo)
                if req is not None:
                    req.tokens.append(int(row[ainfo]))
                    delivered += 1
                    first += 1
                    if now is not None:
                        self._stamp_first_token(req, now)
                    self._maybe_retire(req)
                continue
            if self._expert_load_len:
                lanes = self._slots * self._decode_block
                self._model.count_expert_load(row[lanes:])
                row = row[:lanes].reshape(self._slots, self._decode_block)
            for col in range(row.shape[1]):
                for slot in ainfo:
                    req = self._running.get(slot)
                    if req is not None:
                        req.tokens.append(int(row[slot, col]))
                        delivered += 1
                        self._maybe_retire(req)
        if _met._ENABLED and delivered:
            r = _met.REGISTRY
            r.counter("serving.decode_tokens").inc(delivered)
            if first:
                r.counter("serving.first_tokens").inc(first)

    @staticmethod
    def _stamp_first_token(req, now):
        req.t_first = now
        r = _met.REGISTRY
        r.histogram("serving.ttft_s").observe(now - req.t_submit)
        if req.t_admit is not None:
            r.histogram("serving.first_token_hold_s").observe(
                now - req.t_admit)

    def step(self):
        """Expire deadlines, admit whatever fits, dispatch ONE decode
        block under the retry/recovery envelope, fetch what the step
        left pending and deliver it, retiring finished requests.
        Returns the list of request ids that reached a terminal state
        during this step."""
        self._fetch_s = 0.0
        whole = _Phase(RecordEvent("serving.step",
                                   running=len(self._running),
                                   queued=len(self._queue)),
                       self._h_step, self._account, "other")
        try:
            with whole:
                done = self._step()
            if whole.t0 is not None:
                # the host's own time inside the step; the idle it causes
                # also holds the tokens' copy and the caller's gap
                # (serving.cycle_starved_s)
                self._h_step_host.observe(whole.seconds - self._fetch_s)
            return done
        finally:
            # a step that failed closed its parts too
            self._end_cycle(whole.t0 is not None)

    def _end_cycle(self, timed):
        """Flush the cycle that this ``step()``'s return ends: its seconds
        and starved seconds a part into the counters and, if it dispatched
        a decode block, the parts a reader takes a median of
        (``_CYCLE_HIST_PARTS``) and the starved seconds into the histograms
        (zeros included, so that a median is one over cycles). The starved
        histogram sums the parts ``serving.starved_s`` has a series for, so
        the two agree whatever was toggled meanwhile."""
        if not timed:
            self._account.forget()
            return
        seconds, starved, decoded = self._account.end_cycle(
            "caller" if self._queue or self._running else "no_work")
        for part, s in seconds.items():
            if s:
                self._c_cycle[part].inc(s)
        cycle_starved = 0.0
        for part, counter in self._c_starved.items():
            if starved[part]:
                counter.inc(starved[part])
                cycle_starved += starved[part]
        if decoded:
            for part, hist in self._h_cycle_part.items():
                hist.observe(seconds[part])
            self._h_cycle_starved.observe(cycle_starved)

    def _step(self):
        before = set(self._done)
        with _Phase(RecordEvent("serving.expire"), None, self._account,
                    "expire"):
            self._expire_deadlines()
        self._admit_ready()
        if _met._ENABLED:
            r = _met.REGISTRY
            r.counter("serving.steps").inc()
            r.gauge("serving.queue_depth").set(len(self._queue))
            r.gauge("serving.slots_active").set(len(self._running))
            r.gauge("serving.slot_utilization").set(
                len(self._running) / self._slots)
            r.gauge("serving.degraded").set(
                1.0 if self._health_report() else 0.0)
        try:
            if self._running:
                state = [t._data for t in self._state_t]
                slots = tuple(sorted(self._running))
                try:
                    self._dispatch_once(state, slots)
                except ServingStepError:
                    raise
                except Exception as e:  # noqa: BLE001
                    self._recover_decode(state, slots, e)
        finally:
            # also under a step-wide failure: the tokens of the step's
            # admits and successful probes are delivered before it
            # propagates, so every step starts with nothing pending
            self._drain_pending()
        return [r for r in self._done if r not in before]

    def results(self):
        """Drive the session until every submitted request reaches a
        terminal state, then deliver {rid: RequestResult} — terminal
        state, prompt + generated ids (partial for TIMED_OUT /
        CANCELLED / FAILED), and the error string for FAILED.
        Delivered results are released exactly like :meth:`run`."""
        while self._queue or self._running:
            self.step()
        out = {rid: RequestResult(
                   req.state,
                   np.concatenate([req.ids,
                                   np.asarray(req.tokens, np.int32)]),
                   req.error, req.timings(),
                   np.asarray(req.commit_steps, np.int32)
                   if self._block_length else None)
               for rid, req in self._done.items()}
        self._done = {}
        # delivered ids leave the in-flight set: a serving loop calling
        # submit()/run() forever must not accumulate every rid ever seen
        self._used_rids.difference_update(out)
        if _met._ENABLED:
            _met.REGISTRY.gauge("serving.inflight_requests").set(
                len(self._used_rids))
        return out

    def run(self):
        """Drain queue + running slots; returns {rid: full token ids}
        (prompt + generated, eos included when emitted) for requests
        completed by THIS drain (or still undelivered from step()
        calls). Requests that ended TIMED_OUT / CANCELLED / FAILED /
        REJECTED deliver their partial ids here — use :meth:`results`
        for the terminal states. Delivered results are released — a
        later run() never re-delivers them, their request_ids become
        reusable, and neither _done nor _used_rids grows unboundedly
        in a long-lived serving session."""
        return {rid: res.ids for rid, res in self.results().items()}

    def cache_entries(self):
        """The layers' cache entries (``StaticCache`` / ``LatentCache`` /
        ``RecurrentCache`` of device arrays) as the last dispatched program
        left them, for a check or a debugger: slot ``i`` is index ``i`` of a
        leaf's slot dim, and an entry's ``length`` says how many positions
        that slot has consumed. The next dispatch donates these arrays: fetch what
        is wanted before the session is stepped again."""
        return self._entries(self._cache_arrays)

    def close(self):
        """Cancel in-flight work, then release shared resources.
        Queued and running requests transition to CANCELLED (their
        pending device futures are dropped — nothing waits on the
        device, so close never hangs), undelivered results are
        discarded, and ``_used_rids`` ends empty. Idempotent; also
        runs via the context-manager exit and the finalizer."""
        if getattr(self, "_closed", False):
            return
        for req in list(getattr(self, "_queue", ())):
            self._finish(req, RequestState.CANCELLED)
        if getattr(self, "_queue", None) is not None:
            self._queue.clear()
        for req in list(getattr(self, "_running", {}).values()):
            self._finish(req, RequestState.CANCELLED)
        self._pending = []
        self._done = {}
        if getattr(self, "_used_rids", None) is not None:
            self._used_rids.clear()
        if getattr(self, "_health_unreg", None) is not None:
            self._health_unreg()
            self._health_unreg = None
        # the compiled programs are bound methods, so the session is a
        # reference cycle: let go of the cache and of the model here and
        # not when the collector next looks (a closed session must not
        # hold a chip's memory against the next model)
        self._cache_arrays = []
        self._model = self._state_t = None
        super().close()

    def executable_counts(self):
        """(n_admit_executables, n_decode_executables): admit is
        bounded by the bucket count, decode must stay 1 however many
        requests flow through (in block mode the block program is THE
        decode executable)."""
        if self._block_length:
            return (self._admit_blk_jit._cache_size(),
                    self._block_jit._cache_size())
        return (self._admit_jit._cache_size(),
                self._decode_blk_jit._cache_size())



def cached_generate(model, input_ids, max_new_tokens=16, temperature=0.0,
                    top_p=None, seed=None, max_length=None, seq_ceiling=None,
                    hard_limit=False, decode_block=None):
    """Shared model.generate() implementation: pick a cache capacity
    (next power of two covering prompt+new, floored at 64), cache one
    DecodeSession per (capacity, sampling config) on the model, and
    generate.

    seq_ceiling: the model's positional limit. With hard_limit=True
    (learned position tables — GPT's wpe) requests past the ceiling
    raise; with hard_limit=False (RoPE — llama) the ceiling is only a
    sizing hint and longer requests are allowed.
    """
    need = input_ids.shape[1] + max_new_tokens
    if hard_limit and seq_ceiling is not None and need > seq_ceiling:
        raise ValueError(
            f"prompt + max_new_tokens = {need} exceeds the model's "
            f"positional table ({seq_ceiling})")
    ceil_eff = seq_ceiling if (hard_limit and seq_ceiling) else \
        max(seq_ceiling or 0, need)
    cap = max_length or min(max(64, 1 << (need - 1).bit_length()),
                            ceil_eff)
    key = (cap, float(temperature), top_p, decode_block)
    sessions = model.__dict__.setdefault("_decode_sessions", {})
    if key not in sessions:
        sessions[key] = DecodeSession(model, cap, temperature=temperature,
                                      top_p=top_p,
                                      decode_block=decode_block)
    return sessions[key].generate(input_ids, max_new_tokens, seed=seed)
