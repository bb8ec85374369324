"""Length-aware decode attention over the fixed-capacity cache (Pallas TPU).

The one-token step of ``inference/decode._cache_attention``: one query per
slot against that slot's cache ``[C, Hkv, D]``, of which only positions
``0 .. len`` hold tokens. The two einsums of the XLA path read all ``C``
positions of K and of V and mask afterwards; this kernel moves from HBM
only the blocks that hold a valid position,
``0 .. ceil((len + 1) / block) - 1`` of each slot, and nothing else.

- Layout as the cache has it, ``[B, C, Hkv, D]``: a block of ``block``
  positions is one contiguous DMA, no transposed copy.
- One program. The valid blocks of all slots form one sequence, walked by
  two cursors: the arithmetic takes a block while the copies of the next
  ``_DEPTH - 1`` are in flight into a ring of VMEM buffers
  (``make_async_copy``), across the ends of slots, so the copy engine never
  waits for a slot to end and a short slot costs no pipeline restart.
- K and V in one pass with an online softmax in float32. Positions past the
  valid length inside the last block are masked (-1e30 before the max, and
  their ``p * v`` dropped, so nothing stored there reaches the result);
  only a slot's last, partial chunk pays for the masks.
- float32 throughout on the VPU: a multiply and a lane reduction for
  ``q . k``, a multiply and an add over positions for ``p . v``. No MXU dot:
  at default precision that would be a bf16 product. The softmax runs in
  base 2 (``q`` carries ``log2(e) / sqrt(D)``), which is what the chip's
  exponential unit computes.
- GQA: the ``g`` query heads of a KV head are a static loop over the chunk
  already in registers.
- One KV head under eight or more query heads (multi-query attention,
  ``_kernel_shared``): the VPU form would hold one position a register (a
  row of ``Hkv`` = 1 fills one sublane of eight) and walk it ``g`` times.
  There the queries are the rows: ``q [g, D] . k [block, D]^T`` and
  ``p [g, block] . v [block, D]`` go to the MXU at ``highest`` precision
  (float32 operands, float32 sums), a whole block at a time under the same
  ring of copies, and the kernel is bound by the copies again.

A length at or past the capacity ``C`` reads all ``C`` positions and no
more, as the XLA path's mask does: a serving session steps a lane up to a
block past its budget before the host retires it.

**Latent attention** (``latent_decode_attention``, ``_kernel_latent``):
the one-token step of an MLA sublayer in its absorbed form
(``models/longcat_flash._absorbed``) is multi-query attention with one
latent head. Its cache is ``c`` ``[B, C, R]`` (the normed latent of each
position) beside ``k_rope`` ``[B, C, r]`` (its rotated rope key), and ``c``
is both the key, beside ``k_rope``, and the value. The same ring walks the
valid blocks of every slot, its two refs ``c`` and ``k_rope``, the block
from ``c``'s row bytes (``sizes``: 256 positions at 1 KiB a row).
``k_rope`` is read where XLA keeps it: a 64-wide minor dim would pad to
128 lanes, so the chip lays ``[B, C, 64]`` out with the positions in lanes;
the kernel takes the ``[B, r, C]`` view (a bitcast, no copy) and copies a
block's lanes (``_block_ring``'s ``v_last``). A block reads ``c`` once for
both products, the queries as the rows: ``s = q_lat [H, R] . c^T + q_rope
[H, r] . k_rope`` and ``acc += p . c``, the operands in the cache's dtype
and the sums in float32 on the MXU; the scale (with ``log2(e)``), the mask
and the online softmax in float32, ``p`` cast to the cache's dtype before
its product; rows of ``c`` past the length are zeroed, so nothing stored
there reaches the result. The scores stay in VMEM: nothing of size ``C`` is
written to HBM. The output ``acc / l`` is in the cache's dtype (the
``q_lat`` and output tiles of 48 slots x 64 heads x 512 take 6 MiB of the
budget in bf16; in float32 they would not fit). ``latent_gate_reason``
takes a call whose latent width is whole lanes (a multiple of 128), whose
heads and rope width fill whole registers of the cache's dtype (8 rows in
float32, 16 in bf16), whose blocks of rope keys are whole lanes (or the
whole capacity), and whose tiles and ring fit ``_VMEM_BUDGET``.

Block and chunk come from the cache's shape and itemsize (``sizes``).
``interpret=True`` runs the same kernel through the Pallas interpreter
(tests/test_decode_attention_kernel.py).

Reference being replaced: the decode kernel behind
incubate/nn/functional/masked_multihead_attention.py.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30

#: bytes of one K (or V) block, one DMA: large enough for the copy engine,
#: small enough that a short slot reads little past its length
_BLOCK_BYTES = 1 << 18
#: blocks of K (and of V) in the ring: one under the arithmetic, the others
#: in flight
_DEPTH = 4
#: vector registers of scores the arithmetic holds at a time
_CHUNK_VREGS = 32
#: what the ring, q and the output may take of VMEM
_VMEM_BUDGET = 12 << 20


def _halve_to(n, unit, target):
    while n % 2 == 0 and n * unit > target:
        n //= 2
    return n


def sizes(c, hkv, d, itemsize, g=1):
    """(block, chunk): positions per DMA block and per arithmetic chunk,
    both dividing the capacity ``c``."""
    block = _halve_to(c, hkv * d * itemsize, _BLOCK_BYTES)
    # a position's scores: one register per 8 KV heads, for each of g
    return block, _halve_to(block, -(-hkv // 8) * g, _CHUNK_VREGS)


def gate_reason(q_shape, cache_shape, cache_dtype):
    """None where the kernel takes the call, else the label of the
    ``attn.dispatch_fallback`` counter. q: [B, 1, H, D]; cache:
    [B, C, Hkv, D]."""
    b, c, hkv, d = cache_shape
    if cache_dtype != jnp.float32:
        return "cache_dtype"
    if d % 128 != 0:
        return "head_dim"           # a position's row must fill the lanes
    itemsize = jnp.dtype(cache_dtype).itemsize
    block, _ = sizes(c, hkv, d, itemsize)
    need = 2 * _DEPTH * block * hkv * d * itemsize + 2 * b * q_shape[2] * d * 4
    return None if need <= _VMEM_BUDGET else "vmem"


def _block_ring(lens_ref, k_hbm, v_hbm, kbuf, vbuf, sems, nslots, block,
                v_last=False):
    """What the kernels walk the cache with: (held, nblocks, copies, ring,
    fetch, primed) over the valid blocks of all slots, ``primed`` the
    cursor after the first ``_DEPTH - 1`` copies have been started.
    ``v_last``: ``v_hbm``'s positions lie along its last dim."""
    capacity = k_hbm.shape[1]

    def held(b):
        """Positions of slot ``b`` that hold tokens, the new one's too: a
        length past the capacity reads no further than the cache goes."""
        return jnp.minimum(lens_ref[b] + 1, capacity)

    def nblocks(b):                     # ceil(held / block)
        return lax.div(held(b) + (block - 1), jnp.int32(block))

    def copies(b, j, buf):
        rows = pl.ds(pl.multiple_of(j * block, block), block)
        v_rows = v_hbm.at[b, :, rows] if v_last else v_hbm.at[b, rows]
        return (pltpu.make_async_copy(k_hbm.at[b, rows], kbuf.at[buf],
                                      sems.at[0, buf]),
                pltpu.make_async_copy(v_rows, vbuf.at[buf],
                                      sems.at[1, buf]))

    def ring(buf):
        return jnp.where(buf + 1 == _DEPTH, 0, buf + 1)

    def fetch(cursor):
        """Start the copies of the block under the cursor (none once it is
        past the last slot) and move it on to the next valid block."""
        b, j, buf = cursor

        @pl.when(b < nslots)
        def _():
            for cp in copies(b, j, buf):
                cp.start()

        done = j + 1 >= nblocks(jnp.minimum(b, nslots - 1))
        return (jnp.where(done, b + 1, b), jnp.where(done, 0, j + 1),
                ring(buf))

    def primed():
        cursor = (jnp.int32(0), jnp.int32(0), jnp.int32(0))
        for _ in range(_DEPTH - 1):
            cursor = fetch(cursor)
        return cursor

    return held, nblocks, copies, ring, fetch, primed


def _kernel(lens_ref, q_ref, k_hbm, v_hbm, o_ref, kbuf, vbuf, sems, *,
            block, chunk):
    nslots, g, hkv, d = q_ref.shape
    held, nblocks, copies, ring, fetch, primed = _block_ring(
        lens_ref, k_hbm, v_hbm, kbuf, vbuf, sems, nslots, block)

    pos_thin = lax.broadcasted_iota(jnp.int32, (chunk, hkv, 1), 0)
    pos_full = lax.broadcasted_iota(jnp.int32, (chunk, hkv, d), 0)

    def attend(qs, buf, i, state, valid=None):
        """Chunk ``i`` of the block in ``buf`` into the running softmax of
        each query group; ``valid`` positions of it hold tokens (all, where
        None)."""
        rows = pl.ds(pl.multiple_of(i * chunk, chunk), chunk)
        k = kbuf[buf, rows].astype(jnp.float32)         # [chunk, hkv, d]
        v = vbuf[buf, rows].astype(jnp.float32)
        out = []
        for qi, (m, l, acc) in zip(qs, state):
            s = jnp.sum(k * qi[None], axis=-1, keepdims=True)
            if valid is not None:
                s = jnp.where(pos_thin < valid, s, NEG_INF)
            m_new = jnp.maximum(m, jnp.max(s, axis=0))  # [hkv, 1]
            alpha = jnp.exp2(m - m_new)
            p = jnp.exp2(s - m_new[None])               # [chunk, hkv, 1]
            pv = p * v
            if valid is not None:
                pv = jnp.where(pos_full < valid, pv, 0.0)
            out.append((m_new, alpha * l + jnp.sum(p, axis=0),
                        alpha * acc + jnp.sum(pv, axis=0)))
        return tuple(out)

    def slot_body(b, carry):
        n = held(b)
        qs = [q_ref[b, i] for i in range(g)]            # each [hkv, d]

        def block_body(j, carry):
            cursor, buf, state = carry
            cursor = fetch(cursor)      # into the buffer freed a block ago
            for cp in copies(b, j, buf):
                cp.wait()
            here = jnp.minimum(n - j * block, block)    # valid in this block
            whole = lax.div(here, jnp.int32(chunk))
            state = lax.fori_loop(
                0, whole, lambda i, st: attend(qs, buf, i, st), state)
            state = lax.cond(
                whole * chunk < here,
                lambda st: attend(qs, buf, whole, st, here - whole * chunk),
                lambda st: st, state)
            return cursor, ring(buf), state

        init = tuple((jnp.full((hkv, 1), NEG_INF, jnp.float32),
                      jnp.zeros((hkv, 1), jnp.float32),
                      jnp.zeros((hkv, d), jnp.float32)) for _ in range(g))
        cursor, buf, state = lax.fori_loop(0, nblocks(b), block_body,
                                           (*carry, init))
        for i, (_m, l, acc) in enumerate(state):
            o_ref[b, i] = acc / l
        return cursor, buf

    lax.fori_loop(0, nslots, slot_body, (primed(), jnp.int32(0)))


def shared_kv(hkv, g):
    """Whether the call is ``_kernel_shared``'s: one KV head, and enough
    query heads on it to fill a register's sublanes."""
    return hkv == 1 and g >= 8


def _kernel_shared(lens_ref, q_ref, k_hbm, v_hbm, o_ref, kbuf, vbuf, sems,
                   *, block):
    """One KV head: q_ref/o_ref [B, G, D] (G: the query heads, padded to
    whole registers), k_hbm/v_hbm [B, C, D], the ring [_DEPTH, block, D]."""
    nslots, rows, d = q_ref.shape
    held, nblocks, copies, ring, fetch, primed = _block_ring(
        lens_ref, k_hbm, v_hbm, kbuf, vbuf, sems, nslots, block)
    highest = dict(precision=lax.Precision.HIGHEST,
                   preferred_element_type=jnp.float32)

    col = lax.broadcasted_iota(jnp.int32, (rows, block), 1)
    row = lax.broadcasted_iota(jnp.int32, (block, d), 0)

    def slot_body(b, carry):
        n = held(b)
        q = q_ref[b]

        def block_body(j, carry):
            cursor, buf, (m, l, acc) = carry
            cursor = fetch(cursor)      # into the buffer freed a block ago
            for cp in copies(b, j, buf):
                cp.wait()
            here = n - j * block        # positions of the block that count
            s = lax.dot_general(q, kbuf[buf].astype(jnp.float32),
                                (((1,), (1,)), ((), ())), **highest)
            s = jnp.where(col < here, s, NEG_INF)
            # nothing stored past the length reaches the result
            v = jnp.where(row < here, vbuf[buf].astype(jnp.float32), 0.0)
            m_new = jnp.maximum(m, jnp.max(s, axis=1, keepdims=True))
            alpha = jnp.exp2(m - m_new)
            p = jnp.exp2(s - m_new)
            l = alpha * l + jnp.sum(p, axis=1, keepdims=True)
            acc = alpha * acc + lax.dot_general(
                p, v, (((1,), (0,)), ((), ())), **highest)
            return cursor, ring(buf), (m_new, l, acc)

        init = (jnp.full((rows, 1), NEG_INF, jnp.float32),
                jnp.zeros((rows, 1), jnp.float32),
                jnp.zeros((rows, d), jnp.float32))
        cursor, buf, (_m, l, acc) = lax.fori_loop(0, nblocks(b), block_body,
                                                  (*carry, init))
        o_ref[b] = acc / l
        return cursor, buf

    lax.fori_loop(0, nslots, slot_body, (primed(), jnp.int32(0)))


def latent_gate_reason(q_shape, cache_shape, rope_dim, cache_dtype):
    """None where ``latent_decode_attention`` takes the call, else the
    label of the ``attn.dispatch_fallback`` counter. q_lat: [B, H, R];
    the latent cache: [B, C, R], beside a rope key of ``rope_dim``."""
    b, c, width = cache_shape
    heads = q_shape[1]
    itemsize = jnp.dtype(cache_dtype).itemsize
    if width % 128 != 0:
        return "latent_width"       # a position's latent must fill lanes
    rows = 32 // itemsize           # the sublanes of a register
    if heads % rows != 0:
        return "heads"              # the queries fill whole registers
    if rope_dim % rows != 0:
        return "rope_dim"           # so do the rope keys, a block's rows
    block, _ = sizes(c, 1, width, itemsize)
    if block % 128 != 0 and block != c:
        return "capacity"           # a block of rope keys fills lanes
    # q_lat and the output, q_rope (its lanes padded), the two rings
    need = (2 * b * heads * width + b * heads * -(-rope_dim // 128) * 128
            + _DEPTH * block * (width + rope_dim)) * itemsize
    return None if need <= _VMEM_BUDGET else "vmem"


def _kernel_latent(lens_ref, ql_ref, qr_ref, c_hbm, kr_hbm, o_ref, cbuf,
                   krbuf, sems, *, block, scale):
    """One latent head: ql_ref/o_ref [B, H, R], qr_ref [B, H, r], c_hbm
    [B, C, R], kr_hbm [B, r, C] (the positions in lanes), the ring
    [_DEPTH, block, R] and [_DEPTH, r, block]."""
    nslots, rows, width = ql_ref.shape
    held, nblocks, copies, ring, fetch, primed = _block_ring(
        lens_ref, c_hbm, kr_hbm, cbuf, krbuf, sems, nslots, block,
        v_last=True)
    f32 = dict(preferred_element_type=jnp.float32)

    col = lax.broadcasted_iota(jnp.int32, (rows, block), 1)
    row = lax.broadcasted_iota(jnp.int32, (block, width), 0)

    def slot_body(b, carry):
        n = held(b)
        ql, qr = ql_ref[b], qr_ref[b]

        def block_body(j, carry):
            cursor, buf, (m, l, acc) = carry
            cursor = fetch(cursor)      # into the buffer freed a block ago
            for cp in copies(b, j, buf):
                cp.wait()
            here = n - j * block        # positions of the block that count
            # nothing stored past the length reaches the result
            c = jnp.where(row < here, cbuf[buf], 0)
            s = (lax.dot_general(ql, c, (((1,), (1,)), ((), ())), **f32)
                 + jnp.dot(qr, krbuf[buf], **f32)) * scale
            s = jnp.where(col < here, s, NEG_INF)
            m_new = jnp.maximum(m, jnp.max(s, axis=1, keepdims=True))
            alpha = jnp.exp2(m - m_new)
            p = jnp.exp2(s - m_new)
            l = alpha * l + jnp.sum(p, axis=1, keepdims=True)
            acc = alpha * acc + jnp.dot(p.astype(c.dtype), c, **f32)
            return cursor, ring(buf), (m_new, l, acc)

        init = (jnp.full((rows, 1), NEG_INF, jnp.float32),
                jnp.zeros((rows, 1), jnp.float32),
                jnp.zeros((rows, width), jnp.float32))
        cursor, buf, (_m, l, acc) = lax.fori_loop(0, nblocks(b), block_body,
                                                  (*carry, init))
        o_ref[b] = (acc / l).astype(o_ref.dtype)
        return cursor, buf

    lax.fori_loop(0, nslots, slot_body, (primed(), jnp.int32(0)))


def latent_decode_attention(q_lat, q_rope, cbuf, krbuf, lens, *, scale,
                            block=None, interpret=False):
    """The absorbed one-token step of latent attention. q_lat: [B, H, R]
    (``q_nope W_UK^T``), q_rope: [B, H, r], both in the cache's dtype;
    cbuf: [B, C, R], krbuf: [B, C, r], the new position's rows already
    written at ``min(lens, C - 1)``; lens: [B] lengths before the write.
    Returns [B, H, R] in the cache's dtype: softmax((q_lat . c + q_rope .
    k_rope) * scale) . c over positions ``0 .. min(lens, C - 1)``."""
    b, h, width = q_lat.shape
    c, r = cbuf.shape[1], krbuf.shape[2]
    block = block or sizes(c, 1, width, cbuf.dtype.itemsize)[0]
    if c % block:
        raise ValueError(f"latent_decode_attention: capacity {c} and "
                         f"block {block} must divide")
    return pl.pallas_call(
        functools.partial(_kernel_latent, block=block,
                          scale=scale * math.log2(math.e)),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(),
            in_specs=[pl.BlockSpec(memory_space=pltpu.VMEM),
                      pl.BlockSpec(memory_space=pltpu.VMEM),
                      pl.BlockSpec(memory_space=pl.ANY),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec(memory_space=pltpu.VMEM),
            scratch_shapes=[pltpu.VMEM((_DEPTH, block, width), cbuf.dtype),
                            pltpu.VMEM((_DEPTH, r, block), krbuf.dtype),
                            pltpu.SemaphoreType.DMA((2, _DEPTH))]),
        out_shape=jax.ShapeDtypeStruct((b, h, width), cbuf.dtype),
        interpret=interpret,
    )(lens.astype(jnp.int32), q_lat.astype(cbuf.dtype),
      q_rope.astype(cbuf.dtype), cbuf, jnp.swapaxes(krbuf, 1, 2))


def _decode_shared(q, kbuf, vbuf, lens, block, interpret):
    """``decode_attention`` where one KV head serves every query head."""
    b, _s, h, d = q.shape
    c = kbuf.shape[1]
    rows = -(-h // 8) * 8               # whole registers of queries
    qg = q.astype(jnp.float32).reshape(b, h, d) \
        * (math.log2(math.e) / math.sqrt(d))
    qg = jnp.pad(qg, ((0, 0), (0, rows - h), (0, 0)))
    out = pl.pallas_call(
        functools.partial(_kernel_shared, block=block),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(),
            in_specs=[pl.BlockSpec(memory_space=pltpu.VMEM),
                      pl.BlockSpec(memory_space=pl.ANY),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec(memory_space=pltpu.VMEM),
            scratch_shapes=[pltpu.VMEM((_DEPTH, block, d), kbuf.dtype),
                            pltpu.VMEM((_DEPTH, block, d), vbuf.dtype),
                            pltpu.SemaphoreType.DMA((2, _DEPTH))]),
        out_shape=jax.ShapeDtypeStruct((b, rows, d), jnp.float32),
        interpret=interpret,
    )(lens.astype(jnp.int32), qg, kbuf.reshape(b, c, d),
      vbuf.reshape(b, c, d))
    return out[:, :h].reshape(b, 1, h, d)


def decode_attention(q, kbuf, vbuf, lens, *, block=None, chunk=None,
                     interpret=False):
    """q: [B, 1, H, D]; kbuf/vbuf: [B, C, Hkv, D], the new token's k/v
    already written at position ``min(lens, C - 1)``; lens: [B] lengths
    before the write. Returns float32 [B, 1, H, D]: softmax(q . k / sqrt(D)) . v over
    positions ``0 .. min(lens, C - 1)``, query head ``kv * g + i`` against KV head
    ``kv``."""
    b, _s, h, d = q.shape
    c, hkv = kbuf.shape[1], kbuf.shape[2]
    g = h // hkv
    auto = sizes(c, hkv, d, kbuf.dtype.itemsize, g)
    block = block or auto[0]
    chunk = chunk or min(auto[1], block)
    if c % block or block % chunk:
        raise ValueError(f"decode_attention: capacity {c}, block {block}, "
                         f"chunk {chunk} must divide in turn")
    if shared_kv(hkv, g):
        return _decode_shared(q, kbuf, vbuf, lens, block, interpret)
    # [B, 1, Hkv*g, D] -> [B, g, Hkv, D]: one [Hkv, D] tile per query group,
    # scaled once for a softmax in base 2
    qg = jnp.swapaxes(q.astype(jnp.float32).reshape(b, hkv, g, d), 1, 2)
    qg = qg * (math.log2(math.e) / math.sqrt(d))
    out = pl.pallas_call(
        functools.partial(_kernel, block=block, chunk=chunk),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(),
            in_specs=[pl.BlockSpec(memory_space=pltpu.VMEM),
                      pl.BlockSpec(memory_space=pl.ANY),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec(memory_space=pltpu.VMEM),
            scratch_shapes=[pltpu.VMEM((_DEPTH, block, hkv, d), kbuf.dtype),
                            pltpu.VMEM((_DEPTH, block, hkv, d), vbuf.dtype),
                            pltpu.SemaphoreType.DMA((2, _DEPTH))]),
        out_shape=jax.ShapeDtypeStruct((b, g, hkv, d), jnp.float32),
        interpret=interpret,
    )(lens.astype(jnp.int32), qg, kbuf, vbuf)
    return jnp.swapaxes(out, 1, 2).reshape(b, 1, h, d)
