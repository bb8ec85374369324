"""The cache write as one Pallas program (ops/pallas/cache_write.py) against
the vmapped ``dynamic_update_slice`` it stands in for, bit for bit, through
the Pallas interpreter: one and four new positions, MHA and GQA rows, float32
and bfloat16 buffers, lengths from 0 to past the capacity (the clamp), every
position outside the written rows left as it was, K and V kept apart, and
the choice of form in ``decode._write_kv``. The program compiled for the
v5e at the serving cells' shapes is in tests/test_decode_attention_kernel.py:
one file, so one process, describes the chip."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

import paddle_tpu.observability as obs
from chip_smoke import _moved_counters
from paddle_tpu.inference import decode
from paddle_tpu.ops.pallas import cache_write as cw

C, D = 32, 128


def _update_slice(buf, new, lens):
    return jax.vmap(
        lambda b, n, l: lax.dynamic_update_slice(b, n, (l, 0, 0))
    )(buf, new, lens)


def _inputs(lens, s, hkv, dtype, seed=0):
    """Buffers that hold a pattern (no two elements of K and V alike in
    float32), new rows drawn apart for K and for V."""
    rng = np.random.RandomState(seed)
    b = len(lens)
    n = b * C * hkv * D
    kbuf = jnp.arange(n, dtype=jnp.float32).reshape(b, C, hkv, D)
    vbuf = -1.0 - kbuf
    kn = jnp.asarray(rng.randn(b, s, hkv, D), jnp.float32)
    vn = jnp.asarray(rng.randn(b, s, hkv, D), jnp.float32)
    return (*(x.astype(dtype) for x in (kbuf, vbuf, kn, vn)),
            jnp.asarray(lens, jnp.int32))


def _same(a, b):
    assert a.dtype == b.dtype and a.shape == b.shape
    np.testing.assert_array_equal(np.asarray(a.astype(jnp.float32)),
                                  np.asarray(b.astype(jnp.float32)))


def _edges(s):
    """One batch: empty, mid-buffer, the last place a write fits, at the
    capacity, and far past it with the last slot's among them."""
    return (0, 13, C - s, C, C - s + 1, 2 * C + 3)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
@pytest.mark.parametrize("hkv", [16, 4], ids=["mha16", "gqa4"])
@pytest.mark.parametrize("s", [1, 4], ids=["s1", "s4"])
def test_program_writes_what_update_slice_writes(s, hkv, dtype):
    kbuf, vbuf, kn, vn, lens = _inputs(_edges(s), s, hkv, dtype)
    k2, v2 = cw.write_rows(kbuf, vbuf, kn, vn, lens, interpret=True)
    _same(k2, _update_slice(kbuf, kn, lens))
    _same(v2, _update_slice(vbuf, vn, lens))


@pytest.mark.parametrize("s", [1, 4], ids=["s1", "s4"])
@pytest.mark.parametrize("length", [0, 13, C - 4, C - 1, C, C + 7, 5 * C])
def test_a_length_is_clamped_as_update_slice_clamps_it(length, s):
    """The write starts at ``min(length, C - s)``, in every slot, the last
    one too: past the end of the cache it lands on the last rows."""
    kbuf, vbuf, kn, vn, lens = _inputs((3, length, length), s, 4,
                                       jnp.float32, seed=1)
    k2, v2 = cw.write_rows(kbuf, vbuf, kn, vn, lens, interpret=True)
    start = min(length, C - s)
    for got, buf, new in ((k2, kbuf, kn), (v2, vbuf, vn)):
        for slot in (1, 2):
            _same(got[slot, start:start + s], new[slot])
            _same(got[slot, :start], buf[slot, :start])
            _same(got[slot, start + s:], buf[slot, start + s:])
        _same(got, _update_slice(buf, new, lens))


@pytest.mark.parametrize("s", [1, 4], ids=["s1", "s4"])
def test_nothing_outside_the_written_rows_moves(s):
    """The new rows lie inside a larger array whose other rows are NaN: a
    copy that took a row too many, or from a neighbour, would carry one
    into the cache; and every position the write does not own keeps the
    buffer's pattern."""
    lens = _edges(s)
    kbuf, vbuf, kn, vn, lens = _inputs(lens, s, 4, jnp.float32, seed=2)

    def poisoned(new):
        wide = jnp.full((len(lens) + 2, s + 2, 4, D), jnp.nan, jnp.float32)
        return wide.at[1:-1, 1:-1].set(new)[1:-1, 1:-1]
    k2, v2 = jax.jit(lambda *a: cw.write_rows(
        a[0], a[1], poisoned(a[2]), poisoned(a[3]), a[4], interpret=True))(
        kbuf, vbuf, kn, vn, lens)
    start = np.minimum(np.asarray(lens), C - s)[:, None]
    pos = np.arange(C)[None, :]
    owned = (pos >= start) & (pos < start + s)
    for got, buf in ((k2, kbuf), (v2, vbuf)):
        got, buf = np.asarray(got), np.asarray(buf)
        assert np.all(np.isfinite(got))
        np.testing.assert_array_equal(got[~owned], buf[~owned])
        assert not np.any(got[owned] == buf[owned])


def test_k_and_v_are_not_swapped():
    shape = (3, C, 4, D)
    new = jnp.ones((3, 1, 4, D), jnp.float32)
    k2, v2 = cw.write_rows(jnp.zeros(shape), jnp.zeros(shape), new, 2 * new,
                           jnp.asarray((0, 7, C), jnp.int32), interpret=True)
    assert float(jnp.max(k2)) == 1.0 and float(jnp.sum(k2)) == 3 * 4 * D
    assert float(jnp.max(v2)) == 2.0 and float(jnp.sum(v2)) == 6 * 4 * D


@pytest.mark.parametrize("what, match", [
    ("dtype", "buffers' dtypes"), ("too_long", "into a cache of")])
def test_write_rows_refuses_what_it_cannot_copy(what, match):
    kbuf, vbuf, kn, vn, lens = _inputs((0, 5), 1, 4, jnp.float32)
    if what == "dtype":
        kn = kn.astype(jnp.bfloat16)
    else:
        kn = vn = jnp.zeros((2, C + 1, 4, D), jnp.float32)
    with pytest.raises(ValueError, match=match):
        cw.write_rows(kbuf, vbuf, kn, vn, lens, interpret=True)


# ------------------------------------------------- the choice of the form

def _write_moves(window):
    return _moved_counters(window.delta, prefix="cache.write_dispatch")


@pytest.mark.parametrize("slots, tpu, form", [
    (3, True, "row_dma"), (1, True, "update_slice"),
    (3, False, "update_slice"), (1, False, "update_slice")],
    ids=["slots_on_tpu", "one_slot_on_tpu", "slots_elsewhere",
         "one_slot_elsewhere"])
@pytest.mark.parametrize("s", [1, 4], ids=["s1", "s4"])
def test_the_shape_and_the_backend_choose_the_form(monkeypatch, s, slots,
                                                   tpu, form):
    """A batch of slots on a TPU goes through the program; one slot (a
    prefill into a session's slot) and every other backend keep the
    ``dynamic_update_slice``. Either way it is counted by its form, never
    as an attention fallback, and writes the same bytes."""
    obs.enable()
    kbuf, vbuf, kn, vn, lens = _inputs((C, 9, C - s)[:slots], s, 4,
                                       jnp.float32, seed=3)
    monkeypatch.setattr(decode, "_kernel_backend", lambda: tpu)
    with obs.window() as w:
        k2, v2 = decode._write_kv(kbuf, vbuf, kn, vn, lens)
    assert _write_moves(w) == {f"cache.write_dispatch{{kernel={form}}}": 1}
    assert _moved_counters(w.delta) == {}
    _same(k2, _update_slice(kbuf, kn, lens))
    _same(v2, _update_slice(vbuf, vn, lens))


@pytest.mark.parametrize("block", [None, 4], ids=["causal", "block4"])
def test_cache_attention_returns_the_same_through_either_form(monkeypatch,
                                                              block):
    """``_cache_attention`` at s = 4 (no decode kernel: the einsums read
    the buffers the write left) with the backend predicate off and on: the
    same output, buffers and lengths, bit for bit, a lane past the capacity
    among them; and a bfloat16 query's rows are cast to the float32
    cache's dtype before the copy."""
    obs.enable()
    rng = np.random.RandomState(4)
    lens = jnp.asarray((0, 12, C - 4, C + 4), jnp.int32)
    q, kn, vn = (jnp.asarray(rng.randn(4, 4, 4, D), jnp.bfloat16)
                 for _ in range(3))
    kbuf, vbuf = (jnp.asarray(rng.randn(4, C, 4, D), jnp.float32)
                  for _ in range(2))
    ref = decode._cache_attention(q, kn, vn, kbuf, vbuf, lens, block=block)
    monkeypatch.setattr(decode, "_kernel_backend", lambda: True)
    with obs.window() as w:
        got = decode._cache_attention(q, kn, vn, kbuf, vbuf, lens,
                                      block=block)
    assert _write_moves(w) == {"cache.write_dispatch{kernel=row_dma}": 1}
    assert _moved_counters(w.delta) == {}
    for a, b in zip(got, ref):
        _same(a, b)
