"""The absorbed latent-attention step's kernel
(``ops/pallas/decode_attention.latent_decode_attention``) against the
einsum form of ``models/longcat_flash._absorbed`` it replaces on a TPU,
through the Pallas interpreter at tiny widths that keep the kernel's layout
(latent 128 or 256, rope 64, 8 or 16 heads): lengths around the block edges
and past the capacity, slots of mixed lengths in one call, values stored
past each length, float32 and bf16, the shape gate, the dispatch, and the
rope key's write in the layout the kernel reads."""
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu.observability as obs
from chip_smoke import _moved_counters
from paddle_tpu.inference import decode
from paddle_tpu.models import longcat_flash as lf
from paddle_tpu.ops.pallas import decode_attention as da

C, BLOCK, ROPE = 64, 16, 64
SCALE = 1.0 / math.sqrt(128 + ROPE)
#: one call: empty, one, both sides of a block edge, the capacity's edge
#: and past it (a lane stepped past its budget reads all C and no more)
EDGES = (0, 1, BLOCK - 1, BLOCK, BLOCK + 1, C - 1, C, C + 5)
LENGTHS = {"edges": EDGES, "all_at_0": (0,) * 3,
           "past_capacity": (C, 2 * C + 3, C - 1),
           "one_slot": (37,)}
#: (cache dtype, heads, latent width, tolerance on the largest difference
#: over the reference's largest value). float32: the order of an online
#: softmax's sums, read up to 4.1e-7 here. bf16: p is rounded to bf16
#: before its normalisation where the einsum form rounds it after, and the
#: output is rounded to bf16 on both sides: 2**-7, two of bf16's steps at
#: 1.0, read 2.8e-3 to 4.8e-3 over these cases
DTYPES = {"float32": (jnp.float32, 8, 128, 1e-5),
          "bfloat16": (jnp.bfloat16, 16, 256, 2.0 ** -7)}


def _inputs(lens, dtype, heads, width, seed=0):
    rng = np.random.RandomState(seed)
    b = len(lens)

    def draw(*shape, scale=1.0):
        return jnp.asarray(rng.randn(*shape) * scale, dtype)
    return (draw(b, heads, width, scale=0.2), draw(b, heads, ROPE),
            draw(b, C, width), draw(b, C, ROPE), jnp.asarray(lens, jnp.int32))


def _einsum(q_lat, q_rope, cbuf, krbuf, lens):
    """``_absorbed``'s einsum form between ``q_lat`` and ``W_UV``: the
    scores over all C columns, masked to ``min(lens, C - 1) + 1`` rows."""
    score = jnp.einsum("bhc,btc->bht", q_lat, cbuf,
                       preferred_element_type=jnp.float32) \
        + jnp.einsum("bhr,btr->bht", q_rope, krbuf,
                     preferred_element_type=jnp.float32)
    score = score * SCALE
    col = jnp.arange(cbuf.shape[1])
    score = jnp.where(col[None, None, :] < (lens + 1)[:, None, None], score,
                      -1e30)
    prob = jax.nn.softmax(score, axis=-1).astype(cbuf.dtype)
    return jnp.einsum("bht,btc->bhc", prob, cbuf,
                      preferred_element_type=jnp.float32)


def _kernel(q_lat, q_rope, cbuf, krbuf, lens):
    return da.latent_decode_attention(q_lat, q_rope, cbuf, krbuf, lens,
                                      scale=SCALE, block=BLOCK,
                                      interpret=True)


def _off(got, want):
    got = np.asarray(got.astype(jnp.float32))
    want = np.asarray(want.astype(jnp.float32))
    return float(np.abs(got - want).max() / np.abs(want).max())


@pytest.mark.parametrize("dtype", DTYPES)
@pytest.mark.parametrize("lens", LENGTHS.values(), ids=LENGTHS.keys())
def test_kernel_matches_the_einsum_form(dtype, lens):
    dt, heads, width, tol = DTYPES[dtype]
    args = _inputs(lens, dt, heads, width)
    out = _kernel(*args)
    assert out.dtype == dt and out.shape == args[0].shape
    assert _off(out, _einsum(*args)) <= tol


@pytest.mark.parametrize("poison", [3e4, np.nan], ids=["large", "nan"])
@pytest.mark.parametrize("dtype", DTYPES)
def test_nothing_past_the_length_reaches_the_result(dtype, poison):
    """Rows past each slot's length, in ``c`` and in ``k_rope``, hold a
    large value (or NaN): the result is the clean cache's."""
    dt, heads, width, tol = DTYPES[dtype]
    q_lat, q_rope, cbuf, krbuf, lens = _inputs(EDGES, dt, heads, width, 1)
    want = _einsum(q_lat, q_rope, cbuf, krbuf, lens)
    past = (jnp.arange(C)[None, :] > lens[:, None])[..., None]
    out = _kernel(q_lat, q_rope, jnp.where(past, poison, cbuf).astype(dt),
                  jnp.where(past, poison, krbuf).astype(dt), lens)
    assert bool(jnp.all(jnp.isfinite(out.astype(jnp.float32))))
    assert _off(out, want) <= tol


def test_the_block_comes_from_the_latent_row_bytes():
    # the cell: 1 KiB rows, 256 a block; a tiny one is one block
    assert da.sizes(8192, 1, 512, 2)[0] == 256
    assert da.sizes(64, 1, 128, 4)[0] == 64
    with pytest.raises(ValueError, match="must divide"):
        da.latent_decode_attention(
            *_inputs((3,), jnp.float32, 8, 128), scale=SCALE, block=48,
            interpret=True)


@pytest.mark.parametrize("q_shape, cache_shape, rope, dtype, reason", [
    ((48, 64, 512), (48, 8192, 512), 64, jnp.bfloat16, None),
    ((4, 64, 512), (4, 512, 512), 64, jnp.bfloat16, None),
    ((2, 8, 128), (2, 64, 128), 64, jnp.float32, None),
    ((2, 4, 16), (2, 32, 16), 4, jnp.float32, "latent_width"),
    ((2, 8, 128), (2, 64, 128), 64, jnp.bfloat16, "heads"),
    ((2, 16, 128), (2, 64, 128), 4, jnp.bfloat16, "rope_dim"),
    ((2, 16, 512), (2, 8448, 512), 64, jnp.bfloat16, "capacity"),
    ((512, 64, 512), (512, 1024, 512), 64, jnp.bfloat16, "vmem"),
], ids=["cell", "reference_check", "tiny", "latent_width", "heads",
        "rope_dim", "capacity", "vmem"])
def test_shape_gate(q_shape, cache_shape, rope, dtype, reason):
    assert da.latent_gate_reason(q_shape, cache_shape, rope, dtype) \
        == reason


# --------------------------------------------------------- the dispatch

def _absorbed_inputs(cfg, lens, seed=2):
    rng = np.random.RandomState(seed)
    b, nh, dn = len(lens), cfg.num_heads, cfg.qk_nope_head_dim

    def draw(*shape):
        return jnp.asarray(rng.randn(*shape), jnp.float32)
    p = {"kv_b_proj": draw(cfg.kv_lora_rank, nh * (dn + cfg.v_head_dim))}
    return (p, draw(b, 1, nh, dn), draw(b, 1, nh, cfg.qk_rope_head_dim),
            draw(b, C, cfg.kv_lora_rank), draw(b, C, cfg.qk_rope_head_dim),
            jnp.asarray(lens, jnp.int32) + 1)


def _absorbed(cfg, args):
    with obs.window() as w:
        out = lf._absorbed(cfg, *args)
    return out, _moved_counters(w.delta)


@pytest.mark.parametrize("lens", [EDGES, (0, 0)], ids=["edges", "all_at_0"])
def test_absorbed_takes_the_kernel_on_the_tpu_only(monkeypatch, lens):
    """Off the TPU ``_absorbed`` keeps its einsums; with the backend
    predicate turned on, a shape the gate takes goes through the kernel,
    is counted, and gives the einsum form's result."""
    cfg = lf.LongCatFlashConfig.tiny(kv_lora_rank=128, qk_rope_head_dim=ROPE,
                                     num_attention_heads=8)
    obs.enable()
    args = _absorbed_inputs(cfg, lens)
    with jax.default_matmul_precision("highest"):
        want, moved = _absorbed(cfg, args)
        assert moved == {"attn.latent_dispatch{kernel=einsum}": 1}
        monkeypatch.setattr(decode, "_kernel_backend", lambda: True)
        got, moved = _absorbed(cfg, args)
    assert moved == {"attn.latent_dispatch{kernel=ragged}": 1}
    assert _off(got, want) <= 1e-5


@pytest.mark.parametrize("kw, reason", [
    (dict(kv_lora_rank=16, qk_rope_head_dim=ROPE, num_attention_heads=8),
     "latent_width"),
    (dict(kv_lora_rank=128, qk_rope_head_dim=ROPE, num_attention_heads=4),
     "heads"),
    (dict(kv_lora_rank=128, qk_rope_head_dim=4, num_attention_heads=8),
     "rope_dim"),
], ids=["latent_width", "heads", "rope_dim"])
def test_a_refused_shape_keeps_the_einsum_form_and_is_counted(
        monkeypatch, kw, reason):
    cfg = lf.LongCatFlashConfig.tiny(**kw)
    obs.enable()
    args = _absorbed_inputs(cfg, (3, 20))
    want, _ = _absorbed(cfg, args)
    monkeypatch.setattr(decode, "_kernel_backend", lambda: True)
    got, moved = _absorbed(cfg, args)
    assert moved == {f"attn.dispatch_fallback{{reason={reason}}}": 1,
                     "attn.latent_dispatch{kernel=einsum}": 1}
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("s, lens", [(1, (0, 5, C - 1, C + 3)), (7, (0, 9))],
                         ids=["step", "prefill"])
def test_the_latent_write_puts_each_row_at_its_length(s, lens):
    """``latent_write`` writes the rope key through its [B, r, C] view (the
    layout the kernel reads, where a TPU keeps it): each slot's new rows
    land at ``clip(lens, 0, C - s)`` of both buffers, as a write in rows
    puts them, and nothing else moves."""
    rng = np.random.RandomState(3)
    b = len(lens)
    bufs = [rng.randn(b, C, w).astype(np.float32) for w in (128, ROPE)]
    new = [rng.randn(b, s, w).astype(np.float32) for w in (128, ROPE)]
    got = decode.latent_write(*map(jnp.asarray, bufs + new),
                              jnp.asarray(lens, jnp.int32))
    for g, buf, rows in zip(got, bufs, new):
        want = buf.copy()
        for i, n in enumerate(lens):
            start = min(max(n, 0), C - s)
            want[i, start:start + s] = rows[i]
        np.testing.assert_array_equal(np.asarray(g), want)
