"""The length-aware decode attention kernel (ops/pallas/decode_attention.py)
against ``_cache_attention``'s einsum path, in float32 through the Pallas
interpreter: ragged lengths around the block edges, lanes shown at length 0,
poisoned positions past the valid length, the dispatch and its shape gate,
and the kernel compiled for the v5e at the serving cells' cache shapes (the
latent attention kernel, the cache write's program and the expert layer
beside it: one process describes the chip)."""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu.observability as obs
from chip_smoke import _moved_counters
from paddle_tpu.inference import decode
from paddle_tpu.ops.pallas import decode_attention as da

C, HKV, D, BLOCK = 64, 2, 128, 16
#: one batch: empty, full, and both sides of a block edge
RAGGED = (0, C - 1, BLOCK - 1, BLOCK, BLOCK + 1, 40)
#: lengths at and past the capacity, the last slot's among them: what a lane
#: reads when a decode block steps it past its budget (the write clamps to
#: the last position, the mask takes all C)
PAST_CAPACITY = (C, 5, C + BLOCK, C - 1, 2 * C + 3)


def _inputs(lens, g, c=C, hkv=HKV, d=D, seed=0):
    rng = np.random.RandomState(seed)
    b = len(lens)

    def draw(*shape):
        return jnp.asarray(rng.randn(*shape), jnp.float32)
    return (draw(b, 1, hkv * g, d), draw(b, 1, hkv, d), draw(b, 1, hkv, d),
            draw(b, c, hkv, d), draw(b, c, hkv, d),
            jnp.asarray(lens, jnp.int32))


def _kernel(q, kbuf, vbuf, lens):
    return da.decode_attention(q, kbuf, vbuf, lens, block=BLOCK, chunk=8,
                               interpret=True)


@pytest.mark.parametrize("lens", [RAGGED, (0,) * 4, (0, 33, 0, 0, 63),
                                  PAST_CAPACITY],
                         ids=["ragged", "all_at_0", "some_at_0",
                              "past_capacity"])
@pytest.mark.parametrize("g", [1, 4], ids=["mha", "gqa4"])
def test_kernel_matches_the_einsum_path(g, lens):
    q, kn, vn, kbuf, vbuf, lens = _inputs(lens, g)
    ref, kbuf, vbuf, _ = decode._cache_attention(q, kn, vn, kbuf, vbuf, lens)
    out = _kernel(q, kbuf, vbuf, lens)
    assert out.dtype == jnp.float32 and out.shape == q.shape
    assert float(jnp.max(jnp.abs(out - ref))) <= 1e-5


@pytest.mark.parametrize("g", [1, 4], ids=["mha", "gqa4"])
def test_nothing_past_the_valid_length_reaches_the_result(g):
    q, kn, vn, kbuf, vbuf, lens = _inputs(RAGGED, g, seed=1)
    ref, kbuf, vbuf, _ = decode._cache_attention(q, kn, vn, kbuf, vbuf, lens)
    past = jnp.arange(C)[None, :, None, None] > lens[:, None, None, None]
    out = _kernel(q, jnp.where(past, jnp.nan, kbuf),
                  jnp.where(past, jnp.nan, vbuf), lens)
    assert bool(jnp.all(jnp.isfinite(out)))
    assert float(jnp.max(jnp.abs(out - ref))) <= 1e-5


@pytest.mark.parametrize("lens", [RAGGED, (0,) * 4, PAST_CAPACITY],
                         ids=["ragged", "all_at_0", "past_capacity"])
@pytest.mark.parametrize("g", [8, 20], ids=["mqa8", "mqa20"])
def test_one_kv_head_under_many_queries_goes_through_the_mxu_form(g, lens):
    """``Hkv`` = 1 and ``g`` >= 8 (ISSUE 32's shape: 20 query heads over
    one KV head): whole blocks as two float32 products at ``highest``; the
    einsum path's result, and nothing stored past the length reaches it."""
    assert da.shared_kv(1, g) and not da.shared_kv(1, 4) \
        and not da.shared_kv(2, 20)
    q, kn, vn, kbuf, vbuf, lens = _inputs(lens, g, hkv=1, seed=2)
    ref, kbuf, vbuf, _ = decode._cache_attention(q, kn, vn, kbuf, vbuf, lens)
    past = jnp.arange(C)[None, :, None, None] \
        > jnp.minimum(lens, C - 1)[:, None, None, None]
    out = _kernel(q, jnp.where(past, jnp.nan, kbuf),
                  jnp.where(past, jnp.nan, vbuf), lens)
    assert out.dtype == jnp.float32 and out.shape == q.shape
    assert bool(jnp.all(jnp.isfinite(out)))
    assert float(jnp.max(jnp.abs(out - ref))) <= 1e-5


def test_sizes_come_from_the_cache_shape_in_code():
    # the serving cells: 256 KB of K a block, 32 registers of scores a chunk
    assert da.sizes(2048, 16, 128, 4) == (32, 16)
    assert da.sizes(1024, 16, 128, 4) == (32, 16)
    assert da.sizes(2048, 8, 128, 4, g=4) == (64, 8)    # GQA: g scores each
    assert da.sizes(64, 2, 128, 4) == (64, 32)          # all of a tiny one
    with pytest.raises(ValueError, match="must divide"):
        da.decode_attention(*(_inputs((0,), 1)[i] for i in (0, 3, 4, 5)),
                            block=48, interpret=True)


@pytest.mark.parametrize("cache_shape, dtype, reason", [
    ((12, 2048, 16, 128), jnp.float32, None),
    ((24, 1024, 16, 128), jnp.float32, None),
    ((8, 2048, 8, 128), jnp.float32, None),
    ((2, 64, 2, 16), jnp.float32, "head_dim"),
    ((2, 64, 2, 128), jnp.float16, "cache_dtype"),
    ((8, 2048, 8, 128), jnp.bfloat16, "cache_dtype"),
    ((2, 7, 1024, 128), jnp.float32, "vmem"),
], ids=["longctx", "chat", "gqa", "head_dim", "float16", "bfloat16", "vmem"])
def test_shape_gate(cache_shape, dtype, reason):
    b, _c, hkv, d = cache_shape
    assert da.gate_reason((b, 1, hkv, d), cache_shape, dtype) == reason


def _attn_moves(window):
    return _moved_counters(window.delta)


@pytest.mark.parametrize("lens", [RAGGED, PAST_CAPACITY],
                         ids=["ragged", "past_capacity"])
def test_dispatch_takes_the_one_token_step_only(monkeypatch, lens):
    """Off the TPU, and for s > 1 anywhere, ``_cache_attention`` runs the
    einsums it ran before; with the backend predicate turned on, the
    one-token call goes through the kernel, is counted, and returns the
    same cache and lengths, bit for bit."""
    obs.enable()
    args = _inputs(lens, 2)
    with obs.window() as w:
        ref = decode._cache_attention(*args)
    assert _attn_moves(w) == {}
    monkeypatch.setattr(decode, "_kernel_backend", lambda: True)
    with obs.window() as w:
        got = decode._cache_attention(*args)
    assert _attn_moves(w) == {"attn.dispatch{kernel=decode_ragged}": 1}
    assert float(jnp.max(jnp.abs(got[0] - ref[0]))) <= 1e-5
    for a, b in zip(got[1:], ref[1:]):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # a prefill (s = 3) is not the kernel's
    q, kn, vn, kbuf, vbuf, lens = args
    pre = [jnp.tile(x, (1, 3, 1, 1)) for x in (q, kn, vn)]
    with obs.window() as w:
        decode._cache_attention(*pre, kbuf, vbuf, jnp.minimum(lens, C - 3))
    assert _attn_moves(w) == {}


def test_a_refused_shape_falls_back_and_is_counted(monkeypatch):
    obs.enable()
    args = _inputs((0, 5, 63), 2, d=16)
    ref = decode._cache_attention(*args)
    monkeypatch.setattr(decode, "_kernel_backend", lambda: True)
    with obs.window() as w:
        got = decode._cache_attention(*args)
    assert _attn_moves(w) == {"attn.dispatch_fallback{reason=head_dim}": 1}
    np.testing.assert_array_equal(np.asarray(got[0]), np.asarray(ref[0]))


# ------------------------------------------- compiled for the chip, not run

@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def no_compile_cache():
    # a compile for a described chip cannot be read back from the cache
    from jax.experimental.compilation_cache import compilation_cache as cc
    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", before)
    cc.reset_cache()


@pytest.mark.parametrize("b, c, hkv, g, dtype", [
    (12, 2048, 16, 1, jnp.float32),
    (24, 1024, 16, 1, jnp.float32),
    (2, 512, 16, 1, jnp.float32),
    (8, 2048, 8, 4, jnp.float32),
    (8, 1024, 2, 2, jnp.float32),
    (256, 3072, 1, 20, jnp.float32),
], ids=["longctx", "chat", "reference_check", "gqa4", "hkv2", "shared_kv"])
def test_kernel_compiles_for_the_v5e(one_chip, no_compile_cache, b, c, hkv,
                                     g, dtype):
    def spec(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
    cache = spec((b, c, hkv, 128), dtype)
    assert da.gate_reason((b, 1, hkv * g, 128), cache.shape, dtype) is None
    compiled = jax.jit(functools.partial(da.decode_attention)).lower(
        spec((b, 1, hkv * g, 128), jnp.bfloat16), cache, cache,
        spec((b,), jnp.int32)).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("b, c", [(48, 8192), (4, 512)],
                         ids=["longcat", "reference_check"])
def test_latent_kernel_compiles_for_the_v5e(one_chip, no_compile_cache, b,
                                            c):
    """The absorbed latent step's kernel at LongCat's widths (64 heads,
    latent 512, rope 64, bf16), in the cell and in its check before the
    window: the rope keys are read where the chip keeps them (positions in
    lanes: the transposed view is a bitcast, with no copy of the cache) and
    nothing of size C is written beside the result."""
    def spec(shape, dt=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
    assert da.latent_gate_reason((b, 64, 512), (b, c, 512), 64,
                                 jnp.bfloat16) is None
    compiled = jax.jit(functools.partial(
        da.latent_decode_attention, scale=192 ** -0.5)).lower(
        spec((b, 64, 512)), spec((b, 64, 64)), spec((b, c, 512)),
        spec((b, c, 64)), spec((b,), jnp.int32)).compile()
    text = compiled.as_text()
    assert text.count("tpu_custom_call") == 1 and " copy(" not in text
    assert compiled.memory_analysis().temp_size_in_bytes == 0


@pytest.mark.parametrize("b, c, hkv, s, dtype", [
    (24, 1024, 16, 1, jnp.float32),
    (12, 2048, 16, 1, jnp.float32),
    (32, 1280, 4, 4, jnp.float32),
    (12, 2048, 16, 256, jnp.float32),
    (8, 1024, 4, 4, jnp.bfloat16),
], ids=["chat", "longctx", "blockdiff", "batch_prefill", "bfloat16_gqa4"])
def test_cache_write_compiles_for_the_v5e(one_chip, no_compile_cache, b, c,
                                          hkv, s, dtype):
    """The cache write's program (ops/pallas/cache_write.py; its results
    are tests/test_cache_write_kernel.py's) at the three serving cells'
    shapes, a DecodeSession's batched prefill and a bfloat16 cache: one
    kernel, the donated buffers written in place with no copy of either
    and no loop over the slots. Kept in this file: one process describes
    the chip."""
    from paddle_tpu.ops.pallas.cache_write import write_rows

    def spec(shape, dt=dtype):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
    cache, new = spec((b, c, hkv, 128)), spec((b, s, hkv, 128))
    compiled = jax.jit(write_rows, donate_argnums=(0, 1)).lower(
        cache, cache, new, new, spec((b,), jnp.int32)).compile()
    text = compiled.as_text()
    assert text.count("tpu_custom_call") == 1
    assert " while(" not in text and " copy(" not in text
    assert compiled.memory_analysis().temp_size_in_bytes == 0


@pytest.mark.parametrize("tokens", [128, 1024], ids=["block_pass", "prefill"])
def test_expert_layer_compiles_for_the_v5e(one_chip, no_compile_cache,
                                           monkeypatch, tokens):
    """The dropless expert layer at SDAR-30B-A3B's widths (128 experts of
    2048 x 768, top-8) through the megablox grouped product, as a block
    pass (32 lanes x 4 positions) and a b=1 prefill of 1024 see it. Kept
    in this file: one process describes the chip."""
    from paddle_tpu.models import sdar_moe
    monkeypatch.setattr(sdar_moe, "_kernel_backend", lambda: True)

    def spec(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
    layer = functools.partial(sdar_moe.expert_ffn, expert_offset=0,
                              num_experts=128)
    compiled = jax.jit(layer).lower(
        spec((tokens, 2048), jnp.bfloat16), spec((tokens, 8), jnp.float32),
        spec((tokens, 8), jnp.int32), spec((128, 2048, 1536), jnp.bfloat16),
        spec((128, 768, 2048), jnp.bfloat16)).compile()
    assert compiled.as_text().count("tpu_custom_call") >= 2
