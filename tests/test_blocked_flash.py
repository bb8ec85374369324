"""q×kv-blocked flash attention (ops/pallas/blocked_flash.py).

Parity vs plain XLA attention — fwd and grads, causal and non-causal,
block-divisible and ragged sequence lengths — all in interpret mode so
the exact TPU kernel code runs on the CPU tier. Shapes are kept small:
the whole module must stay well under the ~15 s tier-1 budget.
"""
import math

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from paddle_tpu.ops.pallas import blocked_flash as bf


def _xla_ref(q, k, v, causal, scale=None):
    """Plain XLA attention in the kernel's [B,H,S,D] layout, f32."""
    d = q.shape[-1]
    sm = scale if scale is not None else 1.0 / math.sqrt(d)
    s = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * sm
    if causal:
        sq, sk = q.shape[2], k.shape[2]
        iq = jax.lax.broadcasted_iota(jnp.int32, (sq, sk), 0)
        ik = jax.lax.broadcasted_iota(jnp.int32, (sq, sk), 1)
        s = jnp.where((iq >= ik)[None, None], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", p,
                      v.astype(jnp.float32)).astype(q.dtype)


def _qkvw(b, h, sq, skv, d, seed=0):
    rng = np.random.RandomState(seed)
    mk = lambda s: jnp.asarray(rng.randn(b, h, s, d).astype(np.float32))
    return mk(sq), mk(skv), mk(skv), jnp.asarray(
        rng.randn(b, h, sq, d).astype(np.float32))


def _assert_parity(q, k, v, w, causal, bq, bkv, grad=True,
                   rtol=2e-4, atol=2e-4):
    out = bf.attention_bhsd(q, k, v, causal=causal, interpret=True,
                            block_q=bq, block_kv=bkv)
    ref = _xla_ref(q, k, v, causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=rtol, atol=atol)
    if not grad:
        return

    def loss(fn):
        return lambda q, k, v: jnp.sum(fn(q, k, v) * w)

    g = jax.grad(loss(lambda q, k, v: bf.attention_bhsd(
        q, k, v, causal=causal, interpret=True,
        block_q=bq, block_kv=bkv)), argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss(lambda q, k, v: _xla_ref(q, k, v, causal)),
                  argnums=(0, 1, 2))(q, k, v)
    for got, want, name in zip(g, gr, ("dq", "dk", "dv")):
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=rtol, atol=atol, err_msg=name)


@pytest.mark.parametrize("causal", [True, False])
def test_parity_multiblock(causal):
    # 2 q-blocks x 2 kv-blocks: exercises init/accumulate/finalize and
    # (causal) the diagonal-straddle mask plus one fully-skipped tile
    q, k, v, w = _qkvw(1, 2, 256, 256, 64)
    _assert_parity(q, k, v, w, causal, 128, 128)


def test_parity_unequal_blocks_causal():
    # bq != bkv: the diagonal crosses kv tiles mid-block, so last_ki /
    # straddle-detection logic differs from the square-block case
    # fwd-only: the bwd kernels' block geometry is already covered by
    # the grad checks above; a second causal grad trace would double
    # the module's interpret-mode tracing bill (~15 s budget)
    q, k, v, w = _qkvw(1, 1, 512, 512, 64, seed=1)
    _assert_parity(q, k, v, w, True, 128, 256, grad=False)


def test_parity_ragged_autoblocks():
    # S=384 is a multiple of 128 but of no preferred block: the picker
    # must fall back to 128 and stay exact
    assert bf._blocks_for(384, 384) == (128, 128)
    q, k, v, w = _qkvw(1, 1, 384, 384, 64, seed=2)
    _assert_parity(q, k, v, w, True, None, None, grad=False)


def test_parity_cross_attention():
    # S != Skv (non-causal): kv-block count differs from q-block count
    q, k, v, w = _qkvw(1, 1, 256, 384, 64, seed=3)
    _assert_parity(q, k, v, w, False, 128, 128, grad=False)


def test_shape_gate():
    # D not a lane multiple, ragged-by-128 seqs, causal cross-attn:
    # all rejected; the long-S shape the dispatch chain routes here is
    # accepted (no VMEM-derived S-cap)
    assert bf.supported((2, 8, 4096, 128), 4096, jnp.bfloat16, True)
    assert bf.supported((2, 8, 16384, 128), 16384, jnp.bfloat16, True)
    assert not bf.supported((2, 8, 512, 80), 512, jnp.bfloat16, True)
    assert not bf.supported((2, 8, 320, 128), 320, jnp.bfloat16, True)
    assert not bf.supported((2, 8, 512, 128), 1024, jnp.bfloat16, True)
    assert bf.supported((2, 8, 512, 128), 1024, jnp.bfloat16, False)
    assert not bf.supported((2, 8, 512, 128), 512, jnp.int8, True)


def test_block_candidates():
    # divisibility-filtered, preferred-first; ragged falls back to the
    # auto-picked pair so the autotuner always has >= 1 blocked variant
    assert bf.block_candidates(4096, 4096) == [
        (512, 512), (256, 512), (512, 1024)]
    assert bf.block_candidates(640, 640) == [(128, 128)]


def test_explicit_block_must_divide():
    q, k, v, _ = _qkvw(1, 1, 256, 256, 64)
    with pytest.raises(ValueError):
        bf.attention_bhsd(q, k, v, causal=True, interpret=True,
                          block_q=192, block_kv=128)


def test_dispatch_fallback_counted_not_raised(monkeypatch):
    """Ride-along fix: a head dim that is not a multiple of the lane
    width must route to plain XLA attention (return None) and tick the
    attn.dispatch_fallback counter — never raise."""
    import paddle_tpu.observability as obs
    from paddle_tpu.ops.pallas import flash_attention as fa

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")

    def delta(reason, q, k):
        c = obs.REGISTRY.counter("attn.dispatch_fallback",
                                 reason=reason)
        before = c.value
        assert fa.flash_attention_maybe(q, k, k, causal=True) is None
        return c.value - before

    q = jnp.zeros((1, 128, 2, 80), jnp.float32)     # D=80: 80 % 64 != 0
    assert delta("head_dim", q, q) == 1.0
    q = jnp.zeros((1, 100, 2, 64), jnp.float32)     # ragged seq
    assert delta("seq_len", q, q) == 1.0


# --------------------------------- the products' operands (PR 35)

def _normalised_errors(q, k, v, w, bq, bkv):
    """(out, dq, dk, dv) of the kernel on the arrays as they are, against
    the float32 reference on the same values: max-abs error over the
    reference's max-abs, as chip_smoke.check_kernel reports them."""
    run = lambda q, k, v: bf.attention_bhsd(
        q, k, v, causal=True, interpret=True, block_q=bq, block_kv=bkv)
    out, vjp = jax.vjp(run, q, k, v)
    got = (out,) + vjp(w)
    f32 = [x.astype(jnp.float32) for x in (q, k, v)]
    ref, ref_vjp = jax.vjp(lambda q, k, v: _xla_ref(q, k, v, True), *f32)
    want = (ref,) + ref_vjp(w.astype(jnp.float32))
    assert [g.dtype for g in got] == [q.dtype] * 4
    return [float(np.max(np.abs(np.asarray(g, np.float32) - np.asarray(r)))
                  / np.max(np.abs(np.asarray(r))))
            for g, r in zip(got, want)]


@pytest.mark.parametrize("dtype, d, s, bq, bkv", [
    (jnp.bfloat16, 64, 256, 128, 128),
    (jnp.bfloat16, 128, 256, 128, 128),
    (jnp.bfloat16, 64, 512, 128, 256),
    (jnp.float16, 64, 256, 128, 128),
], ids=["bf16-d64", "bf16-d128", "bf16-d64-bq128-bkv256", "f16-d64"])
def test_parity_in_the_arrays_dtype(dtype, d, s, bq, bkv):
    # bf16 / float16 arrays multiply natively: p and ds are rounded once,
    # to the operands' dtype, at their product, and each result once at
    # its store. The tolerance is four unit roundoffs of that dtype at the
    # largest entry (2 * eps: 1.6e-2 bf16, 2.0e-3 float16). Read in
    # interpret mode over two seeds when the products changed: 0.5-1.4
    # roundoffs, and 0.5-1.4 with float32 operands too: the store's own
    # rounding leads, not the operands'. A product or a tile left out
    # reads 50 roundoffs and more.
    q, k, v, w = (x.astype(dtype) for x in _qkvw(1, 2, s, s, d, seed=1))
    errs = _normalised_errors(q, k, v, w, bq, bkv)
    tol = 2 * float(jnp.finfo(dtype).eps)
    assert all(e <= tol for e in errs), (errs, tol)


def _kernel_products(jaxpr, found=None):
    """(lhs dtype, rhs dtype, result dtype) of every dot_general inside the
    pallas_call bodies of ``jaxpr``, through every nested jaxpr."""
    found = [] if found is None else found

    def subjaxprs(params):
        for val in params.values():
            for x in (val if isinstance(val, (tuple, list)) else (val,)):
                if hasattr(x, "jaxpr") and hasattr(x, "consts"):
                    yield x.jaxpr                       # ClosedJaxpr
                elif hasattr(x, "eqns"):
                    yield x

    def walk(jp, inside):
        for eqn in jp.eqns:
            kernel = inside or eqn.primitive.name == "pallas_call"
            if inside and eqn.primitive.name == "dot_general":
                found.append(tuple(str(a.aval.dtype) for a in
                                   (*eqn.invars, *eqn.outvars)))
            for sub in subjaxprs(eqn.params):
                walk(sub, kernel)
    walk(jaxpr, False)
    return found


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bf16", "f32"])
def test_every_product_takes_the_arrays_dtype(dtype):
    # the forward's 2 products, dq's 3, dk/dv's 4, each in a masked and an
    # unmasked body: operands as the arrays are, float32 accumulation
    q = jnp.zeros((1, 1, 256, 64), dtype)
    run = lambda q, k, v: bf.attention_bhsd(
        q, k, v, causal=True, interpret=True, block_q=128, block_kv=128)
    fwd = _kernel_products(jax.make_jaxpr(run)(q, q, q).jaxpr)
    both = _kernel_products(jax.make_jaxpr(jax.grad(
        lambda q, k, v: run(q, k, v).astype(jnp.float32).sum(),
        argnums=(0, 1, 2)))(q, q, q).jaxpr)
    name = str(jnp.dtype(dtype))
    assert len(fwd) == 2 * 2 and len(both) == 2 * (2 + 3 + 4)
    assert set(fwd + both) == {(name, name, "float32")}


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bf16", "f32"])
def test_matmul_operands_counted_once_a_traced_call(monkeypatch, dtype):
    """``attn.matmul_operands{kernel=blocked, dtype}`` ticks once a traced
    call, with the arrays' dtype, beside ``attn.dispatch`` and its one
    label (the train loop's check wants exactly one ``attn.dispatch{``
    key)."""
    import paddle_tpu.observability as obs
    from paddle_tpu.ops.pallas import flash_attention as fa

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    q = jnp.zeros((1, 8192, 2, 64), dtype)          # [B, S, H, D]
    with obs.window() as w:
        jax.make_jaxpr(lambda q, k, v: fa.flash_attention_maybe(
            q, k, v, causal=True))(q, q, q)
    from chip_smoke import _moved_counters
    assert _moved_counters(w.delta) == {
        "attn.dispatch{kernel=blocked}": 1,
        f"attn.matmul_operands{{dtype={jnp.dtype(dtype)},kernel=blocked}}": 1}


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


@pytest.mark.parametrize("shape, dtype, causal", [
    ((2, 32, 8192, 64), jnp.bfloat16, True),
    ((2, 8, 4096, 128), jnp.bfloat16, False),
    ((1, 2, 4096, 64), jnp.float32, True),
], ids=["lfm2_cell", "d128_noncausal", "f32"])
def test_kernels_compile_for_the_v5e(one_chip, no_compile_cache, shape,
                                     dtype, causal):
    # what interpret mode cannot show: Mosaic takes the three kernels as
    # written (the lane repeat, the transposed tile, the prefetched tables)
    x = jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def fwd_bwd(q, k, v, w):
        out, vjp = jax.vjp(lambda q, k, v: bf.attention_bhsd(
            q, k, v, causal=causal), q, k, v)
        return (out,) + vjp(w)
    compiled = jax.jit(fwd_bwd).lower(x, x, x, x).compile()
    assert compiled.as_text().count("tpu_custom_call") == 3
