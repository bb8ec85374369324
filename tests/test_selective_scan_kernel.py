"""The prefill scan kernel (ops/pallas/selective_scan.py) against the
sequential scan, in float32 through the Pallas interpreter: a state to start
from, lengths across the chunk's edge, tails of ``delta = 0``; through the
model with the backend predicate turned on; and compiled, not run, for the
v5e at the serving cell's shapes."""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
import paddle_tpu.observability as obs
from paddle_tpu.models import jamba
from paddle_tpu.ops.pallas import selective_scan as ss


def _inputs(b, s, inner, n, seed=0, tail=0):
    rng = np.random.RandomState(seed)
    x = rng.randn(b, s, inner)
    delta = np.log1p(np.exp(rng.randn(b, s, inner) - 3.0))
    if tail:
        delta[:, s - tail:] = 0.0
    a = -np.broadcast_to(np.arange(1.0, n + 1)[:, None], (n, inner))
    return [jnp.asarray(t, jnp.float32) for t in (
        x, delta, rng.randn(b, s, n), rng.randn(b, s, n), a,
        rng.randn(inner), rng.randn(b, n, inner))]


@pytest.mark.parametrize("b, s, inner, n, tail", [
    (1, 16, 256, 16, 0),        # one chunk of 16, a tile of all 2 rows
    (2, 256, 1024, 16, 0),      # two chunks of 128, a tile of 8 rows
    (1, 384, 2048, 4, 131),     # the tail starts inside the second chunk
    (1, 40, 128, 16, 39),       # chunks of 8 (gcd), all but one padding
], ids=["one_chunk", "two_chunks", "tail_across_chunks", "odd_length"])
def test_kernel_matches_the_sequential_scan(b, s, inner, n, tail):
    args = _inputs(b, s, inner, n, seed=s, tail=tail)
    want_y, want_h = ss.sequential(*args)
    y, h = ss.selective_scan(*args, interpret=True)
    assert y.shape == (b, s, inner) and h.shape == (b, n, inner)
    assert float(jnp.abs(y - want_y).max()) <= 1e-5
    assert float(jnp.abs(h - want_h).max()) <= 1e-5


def test_a_tail_of_zero_steps_leaves_the_state_of_the_prefix():
    """What the model leans on: positions with ``delta = 0`` pass the
    state through, so a padded scan ends where the valid prefix ended."""
    args = _inputs(1, 256, 256, 16, seed=3, tail=256 - 77)
    _y, h = ss.selective_scan(*args, interpret=True)
    short = [t[:, :77] for t in args[:4]] + args[4:]
    _y, want = ss.sequential(*short)
    assert float(jnp.abs(h - want).max()) <= 1e-6


def test_tiling_and_refusals():
    assert ss.tiling(1024, 5120) == (128, 8)
    assert ss.tiling(3072, 5120) == (128, 8)
    assert ss.tiling(16, 5120) == (16, 8)
    assert ss.tiling(48, 256) == (16, 2)
    with pytest.raises(ValueError, match="lanes"):
        ss.tiling(64, 100)
    args = _inputs(1, 16, 128, 4)
    with pytest.raises(ValueError, match="float32"):
        ss.selective_scan(args[0].astype(jnp.bfloat16), *args[1:],
                          interpret=True)


@pytest.mark.parametrize("plen", [100, 128, 129])
def test_in_the_model_the_kernel_gives_the_sequential_scans_state(
        monkeypatch, plen):
    """With the backend predicate on, a prefill goes through the kernel
    (interpreted here), is counted as ``chunked``, and a prompt padded to
    256 leaves the window, state and logits of the sequential scan over
    the unpadded one."""
    from paddle_tpu.inference import decode
    obs.enable()
    paddle.seed(4)
    model = jamba.JambaForCausalLM(jamba.JambaConfig.tiny(
        hidden_size=64, num_layers=3, attn_layer_period=3,
        initializer_range=0.3))
    model.eval()
    ids = np.random.RandomState(plen).randint(0, 96, (1, plen)) \
        .astype(np.int32)

    def prefill(ids, told):
        entries = decode._entries(model.init_cache(1, 256))
        if told is not None:
            entries = [e.prefilling(jnp.int32(told)) for e in entries]
        caches = jax.tree_util.tree_map(paddle.to_tensor, entries)
        logits, caches = model.forward_with_cache(paddle.to_tensor(ids),
                                                  caches)
        return logits.numpy(), decode._entries(caches)

    want, plain = prefill(ids, None)
    monkeypatch.setattr(jamba, "_kernel_backend", lambda: True)
    with obs.window() as w:
        got, told = prefill(np.pad(ids, ((0, 0), (0, 256 - plen))), plen)
    moved = {c["labels"]["kernel"]: c["value"] for c in w.delta.changed()
             if c["name"] == "ssm.scan_dispatch"}
    assert moved == {"chunked": 2}              # layers 0 and 2
    assert np.abs(got[:, :plen] - want).max() <= 1e-4
    for a, b in zip(told, plain):
        if isinstance(a, decode.RecurrentCache):
            np.testing.assert_allclose(a.conv, b.conv, atol=2e-5)
            np.testing.assert_allclose(a.ssm, b.ssm, atol=2e-5)


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


@pytest.mark.parametrize("b, s", [(1, 32), (1, 256), (1, 1024), (1, 3072),
                                  (4, 256)],
                         ids=["bucket32", "bucket256", "bucket1024",
                              "capacity", "reference_check"])
def test_kernel_compiles_for_the_v5e(one_chip, no_compile_cache, b, s):
    """The cell's shapes: 5,120 channels of 16 states, a prompt's bucket
    (or, for a DecodeSession, a batch of them): one kernel, and nothing of
    shape [S, I, N] beside it."""
    inner, n = 5120, 16

    def spec(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.float32, sharding=one_chip)
    compiled = jax.jit(functools.partial(ss.selective_scan)).lower(
        spec(b, s, inner), spec(b, s, inner), spec(b, s, n), spec(b, s, n),
        spec(n, inner), spec(inner), spec(b, n, inner)).compile()
    text = compiled.as_text()
    assert text.count("tpu_custom_call") == 1
    assert compiled.memory_analysis().temp_size_in_bytes \
        < 4 * 4 * b * s * inner         # not the [S, I, N] of a plain scan
