"""Monolithic Pallas attention kernel numerics (interpret mode on CPU;
the on-device win was read on an earlier chip by a probe in git history
before PR 30: 1.33 vs 2.31 ms/layer fwd+bwd against the library flash
kernel)."""
import math

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from paddle_tpu.ops.pallas.simple_attention import (attention_bhsd,
                                                    supported)

B, H, S, D = 2, 3, 256, 128


def naive(q, k, v, causal=True):
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k) / math.sqrt(D)
    if causal:
        mask = jnp.tril(jnp.ones((S, S), bool))
        s = jnp.where(mask, s, -1e30)
    p = jax.nn.softmax(s, -1)
    return jnp.einsum("bhqk,bhkd->bhqd", p, v)


@pytest.fixture(scope="module")
def qkv():
    key = jax.random.PRNGKey(0)
    mk = lambda i: jax.random.normal(jax.random.fold_in(key, i),
                                     (B, H, S, D), jnp.float32)
    return mk(0), mk(1), mk(2)


@pytest.mark.parametrize("causal", [True, False])
def test_forward_matches_naive(qkv, causal):
    q, k, v = qkv
    out = attention_bhsd(q, k, v, causal=causal, interpret=True)
    np.testing.assert_allclose(np.asarray(out),
                               np.asarray(naive(q, k, v, causal)),
                               rtol=2e-4, atol=2e-5)


@pytest.mark.parametrize("argi", [0, 1, 2])
def test_grads_match_naive(qkv, argi):
    q, k, v = qkv
    args = [q, k, v]

    def fp(t):
        a = list(args)
        a[argi] = t
        return attention_bhsd(*a, causal=True, interpret=True).sum()

    def fn(t):
        a = list(args)
        a[argi] = t
        return naive(*a, True).sum()

    np.testing.assert_allclose(np.asarray(jax.grad(fp)(args[argi])),
                               np.asarray(jax.grad(fn)(args[argi])),
                               rtol=2e-3, atol=2e-4)


def test_supported_gate():
    assert supported((8, 8, 1024, 128), jnp.bfloat16)
    assert not supported((8, 8, 4096, 128), jnp.bfloat16)  # VMEM blow
    assert not supported((8, 8, 1000, 128), jnp.bfloat16)  # not tiled


class TestQBlockKernel:
    """Q-blocked variant for longer sequences (simple_attention2):
    streams q in blocks, accumulates dk/dv across the q-block grid."""

    def test_fwd_and_dk_accumulation(self):
        from paddle_tpu.ops.pallas.simple_attention2 import (
            attention_bhsd as qb, _pick_bq)
        S2 = 1024
        assert _pick_bq(S2, 128, 4) < S2  # blocking actually engaged? 
        key = jax.random.PRNGKey(1)
        mk = lambda i: jax.random.normal(jax.random.fold_in(key, i),
                                         (1, 2, S2, 128), jnp.float32)
        q, k, v = mk(0), mk(1), mk(2)

        def dense(q, k, v):
            s = jnp.einsum("bhqd,bhkd->bhqk", q, k) / math.sqrt(128)
            mask = jnp.tril(jnp.ones((S2, S2), bool))
            s = jnp.where(mask, s, -1e30)
            return jnp.einsum("bhqk,bhkd->bhqd",
                              jax.nn.softmax(s, -1), v)

        out = qb(q, k, v, causal=True, interpret=True)
        np.testing.assert_allclose(np.asarray(out),
                                   np.asarray(dense(q, k, v)),
                                   rtol=2e-4, atol=2e-5)
        gk = jax.grad(lambda t: qb(q, t, v, causal=True,
                                   interpret=True).sum())(k)
        gk_ref = jax.grad(lambda t: dense(q, t, v).sum())(k)
        np.testing.assert_allclose(np.asarray(gk), np.asarray(gk_ref),
                                   rtol=2e-3, atol=2e-4)

    def test_supported_ranges(self):
        from paddle_tpu.ops.pallas import simple_attention2 as sa2
        assert sa2.supported((4, 8, 2048, 128), jnp.bfloat16)
        # S=4096 needs whole-k/v f32 in VMEM (~8 MB) + strips: over
        # budget -> falls back to the library streaming flash kernel
        assert not sa2.supported((1, 8, 4096, 128), jnp.bfloat16)
        assert not sa2.supported((1, 8, 2048, 100), jnp.bfloat16)
