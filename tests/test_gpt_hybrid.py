"""Hybrid-parallel GPT engine on the virtual 8-device mesh: every
parallelism axis compiles and executes, and parallel losses match the
single-device run (the reference's hybrid_strategy loss-parity tests,
test/collective/fleet/hybrid_parallel_mp_model.py style)."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

import paddle_tpu.observability as obs
from chip_smoke import _moved_counters
from paddle_tpu.models.gpt import GPTConfig
from paddle_tpu.models.gpt_hybrid import (ParallelConfig, adamw_init,
                                          build_mesh, init_params, loss_fn,
                                          moment_specs, setup, shard_params)


CFG = GPTConfig(vocab_size=64, hidden_size=32, num_layers=4, num_heads=4,
                max_seq_len=16)


def _batch(b=8, s=16):
    rng = np.random.RandomState(0)
    ids = jnp.asarray(rng.randint(0, CFG.vocab_size, (b, s)))
    return ids, ids


def _ref_loss():
    pcfg = ParallelConfig(dp=1, pp=1, tp=1, param_dtype=jnp.float32,
                          compute_dtype=jnp.float32, remat=False)
    mesh = build_mesh(pcfg, jax.devices()[:1])
    params = init_params(CFG, pcfg, jax.random.PRNGKey(0))
    return float(loss_fn(params, _batch(), CFG, pcfg, mesh))


@pytest.mark.parametrize("pcfg_kw", [
    dict(dp=2, pp=1, tp=4),
    dict(dp=2, pp=1, tp=4, sp=True),
    dict(dp=1, pp=2, tp=2, microbatches=4),
    dict(dp=2, pp=2, tp=2, sp=True, microbatches=2),
])
def test_hybrid_loss_parity(pcfg_kw):
    ref = _ref_loss()
    pcfg = ParallelConfig(param_dtype=jnp.float32,
                          compute_dtype=jnp.float32, remat=False,
                          **pcfg_kw)
    mesh = build_mesh(pcfg)
    params = init_params(CFG, pcfg, jax.random.PRNGKey(0))
    with mesh:
        params, _ = shard_params(params, mesh, CFG, pcfg)
        loss = float(loss_fn(params, _batch(), CFG, pcfg, mesh))
    np.testing.assert_allclose(loss, ref, rtol=2e-5, atol=2e-5)


def test_train_step_runs_and_decreases():
    pcfg = ParallelConfig(dp=2, pp=2, tp=2, sp=True, microbatches=2,
                          param_dtype=jnp.float32,
                          compute_dtype=jnp.float32)
    mesh, params, opt_state, step = setup(CFG, pcfg, seed=0)
    batch = _batch()
    with mesh:
        losses = []
        for _ in range(5):
            params, opt_state, loss = step(params, opt_state, batch)
            losses.append(float(loss))
    assert losses[-1] < losses[0], losses


def test_moe_expert_parallel():
    pcfg = ParallelConfig(dp=2, pp=1, tp=2, num_experts=4,
                          param_dtype=jnp.float32,
                          compute_dtype=jnp.float32)
    mesh, params, opt_state, step = setup(CFG, pcfg, seed=0,
                                          devices=jax.devices()[:4])
    batch = _batch()
    with mesh:
        losses = []
        for _ in range(5):
            params, opt_state, loss = step(params, opt_state, batch)
            losses.append(float(loss))
    assert np.isfinite(losses).all()
    assert losses[-1] < losses[0], losses


# ------------------------------------------------ ZeRO-1's moment layout

#: dp x tp + sp as the four-chip cell has it, on four of the CPU devices
ZERO1 = dict(dp=2, pp=1, tp=2, sp=True, param_dtype=jnp.float32,
             compute_dtype=jnp.float32)
#: an odd hidden size: tp holds every other dim of a layer's leaves, so
#: none has a free dim of its own that divides by dp=2
ODD = GPTConfig(vocab_size=64, hidden_size=15, num_layers=4, num_heads=3,
                max_seq_len=16)


def _specs_of(cfg, **kw):
    pcfg = ParallelConfig(**{**ZERO1, **kw})
    mesh = build_mesh(pcfg, jax.devices()[:pcfg.dp * pcfg.pp * pcfg.tp])
    params = init_params(cfg, pcfg, jax.random.PRNGKey(0))
    with mesh:
        params, specs = shard_params(params, mesh, cfg, pcfg)
    with obs.window() as w:
        mspecs = moment_specs(params, pcfg, specs)
    return params, specs, mspecs, _moved_counters(
        w.delta, prefix="zero1.moment_shard")


@pytest.mark.parametrize("case", ["stacked_leaf", "inner_dims_do_not_divide",
                                  "pp2_stacking", "expert_leaf", "dp1"])
def test_moment_specs_put_dp_inside_the_layer(case):
    blocks = lambda t: t["blocks"]                       # noqa: E731
    if case == "stacked_leaf":
        _, _, ms, ticks = _specs_of(CFG)
        assert blocks(ms)["fc1_w"] == P(None, "dp", "tp")
        assert blocks(ms)["fc2_w"] == P(None, "tp", "dp")
        assert blocks(ms)["proj_w"] == P(None, "tp", "dp")
        assert blocks(ms)["qkv_w"] == P(None, "dp", "tp")
        assert blocks(ms)["ln1_g"] == P(None, "dp")
        # [L, 3h] over tp has no free dim of its own: the layer dim
        assert blocks(ms)["qkv_b"] == P("dp", "tp")
        assert ms["wte"] == P("tp", "dp")
        assert ticks == {"zero1.moment_shard{dim=in_layer}": 14,
                         "zero1.moment_shard{dim=layer}": 2}
    elif case == "inner_dims_do_not_divide":
        _, _, ms, ticks = _specs_of(ODD, tp=1, sp=False)
        assert blocks(ms)["qkv_w"] == P("dp", None, "tp")
        assert blocks(ms)["proj_w"] == P("dp", "tp", None)
        assert blocks(ms)["ln1_g"] == P("dp", None)
        assert ms["wpe"] == P("dp", None) and ms["lnf_g"] == P(None)
        assert ticks == {"zero1.moment_shard{dim=layer}": 12,
                         "zero1.moment_shard{dim=in_layer}": 1,
                         "zero1.moment_shard{dim=none}": 3}
    elif case == "pp2_stacking":
        # [pp, L/pp, ...]: neither stack dim (2 and 2 both divide by dp)
        params, _, ms, ticks = _specs_of(CFG, pp=2, tp=1, sp=False,
                                         microbatches=2)
        assert blocks(params)["fc1_w"].shape[:2] == (2, 2)
        assert blocks(ms)["fc1_w"] == P("pp", None, "dp", "tp")
        assert blocks(ms)["ln1_g"] == P("pp", None, "dp")
        # the fallback of a leaf with no free dim of its own
        assert blocks(ms)["qkv_b"] == P("pp", "dp", "tp")
        assert ticks["zero1.moment_shard{dim=layer}"] == 2
        # [pp, chunk, Lc, ...] where a stage holds two chunks
        params, _, ms, _ = _specs_of(CFG, pp=2, tp=1, sp=False,
                                     microbatches=2, pp_schedule="1f1b",
                                     vpp_chunks=2)
        assert blocks(params)["fc1_w"].shape[:3] == (2, 2, 1)
        assert blocks(ms)["fc1_w"] == P("pp", None, None, "dp", "tp")
    elif case == "expert_leaf":
        _, specs, ms, ticks = _specs_of(CFG, sp=False, num_experts=4)
        for leaf in ("fc1_w", "fc1_b", "fc2_w", "fc2_b"):
            assert blocks(ms)[leaf] == blocks(specs)[leaf]
        assert blocks(ms)["gate_w"] == P(None, "dp", None)
        assert ticks["zero1.moment_shard{dim=none}"] == 4
    else:
        _, specs, ms, ticks = _specs_of(CFG, dp=1, tp=4)
        assert ms == specs and ticks == {}


def _three_steps(**kw):
    pcfg = ParallelConfig(**{**ZERO1, **kw})
    mesh, params, opt, step = setup(CFG, pcfg, seed=0,
                                    devices=jax.devices()[:4])
    losses = []
    with mesh:
        for _ in range(3):
            params, opt, loss = step(params, opt, _batch())
            losses.append(float(loss))
    return jax.tree_util.tree_map(np.asarray, (params, opt)), losses


def test_zero1_in_layer_moments_train_as_unsharded_moments():
    """dp=2 x tp=2 + sp, three steps: the reduce-scatter into in-layer
    shards gives the parameters, moments and losses of zero1=False."""
    (p1, o1), l1 = _three_steps(zero1=True)
    (p0, o0), l0 = _three_steps(zero1=False)
    np.testing.assert_allclose(l1, l0, rtol=2e-5, atol=2e-5)
    for got, want in zip(jax.tree_util.tree_leaves((p1, o1)),
                         jax.tree_util.tree_leaves((p0, o0))):
        np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)


def test_moments_saved_over_the_layer_dim_restore_inside_the_layer(tmp_path):
    """A checkpoint holds moments by shape, not by sharding: one written
    under the parent's spec (dp over the layers) fills the new layout."""
    from paddle_tpu.core.tensor import Tensor
    from paddle_tpu.distributed.checkpoint import (load_state_dict,
                                                   save_state_dict)
    params, _, mspecs, _ = _specs_of(CFG)
    pcfg = ParallelConfig(**ZERO1)
    mesh = build_mesh(pcfg, jax.devices()[:4])
    old = {"fc1_w": P("dp", None, "tp"), "fc2_w": P("dp", "tp", None),
           "ln1_g": P("dp", None)}
    rng = np.random.RandomState(3)
    want = {k: rng.randn(*params["blocks"][k].shape).astype(np.float32)
            for k in old}
    save_state_dict(
        {k: Tensor(jax.device_put(want[k], NamedSharding(mesh, s)))
         for k, s in old.items()}, str(tmp_path / "ck"))
    opt = adamw_init(params, pcfg, mesh, None, mspecs=mspecs)
    into = {k: Tensor(opt["m"]["blocks"][k]) for k in old}
    load_state_dict(into, str(tmp_path / "ck"))
    for k in old:
        assert into[k]._data.sharding.spec == mspecs["blocks"][k] != old[k]
        np.testing.assert_array_equal(np.asarray(into[k]._data), want[k])
