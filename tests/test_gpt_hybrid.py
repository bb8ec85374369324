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
        assert blocks(ms)["qkv_w"] == P(None, "dp", None, "tp")
        assert blocks(ms)["ln1_g"] == P(None, "dp")
        # [L, 3, h] over tp has no free dim of its own that divides: the
        # layer dim
        assert blocks(ms)["qkv_b"] == P("dp", None, "tp")
        assert ms["wte"] == P("tp", "dp")
        assert ticks == {"zero1.moment_shard{dim=in_layer}": 14,
                         "zero1.moment_shard{dim=layer}": 2}
    elif case == "inner_dims_do_not_divide":
        _, _, ms, ticks = _specs_of(ODD, tp=1, sp=False)
        assert blocks(ms)["qkv_w"] == P("dp", None, None, "tp")
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
        assert blocks(ms)["qkv_b"] == P("pp", "dp", None, "tp")
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


# ------------------------------------------------ the qkv layout on the mesh

def _plain_logits(p, ids):
    """The benchmark's plain reference (``benchmarks/ledger/arch/
    gpt_dense.py``, what ``matches_reference`` compares a train cell with)
    on ``init_params``' own tree: the product over the flat [h, 3h] weight,
    split in thirds (columns [q | k | v])."""
    import importlib.util
    import os
    spec = importlib.util.spec_from_file_location(
        "ledger_arch_gpt_dense", os.path.join(
            os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
            "benchmarks", "ledger", "arch", "gpt_dense.py"))
    arch = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(arch)
    return arch.reference_logits(p, ids, CFG.num_heads)


def _plain_loss(p, ids):
    logp = jax.nn.log_softmax(_plain_logits(p, ids)[:, :-1], -1)
    return -jnp.mean(jnp.take_along_axis(logp, ids[:, 1:, None], -1))


@pytest.mark.parametrize("pcfg_kw", [
    dict(dp=1, pp=1, tp=1),
    dict(dp=2, pp=1, tp=2, sp=True),
    dict(dp=1, pp=2, tp=2, microbatches=4),
    # the manual-tp stage body (models/gpt_manual_tp.py)
    dict(dp=1, pp=2, tp=2, microbatches=4, pp_schedule="zbh1"),
], ids=["one_device", "dp2_tp2_sp", "pp2_tp2", "manual_tp"])
def test_qkv_layout_parity_with_a_plain_split_in_thirds(pcfg_kw):
    """``shard_params`` lays qkv_w [L, h, 3, h] with tp on the last dim:
    the same function of ``init_params``' numbers as the flat product
    split in thirds, logits and gradients, on every engine."""
    pcfg = ParallelConfig(param_dtype=jnp.float32, fused_ce=False,
                          compute_dtype=jnp.float32, **pcfg_kw)
    L, h = CFG.num_layers, CFG.hidden_size
    ids = _batch()[0]
    flat = init_params(CFG, pcfg, jax.random.PRNGKey(0))
    # a bias of zeros would not show a bias laid out wrongly
    flat["blocks"]["qkv_b"] = 0.02 * jax.random.normal(
        jax.random.PRNGKey(1), (L, 3 * h))
    assert flat["blocks"]["qkv_w"].shape == (L, h, 3 * h)
    want_loss, want = jax.value_and_grad(_plain_loss)(flat, ids)
    mesh = build_mesh(pcfg, jax.devices()[:pcfg.dp * pcfg.pp * pcfg.tp])
    with mesh:
        params, specs = shard_params(flat, mesh, CFG, pcfg)
        blocks = params["blocks"]
        assert blocks["qkv_w"].shape[-3:] == (h, 3, h)
        assert blocks["qkv_b"].shape[-2:] == (3, h)
        assert tuple(specs["blocks"]["qkv_w"])[-3:] == (None, None, "tp")
        assert blocks["qkv_w"].sharding.shard_shape(
            blocks["qkv_w"].shape)[-1] == h // pcfg.tp
        if pcfg.pp_schedule == "zbh1":
            from paddle_tpu.models.gpt_hybrid import _train_grads_1f1b
            loss, got = jax.jit(lambda p: _train_grads_1f1b(
                p, (ids, ids), CFG, pcfg, mesh))(params)
        else:
            from paddle_tpu.models.gpt_hybrid import forward
            logits = jax.jit(
                lambda p: forward(p, ids, CFG, pcfg, mesh))(params)
            np.testing.assert_allclose(logits, _plain_logits(flat, ids),
                                       atol=1e-5, rtol=0)
            loss, got = jax.jit(jax.value_and_grad(
                lambda p: loss_fn(p, (ids, ids), CFG, pcfg, mesh)))(params)
    np.testing.assert_allclose(loss, want_loss, atol=1e-5, rtol=0)
    for leaf in ("qkv_w", "qkv_b", "proj_w"):
        np.testing.assert_allclose(
            np.asarray(got["blocks"][leaf]).reshape(
                want["blocks"][leaf].shape),
            want["blocks"][leaf], atol=1e-5, rtol=0, err_msg=leaf)


def test_a_tree_saved_in_the_flat_shape_loads_into_the_new_one(tmp_path):
    """An older checkpoint holds qkv_w [L, h, 3h]: ``shard_params`` reads
    it by the reshape it applies to ``init_params``' tree, and a tree saved
    from ``setup`` ([L, h, 3, h]) passes through unchanged."""
    pcfg = ParallelConfig(dp=1, pp=1, tp=2, param_dtype=jnp.float32,
                          compute_dtype=jnp.float32)
    L, h = CFG.num_layers, CFG.hidden_size
    old = init_params(CFG, pcfg, jax.random.PRNGKey(0))["blocks"]
    np.savez(tmp_path / "old.npz", **old)
    saved = dict(np.load(tmp_path / "old.npz"))
    assert saved["qkv_w"].shape == (L, h, 3 * h)
    mesh, params, _, _ = setup(CFG, pcfg, seed=0, devices=jax.devices()[:2])
    for tree in ({**params, "blocks": saved}, params):
        with mesh:
            loaded, _ = shard_params(tree, mesh, CFG, pcfg)
        for k, v in loaded["blocks"].items():
            assert v.shape == params["blocks"][k].shape, k
            assert v.sharding == params["blocks"][k].sharding, k
            np.testing.assert_array_equal(v, params["blocks"][k])
    # column c*h + j of the flat weight is [c, j] of the new one
    np.testing.assert_array_equal(
        params["blocks"]["qkv_w"][1, :, 2, 5], saved["qkv_w"][1, :, 2 * h + 5])
