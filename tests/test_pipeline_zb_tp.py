"""Zero-bubble schedules UNDER tp>1 — the round-5 capability.

The reference's ZBH1/ZBVPP passes schedule under any hybrid strategy
(mp collectives inside a chunk are just host-issued ops,
pipeline_zero_bubble.py:62,:151). The compiled analogs compose with
tp>1 through the manual-tp stage body (models/gpt_manual_tp.py):
explicit collectives over a manual 'tp' axis inside the cond-gated
phases, legal because the phase predicates vary only over 'pp'.

Parity oracle: the GSPMD-auto 1F1B path on the SAME params/batch —
both paths must compute the identical loss and grads (f32 here so the
comparison is tight).
"""
import os

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from paddle_tpu.models.gpt import GPTConfig
from paddle_tpu.models import gpt_hybrid as GH


CFG = GPTConfig(vocab_size=64, hidden_size=32, num_layers=4,
                num_heads=4, max_seq_len=32, ffn_mult=2)


def _flat_blocks(grads, pcfg, cfg):
    """Reassemble stage-stacked block grads to the flat [L, ...] layout
    (handles the linear and ZB-V stackings)."""
    def fix(x):
        x = np.asarray(x)
        if pcfg.pp_schedule == "zbvpp":
            npp, L = pcfg.pp, cfg.num_layers
            ds = np.concatenate([np.arange(npp),
                                 np.arange(npp - 1, -1, -1)])
            ls = np.concatenate([np.zeros(npp, np.int64),
                                 np.ones(npp, np.int64)])
            return x[ds, ls].reshape((L,) + x.shape[3:])
        return x.reshape((-1,) + x.shape[2:])
    return {k: fix(v) for k, v in grads["blocks"].items()}


def _run(pcfg, cfg=CFG):
    key = jax.random.PRNGKey(0)
    ids = jax.random.randint(jax.random.PRNGKey(1), (4, 16), 0,
                             cfg.vocab_size)
    batch = (ids, ids)
    mesh = GH.build_mesh(pcfg)
    params = GH.init_params(cfg, pcfg, key)
    params, _specs = GH.shard_params(params, mesh, cfg, pcfg)
    with mesh:
        loss, grads = jax.jit(
            lambda p, b: GH._train_grads_1f1b(p, b, cfg, pcfg, mesh))(
                params, batch)
        loss.block_until_ready()
    return float(loss), {
        **_flat_blocks(grads, pcfg, cfg),
        "wte": np.asarray(grads["wte"]),
        "wpe": np.asarray(grads["wpe"]),
        "lnf_g": np.asarray(grads["lnf_g"]),
        "lnf_b": np.asarray(grads["lnf_b"]),
    }


def _parity(sched, sp, dp=1, cfg=CFG, cm=False):
    pk = dict(dp=dp, tp=2, pp=2, sp=sp, microbatches=4,
              param_dtype=jnp.float32, compute_dtype=jnp.float32,
              fused_ce=False, remat=True)
    # the oracle is always the GSPMD-auto 1F1B (no ring): the ring
    # collective matmuls must compute the same function
    rl, rg = _run(GH.ParallelConfig(pp_schedule="1f1b", **pk), cfg)
    zl, zg = _run(GH.ParallelConfig(pp_schedule=sched,
                                    collective_matmul=cm, **pk), cfg)
    np.testing.assert_allclose(zl, rl, rtol=2e-5)
    for k in rg:
        np.testing.assert_allclose(zg[k], rg[k], rtol=2e-4, atol=2e-5,
                                   err_msg=k)


@pytest.mark.parametrize("sp", [False, True])
def test_zbh1_tp2_matches_gspmd_1f1b(sp):
    """ZBH1 with a tp=2 stage body (explicit in-branch psums; sp adds
    all_gather/psum_scatter) computes the same loss+grads as the
    GSPMD-auto 1F1B engine."""
    _parity("zbh1", sp)


def test_zbvpp_tp2_sp_matches_gspmd_1f1b():
    """ZB-V with tp=2 + sequence parallel — the two-lane schedule whose
    in-tick phase races motivated the serialize_phases barriers."""
    _parity("zbvpp", True)


def test_zbh1_tp2_dp2_hybrid_composes():
    """dp2 x pp2 x tp2 (8 devices): the dp gradient psum sits outside
    the manual {'pp','tp'} region and must still compose."""
    _parity("zbh1", True, dp=2)


def test_manual_tp_guards():
    """Divisibility + platform guards fail fast with diagnoses."""
    from paddle_tpu.models.gpt_manual_tp import train_grads_zb_manual_tp
    pcfg = GH.ParallelConfig(dp=1, tp=2, pp=2, microbatches=2,
                             pp_schedule="zbh1")
    bad_heads = GPTConfig(vocab_size=64, hidden_size=30, num_layers=4,
                          num_heads=3, max_seq_len=32)
    ids = jnp.zeros((2, 8), jnp.int32)
    with pytest.raises(ValueError, match="num_heads"):
        train_grads_zb_manual_tp(None, (ids, ids), bad_heads, pcfg,
                                 None)
    # XLA:CPU needs the sequential thunk scheduler (conftest sets it);
    # without the flag the build must refuse with the diagnosis rather
    # than deadlock 40s into the first step
    old = os.environ.get("XLA_FLAGS", "")
    try:
        os.environ["XLA_FLAGS"] = old.replace(
            "--xla_cpu_enable_concurrency_optimized_scheduler=false",
            "")
        with pytest.raises(RuntimeError, match="concurrency"):
            train_grads_zb_manual_tp(None, (ids, ids), CFG, pcfg, None)
    finally:
        os.environ["XLA_FLAGS"] = old


def test_zbh1_tp2_nondivisible_vocab_pads():
    """vocab_size % tp != 0 (the GPT-2 50257 shape class): the manual
    head pads wte to a tp multiple with -inf-masked rows — same loss
    and grads as the GSPMD oracle, zero grads for rows that do not
    exist. Keeps planner-driven zero_bubble configs runnable for any
    vocab (round-5 review finding)."""
    cfg63 = GPTConfig(vocab_size=63, hidden_size=32, num_layers=4,
                      num_heads=4, max_seq_len=32, ffn_mult=2)
    _parity("zbh1", False, cfg=cfg63)


def test_collective_matmul_under_pp_via_manual_tp():
    """The round-4 'cm under pp>1' hole, closed for the LOCKSTEP 1F1B
    route: ring collective matmuls (sp_*_matmul_local) inside the
    manual-tp stage body — tp manual at the same level as pp, no
    nested region, so the Shardy wall (gpt_hybrid._use_cm) does not
    apply. The cond-gated zero-bubble schedules cannot host the
    ring (ppermute lowers to a whole-mesh op; idle stages never
    arrive — probe leg E) and must refuse it with a diagnosis."""
    _parity("1f1b", True, cm=True)
    with pytest.raises(ValueError, match="collective_matmul"):
        GH._validate_pp_schedule(GH.ParallelConfig(
            dp=1, tp=2, pp=2, sp=True, microbatches=4,
            pp_schedule="zbh1", collective_matmul=True))
    # planner precedence: zero_bubble wins, the ring is dropped
    from paddle_tpu.distributed.planner import PlanCandidate
    pc = PlanCandidate(dp=1, tp=2, pp=2, sp=True, microbatches=4)
    cfgzb = pc.to_parallel_config(zero_bubble=True)
    assert cfgzb.pp_schedule == "zbh1" and not cfgzb.collective_matmul


@pytest.mark.parametrize("sched", ["zbh1", "zbvpp"])
def test_zero_bubble_moe_manual_ep_matches_gspmd(sched):
    """Zero-bubble x EP-MoE (round 5, the last schedule composition):
    the manual-ep stage body — explicit all_to_all over the manual dp
    axis inside the cond-gated phases (probe leg F) — matches the
    GSPMD 1F1B MoE engine's loss and grads exactly."""
    cfg = GPTConfig(vocab_size=64, hidden_size=32, num_layers=4,
                    num_heads=4, max_seq_len=32, ffn_mult=2)
    pk = dict(dp=2, tp=1, pp=2, sp=False, microbatches=4,
              num_experts=4, param_dtype=jnp.float32,
              compute_dtype=jnp.float32, fused_ce=False, remat=True)
    ids = jax.random.randint(jax.random.PRNGKey(1), (8, 16), 0, 64)

    def run(pcfg):
        mesh = GH.build_mesh(pcfg)
        params = GH.init_params(cfg, pcfg, jax.random.PRNGKey(0))
        params, _ = GH.shard_params(params, mesh, cfg, pcfg)
        with mesh:
            loss, grads = jax.jit(
                lambda p, b: GH._train_grads_1f1b(p, b, cfg, pcfg,
                                                  mesh))(
                    params, (ids, ids))
            loss.block_until_ready()
        return float(loss), {
            **_flat_blocks(grads, pcfg, cfg),
            "wte": np.asarray(grads["wte"]),
            "lnf_g": np.asarray(grads["lnf_g"]),
        }

    rl, rg = run(GH.ParallelConfig(pp_schedule="1f1b", **pk))
    zl, zg = run(GH.ParallelConfig(pp_schedule=sched, **pk))
    np.testing.assert_allclose(zl, rl, rtol=2e-5)
    for k in rg:
        np.testing.assert_allclose(zg[k], rg[k], rtol=3e-4, atol=3e-5,
                                   err_msg=k)
