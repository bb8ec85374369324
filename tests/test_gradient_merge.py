"""Gradient merge (k-step accumulation) + no_sync deferral tests
(VERDICT r2 item 8; reference auto_parallel_gradient_merge.py and
DataParallel.no_sync)."""
import numpy as np

import paddle_tpu as paddle
from paddle_tpu import nn


def _mlp(seed=0):
    paddle.seed(seed)
    return nn.Sequential(nn.Linear(8, 16), nn.ReLU(), nn.Linear(16, 4))


def test_gradient_merge_matches_big_batch_sgd():
    """k merged microbatch steps == one step on the k-times batch."""
    paddle.seed(0)
    x = paddle.randn([16, 8])
    y = paddle.randn([16, 4])
    loss_fn = nn.MSELoss()

    m1 = _mlp()
    opt1 = paddle.optimizer.GradientMergeOptimizer(
        paddle.optimizer.SGD(0.1, parameters=m1.parameters()), k_steps=4)
    for i in range(4):
        loss = loss_fn(m1(x[i * 4:(i + 1) * 4]), y[i * 4:(i + 1) * 4])
        loss.backward()
        opt1.step()
        opt1.clear_grad()

    m2 = _mlp()
    opt2 = paddle.optimizer.SGD(0.1, parameters=m2.parameters())
    loss = loss_fn(m2(x), y)
    loss.backward()
    opt2.step()
    opt2.clear_grad()

    for p1, p2 in zip(m1.parameters(), m2.parameters()):
        np.testing.assert_allclose(p1.numpy(), p2.numpy(), rtol=1e-6,
                                   atol=1e-6)


def test_gradient_merge_inner_not_stepped_midwindow():
    m = _mlp()
    inner = paddle.optimizer.SGD(0.1, parameters=m.parameters())
    opt = paddle.optimizer.GradientMergeOptimizer(inner, k_steps=3)
    w0 = m[0].weight.numpy().copy()
    x = paddle.randn([4, 8])
    for i in range(2):
        loss = paddle.mean(m(x))
        loss.backward()
        opt.step()
        opt.clear_grad()
        np.testing.assert_array_equal(m[0].weight.numpy(), w0)
    loss = paddle.mean(m(x))
    loss.backward()
    opt.step()
    assert not np.array_equal(m[0].weight.numpy(), w0)


def test_fleet_strategy_gradient_merge_wires_up():
    from paddle_tpu.distributed import fleet
    from paddle_tpu.optimizer.gradient_merge import GradientMergeOptimizer
    strategy = fleet.DistributedStrategy()
    strategy.gradient_merge = True
    strategy.gradient_merge_configs = {"k_steps": 2, "avg": True}
    fleet.init(is_collective=True, strategy=strategy)
    m = _mlp()
    opt = fleet.distributed_optimizer(
        paddle.optimizer.SGD(0.1, parameters=m.parameters()))
    assert isinstance(opt, GradientMergeOptimizer)
    assert opt._k_steps == 2


def test_hybrid_engine_compiled_gradient_merge():
    """ParallelConfig.gradient_merge_steps: merged compiled step matches
    the unmerged step on the same global batch."""
    import jax
    from paddle_tpu.models.gpt import GPTConfig
    from paddle_tpu.models.gpt_hybrid import ParallelConfig, setup
    cfg = GPTConfig.tiny()
    ids = np.random.default_rng(0).integers(0, 256, (8, 16))

    losses = {}
    params_out = {}
    for k in (1, 2):
        pcfg = ParallelConfig(dp=1, pp=1, tp=1, gradient_merge_steps=k,
                              remat=False)
        mesh, params, opt_state, step = setup(cfg, pcfg, seed=0,
                                              devices=jax.devices()[:1])
        batch = (ids, ids)
        with mesh:
            params, opt_state, loss = step(params, opt_state, batch)
        losses[k] = float(loss)
        params_out[k] = jax.tree_util.tree_map(np.asarray, params)
    assert np.isclose(losses[1], losses[2], rtol=1e-4)
    flat1 = jax.tree_util.tree_leaves(params_out[1])
    flat2 = jax.tree_util.tree_leaves(params_out[2])
    for a, b in zip(flat1, flat2):
        # chunked bf16 grad reduction can flip near-zero grad signs; the
        # first-Adam-step bound is 2*lr = 6e-4 for such params
        np.testing.assert_allclose(a, b, rtol=2e-3, atol=7e-4)


def test_no_sync_defers_explicit_collectives():
    """Inside no_sync, framework collectives are recorded (no traffic);
    exit replays each deduped call once."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P
    from paddle_tpu.distributed import collective as C
    from paddle_tpu.distributed.parallel import DataParallel
    from paddle_tpu.distributed.mesh import ProcessMesh

    mesh = ProcessMesh(shape=[len(jax.devices())], dim_names=["dp"])
    m = _mlp()
    dp = DataParallel(m, mesh=mesh)

    g = paddle.randn([8, 4])
    g._data = jax.device_put(g._data,
                             NamedSharding(mesh.jax_mesh, P("dp", None)))
    executed = []
    orig_put = jax.device_put

    def counting_put(*a, **k):
        executed.append(1)
        return orig_put(*a, **k)

    with dp.no_sync():
        jax.device_put = counting_put
        try:
            # the grad-sync collective fires twice (two microbatches)
            C.all_reduce(g)
            C.all_reduce(g)
            assert executed == []            # zero cross-device traffic
            assert not g._data.sharding.is_fully_replicated
        finally:
            jax.device_put = orig_put
    # on exit: replayed ONCE (deduped), grad now replicated
    assert g._data.sharding.is_fully_replicated


def test_no_sync_defers_stage2_relay():
    """GroupShardedStage2's grad re-lay hook is deferred under no_sync."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P
    from paddle_tpu.distributed import collective as C
    from paddle_tpu.distributed.sharding import GroupShardedStage2
    from paddle_tpu.distributed.mesh import ProcessMesh

    mesh = ProcessMesh(shape=[len(jax.devices())], dim_names=["dp"])
    m = _mlp()
    st2 = GroupShardedStage2(m, group=None)
    x = paddle.randn([8, 8])
    with C.defer_collectives():
        loss = paddle.mean(st2(x))
        loss.backward()
        # inside the window no grad has been re-laid to the sharded spec
        for p in m.parameters():
            if p.grad is not None:
                assert p.grad._data.sharding.is_fully_replicated
    # after exit the largest-dim grads are group-sharded
    relaid = [p for p in m.parameters()
              if p.grad is not None
              and not p.grad._data.sharding.is_fully_replicated]
    assert relaid, "stage-2 re-lay should have fired at window exit"


def test_split_accum_composes_with_pipeline():
    """Gradient merge under pp in the compiled engines (VERDICT r3
    item 10): the split accum engine at pp=2 accumulates stage grads
    across k=2 outer 1F1B rounds; its update matches the FUSED
    gradient_merge_steps=2 run exactly (same chunks, same order) and a
    single 2x-microbatch step closely (same math, different reduction
    order). Reference: auto_parallel_gradient_merge.py composing with
    the pipeline passes."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.models.gpt import GPTConfig
    from paddle_tpu.models import gpt_hybrid as GH

    cfg = GPTConfig(vocab_size=128, hidden_size=32, num_layers=4,
                    num_heads=2, max_seq_len=32)
    ids = jnp.asarray(np.random.RandomState(0).randint(0, 128, (8, 32)))
    base = dict(dp=1, pp=2, tp=1, microbatches=2, pp_schedule="1f1b",
                remat=True)

    def fresh_state(pcfg):
        mesh = GH.build_mesh(pcfg, jax.devices()[:2])
        with mesh:
            params = GH.init_params(cfg, pcfg, jax.random.PRNGKey(0))
            params, specs = GH.shard_params(params, mesh, cfg, pcfg)
            mspecs = GH.moment_specs(params, pcfg, specs)
            opt = GH.adamw_init(params, pcfg, mesh, specs, mspecs=mspecs)
        return mesh, params, opt, specs, mspecs

    # split engine: two half-batch 1F1B chunks + one apply
    pcfg = GH.ParallelConfig(**base)
    mesh, params, opt, specs, mspecs = fresh_state(pcfg)
    grad_step, apply_step = GH.build_accum_steps(
        cfg, pcfg, mesh, state_specs=(specs, mspecs))
    acc = GH.init_grad_accum(params)
    with mesh:
        acc, _ = grad_step(params, acc, (ids[:4], ids[:4]))
        acc, _ = grad_step(params, acc, (ids[4:], ids[4:]))
        p_split, _o, _a = apply_step(params, opt, acc, 2)

    # fused engine: gradient_merge_steps=2 over the same global batch
    pcfg_f = GH.ParallelConfig(gradient_merge_steps=2, **base)
    mesh_f, params_f, opt_f, _, _ = fresh_state(pcfg_f)
    step_f = GH.build_train_step(cfg, pcfg_f, mesh_f)
    with mesh_f:
        p_fused, _o, _l = step_f(params_f, opt_f, (ids, ids))

    for a, b in zip(jax.tree_util.tree_leaves(p_split),
                    jax.tree_util.tree_leaves(p_fused)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-6, atol=1e-6)

    # and a single 2x-microbatch step over the full batch (same update
    # math, reduction order differs -> close, not bitwise)
    pcfg_b = GH.ParallelConfig(**{**base, "microbatches": 4})
    mesh_b, params_b, opt_b, _, _ = fresh_state(pcfg_b)
    step_b = GH.build_train_step(cfg, pcfg_b, mesh_b)
    with mesh_b:
        p_big, _o, _l = step_b(params_b, opt_b, (ids, ids))
    for a, b in zip(jax.tree_util.tree_leaves(p_split),
                    jax.tree_util.tree_leaves(p_big)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-4, atol=2e-4)


def test_gradient_merge_composes_with_zero_bubble_schedules():
    """gradient_merge_steps=2 at pp=2 produces the SAME update under
    the 1f1b, zbh1 and zbvpp compiled schedules — merge composes with
    the zero-bubble rings exactly as with 1F1B (the schedules compute
    identical gradients, so the merged update must be identical too)."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.models.gpt import GPTConfig
    from paddle_tpu.models import gpt_hybrid as GH

    cfg = GPTConfig(vocab_size=128, hidden_size=32, num_layers=4,
                    num_heads=2, max_seq_len=32)
    ids = jnp.asarray(np.random.RandomState(0).randint(0, 128, (8, 32)))
    outs = {}
    for sched in ("1f1b", "zbh1", "zbvpp"):
        pcfg = GH.ParallelConfig(dp=1, pp=2, tp=1, microbatches=2,
                                 pp_schedule=sched, remat=True,
                                 gradient_merge_steps=2)
        mesh, params, opt, step = GH.setup(cfg, pcfg, seed=0,
                                           devices=jax.devices()[:2])
        with mesh:
            p1, _o, loss = step(params, opt, (ids, ids))
        outs[sched] = (float(loss),
                       jax.tree_util.tree_leaves(
                           jax.tree_util.tree_map(np.asarray, p1)))
    for sched in ("zbh1", "zbvpp"):
        np.testing.assert_allclose(outs["1f1b"][0], outs[sched][0],
                                   rtol=2e-6)
        for a, b in zip(outs["1f1b"][1], outs[sched][1]):
            if a.shape != b.shape:     # zbvpp stacks blocks [pp,2,Lc]
                b = b.reshape(a.shape) if a.size == b.size else b
            assert a.size == b.size
            np.testing.assert_allclose(
                np.sort(a.reshape(-1)), np.sort(b.reshape(-1)),
                rtol=5e-5, atol=1e-6)
