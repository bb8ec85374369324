"""LFM2-MoE on the training path (ISSUE 34): the model against the plain
float32 reference the benchmark keeps (``benchmarks/ledger/arch/
lfm2_moe.py``), the shares of an expert layer against the uncut layer, the
grouped product's backward rule against ``ragged_dot``'s own, the expert
bias rule, and a step under ``gpt_hybrid``'s engine; tiny sizes, CPU."""
import dataclasses
import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu.observability as obs
from paddle_tpu.models import gpt_hybrid as gh
from paddle_tpu.models import lfm2_moe as lm
from paddle_tpu.models import sdar_moe


def _load_arch():
    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "benchmarks", "ledger", "arch",
        "lfm2_moe.py")
    spec = importlib.util.spec_from_file_location("ledger_arch_lfm2_moe",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


arch = _load_arch()
F32 = dict(param_dtype=jnp.float32, compute_dtype=jnp.float32,
           remat_policy="names",
           remat_save_names=("qkv", "attn_out", "in_proj", "ffn1"))
#: all three layer kinds: conv + dense, attention + experts, conv + experts;
#: 5 of 8 experts held, from the third on
CFG = lm.LFM2MoeConfig.tiny(num_layers=3, num_local_experts=5,
                            expert_offset=2)
HYPER = dict(num_experts_per_tok=2, expert_offset=2)


@pytest.fixture(scope="module")
def model():
    pcfg = gh.ParallelConfig(**F32)
    mesh = gh.build_mesh(pcfg, jax.devices()[:1])
    params = lm.init_params(CFG, pcfg, jax.random.PRNGKey(0))
    # a bias that moves the choice, as training leaves it
    params["expert_bias"] = 0.1 * jax.random.normal(
        jax.random.PRNGKey(5), params["expert_bias"].shape)
    ids = np.random.RandomState(0).randint(0, CFG.vocab_size, (2, 24))
    return pcfg, mesh, params, ids


@pytest.fixture(scope="module")
def grads(model):
    pcfg, mesh, params, ids = model
    train, frozen = lm.TRAIN_MODEL.split(params)
    batch = (jnp.asarray(ids), jnp.asarray(ids))
    with mesh:
        (loss, routing), got = jax.value_and_grad(
            lambda t: lm.loss_and_routing({**t, **frozen}, batch, CFG, pcfg,
                                          mesh), has_aux=True)(train)
    load = lm.expert_load(routing, CFG.num_experts)
    want_loss, want, own, margin = arch.reference_loss_and_grads(
        params, ids, CFG.num_heads, **HYPER)
    # in float32 the program's choice is the reference's wherever the
    # margin is more than rounding
    clear = np.asarray(margin) > 1e-5
    assert clear.mean() > 0.9
    assert (np.sort(np.asarray(routing), -1)[clear]
            == np.sort(np.asarray(own), -1)[clear]).all()
    return loss, load, got, want_loss, want


def _leaf_paths():
    pcfg = gh.ParallelConfig(**F32)
    shapes = jax.eval_shape(
        lambda k: lm.init_params(CFG, pcfg, k), jax.random.PRNGKey(0))
    train, _frozen = lm.TRAIN_MODEL.split(shapes)
    return [jax.tree_util.keystr(path) for path, _leaf
            in jax.tree_util.tree_leaves_with_path(train)]


def test_forward_matches_the_reference(model):
    pcfg, mesh, params, ids = model
    with mesh:
        got = lm.forward(params, jnp.asarray(ids), CFG, pcfg, mesh)
    want = arch.reference_logits(params, ids, CFG.num_heads, **HYPER)
    assert got.shape == (2, 24, CFG.vocab_size)
    assert float(jnp.max(jnp.abs(got - want)) / jnp.max(jnp.abs(want))) \
        < 1e-5


def test_loss_matches_the_reference_and_the_load_counts_every_pair(grads):
    loss, load, _got, want_loss, _want = grads
    assert abs(float(loss) - float(want_loss)) < 1e-5
    assert load.shape == (2, CFG.num_experts) and load.dtype == jnp.int32
    assert load.sum(axis=1).tolist() == [2 * 24 * 2] * 2


@pytest.mark.parametrize("path", _leaf_paths())
def test_every_leafs_gradient_matches_the_reference(grads, path):
    _loss, _load, got, _want_loss, want = grads
    flat_got = {jax.tree_util.keystr(p): g for p, g
                in jax.tree_util.tree_leaves_with_path(got)}
    flat_want = {jax.tree_util.keystr(p): g for p, g
                 in jax.tree_util.tree_leaves_with_path(want)}
    g, w = flat_got[path], flat_want[path]
    assert float(jnp.linalg.norm(w)) > 0
    assert float(jnp.linalg.norm(g - w) / jnp.linalg.norm(w)) < 1e-4


def test_the_bias_moves_the_choice_and_has_no_gradient(model):
    pcfg, mesh, params, ids = model
    batch = (jnp.asarray(ids), jnp.asarray(ids))
    with mesh:
        g = jax.grad(lambda b: lm.loss_fn({**params, "expert_bias": b},
                                          batch, CFG, pcfg, mesh))(
            params["expert_bias"])
        _x, with_bias = lm.forward_hidden(params, jnp.asarray(ids), CFG,
                                          pcfg, mesh)
        _x, without = lm.forward_hidden(
            {**params, "expert_bias": jnp.zeros_like(params["expert_bias"])},
            jnp.asarray(ids), CFG, pcfg, mesh)
    assert float(jnp.max(jnp.abs(g))) == 0.0
    assert not np.array_equal(np.asarray(with_bias), np.asarray(without))


# ------------------------------------------------------- the shares add up

@pytest.mark.parametrize("shares", [4, 2])
def test_the_shares_of_an_expert_layer_add_up_to_the_uncut_layer(shares):
    """64 tiny experts, 8 a token; each share holds 64 / shares of them,
    routes over all 64 and computes its own part: the parts add up to the
    plain reference's output for the whole layer."""
    experts, top_k, hid, inter, tokens = 64, 8, 16, 8, 40
    key = jax.random.split(jax.random.PRNGKey(7), 5)
    u = jax.random.normal(key[0], (1, tokens, hid))
    p = {"router": jax.random.normal(key[1], (hid, experts)),
         "gate_up": 0.3 * jax.random.normal(key[2], (experts, hid, 2 * inter)),
         "down": 0.3 * jax.random.normal(key[3], (experts, inter, hid))}
    bias = 0.2 * jax.random.normal(key[4], (experts,))
    hyper = dict(arch.HYPER, num_experts_per_tok=top_k)
    whole = arch._experts(u[0], p, bias, hyper)[0]
    held = experts // shares
    total, pairs = 0.0, 0
    for share in range(shares):
        cfg = lm.LFM2MoeConfig.tiny(
            hidden_size=hid, moe_intermediate_size=inter,
            num_experts=experts, num_experts_per_tok=top_k,
            num_local_experts=held, expert_offset=share * held)
        mine = {"router": p["router"],
                "gate_up": p["gate_up"][share * held:(share + 1) * held],
                "down": p["down"][share * held:(share + 1) * held]}
        y, routing = lm.moe_ffn(u, mine, bias, cfg)
        load = lm.expert_load(routing, experts)
        total = total + y[0]
        pairs += int(load[share * held:(share + 1) * held].sum())
        # the reference given the same share computes the same part
        part = arch._experts(u[0], mine, bias,
                             dict(hyper, expert_offset=share * held))[0]
        assert float(jnp.max(jnp.abs(y[0] - part))) < 1e-5
    assert pairs == tokens * top_k
    assert float(jnp.max(jnp.abs(total - whole))) < 1e-5


# ------------------------------------- the grouped product's backward rule

#: group sizes over 6 groups of which the first 4 are held: uneven, with
#: rows of absent groups, and with an empty held group
GROUPS = {"uneven": [5, 9, 3, 7, 6, 2], "empty_held_group": [7, 0, 11, 4, 3, 7],
          "nothing_absent": [10, 2, 9, 11, 0, 0],
          "one_group_has_all": [0, 0, 32, 0, 0, 0]}


@pytest.mark.parametrize("case", sorted(GROUPS))
@pytest.mark.parametrize("which", ["rows", "weights"])
def test_grouped_backward_rule_is_ragged_dots_own(case, which):
    sizes = jnp.asarray(GROUPS[case], jnp.int32)
    m, k, n, held = int(sizes.sum()), 16, 24, 4
    key = jax.random.split(jax.random.PRNGKey(3), 3)
    lhs = jax.random.normal(key[0], (m, k))
    rhs = jax.random.normal(key[1], (held, k, n))
    probe = jax.random.normal(key[2], (m, n))
    arg = 0 if which == "rows" else 1

    def through(interpret):
        def f(lhs, rhs):
            return jnp.sum(probe * sdar_moe._grouped(
                lhs, rhs, sizes, jnp.float32, interpret=interpret))
        return jax.grad(f, argnums=arg)(lhs, rhs)

    got, want = through(True), through(False)
    assert got.shape == want.shape
    assert float(jnp.max(jnp.abs(got - want))) < 1e-4
    if which == "rows":              # rows of absent groups receive zero
        absent = int(sizes[:held].sum())
        assert float(jnp.max(jnp.abs(got[absent:]), initial=0.0)) == 0.0
    else:                            # an empty held group gets no gradient
        for g, size in enumerate(GROUPS[case][:held]):
            if size == 0:
                assert float(jnp.max(jnp.abs(got[g]))) == 0.0


def test_a_differentiated_kernel_trace_counts_its_three_passes():
    sizes = jnp.asarray([5, 9, 3, 7], jnp.int32)
    lhs, rhs = jnp.ones((24, 16)), jnp.ones((4, 16, 24))
    with obs.window() as w:
        jax.grad(lambda a: jnp.sum(sdar_moe._grouped(
            a, rhs, sizes, jnp.float32, interpret=True)))(lhs)
    moved = {c["labels"]["pass"]: c["value"] for c in w.delta.changed()
             if c["name"] == "moe.grouped_dispatch"}
    assert moved == {"fwd": 1, "dx": 1, "dw": 1}


# ------------------------------------- the held experts' buffer (ISSUE 37)

def _row_buffers(delta):
    return {c["labels"]["rows"]: c["value"] for c in delta.changed()
            if c["name"] == "moe.row_buffer"}


@pytest.mark.parametrize("held, moved", [
    (2, {"held": 1}), (8, {})], ids=["a_share", "every_expert"])
def test_a_traced_layer_counts_the_buffer_it_built(held, moved):
    """512 tokens x 2 of 8 experts: a holder of two walks its pairs a
    buffer of 512 rows a trip; a holder of all eight passes over all 1,024
    once and counts nothing."""
    cfg = lm.LFM2MoeConfig.tiny(num_local_experts=held, expert_offset=0)
    h = cfg.hidden_size
    p = {"router": jnp.ones((h, 8)),
         "gate_up": jnp.ones((held, h, 2 * cfg.moe_intermediate_size)),
         "down": jnp.ones((held, cfg.moe_intermediate_size, h))}
    with obs.window() as w:
        jax.make_jaxpr(lambda u: lm.moe_ffn(u, p, jnp.zeros((8,)), cfg))(
            jnp.ones((2, 256, h)))
    assert _row_buffers(w.delta) == moved


def test_a_step_that_overflows_the_buffer_runs_the_same_program():
    """The engine's step (each layer under ``jax.checkpoint``, the state
    donated) on 512 tokens with experts 3 and 4 of 8 held: a step that the
    router spreads fits the buffer of 512 rows; with a bias that sends every
    token to the two held experts each expert layer takes two trips, in the
    program that was compiled, and its gradients are the reference's."""
    cfg = lm.LFM2MoeConfig.tiny(num_layers=3, num_local_experts=2,
                                expert_offset=3)
    pcfg = gh.ParallelConfig(**F32)
    ids = jnp.asarray(np.random.RandomState(2).randint(
        0, cfg.vocab_size, (2, 256)))
    with obs.window() as w:
        mesh, params, opt_state, step = lm.setup(
            cfg, pcfg, seed=1, devices=jax.devices()[:1])
        params, opt_state, loss = step(params, opt_state, (ids, ids))
    built = _row_buffers(w.delta)
    assert built["held"] >= cfg.num_expert_layers
    held_load = np.asarray(params["expert_load"])[:, 3:5].sum(axis=1)
    assert (held_load <= 512).all()
    crowd = jax.device_put(
        jnp.zeros((cfg.num_expert_layers, 8)).at[:, 3:5].set(10.0),
        params["expert_bias"].sharding)
    crowded = dict(params, expert_bias=crowd)
    want_loss, want, _own, _margin = arch.reference_loss_and_grads(
        crowded, ids, cfg.num_heads, num_experts_per_tok=2, expert_offset=3)
    train, frozen = lm.TRAIN_MODEL.split(crowded)
    with mesh:
        got_loss, got = jax.value_and_grad(
            lambda t: lm.loss_fn({**t, **frozen}, (ids, ids), cfg, pcfg,
                                 mesh))(train)
    assert abs(float(got_loss) - float(want_loss)) < 1e-5
    for g, w_ in zip(jax.tree_util.tree_leaves(got),
                     jax.tree_util.tree_leaves(want)):
        assert float(jnp.linalg.norm(g - w_)) \
            <= 1e-4 * float(jnp.linalg.norm(w_)) + 1e-7
    params, opt_state, loss = step(crowded, opt_state, (ids, ids))
    assert np.isfinite(float(loss)) and step._cache_size() == 1
    assert (np.asarray(params["expert_load"])[:, 3:5].sum(axis=1)
            == held_load + 1024).all()


# ------------------------------------------------------------ the bias rule

def test_bias_rule_moves_each_bias_by_the_rate_towards_the_mean_load():
    frozen = {"expert_bias": jnp.zeros((2, 4)),
              "expert_load": jnp.asarray([[1, 1, 1, 1], [5, 0, 0, 0]],
                                         jnp.int32),
              "expert_peak": jnp.asarray([7, 1], jnp.int32)}
    # one sequence of 5 tokens, 4 experts a token: loads [10, 2, 4, 4]
    # in the first layer, [5, 5, 5, 5] in the second
    first = [[0, 0, 1, 2], [0, 0, 1, 2], [0, 0, 2, 3], [0, 0, 2, 3],
             [0, 0, 3, 3]]
    second = [[0, 1, 2, 3]] * 5
    routing = jnp.asarray([[first], [second]], jnp.int32)
    assert lm.expert_load(routing, 4).tolist() == [[10, 2, 4, 4],
                                                   [5, 5, 5, 5]]
    out = lm.update_routing(frozen, routing, rate=1e-3)
    np.testing.assert_allclose(
        np.asarray(out["expert_bias"]),
        [[-1e-3, 1e-3, 1e-3, 1e-3], [0, 0, 0, 0]], atol=1e-9)
    assert out["expert_load"].tolist() == [[11, 3, 5, 5], [10, 5, 5, 5]]
    assert out["expert_load"].dtype == jnp.int32
    # the step's busiest expert of each layer, added to what the steps
    # before summed
    assert out["expert_peak"].tolist() == [7 + 10, 1 + 5]


@pytest.fixture(scope="module")
def trained():
    """Four steps of the engine's step on one batch, the warm-up over two
    of them."""
    pcfg = gh.ParallelConfig(**F32)
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(lm, "LR_WARMUP_STEPS", 2)
        mesh, params, opt_state, step = lm.setup(
            CFG, pcfg, seed=1, devices=jax.devices()[:1])
        first = jax.tree_util.tree_map(np.asarray, params)
        ids = jnp.asarray(np.random.RandomState(1).randint(
            0, CFG.vocab_size, (2, 24)))
        losses = []
        for _ in range(4):          # the first call traces the schedule
            params, opt_state, loss = step(params, opt_state, (ids, ids))
            losses.append(float(loss))
    return first, params, opt_state, step, losses


def test_the_schedule_warms_up_linearly_then_holds(monkeypatch):
    monkeypatch.setattr(lm, "LR_WARMUP_STEPS", 4)
    got = [float(lm.learning_rate(jnp.int32(step))) for step in (1, 2, 4, 9)]
    np.testing.assert_allclose(got, [0.75e-4, 1.5e-4, 3e-4, 3e-4], rtol=1e-6)


def test_a_step_under_the_engine_lowers_the_loss_and_compiles_once(trained):
    _first, _params, opt_state, step, losses = trained
    assert losses[-1] < losses[0] and all(np.isfinite(losses))
    assert step._cache_size() == 1
    assert int(opt_state["step"]) == 4


def test_the_frozen_leaves_get_no_moments_and_the_models_own_update(trained):
    first, params, opt_state, _step, _losses = trained
    assert not set(lm.FROZEN) & set(opt_state["m"]) \
        and not set(lm.FROZEN) & set(opt_state["v"])
    assert set(opt_state["m"]) == set(params) - set(lm.FROZEN)
    bias = np.asarray(params["expert_bias"])
    # four steps of +-rate (or 0 where a load met the mean): a multiple of
    # the rate, never decayed (AdamW's decay would shrink it) and not zero
    rate = lm.EXPERT_BIAS_UPDATE_RATE
    np.testing.assert_allclose(bias / rate, np.rint(bias / rate), atol=1e-4)
    assert np.abs(bias).max() <= 4 * rate + 1e-9 and np.abs(bias).max() > 0
    load = np.asarray(params["expert_load"])
    assert load.sum(axis=1).tolist() == [4 * 2 * 24 * 2] * 2
    assert (np.asarray(first["expert_load"]) == 0).all()
    # four steps' busiest experts: no less than the busiest of the sum
    peak = np.asarray(params["expert_peak"])
    assert (peak >= load.max(axis=1)).all() and (peak <= 4 * 2 * 24).all()


def test_count_expert_load_ticks_the_counters_from_the_state(trained):
    _first, params, _opt_state, _step, _losses = trained
    with obs.window() as w:
        load = lm.count_expert_load(params, CFG)
    moved = {c["name"]: c["value"] for c in w.delta.changed()}
    assert moved["moe.assignments"] == load.sum() == 2 * 4 * 2 * 24 * 2
    assert moved["moe.busiest_expert_assignments"] \
        == np.asarray(params["expert_peak"]).sum() >= load.max(axis=1).sum()
    assert moved["moe.held_assignments"] == load[:, 2:7].sum()
    assert moved["moe.layer_passes"] == 2


@pytest.mark.parametrize("field,value", [
    ("tp", 2), ("pp", 2), ("dp", 2), ("sp", True), ("num_experts", 4),
    ("collective_matmul", True)])
def test_a_parallel_config_it_cannot_honour_raises_by_name(field, value):
    pcfg = gh.ParallelConfig(**{**F32, field: value})
    with pytest.raises(ValueError, match=field):
        lm.setup(CFG, pcfg, seed=0, devices=jax.devices()[:1])


def test_named_scopes_reach_the_lowered_train_step(trained):
    _first, params, opt_state, step, _losses = trained
    ids = jnp.zeros((2, 24), jnp.int32)
    text = step.lower(params, opt_state, (ids, ids)).as_text(
        debug_info=True)
    for scope in ("short_conv", "attention_operator", "dense_mlp",
                  "moe_router", "moe_experts", "expert_bias_update",
                  "lm_head_ce", "adamw_update"):
        assert scope in text, scope


@pytest.mark.parametrize("policy", ["names", "full", "dots"])
def test_the_routing_is_saved_for_the_backward_pass_whatever_the_policy(
        model, policy, capsys):
    """A choice made again in the backward pass may fall otherwise where
    two scores tie within a rounding (PERF.md, PR 34): the layer's
    ``jax.checkpoint`` keeps it under every remat policy."""
    _pcfg, mesh, params, ids = model
    pcfg = gh.ParallelConfig(**{**F32, "remat_policy": policy})
    train, frozen = lm.TRAIN_MODEL.split(params)
    batch = (jnp.asarray(ids), jnp.asarray(ids))
    with mesh:
        jax.ad_checkpoint.print_saved_residuals(
            lambda t: lm.loss_fn({**t, **frozen}, batch, CFG, pcfg, mesh),
            train)
    kept = [line for line in capsys.readouterr().out.splitlines()
            if "routing" in line and line.startswith("i32")]
    assert len(kept) == CFG.num_expert_layers, kept
