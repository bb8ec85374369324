"""LongCat-Flash on the serving path: the double layer, latent attention in
its two forms over a latent cache, the router over routed and zero-compute
experts, and the session, each held to the plain float32 reference that the
benchmark keeps (``benchmarks/ledger/arch/longcat_flash.py``), at tiny
sizes on the CPU, float32 on both sides (the latent kernel's check also
in bf16)."""
import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
import paddle_tpu.observability as obs
from paddle_tpu.inference import decode
from paddle_tpu.inference.decode import ContinuousBatchingSession, LatentCache
from paddle_tpu.models import longcat_flash as lf
from paddle_tpu.models.longcat_flash import (LongCatFlashConfig,
                                             LongCatFlashForCausalLM)


def _load_arch():
    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "benchmarks", "ledger", "arch",
        "longcat_flash.py")
    spec = importlib.util.spec_from_file_location("ledger_arch_longcat", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


arch = _load_arch()
#: a share of the experts (4 of 8 from 2), weights large enough that greedy
#: tokens vary with the prompt, the cache in float32 as the weights are
CFG = LongCatFlashConfig.tiny(num_local_experts=4, expert_offset=2,
                              initializer_range=0.3, cache_dtype="float32")


def _sizes(cfg):
    return {k: getattr(cfg, k) for k in (
        "vocab_size", "hidden_size", "ffn_hidden_size",
        "expert_ffn_hidden_size", "num_layers", "num_attention_heads",
        "kv_lora_rank", "q_lora_rank", "qk_rope_head_dim",
        "qk_nope_head_dim", "v_head_dim", "n_routed_experts",
        "zero_expert_num", "moe_topk", "num_local_experts", "expert_offset",
        "rms_norm_eps", "rope_theta", "routed_scaling_factor")}


@pytest.fixture(scope="module")
def tiny():
    """(model, the reference's parameters)."""
    paddle.seed(0)
    model = LongCatFlashForCausalLM(CFG)
    model.eval()
    return model, arch.from_serving_state(model.state_dict(), _sizes(CFG))


def _ids(n, seed, batch=None):
    shape = (n,) if batch is None else (batch, n)
    return np.random.RandomState(seed).randint(0, CFG.vocab_size, shape) \
        .astype(np.int32)


# ------------------------------------------------ against the reference

def test_forward_matches_the_reference(tiny):
    """300 positions: the expanded form's query chunks of 256, the second
    of 44 against 300 keys; the reference's chunks are 128."""
    model, params = tiny
    ids = _ids(300, 0, batch=2)
    ref = np.asarray(arch.reference_logits(params, ids))
    with jax.default_matmul_precision("highest"):
        got = model(paddle.to_tensor(ids)).numpy()
    assert np.abs(got - ref).max() <= 1e-4 * np.abs(ref).max()


def test_prefill_then_decoding_through_the_latent_cache_matches(tiny):
    """Logits, not tokens: a prefill into the latent entries, then one
    position a call through the absorbed form, against the reference's
    full forward."""
    model, params = tiny
    ids = _ids(21, 1, batch=2)
    ref = np.asarray(arch.reference_logits(params, ids))
    caches = model.init_cache(2, max_length=32)
    assert all(isinstance(c, LatentCache) and c.kind == "latent"
               for c in caches) and len(caches) == 2 * CFG.num_layers
    with jax.default_matmul_precision("highest"):
        logits, caches = model.forward_with_cache(
            paddle.to_tensor(ids[:, :13]), caches)
        got = [logits.numpy()]
        for t in range(13, 21):
            logits, caches = model.forward_with_cache(
                paddle.to_tensor(ids[:, t:t + 1]), caches)
            got.append(logits.numpy())
    assert np.abs(np.concatenate(got, 1) - ref).max() \
        <= 1e-4 * np.abs(ref).max()
    assert all(int(c.length.numpy()[0]) == 21 for c in caches)
    # the first sublayer's rows are the reference's latents
    c, k_rope = arch.reference_first_latents(params, ids)
    np.testing.assert_allclose(caches[0].c.numpy()[:, :21], c, atol=1e-5)
    np.testing.assert_allclose(caches[0].k_rope.numpy()[:, :21], k_rope,
                               atol=1e-5)


def test_the_absorbed_form_is_the_expanded_one():
    """One query against S cached latents through ``W_UK`` / ``W_UV``
    equals the last row of the expanded causal attention over the same S
    positions."""
    cfg = CFG
    rng = np.random.RandomState(3)
    b, s = 2, 37
    nh, dn, dr = cfg.num_heads, cfg.qk_nope_head_dim, cfg.qk_rope_head_dim
    p = {"kv_b_proj": jnp.asarray(rng.randn(
        cfg.kv_lora_rank, nh * (dn + cfg.v_head_dim)), jnp.float32)}
    q_nope = jnp.asarray(rng.randn(b, s, nh, dn), jnp.float32)
    q_rope = jnp.asarray(rng.randn(b, s, nh, dr), jnp.float32)
    c = jnp.asarray(rng.randn(b, s, cfg.kv_lora_rank), jnp.float32)
    k_rope = jnp.asarray(rng.randn(b, s, dr), jnp.float32)
    with jax.default_matmul_precision("highest"):
        want = lf._expanded(cfg, p, q_nope, q_rope, c, k_rope)[:, -1:]
        # the cache holds rows past the valid ones: they must not count
        pad = ((0, 0), (0, 11), (0, 0))
        got = lf._absorbed(cfg, p, q_nope[:, -1:], q_rope[:, -1:],
                           jnp.pad(c, pad, constant_values=9.0),
                           jnp.pad(k_rope, pad, constant_values=9.0),
                           jnp.full((b,), s, jnp.int32))
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


# ------------------------------------------------------------ the router

def _layer(model, i=0):
    return {n: getattr(model.layers[i], n)._data for n in lf._LAYER_PARAMS}


def _h(t, seed=5):
    return jnp.asarray(np.random.RandomState(seed).randn(t, CFG.hidden_size),
                       jnp.float32)


def test_a_zero_expert_pick_contributes_six_times_its_score_times_h(tiny):
    """With the held experts' weights at zero, the MoE is its zero-compute
    picks alone: ``sum 6 * s_j * h`` over them, exactly."""
    model, _ = tiny
    p = dict(_layer(model), experts_down=jnp.zeros_like(
        _layer(model)["experts_down"]))
    h = _h(40)
    with jax.default_matmul_precision("highest"):
        out, index = lf.moe(CFG, p, h)
        logits = h @ p["router"]
    scores = jax.nn.softmax(logits, -1)
    g = CFG.routed_scaling_factor * jnp.take_along_axis(scores, index, -1)
    zero = index >= CFG.n_routed_experts
    assert zero.any(axis=1).mean() > 0.5        # the test sees such picks
    want = jnp.sum(jnp.where(zero, g, 0.0), -1, keepdims=True) * h
    np.testing.assert_allclose(out, want, rtol=1e-6, atol=1e-6)


def test_the_bias_moves_the_choice_and_not_the_weights(tiny):
    model, _ = tiny
    p = _layer(model)
    h = _h(16)
    k, scale = CFG.moe_topk, CFG.routed_scaling_factor
    w0, i0 = lf.route(h, p["router"], jnp.zeros(12), k, scale)
    favoured = int(np.argmin(np.bincount(np.asarray(i0).ravel(),
                                         minlength=12)))
    bias = jnp.zeros(12).at[favoured].set(10.0)
    w1, i1 = lf.route(h, p["router"], bias, k, scale)
    assert (np.asarray(i1) == favoured).any(axis=1).all()
    scores = jax.nn.softmax(h @ p["router"], -1)
    np.testing.assert_allclose(
        w1, scale * jnp.take_along_axis(scores, i1, -1), rtol=1e-6)
    # the weights are the scores and not renormalised
    assert float(jnp.abs(jnp.sum(w0, -1) - scale).max()) > 1e-3


@pytest.mark.parametrize("holders", [2, 4])
def test_expert_shares_add_up_to_the_whole_layer(holders):
    """The held experts' parts of all the shares, with the zero experts'
    part counted once (every holder computes it for its own tokens), add
    up to the uncut reference's MoE."""
    cfg = LongCatFlashConfig.tiny(initializer_range=0.3,
                                  cache_dtype="float32")
    paddle.seed(4)
    whole = LongCatFlashForCausalLM(cfg)
    p = _layer(whole)
    h = _h(64, seed=6)
    per = cfg.n_routed_experts // holders
    sizes = _sizes(cfg)
    with jax.default_matmul_precision("highest"):
        scores, index, _ = arch.choose(sizes, p["router"], p["router_bias"],
                                       h)
        want = arch.moe_part(sizes, scores, index, h, p["experts_gate_up"],
                             p["experts_down"], 0)
        zero = arch.moe_part(sizes, scores, index, h,
                             p["experts_gate_up"][:0], p["experts_down"][:0],
                             0)
        total = zero
        for at in range(0, cfg.n_routed_experts, per):
            share = LongCatFlashConfig.tiny(
                initializer_range=0.3, num_local_experts=per,
                expert_offset=at)
            part, got_index = lf.moe(share, dict(
                p, experts_gate_up=p["experts_gate_up"][at:at + per],
                experts_down=p["experts_down"][at:at + per]), h)
            total = total + part - zero
            np.testing.assert_array_equal(got_index, index)
    np.testing.assert_allclose(total, want, rtol=1e-4, atol=1e-4)
    assert float(jnp.abs(want - zero).max()) > 0.1    # experts did work


def test_a_zero_pick_never_reaches_a_grouped_product(tiny, monkeypatch):
    """The rows the grouped products are given hold no zero-compute pick:
    every zero pick is sorted after the held experts' groups."""
    from paddle_tpu.models import sdar_moe
    model, _ = tiny
    p = _layer(model)
    seen = []
    grouped = sdar_moe._grouped

    def spy(lhs, rhs, group_sizes, *a, **kw):
        seen.append((rhs.shape[0], np.asarray(group_sizes)))
        return grouped(lhs, rhs, group_sizes, *a, **kw)
    monkeypatch.setattr(sdar_moe, "_grouped", spy)
    h = _h(50)
    _out, index = lf.moe(CFG, p, h)
    held = np.asarray((index >= CFG.expert_offset)
                      & (index < CFG.expert_offset + CFG.num_local_experts))
    for n_held, sizes in seen:
        assert n_held == CFG.num_local_experts
        assert sizes[:n_held].sum() == held.sum()


# ---------------------------------------------------------- the session

def _greedy_reference(params, prompt, new):
    ids = list(prompt)
    for _ in range(new):
        logits = np.asarray(arch.reference_logits(
            params, np.asarray(ids, np.int32)[None]))[0, -1]
        ids.append(int(np.argmax(logits)))
    return np.asarray(ids, np.int32)


def test_the_session_generates_the_reference_greedy_tokens(tiny):
    """Four requests through two slots, blocks of four steps, slots reused
    after a retire: each request's tokens are the reference's greedy ones
    (where its top two logits are not tied)."""
    model, params = tiny
    prompts = [_ids(n, 40 + n) for n in (5, 13, 9, 20)]
    budgets = [7, 5, 9, 6]
    with ContinuousBatchingSession(model, max_slots=2, max_length=64,
                                   decode_block=4) as sess:
        rids = [sess.submit(p, b) for p, b in zip(prompts, budgets)]
        out = sess.run()
        n_admit, n_decode = sess.executable_counts()
    assert n_decode == 1 and n_admit <= 2
    for rid, prompt, budget in zip(rids, prompts, budgets):
        got = out[rid]
        logits = np.asarray(arch.reference_logits(
            params, got[None, :-1]))[0, len(prompt) - 1:]
        top2 = np.sort(logits, -1)[:, -2:]
        sure = top2[:, 1] - top2[:, 0] > 1e-4
        assert sure.sum() >= budget - 1
        assert (np.argmax(logits, -1) == got[len(prompt):])[sure].all()


def test_a_prefill_runs_at_the_least_length_that_holds_its_prompt(
        tiny, monkeypatch):
    """A prefill padded to s positions runs at a multiple of
    ``_PREFILL_STEP`` past s / 2 (or s): the counted positions' logits and
    latent rows are those of the whole padded call, the rest zeros and
    untouched rows; through the session the tokens stay the reference's."""
    assert lf.prefill_lengths(4096) == [2560, 3072, 3584, 4096]
    assert lf.prefill_lengths(1024) == [1024]
    assert lf.prefill_lengths(256) == [256]
    monkeypatch.setattr(lf, "_PREFILL_STEP", 4)
    assert lf.prefill_lengths(16) == [12, 16]
    model, params = tiny
    leaves = {"embed_tokens": model.embed_tokens._data,
              "norm": model.norm._data, "lm_head": model.lm_head._data,
              "layers": [{n: getattr(layer, n)._data
                          for n in lf._LAYER_PARAMS}
                         for layer in model.layers]}
    ids = jnp.asarray(_ids(16, 3, batch=2))

    def empty():
        return [(jnp.zeros((2, 32, CFG.kv_lora_rank)),
                 jnp.zeros((2, 32, CFG.qk_rope_head_dim)),
                 jnp.zeros((2,), jnp.int32))
                for _ in range(2 * CFG.num_layers)]
    whole, whole_caches, _ = lf.forward_arrays(CFG, leaves, ids, empty())
    for take, n in ((5, 12), (12, 12), (13, 16)):
        got, caches, routing = jax.jit(
            lambda i, c, t: lf.prefill_arrays(CFG, leaves, i, c, t))(
                ids, empty(), jnp.asarray([take, 2], jnp.int32))
        np.testing.assert_allclose(got[:, :take], whole[:, :take],
                                   rtol=1e-4, atol=1e-5)
        assert not np.asarray(got[:, n:]).any()
        assert not np.asarray(routing[0][:, n:]).any()
        for (c, kr, length), (wc, wkr, _) in zip(caches, whole_caches):
            np.testing.assert_allclose(c[:, :take], wc[:, :take],
                                       rtol=1e-4, atol=1e-5)
            assert not np.asarray(c[:, n:]).any()
            assert (np.asarray(length) == n).all()
    prompts = [_ids(n, 70 + n) for n in (5, 13, 9, 20)]
    with ContinuousBatchingSession(model, max_slots=2, max_length=64,
                                   decode_block=4) as sess:
        rids = [sess.submit(p, 6) for p in prompts]
        out = sess.run()
    for rid, prompt in zip(rids, prompts):
        logits = np.asarray(arch.reference_logits(
            params, out[rid][None, :-1]))[0, len(prompt) - 1:]
        top2 = np.sort(logits, -1)[:, -2:]
        sure = top2[:, 1] - top2[:, 0] > 1e-4
        assert sure.sum() >= 5
        assert (np.argmax(logits, -1) == out[rid][len(prompt):])[sure].all()


def test_the_decode_program_counts_the_expert_load(tiny):
    """The one-token decode program sums the model's load vector over a
    block's steps and the stepping lanes; it is ticked at delivery."""
    model, _ = tiny
    obs.enable()
    with obs.window() as w:
        with ContinuousBatchingSession(model, max_slots=3, max_length=64,
                                       decode_block=4) as sess:
            sess.submit(_ids(9, 2), 9)       # the admit's token + 2 blocks
            sess.run()
    moved = {c["name"]: c["value"] for c in w.delta.changed()
             if c["type"] == "counter" and not c["labels"]}
    steps, layers, k = 2 * 4, CFG.num_layers, CFG.moe_topk
    assert moved["moe.layer_passes"] == steps * layers
    assert moved["moe.assignments"] == steps * layers * 1 * k
    assert 0 < moved["moe.zero_assignments"] < moved["moe.assignments"]
    assert 0 < moved["moe.held_assignments"] < moved["moe.assignments"]
    assert moved["moe.held_assignments"] / CFG.num_local_experts \
        <= moved["moe.busiest_expert_assignments"] \
        <= moved["moe.held_assignments"]
    # all three lanes' tokens reach experts, one lane's picks count
    assert moved["moe.experts_touched"] \
        <= CFG.num_local_experts * moved["moe.layer_passes"]
    held = obs.REGISTRY.gauge("cache.bytes", kind="latent").value
    assert held == 2 * layers * (3 * 64 * (CFG.kv_lora_rank
                                           + CFG.qk_rope_head_dim) * 4
                                 + 3 * 4)


#: cache dtype: (heads that fill a register of it, tolerance on the decode
#: steps' logits, kernel against einsums, over their largest value).
#: float32: the order of the online softmax's sums (read 1.5e-6). bf16:
#: ``p`` rounded before its normalisation where the einsums round it after,
#: through two double layers' later products (read 7.7e-3; the kernel
#: without its rope product reads 1.3)
KERNEL_CACHES = {"float32": (8, 1e-5), "bfloat16": (16, 2.0 ** -6)}


@pytest.mark.parametrize("cache_dtype", KERNEL_CACHES)
def test_the_latent_cache_is_written_by_update_and_read_by_its_kernel(
        monkeypatch, cache_dtype):
    """On a TPU (here: the backend predicate turned on) a latent entry's
    rows are written in the update form (``latent_write``), never through
    ``_write_kv``'s row-DMA program (a [B, C, W] buffer's positions lie in
    the two dims a TPU tiles, where a copy cannot start at any row), while
    a KV entry still takes the row-DMA; the absorbed step reads the cache through the
    latent kernel (a latent width of 128 and heads that fill a register:
    the kernel's gate takes the shape) in every call, the decode attention
    kernel's gate is never asked and nothing falls back. The decode steps'
    logits are the CPU einsum path's within the cache dtype's tolerance,
    and in float32 a session's tokens are too (in bf16 a near tie may
    round either way)."""
    heads, tol = KERNEL_CACHES[cache_dtype]
    cfg = LongCatFlashConfig.tiny(kv_lora_rank=128, qk_rope_head_dim=64,
                                  num_attention_heads=heads,
                                  initializer_range=0.3,
                                  cache_dtype=cache_dtype)
    paddle.seed(1)
    model = LongCatFlashForCausalLM(cfg)
    model.eval()
    prompts = [_ids(7, 50), _ids(12, 51)]

    def generate():
        with obs.window() as w:
            with ContinuousBatchingSession(model, max_slots=2, max_length=32,
                                           decode_block=2) as sess:
                rids = [sess.submit(p, 5) for p in prompts]
                out = sess.run()
        moved = {(c["name"], c["labels"].get("kernel")
                  or c["labels"].get("reason")): c["value"]
                 for c in w.delta.changed() if c["type"] == "counter"}
        return [out[r].tolist() for r in rids], moved

    def step_logits():
        """A prefill of 13 positions, then 4 steps through the cache."""
        ids = _ids(17, 52, batch=2)
        caches = model.init_cache(2, max_length=32)
        _, caches = model.forward_with_cache(paddle.to_tensor(ids[:, :13]),
                                             caches)
        got = []
        for t in range(13, 17):
            logits, caches = model.forward_with_cache(
                paddle.to_tensor(ids[:, t:t + 1]), caches)
            got.append(logits.numpy())
        return np.concatenate(got, 1)

    obs.enable()
    want, moved = generate()
    want_logits = step_logits()
    assert ("attn.latent_dispatch", "ragged") not in moved
    assert moved[("attn.latent_dispatch", "einsum")] >= 1
    monkeypatch.setattr(decode, "_kernel_backend", lambda: True)
    got, moved = generate()
    if cache_dtype == "float32":
        assert got == want
    assert moved[("cache.write_dispatch", "update_slice")] >= 1
    assert ("cache.write_dispatch", "row_dma") not in moved
    kv = decode.init_static_cache(2, 32, 2, 128)
    assert decode._write_kv(kv.k._data, kv.v._data, kv.k._data[:, :1],
                            kv.v._data[:, :1], kv.length._data)[0].shape \
        == (2, 32, 2, 128)
    assert obs.counter("cache.write_dispatch", kernel="row_dma").value >= 1
    assert not any(name == "attn.dispatch_fallback" for name, _ in moved)
    assert moved[("attn.latent_dispatch", "ragged")] >= 1
    assert ("attn.latent_dispatch", "einsum") not in moved
    assert {k for name, k in moved if name == "attn.dispatch"} \
        == {"latent_absorbed", "mla_expanded"}
    got_logits = step_logits()
    assert np.abs(got_logits - want_logits).max() \
        <= tol * np.abs(want_logits).max()


def test_named_scopes_reach_the_lowered_decode_program(tiny):
    model, _ = tiny
    with ContinuousBatchingSession(model, max_slots=2, max_length=32,
                                   decode_block=2) as sess:
        state = [t._data for t in sess._state_t]
        text = sess._decode_blk_jit.lower(
            *state, sess._tokens, sess._key, jnp.zeros((2,), jnp.int8),
            *sess._cache_arrays).as_text(debug_info=True)
        admit = sess._admit_jit.lower(
            *state, jnp.zeros((1, 16), jnp.int32), jnp.int32(5),
            jnp.int32(1), sess._tokens, sess._key,
            *sess._cache_arrays).as_text(debug_info=True)
    for scope in ("mla_absorbed", "moe_router", "moe_experts",
                  "zero_experts", "dense_mlp", "write_kv"):
        assert f"{scope}/" in text, scope
    assert "mla_expanded/" in admit and "mla_absorbed/" not in admit


@pytest.mark.parametrize("key, value", [
    ("attention_bias", True), ("attention_method", "MHA"),
    ("mla_scale_q_lora", False), ("mla_scale_kv_lora", False),
    ("zero_expert_type", "copy")])
def test_the_config_refuses_what_it_does_not_compute(key, value):
    with pytest.raises(ValueError, match=key):
        LongCatFlashConfig.tiny(**{key: value})


def test_published_sizes_by_shape_alone():
    """The published widths, held by the parameters' shapes without
    drawing a weight: a double layer without its experts and one expert
    are what ``arch`` counts."""
    cfg = LongCatFlashConfig(num_layers=1, vocab_size=16384,
                             num_local_experts=16)
    shapes = lf._shapes(cfg)
    experts = sum(int(np.prod(shapes[n])) for n in ("experts_gate_up",
                                                    "experts_down"))
    rest = sum(int(np.prod(s)) for n, s in shapes.items()
               if n not in ("experts_gate_up", "experts_down",
                            "router_bias"))
    sizes = dict(_sizes(cfg), num_layers=1)
    assert rest == arch.double_layer_weight_count(sizes) == 638_873_600
    assert experts == 16 * arch.expert_weight_count(sizes) == 603_979_776
    assert shapes["router"] == (6144, 768)
