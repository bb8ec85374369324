"""SDAR-MoE on the serving path (ISSUE 28): the model, its dropless expert
layer and the session's generation by diffusion over blocks, each held to
the plain float32 reference that the benchmark keeps
(``benchmarks/ledger/arch/sdar_moe.py``), at tiny sizes on the CPU."""
import importlib.util
import os

import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
import paddle_tpu.observability as obs
from paddle_tpu.inference import decode
from paddle_tpu.inference.decode import ContinuousBatchingSession
from paddle_tpu.models import llama, sdar_moe
from paddle_tpu.models.sdar_moe import SDARMoeConfig, SDARMoeForCausalLM

B = 4                 # block length
MASK = 159


def _load_arch():
    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "benchmarks", "ledger", "arch",
        "sdar_moe.py")
    spec = importlib.util.spec_from_file_location("ledger_arch_sdar_moe",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


arch = _load_arch()


@pytest.fixture(scope="module")
def tiny():
    """(model, reference parameters, the reference's static numbers)."""
    cfg = SDARMoeConfig.tiny()
    paddle.seed(0)
    model = SDARMoeForCausalLM(cfg)
    model.eval()
    static = arch.static_config(vars(cfg))
    params = arch.from_serving_state(model.state_dict(),
                                     cfg.num_hidden_layers)
    return model, params, static


def _ids(n, seed, batch=None):
    shape = (n,) if batch is None else (batch, n)
    return np.random.RandomState(seed).randint(0, MASK, shape) \
        .astype(np.int32)


# ------------------------------------------------ (a) the whole forward

def test_forward_under_the_block_mask_matches_the_reference(tiny):
    model, params, static = tiny
    ids = _ids(14, 0, batch=2)
    got = np.asarray(model(paddle.to_tensor(ids))._data)
    ref = np.asarray(arch.reference_logits(
        params, ids, np.zeros(ids.shape, bool), B, static))
    np.testing.assert_allclose(got, ref, atol=1e-5)
    # the mask is the block mask: position 0 sees position 3, not 4
    moved = ids.copy()
    moved[:, 3] = (moved[:, 3] + 1) % MASK
    assert np.abs(np.asarray(model(paddle.to_tensor(moved))._data)[:, 0]
                  - got[:, 0]).max() > 1e-6
    moved = ids.copy()
    moved[:, 4] = (moved[:, 4] + 1) % MASK
    np.testing.assert_array_equal(
        np.asarray(model(paddle.to_tensor(moved))._data)[:, :4], got[:, :4])


# ---------------------------------- (b) prefill, then passes over a block

@pytest.mark.parametrize("plen", [8, 11], ids=["rem0", "rem3"])
def test_prefill_then_block_passes_match_the_reference(tiny, plen):
    """The prompt's whole blocks go through the cache; the next block,
    partly open, then attends to them and to itself. Compared with the
    reference's forward pass over the whole partly open sequence. A
    denoising pass leaves rows behind that the commit pass overwrites."""
    model, params, static = tiny
    whole = plen // B * B
    ids = _ids(whole + 2 * B, plen)[None]
    is_open = np.zeros(ids.shape, bool)
    is_open[:, plen:whole + B] = True
    is_open[:, whole + B + 1:] = True
    shown = np.where(is_open, MASK, ids)
    ref = np.asarray(arch.reference_logits(params, ids, is_open, B, static))

    caches = model.init_cache(1, max_length=32)
    _, caches = model.forward_with_cache(
        paddle.to_tensor(np.pad(shown[:, :whole], ((0, 0), (0, 8)))), caches)
    caches = [decode.StaticCache(c.k, c.v, paddle.to_tensor(
        np.asarray([whole], np.int32))) for c in caches]
    first = paddle.to_tensor(shown[:, whole:whole + B])
    # a denoising pass on other ids: its K/V must not outlive the commit
    noise = paddle.to_tensor(_ids(B, 99)[None])
    _, dirty = model.forward_with_cache(noise, caches)
    caches = [decode.StaticCache(d.k, d.v, c.length)
              for d, c in zip(dirty, caches)]
    got, caches = model.forward_with_cache(first, caches)
    np.testing.assert_allclose(np.asarray(got._data),
                               ref[:, whole:whole + B], atol=1e-5)
    assert int(caches[0].length._data[0]) == whole + B
    got, _ = model.forward_with_cache(
        paddle.to_tensor(shown[:, whole + B:]), caches)
    np.testing.assert_allclose(np.asarray(got._data), ref[:, whole + B:],
                               atol=1e-5)


# ------------------------------------ (c) the session in the new mode

@pytest.mark.parametrize("remasking, steps", [
    ("low_confidence_static", 2), ("low_confidence_static", 3),
    ("low_confidence_dynamic", 4)])
def test_session_generates_what_the_published_loop_generates(
        tiny, remasking, steps):
    """Five requests through two slots, prompts with every remainder,
    budgets not a multiple of the block: the same tokens, fixed by the same
    passes, as the reference's b=1 loop. 0.008 is a confidence that some
    positions of this tiny model pass and some do not."""
    model, params, static = tiny
    prompts = [_ids(n, n) for n in (5, 8, 11, 3, 14)]
    budgets = [7, 9, 4, 10, 6]
    with ContinuousBatchingSession(
            model, max_slots=2, max_length=32, generation="block_diffusion",
            denoising_steps=steps, remasking=remasking,
            confidence_threshold=0.008) as sess:
        rids = [sess.submit(p, n) for p, n in zip(prompts, budgets)]
        assert sess.generated(rids[0]) == 0
        results = sess.results()
        assert sess.executable_counts() == (1, 1)
    threshold_met = 0
    for rid, prompt, budget in zip(rids, prompts, budgets):
        tokens, fixed_at = arch.reference_generate(
            params, prompt, budget, B, steps, static, remasking, 0.008)
        res = results[rid]
        assert res.state.name == "DONE"
        np.testing.assert_array_equal(res.ids[:len(prompt)], prompt)
        np.testing.assert_array_equal(res.ids[len(prompt):], tokens)
        np.testing.assert_array_equal(res.commit_steps, fixed_at)
        assert MASK not in tokens
        pairs = list(zip((len(prompt) + np.arange(budget)) // B, fixed_at))
        threshold_met += len(pairs) - len(set(pairs))
    if remasking == "low_confidence_dynamic":
        assert threshold_met    # some pass fixed two positions of a block


def test_capacity_counts_the_last_block_whole(tiny):
    model, _params, _static = tiny
    with ContinuousBatchingSession(model, max_slots=1, max_length=16,
                                   generation="block_diffusion") as sess:
        sess.submit(_ids(9, 0), 7)              # ends at 16
        with pytest.raises(ValueError, match="capacity"):
            sess.submit(_ids(9, 0), 8)          # its last block ends at 20


# ------------------------------------------- (d), (e) the expert layer

def _layer_inputs(tokens=24, hidden=16, inter=8, experts=8, top_k=2, seed=0):
    rng = np.random.RandomState(seed)
    h = jnp.asarray(rng.randn(tokens, hidden), jnp.float32)
    router = jnp.asarray(rng.randn(hidden, experts), jnp.float32)
    gate_up = jnp.asarray(rng.randn(experts, hidden, 2 * inter) * 0.3,
                          jnp.float32)
    down = jnp.asarray(rng.randn(experts, inter, hidden) * 0.3, jnp.float32)
    weights, index = sdar_moe.route(h, router, top_k, True)
    return h, weights, index, gate_up, down


def _plain_experts(h, weights, index, gate_up, down):
    """Every token times each of its experts, one pair at a time."""
    h, weights, index, gate_up, down = map(
        np.asarray, (h, weights, index, gate_up, down))
    inter = down.shape[1]
    out = np.zeros_like(h)
    for t in range(h.shape[0]):
        for w, e in zip(weights[t], index[t]):
            gu = h[t] @ gate_up[e]
            act = gu[:inter] / (1.0 + np.exp(-gu[:inter])) * gu[inter:]
            out[t] += w * (act @ down[e])
    return out


@pytest.mark.parametrize("holders", [1, 2, 4])
def test_expert_shares_add_up_to_the_whole_layer(holders):
    h, weights, index, gate_up, down = _layer_inputs()
    experts = gate_up.shape[0]
    held = experts // holders
    parts = [sdar_moe.expert_ffn(
        h, weights, index, gate_up[o:o + held], down[o:o + held], o, experts)
        for o in range(0, experts, held)]
    np.testing.assert_allclose(
        np.sum(parts, axis=0), _plain_experts(h, weights, index, gate_up,
                                              down), atol=1e-5)
    if holders > 1:     # a share is a part, not the whole
        assert np.abs(np.asarray(parts[0])
                      - np.sum(parts, axis=0)).max() > 1e-3


def test_no_token_is_dropped_when_all_go_to_one_expert():
    h, weights, _index, gate_up, down = _layer_inputs(tokens=40)
    index = jnp.tile(jnp.asarray([[5, 2]], jnp.int32), (40, 1))
    got = sdar_moe.expert_ffn(h, weights, index, gate_up, down, 0, 8)
    np.testing.assert_allclose(
        got, _plain_experts(h, weights, index, gate_up, down), atol=1e-5)
    assert np.abs(np.asarray(got)).min(axis=1).max() > 0   # no row is zero


def test_the_kernel_path_computes_what_ragged_dot_computes(monkeypatch):
    """On a TPU the grouped product is the megablox kernel; interpreted
    here, at sizes its tiles do not divide."""
    h, weights, index, gate_up, down = _layer_inputs(
        tokens=20, hidden=128, inter=128)
    ref = sdar_moe.expert_ffn(h, weights, index, gate_up[2:6], down[2:6], 2,
                              8)
    real = sdar_moe._grouped
    monkeypatch.setattr(
        sdar_moe, "_grouped",
        lambda *a, **kw: real(*a, interpret=True, **kw))
    got = sdar_moe.expert_ffn(h, weights, index, gate_up[2:6], down[2:6], 2,
                              8)
    np.testing.assert_allclose(got, ref, atol=1e-4)
    assert sdar_moe._gmm_tiling(2048, 1536) == (128, 2048, 512)
    assert sdar_moe._gmm_tiling(768, 2048) == (128, 768, 1024)


def test_routing_is_softmax_top_k_renormalised():
    h, weights, index, _gu, _down = _layer_inputs()
    assert weights.dtype == jnp.float32
    np.testing.assert_allclose(np.asarray(weights).sum(-1), 1.0, atol=1e-6)
    assert (np.asarray(weights)[:, 0] >= np.asarray(weights)[:, 1]).all()
    assert (np.asarray(index)[:, 0] != np.asarray(index)[:, 1]).all()


# --------------------------------------------- (f) the default mode

def test_default_mode_is_untouched_by_the_new_arguments():
    from paddle_tpu.models.gpt import GPTConfig, GPTForCausalLM
    paddle.seed(3)
    model = GPTForCausalLM(GPTConfig(vocab_size=128, hidden_size=32,
                                     num_layers=1, num_heads=2,
                                     max_seq_len=32))
    with ContinuousBatchingSession(model, max_slots=2, max_length=32,
                                   decode_block=4) as sess:
        assert sess._block_length is None
        rid = sess.submit(_ids(5, 0) % 128, 6)
        res = sess.results()[rid]
        assert sess.executable_counts() == (1, 1)
    assert res.commit_steps is None and len(res.ids) == 11
    with pytest.raises(ValueError, match="generation"):
        ContinuousBatchingSession(model, max_slots=1, max_length=32,
                                  generation="diffusion")


# ------------------------------------------ (g) counters and scopes

def test_block_counters_tick_where_stated(tiny):
    model, _params, _static = tiny
    cfg = model.cfg
    with ContinuousBatchingSession(
            model, max_slots=2, max_length=32, generation="block_diffusion",
            denoising_steps=2) as sess:
        with obs.window() as w:
            rid = sess.submit(_ids(6, 1), 5)   # blocks 4-7 (2 open), 8-11
            res = sess.results()[rid]
    moved = {c["name"]: c["value"] for c in w.delta.changed()
             if c["type"] == "counter" and not c["labels"]}
    passes, layers, top_k = 3, cfg.num_hidden_layers, cfg.num_experts_per_tok
    assert len(res.ids) == 11 and list(res.commit_steps[:2]) == [0, 0]
    assert moved["serving.block_dispatches"] == 2
    assert moved["serving.block_lane_passes"] == 2 * 2 * passes
    assert moved["serving.block_open_positions"] == 2 + 4
    assert moved["serving.block_discarded_tokens"] == 1     # position 11
    assert moved["serving.decode_tokens"] == 5
    assert moved["serving.first_tokens"] == 1
    assert moved["serving.prefill_tokens"] == 4
    assert moved["serving.prefill_padded_tokens"] == 16
    assert "serving.decode_lane_steps" not in moved
    # one stepping lane of 4 positions, each to top_k experts, a layer-pass
    assert moved["moe.layer_passes"] == 2 * passes * layers
    assert moved["moe.assignments"] == 2 * passes * layers * B * top_k
    assert top_k * moved["moe.layer_passes"] \
        <= moved["moe.experts_touched"] \
        <= cfg.num_experts * moved["moe.layer_passes"]
    even = moved["moe.assignments"] / cfg.num_experts
    assert even <= moved["moe.busiest_expert_assignments"] \
        <= moved["moe.assignments"] / top_k
    assert res.timings["first_token"] >= res.timings["admit"]


def test_a_model_that_counts_no_expert_load_generates_the_same(
        tiny, monkeypatch):
    """The load vector is the model's to offer: a session over a model
    without one carries none, ticks no ``moe.*`` counter and generates
    the same tokens."""
    model, _params, _static = tiny
    prompts = [_ids(6, 1), _ids(9, 2)]

    def generate():
        with ContinuousBatchingSession(
                model, max_slots=2, max_length=32,
                generation="block_diffusion", denoising_steps=2) as sess:
            with obs.window() as w:
                rids = [sess.submit(p, 7) for p in prompts]
                res = sess.results()
        moved = {c["name"] for c in w.delta.changed()}
        return [list(res[r].ids) for r in rids], moved

    want, moved = generate()
    assert "moe.assignments" in moved
    monkeypatch.setattr(type(model), "EXPERT_LOAD_LEN", 0)
    got, moved = generate()
    assert got == want
    assert not any(name.startswith("moe.") for name in moved)


def test_named_scopes_reach_the_lowered_block_program(tiny):
    model, _params, _static = tiny
    with ContinuousBatchingSession(
            model, max_slots=2, max_length=32,
            generation="block_diffusion") as sess:
        state = [t._data for t in sess._state_t]
        text = sess._block_jit.lower(
            *state, jnp.zeros((2, B), jnp.int32), jnp.zeros((2, B), bool),
            sess._key, jnp.zeros((2,), jnp.int8),
            *sess._cache_arrays).as_text(debug_info=True)
    for scope in ("block_denoise", "block_commit", "moe_router",
                  "moe_experts", "cache_attention"):
        assert f"{scope}/" in text, scope


# ------------------------------------------------ the rotary helper

def test_half_split_rope_is_the_interleaved_one_on_permuted_columns():
    x = jnp.asarray(np.random.RandomState(0).randn(2, 5, 3, 8), jnp.float32)
    off = jnp.asarray([0, 7], jnp.int32)
    inter = llama.rope(x, off, 1e4)
    # column i of a half-split head is column 2i (first half) or
    # 2(i - D/2) + 1 (second half) of the interleaved head
    perm = np.concatenate([np.arange(0, 8, 2), np.arange(1, 8, 2)])
    half = llama.rope(x[..., perm], off, 1e4, half_split=True)
    np.testing.assert_allclose(half, np.asarray(inter)[..., perm],
                               atol=1e-6)
    got = llama.apply_rotary_pos_emb(paddle.to_tensor(np.asarray(x)), 0,
                                     1e4, half_split=True)
    np.testing.assert_allclose(
        np.asarray(got._data),
        llama.rope(x, jnp.zeros((1,), jnp.int32), 1e4, half_split=True),
        atol=1e-6)
