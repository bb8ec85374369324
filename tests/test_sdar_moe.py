"""SDAR-MoE on the serving path (ISSUE 28): the model, its dropless expert
layer and the session's generation by diffusion over blocks, each held to
the plain float32 reference that the benchmark keeps
(``benchmarks/ledger/arch/sdar_moe.py``), at tiny sizes on the CPU."""
import functools
import importlib.util
import os

import jax
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


# ------------------------- (e') the rows of the experts held (ISSUE 37)

#: 512 tokens, 2 of 8 experts a token: 1,024 pairs; experts 3 and 4 held,
#: whose buffer is 512 rows (their even share of 256 x 1.5, up to the row
#: tile). A case: the slots that go to a held expert, and to which
ROWS_TOKENS, ROWS_OFFSET, ROWS_HELD = 512, 3, 2
ROWS_CASES = {
    # a subset in the middle of the experts, routed as the router routes
    "as_routed": None,
    "an_empty_held_group": (300, (3,)),
    "the_buffer_is_full": (512, (3, 4)),
    "one_pair_over": (513, (3, 4)),             # a second trip
    "every_pair_is_held": (1024, (3, 4)),       # dropless, for a subset
}


def _rows_inputs(case, hidden, inter):
    h, weights, index, gate_up, down = _layer_inputs(
        tokens=ROWS_TOKENS, hidden=hidden, inter=inter, seed=3)
    if ROWS_CASES[case] is not None:
        n, held = ROWS_CASES[case]
        rng = np.random.RandomState(5)
        flat = rng.choice([0, 1, 2, 5, 6, 7], 2 * ROWS_TOKENS)
        # one held expert: every other slot, so no token has it twice
        slots = np.arange(n) if len(held) == 2 else 2 * np.arange(n)
        flat[slots] = np.asarray(held)[np.arange(n) % len(held)]
        index = jnp.asarray(flat.reshape(ROWS_TOKENS, 2), jnp.int32)
    share = slice(ROWS_OFFSET, ROWS_OFFSET + ROWS_HELD)
    n_held = int(np.isin(np.asarray(index), [3, 4]).sum())
    return (h, weights, gate_up[share], down[share]), index, n_held


def _dense_share(index, offset, h, weights, gate_up, down):
    """Every token through every held expert, weighted by the routing."""
    held, inter = down.shape[:2]
    gu = jnp.einsum("th,ehn->etn", h, gate_up)
    y = jnp.einsum("eti,eih->eth",
                   jax.nn.silu(gu[..., :inter]) * gu[..., inter:], down)
    p = jnp.sum(weights[:, :, None] * (
        index[:, :, None] == offset + jnp.arange(held)), axis=1)
    return jnp.einsum("te,eth->th", p, y)


def _value_and_grads(fn, operands, probe):
    """-> (the layer's output, its gradients along ``probe``)."""
    def loss(*ops):
        y = fn(*ops)
        return jnp.sum(y * probe), y
    (_loss, y), grads = jax.jit(jax.value_and_grad(
        loss, argnums=(0, 1, 2, 3), has_aux=True))(*operands)
    return y, grads


def _assert_same(got, want, atol):
    np.testing.assert_allclose(got[0], want[0], rtol=1e-4, atol=atol)
    for g, w in zip(got[1], want[1]):
        np.testing.assert_allclose(g, w, rtol=1e-4, atol=atol)


@pytest.mark.parametrize("case", sorted(ROWS_CASES))
@pytest.mark.parametrize("path", ["ragged_dot", "kernels"])
def test_a_share_of_the_experts_works_on_its_rows_and_drops_no_pair(
        case, path, monkeypatch):
    """Value and the gradients of h, weights, w_gate_up and w_down: the
    layer (which walks the held experts' pairs a buffer of rows a trip)
    against the dense reference and against one pass over all T x k rows,
    and the trips' parts add up to that pass."""
    hidden, inter = (16, 8) if path == "ragged_dot" else (128, 128)
    if path == "kernels":
        real = sdar_moe._grouped
        monkeypatch.setattr(
            sdar_moe, "_grouped",
            lambda *a, **kw: real(*a, interpret=True, **kw))
    operands, index, n_held = _rows_inputs(case, hidden, inter)
    pairs = index.size
    rows = sdar_moe._held_rows(pairs, ROWS_HELD, 8)
    assert (rows, pairs) == (512, 1024)
    probe = jnp.asarray(np.random.RandomState(7).randn(ROWS_TOKENS, hidden),
                        jnp.float32)
    atol = 1e-4 if path == "ragged_dot" else 2e-3

    def layer(h, weights, gate_up, down):
        return sdar_moe.expert_ffn(h, weights, index, gate_up, down,
                                   ROWS_OFFSET, 8)

    def form(m, first=0):
        return functools.partial(
            sdar_moe._ffn_rows, m, first,
            *sdar_moe._sort_pairs(index, ROWS_OFFSET, 8))

    want = _value_and_grads(
        functools.partial(_dense_share, index, ROWS_OFFSET), operands, probe)
    got = _value_and_grads(layer, operands, probe)
    _assert_same(got, want, atol)
    whole = _value_and_grads(form(pairs), operands, probe)
    _assert_same(whole, want, atol)
    trips = [_value_and_grads(form(rows, first), operands, probe)
             for first in range(0, max(n_held, 1), rows)]
    assert len(trips) == -(-max(n_held, 1) // rows)
    if len(trips) > 1:  # the first trip alone would drop a pair
        assert np.abs(np.asarray(trips[0][0] - whole[0])).max() > 1e-2
    _assert_same(jax.tree_util.tree_map(lambda *parts: sum(parts), *trips),
                 whole, atol)
    if case == "every_pair_is_held":    # no row of the output is zero
        assert np.abs(np.asarray(got[0])).min(axis=1).max() > 0
    if case == "an_empty_held_group":   # and it gets no gradient
        assert float(jnp.max(jnp.abs(got[1][2][1]))) == 0.0


@pytest.mark.parametrize("case", ["as_routed", "one_pair_over"])
def test_the_branch_differentiates_under_a_checkpoint(case):
    """As ``lfm2_moe._remat`` wraps the layer: ``jax.checkpoint`` with a
    policy that saves names only, then ``jax.grad``."""
    operands, index, _n = _rows_inputs(case, 16, 8)
    probe = jnp.ones((ROWS_TOKENS, 16), jnp.float32)
    layer = jax.checkpoint(
        lambda *ops: sdar_moe.expert_ffn(ops[0], ops[1], index, *ops[2:],
                                         ROWS_OFFSET, 8),
        policy=jax.checkpoint_policies.save_only_these_names("routing"))
    _assert_same(
        _value_and_grads(layer, operands, probe),
        _value_and_grads(functools.partial(_dense_share, index, ROWS_OFFSET),
                         operands, probe), 1e-4)


def test_the_buffer_is_the_even_share_and_a_half_in_row_tiles():
    assert sdar_moe._held_rows(65536, 16, 64) == 24576     # the train cell
    assert sdar_moe._held_rows(1024, 2, 8) == 512          # 384, a tile up
    assert sdar_moe._held_rows(48, 4, 8) == 48             # never over T x k
    assert sdar_moe._held_rows(4096, 128, 128) == 4096     # every expert held


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
