"""Continuous batching over the dense fixed-capacity cache (round 5).

Reference capability being matched: block_multihead_attention's paged
KV serving — variable-length multi-request batches with mid-flight
admission/retirement (/root/reference/python/paddle/incubate/nn/
functional/block_multihead_attention.py). The TPU design keeps a
static [slots, capacity] cache; the dynamism is host-side slot
management over two fixed executables (admit-per-bucket + one decode).
"""
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.inference.decode import (ContinuousBatchingSession,
                                         DecodeSession)
from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM


@pytest.fixture(scope="module")
def tiny_model():
    paddle.seed(7)
    return LlamaForCausalLM(LlamaConfig.tiny())


def _isolated(model, ids, n):
    return DecodeSession(model, 64).generate(
        paddle.to_tensor(np.asarray(ids)[None]),
        max_new_tokens=n).numpy()[0]


def test_overlapping_lifetimes_match_isolated_decodes(tiny_model):
    """Three requests with different prompts/budgets through TWO slots:
    r2 is admitted mid-flight into the slot r1 frees, while r0 keeps
    decoding — every per-request output must equal the isolated
    single-request greedy decode, and the executable count stays at
    (buckets used, 1)."""
    m = tiny_model
    rng = np.random.RandomState(3)
    prompts = [rng.randint(0, 256, (n,)).astype(np.int32)
               for n in (5, 3, 9)]
    budgets = [12, 4, 6]

    sess = ContinuousBatchingSession(m, max_slots=2, max_length=64)
    rids = [sess.submit(p, b) for p, b in zip(prompts, budgets)]

    # with 2 slots, r2 waits in the queue; r1 (budget 4) retires first
    # and frees its slot while r0 (budget 12) is still decoding
    completed = []
    steps = 0
    while (sess._queue or sess._running) and steps < 64:
        done = sess.step()
        completed.extend(done)
        steps += 1
        if steps == 1:
            # after the first step both slots are occupied, r2 queued
            assert len(sess._running) == 2 and len(sess._queue) == 1
    out = sess.run()

    # r1 finished before r0 (overlapping lifetimes, not FIFO completion)
    assert completed.index(rids[1]) < completed.index(rids[0])

    for rid, prompt, budget in zip(rids, prompts, budgets):
        ref = _isolated(m, prompt, budget)
        np.testing.assert_array_equal(out[rid], ref,
                                      err_msg=f"request {rid}")

    n_admit, n_decode = sess.executable_counts()
    assert n_decode == 1, "decode must stay one executable"
    assert n_admit <= 3, "admit is bounded by the bucket count"


def test_slot_reuse_many_requests_bounded_executables(tiny_model):
    """Eight short requests through two slots: every slot is reused
    several times; outputs still match isolated decodes and the
    executable pool does not grow with request count."""
    m = tiny_model
    rng = np.random.RandomState(11)
    sess = ContinuousBatchingSession(m, max_slots=2, max_length=64)
    reqs = []
    for i in range(8):
        p = rng.randint(0, 256, (rng.randint(2, 12),)).astype(np.int32)
        b = int(rng.randint(2, 6))
        reqs.append((sess.submit(p, b), p, b))
    out = sess.run()
    for rid, p, b in reqs:
        np.testing.assert_array_equal(out[rid], _isolated(m, p, b),
                                      err_msg=f"request {rid}")
    n_admit, n_decode = sess.executable_counts()
    assert n_decode == 1 and n_admit <= 4


def test_eos_retires_slot_early(tiny_model):
    """A request whose sampled token hits eos retires before its budget
    and frees the slot for the queue."""
    m = tiny_model
    rng = np.random.RandomState(5)
    prompt = rng.randint(0, 256, (4,)).astype(np.int32)
    # find what greedy emits first so we can use it as "eos"
    first = int(_isolated(m, prompt, 2)[len(prompt)])
    sess = ContinuousBatchingSession(m, max_slots=1, max_length=64,
                                     eos_token_id=first)
    rid = sess.submit(prompt, 10)
    rid2 = sess.submit(rng.randint(0, 256, (3,)).astype(np.int32), 2)
    out = sess.run()
    # retired at the eos token, well under budget
    assert len(out[rid]) == len(prompt) + 1
    assert out[rid][-1] == first
    assert len(out[rid2]) == 3 + 2


def test_capacity_guard(tiny_model):
    sess = ContinuousBatchingSession(tiny_model, max_slots=1,
                                     max_length=16)
    with pytest.raises(ValueError, match="capacity"):
        sess.submit(np.zeros(10, np.int32), 8)


def test_run_delivers_each_request_once(tiny_model):
    """run() returns only undelivered completions and releases them —
    a second drain never re-delivers (review finding); request_id
    collisions with IN-FLIGHT requests are refused, while delivered
    ids become reusable (so a long-lived serving session's id set does
    not grow forever)."""
    m = tiny_model
    rng = np.random.RandomState(31)
    sess = ContinuousBatchingSession(m, max_slots=1, max_length=64)
    p1 = rng.randint(0, 256, (4,)).astype(np.int32)
    rid1 = sess.submit(p1, 3, request_id=5)
    out1 = sess.run()
    assert set(out1) == {5}
    p2 = rng.randint(0, 256, (6,)).astype(np.int32)
    rid2 = sess.submit(p2, 2)
    assert rid2 != 5
    # rid2 is in flight: a colliding explicit id is refused
    with pytest.raises(ValueError, match="already in use"):
        sess.submit(p1, 2, request_id=rid2)
    out2 = sess.run()
    assert set(out2) == {rid2}, "earlier results must not re-deliver"
    # delivered ids are released — reuse is allowed and tracked afresh
    assert sess._used_rids == set()
    rid3 = sess.submit(p1, 2, request_id=rid2)
    assert rid3 == rid2
    out3 = sess.run()
    assert set(out3) == {rid3}


def _five_requests():
    rng = np.random.RandomState(41)
    return [(rng.randint(0, 256, (rng.randint(2, 10),))
             .astype(np.int32), int(rng.randint(2, 9)))
            for _ in range(5)]


@pytest.mark.parametrize("decode_block", [1, 4, 16],
                         ids=["block1", "block4", "block16"])
def test_decode_block_mode_same_outputs(tiny_model, decode_block):
    """decode_block=k emits [slots, k] token blocks per dispatch (one
    while_loop program — the DecodeSession block decoder over the slot
    batch); retirement lags up to k-1 steps, the wasted decodes are
    discarded and the slot's cache is reset on admission, so outputs are
    unchanged and the executable count stays 1."""
    m = tiny_model
    reqs = _five_requests()
    sess = ContinuousBatchingSession(m, max_slots=2, max_length=64,
                                     decode_block=decode_block)
    rids = [sess.submit(p, b) for p, b in reqs]
    out = sess.run()
    for rid, (p, b) in zip(rids, reqs):
        np.testing.assert_array_equal(out[rid], _isolated(m, p, b),
                                      err_msg=f"request {rid}")
    assert sess.executable_counts()[1] == 1


def test_a_session_without_decode_block_runs_the_block_program(tiny_model):
    """No ``decode_block`` is a block of one step through the one decode
    program: the tokens of ``decode_block=1`` and of the isolated decodes,
    one decode executable, ``serving.decode_lane_steps`` = slots x
    dispatches. A keyword the session does not have (``sync_every``) raises
    ``TypeError``, as any other does."""
    import paddle_tpu.observability as obs

    obs.enable()
    m = tiny_model
    reqs = _five_requests()

    def run(**session):
        with obs.window() as w, ContinuousBatchingSession(
                m, max_slots=2, max_length=64, **session) as sess:
            rids = [sess.submit(p, b) for p, b in reqs]
            out = sess.run()
            assert sess.executable_counts()[1] == 1
        return [out[r] for r in rids], w

    got, w = run()
    one, w_one = run(decode_block=1)
    for a, b, (p, n) in zip(got, one, reqs):
        np.testing.assert_array_equal(a, b)
        np.testing.assert_array_equal(a, _isolated(m, p, n))
    dispatches = w.value("serving.step_phase_s", phase="dispatch")
    assert dispatches > 0
    assert w.value("serving.decode_lane_steps") == 2 * dispatches
    assert w_one.value("serving.decode_lane_steps") == 2 * dispatches
    with pytest.raises(TypeError, match="sync_every"):
        ContinuousBatchingSession(m, max_slots=2, max_length=64,
                                  sync_every=2)


def _both_paths(monkeypatch, reqs, **session):
    """Greedy tokens of ``reqs`` (prompt length, budget) through a two-slot
    session of a tiny GQA model whose head_dim the kernel's shape gate
    takes, first on the einsum path, then with the dispatcher's backend
    predicate turned on (the CPU runs the kernel in interpret mode). Each
    side: (tokens by request, the counters' window)."""
    import paddle_tpu.observability as obs
    from paddle_tpu.inference import decode

    obs.enable()
    paddle.seed(5)
    m = LlamaForCausalLM(LlamaConfig(
        vocab_size=256, hidden_size=256, intermediate_size=128,
        num_layers=2, num_heads=2, num_kv_heads=1, max_seq_len=64))
    rng = np.random.RandomState(17)
    reqs = [(rng.randint(0, 256, (n,)).astype(np.int32), b) for n, b in reqs]

    def run():
        with obs.window() as w, ContinuousBatchingSession(
                m, max_slots=2, max_length=64, **session) as sess:
            rids = [sess.submit(p, b) for p, b in reqs]
            out = sess.run()
        return [out[r] for r in rids], w

    ref = run()
    monkeypatch.setattr(decode, "_kernel_backend", lambda: True)
    return ref, run()


def _kernel_took_every_step(w_ref, w):
    from chip_smoke import _moved_counters
    moved = _moved_counters(w.delta)
    return (_moved_counters(w_ref.delta) == {}
            and moved.get("attn.dispatch{kernel=decode_ragged}", 0) > 0
            and not any(k.startswith("attn.dispatch_fallback")
                        for k in moved))


def test_decode_kernel_in_the_session_matches_the_einsum_path(monkeypatch):
    """The one-token step through the length-aware Pallas kernel: greedy
    tokens equal the einsum path's with an admission and two retirements
    mid-run, the dispatch is counted and nothing falls back, and the two
    cache counters give the share of the buffer that held tokens."""
    (ref, w_ref), (got, w) = _both_paths(
        monkeypatch, ((5, 9), (3, 5), (9, 5)), decode_block=4)
    for a, b in zip(got, ref):
        np.testing.assert_array_equal(a, b)
    assert _kernel_took_every_step(w_ref, w)
    # two blocks of 4 steps over 2 slots x 64: the first with prompts of
    # 5 and 3 cached; the 3 retires at its budget of 5, the 9 is admitted
    # into its slot, and the second block starts from 5 + 4 and 9
    for win in (w_ref, w):
        assert win.value("serving.decode_cache_positions") == \
            4 * (5 + 3) + 4 * (9 + 9)
        assert win.value("serving.decode_cache_capacity") == 2 * (2 * 64 * 4)


def _write_forms(w):
    return {form for form in ("row_dma", "update_slice")
            if w.value("cache.write_dispatch", default=0, kernel=form)}


def test_cache_write_program_in_the_session_writes_the_same(monkeypatch):
    """The cache write through its Pallas program (the backend predicate
    on: two slots, so every decode step's write; an admit's is one slot's
    and stays a ``dynamic_update_slice``) against the ``update_slice``
    form: the same greedy tokens with a lane that fills its slot to the
    last position and ends mid-block, stepping on at lengths at and past
    the capacity, where the write clamps as the update does."""
    (ref, w_ref), (got, w) = _both_paths(
        monkeypatch, ((50, 15), (3, 5), (9, 6)), decode_block=4)
    assert [len(x) for x in ref] == [50 + 15, 3 + 5, 9 + 6]
    for a, b in zip(got, ref):
        np.testing.assert_array_equal(a, b)
    assert _write_forms(w_ref) == {"update_slice"}
    assert _write_forms(w) == {"row_dma", "update_slice"}
    # ticked as the decode block is traced, once, for each of two layers
    assert w.value("cache.write_dispatch", kernel="row_dma") == 2


def test_cache_write_program_under_block_diffusion(monkeypatch):
    """Generation by diffusion over blocks (s = 4, GQA, the block mask's
    einsums) with the cache written through the program: the same tokens,
    fixed by the same passes, as the ``update_slice`` form."""
    import paddle_tpu.observability as obs
    from paddle_tpu.inference import decode
    from paddle_tpu.models.sdar_moe import SDARMoeConfig, SDARMoeForCausalLM

    obs.enable()
    paddle.seed(0)
    model = SDARMoeForCausalLM(SDARMoeConfig.tiny())
    model.eval()
    rng = np.random.RandomState(3)
    reqs = [(rng.randint(0, 159, (n,)).astype(np.int32), b)
            for n, b in ((5, 7), (8, 9), (11, 4), (14, 6))]

    def run():
        with obs.window() as w, ContinuousBatchingSession(
                model, max_slots=2, max_length=32,
                generation="block_diffusion", denoising_steps=2) as sess:
            rids = [sess.submit(p, b) for p, b in reqs]
            res = sess.results()
        return [(list(res[r].ids), list(res[r].commit_steps))
                for r in rids], w

    ref, w_ref = run()
    monkeypatch.setattr(decode, "_kernel_backend", lambda: True)
    got, w = run()
    assert got == ref
    assert _write_forms(w_ref) == {"update_slice"}
    assert _write_forms(w) == {"row_dma", "update_slice"}


@pytest.mark.parametrize("decode_block", [1, 4, 16],
                         ids=["block1", "block4", "block16"])
def test_decode_kernel_when_a_lane_steps_past_the_capacity(monkeypatch,
                                                           decode_block):
    """A request that fills its slot to the last position (prompt + budget
    - 1 == max_length) and whose budget ends inside a decode block (at the
    block's end where it is one step): the lane steps on, at lengths past
    the capacity, until the block ends and the host retires it. The kernel
    then reads the whole slot and no further, and every request's tokens
    equal the einsum path's; the other slot admits and retires meanwhile,
    its discarded steps overwritten by the next admit's reset."""
    (ref, w_ref), (got, w) = _both_paths(
        monkeypatch, ((50, 15), (3, 5), (9, 6)), decode_block=decode_block)
    assert [len(x) for x in ref] == [50 + 15, 3 + 5, 9 + 6]
    for a, b in zip(got, ref):
        np.testing.assert_array_equal(a, b)
    assert _kernel_took_every_step(w_ref, w)
