"""Jamba on the serving path (ISSUE 32): the model, the recurrent state of
its Mamba layers beside the KV of its attention layers behind the cache's
one handle, and both sessions, each held to the plain float32 reference that
the benchmark keeps (``benchmarks/ledger/arch/jamba.py``), at tiny sizes on
the CPU."""
import gc
import importlib.util
import os
import weakref

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
import paddle_tpu.observability as obs
from paddle_tpu import _chaos
from paddle_tpu.inference import decode
from paddle_tpu.inference.admission import RequestState
from paddle_tpu.inference.decode import (ContinuousBatchingSession,
                                         DecodeSession, RecurrentCache,
                                         StaticCache)
from paddle_tpu.models.jamba import JambaConfig, JambaForCausalLM


def _load_arch():
    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "benchmarks", "ledger", "arch",
        "jamba.py")
    spec = importlib.util.spec_from_file_location("ledger_arch_jamba", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


arch = _load_arch()
#: weights large enough that greedy tokens vary with the prompt
CFG = JambaConfig.tiny(initializer_range=0.3)


@pytest.fixture(scope="module")
def tiny():
    """(model, the reference's parameters): two periods of four layers,
    attention at 1 and 5, one KV head."""
    paddle.seed(0)
    model = JambaForCausalLM(CFG)
    model.eval()
    return model, arch.from_serving_state(model.state_dict(), CFG.num_layers,
                                          CFG.rms_norm_eps)


def _ids(n, seed, batch=None):
    shape = (n,) if batch is None else (batch, n)
    return np.random.RandomState(seed).randint(0, CFG.vocab_size, shape) \
        .astype(np.int32)


_ALONE = {}


def _isolated(model, ids, n):
    """One request alone, through a DecodeSession (one a model: its
    programs are compiled once)."""
    if id(model) not in _ALONE:
        _ALONE[id(model)] = (model, DecodeSession(model, 64))
    return _ALONE[id(model)][1].generate(
        paddle.to_tensor(np.asarray(ids)[None]),
        max_new_tokens=n).numpy()[0]


def test_layer_kinds_and_caches(tiny):
    model, _ = tiny
    kinds = [layer.kind for layer in model.layers]
    assert [i for i, k in enumerate(kinds) if k == "attention"] == [1, 5]
    caches = model.init_cache(3, max_length=32)
    assert [type(c) for c in caches] == [
        StaticCache if k == "attention" else RecurrentCache for k in kinds]
    assert caches[0].conv.shape == [3, 3, 64]       # [K - 1, slots, I]
    assert caches[0].ssm.shape == [3, 4, 64]        # [slots, N, I]
    assert caches[1].k.shape == [3, 32, 1, 8]


def test_forward_matches_the_reference(tiny):
    model, params = tiny
    ids = _ids(21, 0, batch=2)
    ref = np.asarray(arch.reference_logits(params, ids, CFG.num_heads))
    got = model(paddle.to_tensor(ids)).numpy()
    assert np.abs(got - ref).max() <= 1e-4


def test_prefill_then_decoding_through_the_cache_matches_the_reference(tiny):
    model, params = tiny
    ids = _ids(21, 1, batch=2)
    ref = np.asarray(arch.reference_logits(params, ids, CFG.num_heads))
    caches = model.init_cache(2, max_length=32)
    logits, caches = model.forward_with_cache(paddle.to_tensor(ids[:, :13]),
                                              caches)
    got = [logits.numpy()]
    for t in range(13, 21):
        logits, caches = model.forward_with_cache(
            paddle.to_tensor(ids[:, t:t + 1]), caches)
        got.append(logits.numpy())
    assert np.abs(np.concatenate(got, 1) - ref).max() <= 1e-4
    assert all(int(c.length.numpy()[0]) == 21 for c in caches)


@pytest.fixture(scope="module")
def one_period():
    """Four layers (Mamba, attention, Mamba, Mamba): what the padding test
    compiles a dozen times."""
    paddle.seed(2)
    model = JambaForCausalLM(JambaConfig.tiny(num_layers=4,
                                              initializer_range=0.3))
    model.eval()
    return model


def _prefill(model, ids, plen=None):
    """The model over ``ids`` [1, s] from fresh caches, as one program;
    ``plen``: the ids are padded, and the entries are told as the admit
    program tells them. Returns (logits, the entries after)."""
    def run(ids):
        entries = decode._entries(model.init_cache(1, 128))
        if plen is not None:
            entries = [e.prefilling(jnp.int32(plen)) for e in entries]
        caches = jax.tree_util.tree_map(
            lambda a: paddle.Tensor._wrap(a, True), entries)
        logits, caches = model.forward_with_cache(
            paddle.Tensor._wrap(ids, True), caches)
        return logits._data, decode._entries(caches)
    return jax.jit(run)(jnp.asarray(ids))


#: around every bucket's edge (16, 32, 64, 128) and the scan's chunk (128)
@pytest.mark.parametrize("plen", [1, 15, 16, 17, 31, 33, 63, 65, 100, 127,
                                  128])
def test_a_padded_prompt_leaves_the_state_of_the_unpadded_one(one_period,
                                                              plen):
    model = one_period
    bucket = next(b for b in (16, 32, 64, 128) if b >= plen)
    ids = _ids(plen, plen)[None]
    padded = np.pad(ids, ((0, 0), (0, bucket - plen)))
    want, plain = _prefill(model, ids)
    got, told = _prefill(model, padded, plen)
    assert float(jnp.abs(got[:, :plen] - want).max()) <= 1e-4
    for a, b in zip(told, plain):
        if isinstance(a, RecurrentCache):
            assert a.take is None and int(a.length[0]) == plen
            np.testing.assert_allclose(a.conv, b.conv, atol=2e-5)
            np.testing.assert_allclose(a.ssm, b.ssm, atol=2e-5)
        else:       # its rule: dead rows past the length the session sets
            np.testing.assert_allclose(a.k[:, :plen], b.k[:, :plen],
                                       atol=2e-5)
    if plen < bucket:
        # and it matters: untold, the scan runs over the padding
        _, untold = _prefill(model, padded)
        assert float(jnp.abs(untold[0].ssm - plain[0].ssm).max()) > 1e-3


def test_continuous_batching_equals_isolated_decodes(tiny):
    """Seven requests through three slots, blocks of four steps: slots are
    reused after a retire (no state leaks into the next request), a lane
    stays empty once the queue has drained, budgets are not multiples of
    the block (a retired slot runs up to three steps more)."""
    model, _ = tiny
    budgets = [5, 9, 6, 4, 11, 7, 2]
    prompts = [_ids(n, 40 + i) for i, n in enumerate((5, 13, 9, 17, 3, 30,
                                                      8))]
    sess = ContinuousBatchingSession(model, max_slots=3, max_length=64,
                                     decode_block=4)
    rids = [sess.submit(p, b) for p, b in zip(prompts, budgets)]
    out = sess.run()
    distinct = set()
    for rid, prompt, budget in zip(rids, prompts, budgets):
        want = _isolated(model, prompt, budget)
        np.testing.assert_array_equal(out[rid], want, err_msg=f"{rid}")
        distinct.update(want[len(prompt):].tolist())
    assert len(distinct) > 8            # the tokens do depend on the text
    n_admit, n_decode = sess.executable_counts()
    assert n_decode == 1 and n_admit <= 4


def test_logits_of_the_session_match_the_reference(tiny):
    """The session's greedy tokens are the reference's argmax, teacher
    forced, wherever its top two logits are not tied."""
    model, params = tiny
    prompt = _ids(11, 7)
    sess = ContinuousBatchingSession(model, max_slots=2, max_length=64,
                                     decode_block=4)
    rid = sess.submit(prompt, 12)
    ids = sess.run()[rid]
    logits = np.asarray(arch.reference_logits(params, ids[None, :-1],
                                              CFG.num_heads))[0, 10:]
    top2 = np.sort(logits, -1)[:, -2:]
    sure = top2[:, 1] - top2[:, 0] > 1e-4
    assert sure.sum() >= 10
    assert (np.argmax(logits, -1) == ids[11:])[sure].all()


@pytest.mark.chaos
def test_a_paused_lane_keeps_its_state_through_a_recovery_probe(tiny):
    """A persistent step failure while one request's slot takes part:
    bisection steps the other lanes in halves while the rest are paused. A
    paused lane's state must not move (its KV would only gain a dead
    row): every other request still equals its isolated decode."""
    model, _ = tiny
    os.environ[_chaos.ENV] = "on"
    _chaos.clear()
    prompts = [_ids(n, 60 + n) for n in (5, 9, 7)]
    sess = ContinuousBatchingSession(model, max_slots=3, max_length=64,
                                     decode_block=2)
    rids = [sess.submit(p, 8) for p in prompts]
    sess.step()
    poison = next(s for s, req in sess._running.items()
                  if req.rid == rids[1])
    _chaos.install("serving.decode_step", kind="error",
                   match=lambda ctx: poison in ctx.get("slots", ()))
    res = sess.results()
    assert res[rids[1]].state is RequestState.FAILED
    for rid, p in zip(rids, prompts):
        if rid != rids[1]:
            np.testing.assert_array_equal(res[rid].ids,
                                          _isolated(model, p, 8))


def test_block_diffusion_over_a_recurrent_entry_raises_by_name(tiny):
    model, _ = tiny
    with pytest.raises(ValueError, match="RecurrentCache"):
        ContinuousBatchingSession(model, max_slots=2, max_length=32,
                                  generation="block_diffusion")


def test_counters_and_gauges(tiny):
    model, _ = tiny
    obs.enable()
    with obs.window() as w:
        sess = ContinuousBatchingSession(model, max_slots=2, max_length=64,
                                         decode_block=4)
        sess.submit(_ids(20, 3), 3)
        sess.run()
    moved = {(c["name"], tuple(sorted(c["labels"].items()))): c["value"]
             for c in w.delta.changed() if c["name"].startswith("ssm.")}
    held = {kind: obs.REGISTRY.gauge("cache.bytes", kind=kind).value
            for kind in ("kv", "recurrent")}
    mamba = 6
    assert moved[("ssm.scan_dispatch", (("kernel", "sequential"),))] >= 1
    assert ("ssm.scan_dispatch", (("kernel", "chunked"),)) not in moved
    sizes = dict(vars(CFG))
    assert held["recurrent"] \
        == 2 * arch.state_bytes_per_slot(sizes) + mamba * 2 * 4
    assert held["kv"] \
        == 2 * 64 * arch.cache_bytes_per_token(sizes, 4) + 2 * 2 * 4


def test_a_closed_session_lets_go_of_the_model():
    """The compiled programs are bound methods, so a session is a cycle;
    ``close`` must not leave the weights to the collector (the benchmark
    builds its cell's model after a check's)."""
    gc.collect()
    gc.disable()
    try:
        paddle.seed(1)
        model = JambaForCausalLM(JambaConfig.tiny(num_layers=4))
        gone = weakref.ref(model)
        with ContinuousBatchingSession(model, max_slots=2, max_length=32,
                                       decode_block=2) as sess:
            sess.submit(_ids(5, 0), 3)
            sess.run()
        del model
        assert gone() is None
    finally:
        gc.enable()


def test_published_sizes_by_shape_alone():
    """3,029,337,472 parameters at the published sizes, and a cache whose
    bytes are the architecture's own count; nothing is allocated."""
    holder = {}

    def build():
        holder["model"] = JambaForCausalLM(JambaConfig(
            param_dtype="bfloat16"))
        return [p._data for _n, p in holder["model"].named_parameters()]

    shapes = jax.eval_shape(build)
    assert sum(int(np.prod(s.shape)) for s in shapes) == 3029337472
    assert {s.dtype for s in shapes} == {jnp.dtype(jnp.bfloat16)}
    model = holder["model"]
    assert [i for i, layer in enumerate(model.layers)
            if layer.kind == "attention"] == [7, 21]
    sizes = dict(vars(model.cfg))
    assert arch.weight_count(sizes) == 3029337472
    cache = jax.eval_shape(
        lambda: decode._entries(model.init_cache(4, max_length=96)))
    floats = sum(a.size * a.dtype.itemsize
                 for a in jax.tree_util.tree_leaves(cache)
                 if a.dtype == jnp.float32)
    assert floats == 4 * arch.state_bytes_per_slot(sizes) \
        + 4 * 96 * arch.cache_bytes_per_token(sizes, 4)
    assert arch.state_bytes_per_slot(sizes) == 10117120
