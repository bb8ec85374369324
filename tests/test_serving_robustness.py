"""Serving under fire (ISSUE 14): request lifecycle with deadlines and
cancellation, bounded-queue admission control with shedding policies,
/healthz readiness semantics, and fault-injected step-failure recovery
(retry envelope -> bisection quarantine of the poison request).

The fault-injection tests drive the env-gated `paddle_tpu._chaos` hook
points and carry the `chaos` marker (pytest.ini) so they are
selectable (`-m chaos`) / deselectable (`-m 'not chaos'`). The serving
harness is the 4-wide fake LM the metrics-server tests use — a few
tiny compiles total, the whole suite stays CPU-cheap.
"""
import os
import time

import numpy as np
import pytest

import paddle_tpu as paddle
import paddle_tpu.observability as obs
from paddle_tpu import _chaos, nn
from paddle_tpu.inference.admission import (AdmissionController,
                                            AdmissionRejected,
                                            RequestState,
                                            ServingStepError)
from paddle_tpu.inference.decode import (ContinuousBatchingSession,
                                         DecodeSession)
from paddle_tpu.observability import server as obs_server


class _TinyLM(nn.Layer):
    def __init__(self, vocab=17, hidden=4):
        super().__init__()
        self.emb = nn.Embedding(vocab, hidden)
        self.proj = nn.Linear(hidden, vocab)
        self._hidden = hidden

    def init_cache(self, batch_size, max_length=16):
        from paddle_tpu.inference.decode import init_static_cache
        return [init_static_cache(batch_size, max_length, 1,
                                  self._hidden)]

    def forward_with_cache(self, ids, caches):
        from paddle_tpu.inference.decode import cache_attention
        x = self.emb(ids)
        q = x.unsqueeze(2)
        out, c0 = cache_attention(q, q, q, caches[0])
        h = out.reshape([x.shape[0], x.shape[1], self._hidden])
        return self.proj(x + h), [c0]


@pytest.fixture(scope="module")
def lm():
    paddle.seed(13)
    return _TinyLM()


@pytest.fixture(autouse=True)
def _metrics_on():
    obs.enable()
    yield
    obs.enable()
    os.environ.pop(obs_server.PORT_ENV, None)
    while obs_server.shared_server() is not None:
        obs_server.session_finished()
    # a session leaked by a failing test must not degrade /healthz for
    # every later test
    obs_server._health_providers.clear()


def _prompt(rng, n=3):
    return rng.randint(0, 17, (n,)).astype(np.int32)


def _isolated(model, ids, n):
    """Greedy single-request oracle for output-parity assertions."""
    with DecodeSession(model, 16) as s:
        return s.generate(paddle.to_tensor(np.asarray(ids)[None]),
                          max_new_tokens=n).numpy()[0]


def _arm_chaos():
    os.environ[_chaos.ENV] = "on"
    _chaos.clear()


# ---------------------------------------------------------------- lifecycle
def test_request_state_machine_and_results(lm):
    rng = np.random.RandomState(0)
    sess = ContinuousBatchingSession(lm, max_slots=1, max_length=16)
    r1 = sess.submit(_prompt(rng), 4)
    r2 = sess.submit(_prompt(rng), 3)
    assert sess.status(r1) is RequestState.QUEUED
    sess.step()
    assert sess.status(r1) is RequestState.DECODING
    assert sess.status(r2) is RequestState.QUEUED
    res = sess.results()
    assert res[r1].ok and res[r1].state is RequestState.DONE
    assert res[r2].ok
    # delivered ids are released: unknown to status(), rid reusable
    assert sess.status(r1) is None
    assert sess._used_rids == set()
    sess.close()


def test_total_deadline_times_out_within_a_step(lm):
    rng = np.random.RandomState(1)
    obs.REGISTRY.reset()
    sess = ContinuousBatchingSession(lm, max_slots=2, max_length=64)
    slow = sess.submit(_prompt(rng), 60, deadline_s=0.05)
    ok = sess.submit(_prompt(rng), 3)
    t0 = time.perf_counter()
    res = sess.results()
    assert res[ok].ok
    assert res[slow].state is RequestState.TIMED_OUT
    # evicted with partial output, not hung: the drain finished well
    # before the 60-token budget could have
    assert len(res[slow].ids) < 3 + 60
    assert time.perf_counter() - t0 < 30
    assert obs.counter("serving.timed_out").value == 1
    # the slot was actually freed: a follow-up request runs to DONE
    r3 = sess.submit(_prompt(rng), 3)
    assert sess.results()[r3].ok
    sess.close()


def test_ttft_deadline_expires_queued_request(lm):
    """A request starved in the queue (slot held by a long decode)
    times out on its TTFT deadline without ever being admitted."""
    rng = np.random.RandomState(2)
    sess = ContinuousBatchingSession(lm, max_slots=1, max_length=64)
    hog = sess.submit(_prompt(rng), 40)
    starved = sess.submit(_prompt(rng), 3, ttft_deadline_s=0.0)
    res = sess.results()
    assert res[hog].ok
    assert res[starved].state is RequestState.TIMED_OUT
    assert len(res[starved].ids) == 3          # prompt only, no tokens
    sess.close()


def test_cancel_queued_and_running(lm):
    rng = np.random.RandomState(3)
    obs.REGISTRY.reset()
    p_keep = _prompt(rng, 4)
    sess = ContinuousBatchingSession(lm, max_slots=2, max_length=16)
    keep = sess.submit(p_keep, 5)
    victim_run = sess.submit(_prompt(rng), 8)
    victim_q = sess.submit(_prompt(rng), 8)    # waits: 2 slots busy
    sess.step()
    assert sess.cancel(victim_run) and sess.cancel(victim_q)
    assert not sess.cancel("nope")             # unknown id -> False
    res = sess.results()
    assert res[victim_run].state is RequestState.CANCELLED
    assert res[victim_q].state is RequestState.CANCELLED
    assert obs.counter("serving.cancelled").value == 2
    # the survivor is untouched: exact parity with an isolated decode
    np.testing.assert_array_equal(res[keep].ids,
                                  _isolated(lm, p_keep, 5))
    assert not sess.cancel(victim_run)         # already terminal
    sess.close()


# ---------------------------------------------------------- admission
def test_bounded_queue_rejects_newest(lm):
    rng = np.random.RandomState(4)
    obs.REGISTRY.reset()
    sess = ContinuousBatchingSession(lm, max_slots=1, max_length=16,
                                     max_queue=1)
    a = sess.submit(_prompt(rng), 3)           # next step's slot
    b = sess.submit(_prompt(rng), 3)           # the one queue seat
    with pytest.raises(AdmissionRejected, match="queue full"):
        sess.submit(_prompt(rng), 3)
    assert obs.counter("serving.rejected").value == 1
    res = sess.results()
    assert res[a].ok and res[b].ok
    sess.close()


def test_priority_lane_evicts_lower_priority(lm):
    rng = np.random.RandomState(5)
    sess = ContinuousBatchingSession(lm, max_slots=1, max_length=16,
                                     max_queue=1,
                                     shed_policy="priority")
    a = sess.submit(_prompt(rng), 3, priority=5)
    low = sess.submit(_prompt(rng), 3, priority=0)
    high = sess.submit(_prompt(rng), 3, priority=5)   # evicts `low`
    with pytest.raises(AdmissionRejected):
        sess.submit(_prompt(rng), 3, priority=5)      # no lower lane
    res = sess.results()
    assert res[low].state is RequestState.REJECTED
    assert res[a].ok and res[high].ok
    sess.close()


def test_admission_controller_validates_config():
    with pytest.raises(ValueError, match="policy"):
        AdmissionController(policy="drop_everything")
    with pytest.raises(ValueError, match="max_queue"):
        AdmissionController(max_queue=0)


def test_overload_sheds_fast_and_latency_stays_bounded(lm):
    """Acceptance rung: 2x slot capacity sustained. The bounded queue
    sheds with fast rejections; accepted requests' latency reaches a
    steady state instead of growing with offered load (shed, never
    collapse), and ZERO requests hang."""
    rng = np.random.RandomState(6)
    # the p99 check at the end reads the process-global latency
    # histogram: start it empty, or an earlier test's slower requests
    # (compile in line) make it order-dependent
    obs.reset()
    before = obs.take_snapshot()
    sess = ContinuousBatchingSession(lm, max_slots=2, max_length=16,
                                     max_queue=2)
    submit_t, finish_t = {}, {}
    accepted, rejected = [], 0
    rounds = 12
    for _ in range(rounds):
        # offered load: 2x the slot count, every round — strictly more
        # than the two steps below can serve
        for _ in range(2 * 2):
            try:
                t0 = time.perf_counter()
                rid = sess.submit(_prompt(rng), 3)
                submit_t[rid] = t0
                accepted.append(rid)
            except AdmissionRejected:
                rejected += 1
        for _ in range(2):
            for rid in sess.step():
                finish_t[rid] = time.perf_counter()
        # the backlog is BOUNDED by construction — this is what keeps
        # accepted-request latency flat under sustained overload
        assert len(sess._queue) <= 2 + 2
    res = sess.results()
    for rid in res:
        finish_t.setdefault(rid, time.perf_counter())
    d = obs.delta(before, obs.take_snapshot())
    assert rejected > 0
    assert d.value("serving.rejected") == rejected
    # zero hung: every accepted request reached DONE and was delivered
    assert sorted(res) == sorted(accepted)
    assert all(r.ok for r in res.values())
    assert sess._used_rids == set()
    # the telemetry window saw every accepted completion
    hist = d.hist("serving.request_latency_s")
    assert hist["count"] == len(accepted)
    # shed-not-collapse: late arrivals wait no longer than early ones
    # (+compile warmup makes the early quarter the SLOW one; the bound
    # is generous because CI wall clocks are noisy)
    lats = [finish_t[r] - submit_t[r] for r in accepted]
    q = max(1, len(lats) // 4)
    early, late = lats[:q], lats[-q:]
    assert (sum(late) / len(late)
            <= 6 * sum(early) / len(early) + 0.25), (early, late)
    p99 = obs.REGISTRY.histogram("serving.request_latency_s")\
        .percentile(0.99)
    assert p99 is not None and p99 <= max(lats) + 1e-6
    sess.close()


# --------------------------------------------------- readiness (/healthz)
def test_healthz_degrades_under_pressure_and_recovers(lm):
    import json
    import urllib.error
    import urllib.request

    os.environ[obs_server.PORT_ENV] = "0"
    rng = np.random.RandomState(7)
    sess = ContinuousBatchingSession(lm, max_slots=1, max_length=16,
                                     max_queue=2)
    srv = obs_server.shared_server()
    assert srv is not None
    with urllib.request.urlopen(srv.url + "/healthz", timeout=5) as r:
        assert r.status == 200 and json.loads(r.read()) == {
            "status": "ok"}
    for _ in range(3):                        # fill slot + queue
        sess.submit(_prompt(rng), 3)
    with pytest.raises(urllib.error.HTTPError) as ei:
        urllib.request.urlopen(srv.url + "/healthz", timeout=5)
    assert ei.value.code == 503
    payload = json.loads(ei.value.read())
    assert payload["status"] == "degraded" and payload["reasons"]
    sess.step()
    assert obs.gauge("serving.degraded").value == 1.0
    sess.results()                            # drain the backlog
    with urllib.request.urlopen(srv.url + "/healthz", timeout=5) as r:
        assert r.status == 200                # ready again
    sess.close()
    # after close the session's provider is unregistered: a fresh
    # server (new session) must not inherit stale pressure
    assert obs_server.health_status()[0] is True


# ------------------------------------------------------ fault injection
@pytest.mark.chaos
def test_transient_step_failure_retried_to_success(lm):
    _arm_chaos()
    obs.REGISTRY.reset()
    rng = np.random.RandomState(8)
    p = _prompt(rng)
    _chaos.install("serving.decode_step", kind="error", times=2)
    sess = ContinuousBatchingSession(lm, max_slots=2, max_length=16)
    rid = sess.submit(p, 4)
    res = sess.results()
    assert res[rid].ok
    np.testing.assert_array_equal(res[rid].ids, _isolated(lm, p, 4))
    assert obs.counter("serving.step_retries").value >= 2
    assert obs.counter("serving.quarantined").value == 0
    sess.close()


@pytest.mark.chaos
def test_persistent_poison_request_is_bisected_out(lm):
    """Acceptance: an injected persistent step failure (active only
    while the poison request's slot participates) fails ONLY that
    request; the session and every other in-flight request run to
    completion with outputs identical to isolated decodes."""
    _arm_chaos()
    obs.REGISTRY.reset()
    rng = np.random.RandomState(9)
    prompts = [_prompt(rng, n) for n in (3, 4, 3)]
    sess = ContinuousBatchingSession(lm, max_slots=3, max_length=16)
    rids = [sess.submit(p, 5) for p in prompts]
    sess.step()                                # all three admitted
    poison_rid = rids[1]
    poison_slot = next(s for s, req in sess._running.items()
                       if req.rid == poison_rid)
    _chaos.install(
        "serving.decode_step", kind="error",
        match=lambda ctx: poison_slot in ctx.get("slots", ()))
    res = sess.results()
    assert res[poison_rid].state is RequestState.FAILED
    assert "chaos" in res[poison_rid].error
    assert obs.counter("serving.quarantined").value == 1
    for rid, p in zip(rids, prompts):
        if rid == poison_rid:
            continue
        assert res[rid].ok
        np.testing.assert_array_equal(res[rid].ids,
                                      _isolated(lm, p, 5))
    # the session stays alive: the freed slot serves a NEW request
    _chaos.clear()
    r_new = sess.submit(prompts[0], 4)
    assert sess.results()[r_new].ok
    sess.close()


@pytest.mark.chaos
def test_admit_failure_quarantines_only_that_request(lm):
    _arm_chaos()
    obs.REGISTRY.reset()
    rng = np.random.RandomState(10)
    p_ok = _prompt(rng)
    sess = ContinuousBatchingSession(lm, max_slots=2, max_length=16,
                                     step_backoff_s=0.0)
    bad = sess.submit(_prompt(rng), 4)
    good = sess.submit(p_ok, 4)
    _chaos.install("serving.admit_step", kind="alloc",
                   match=lambda ctx: ctx.get("rid") == bad)
    res = sess.results()
    assert res[bad].state is RequestState.FAILED
    assert "RESOURCE_EXHAUSTED" in res[bad].error
    assert res[good].ok
    np.testing.assert_array_equal(res[good].ids,
                                  _isolated(lm, p_ok, 4))
    assert obs.counter("serving.quarantined").value == 1
    sess.close()


@pytest.mark.chaos
def test_slow_step_chaos_trips_the_deadline(lm):
    """An injected slow step (transport stall) makes the in-flight
    request blow its total deadline: it returns TIMED_OUT instead of
    stretching the tail."""
    _arm_chaos()
    rng = np.random.RandomState(11)
    _chaos.install("serving.decode_step", kind="slow", seconds=0.06)
    sess = ContinuousBatchingSession(lm, max_slots=1, max_length=64)
    rid = sess.submit(_prompt(rng), 50, deadline_s=0.15)
    res = sess.results()
    assert res[rid].state is RequestState.TIMED_OUT
    sess.close()


@pytest.mark.chaos
def test_step_wide_failure_raises_and_session_stays_closeable(lm):
    """When DISJOINT slot subsets keep failing, bisection refuses to
    quarantine innocents: step()/run() raise ServingStepError, the step
    that raised still delivers its admits' tokens (nothing stays pending
    for a later step to credit to a slot's next request), and the
    exception path still releases the metrics-server refcount via the
    session lifecycle (context exit / close)."""
    os.environ[obs_server.PORT_ENV] = "0"
    _arm_chaos()
    rng = np.random.RandomState(12)
    with ContinuousBatchingSession(lm, max_slots=2, max_length=16,
                                   step_backoff_s=0.0) as sess:
        assert obs_server.shared_server() is not None
        rids = [sess.submit(_prompt(rng), 4), sess.submit(_prompt(rng), 4)]
        _chaos.install("serving.decode_step", kind="error")
        with pytest.raises(ServingStepError, match="disjoint"):
            sess.run()
        assert sess._pending == []
        assert [sess.generated(r) for r in rids] == [1, 1]
    # exception path through run(): the context exit released the ref
    assert obs_server.shared_server() is None
    sess.close()                               # double-close idempotent


@pytest.mark.chaos
def test_chaos_env_spec_and_alloc_site():
    """The env-spec form (`site:kind:arg`) works without any
    programmatic install — here an allocation failure at the cache
    allocation site, budget 1."""
    from paddle_tpu._chaos import ChaosAllocError
    from paddle_tpu.inference.decode import init_static_cache
    _chaos.clear()
    os.environ[_chaos.ENV] = "serving.cache_alloc:alloc:1"
    with pytest.raises(ChaosAllocError, match="RESOURCE_EXHAUSTED"):
        init_static_cache(1, 8, 1, 4)
    init_static_cache(1, 8, 1, 4)              # budget spent: fine now


def test_chaos_rules_inert_without_env(lm):
    """Programmatic rules NEVER fire unless PADDLE_TPU_CHAOS is set —
    a stray import/install cannot inject faults into production."""
    os.environ.pop(_chaos.ENV, None)
    _chaos.clear()
    _chaos.install("serving.decode_step", kind="error")
    try:
        rng = np.random.RandomState(14)
        sess = ContinuousBatchingSession(lm, max_slots=1, max_length=16)
        rid = sess.submit(_prompt(rng), 3)
        assert sess.results()[rid].ok
        sess.close()
    finally:
        _chaos.clear()


def test_cancelling_the_only_running_request_serves_the_queue(lm):
    """Cancelling the only running request between two steps leaves no
    slot decoding: the next step admits the queued request into the freed
    slot, serves it, and results() returns."""
    rng = np.random.RandomState(16)
    sess = ContinuousBatchingSession(lm, max_slots=1, max_length=16)
    victim = sess.submit(_prompt(rng), 8)
    queued = sess.submit(_prompt(rng), 3)
    sess.step()
    assert sess.cancel(victim)
    t0 = time.perf_counter()
    res = sess.results()
    assert time.perf_counter() - t0 < 30       # terminates
    assert res[victim].state is RequestState.CANCELLED
    assert res[queued].ok
    sess.close()


def test_abandoned_session_is_not_pinned_by_health_registry(lm):
    """Regression (review finding): the health-provider registration
    must hold the session only weakly — a session dropped without
    close() still gets finalized (its provider then reports None)."""
    import gc
    import weakref

    rng = np.random.RandomState(17)
    sess = ContinuousBatchingSession(lm, max_slots=1, max_length=16,
                                     max_queue=1)
    sess.submit(_prompt(rng), 3)
    sess.submit(_prompt(rng), 3)               # backlog: degraded
    assert obs_server.health_status()[0] is False
    ref = weakref.ref(sess)
    del sess
    gc.collect()
    assert ref() is None, "session leaked via the provider registry"
    # the dead provider reports healthy, not stale pressure
    assert obs_server.health_status()[0] is True


# ------------------------------------------------------------ close()
def test_close_cancels_inflight_and_is_idempotent(lm):
    obs.REGISTRY.reset()
    rng = np.random.RandomState(15)
    sess = ContinuousBatchingSession(lm, max_slots=1, max_length=16)
    sess.submit(_prompt(rng), 8)
    sess.submit(_prompt(rng), 8)               # queued
    sess.step()
    t0 = time.perf_counter()
    sess.close()
    assert time.perf_counter() - t0 < 5        # no hang on futures
    assert sess._used_rids == set()
    assert not sess._running and not sess._queue and not sess._pending
    assert obs.counter("serving.cancelled").value == 2
    sess.close()                               # idempotent
    assert sess._used_rids == set()
