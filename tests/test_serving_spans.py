"""The serving session times itself (ISSUE 25): `RecordEvent` spans on the
jax.profiler trace's own host plane and clock, per-phase histograms beside
them, request lifecycle stamps in `RequestResult.timings`, and
work-and-waste counters ticked where the work is dispatched."""
import glob
import json
import time

import jax
import numpy as np
import pytest

import paddle_tpu as paddle
import paddle_tpu.observability as obs
import paddle_tpu.profiler as profiler
from paddle_tpu import _chaos
from paddle_tpu.inference import decode
from paddle_tpu.inference.decode import ContinuousBatchingSession
from paddle_tpu.inference.admission import RequestState
from paddle_tpu.models.gpt import GPTConfig, GPTForCausalLM
from paddle_tpu.models.sdar_moe import SDARMoeConfig, SDARMoeForCausalLM

PHASES = ("admit", "dispatch", "fetch", "deliver")
PARTS = decode._PARTS
MODES = ("autoregressive", "block_diffusion")


@pytest.fixture(scope="module")
def tiny_model():
    paddle.seed(5)
    return GPTForCausalLM(GPTConfig(vocab_size=128, hidden_size=32,
                                    num_layers=2, num_heads=2,
                                    max_seq_len=64))


@pytest.fixture(scope="module")
def models(tiny_model):
    """The model of each generation mode."""
    paddle.seed(0)
    blocks = SDARMoeForCausalLM(SDARMoeConfig.tiny())
    blocks.eval()
    return {"autoregressive": tiny_model, "block_diffusion": blocks}


def _mode_session(models, mode, **kw):
    if mode == "block_diffusion":
        return ContinuousBatchingSession(
            models[mode], max_slots=2, max_length=64, generation=mode,
            denoising_steps=2, **kw)
    return _session(models[mode], **kw)


def _prompt(n, seed=0):
    return np.random.RandomState(seed).randint(0, 128, (n,))


def _session(model, **kw):
    kw.setdefault("max_slots", 2)
    kw.setdefault("decode_block", 4)
    return ContinuousBatchingSession(model, max_length=64, **kw)


def _warm(sess):
    """Compile the admit programs of buckets 16 and 32 and the block."""
    sess.submit(_prompt(5), 2)
    sess.submit(_prompt(20), 2)
    sess.results()


def _host_events(tmp_path, prefix=("bench.", "serving.")):
    """[(name, start ns, end ns, stats)] of the one line of the host plane
    that holds the bench.* and serving.* spans."""
    path, = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    data = jax.profiler.ProfileData.from_file(path)
    lines = []
    for plane in data.planes:
        for line in plane.lines:
            events = [(e.name, e.start_ns, e.start_ns + e.duration_ns,
                       dict(e.stats)) for e in line.events
                      if e.name.startswith(prefix)]
            if events:
                lines.append((plane.name, events))
    assert len(lines) == 1, [(p, len(e)) for p, e in lines]
    plane, events = lines[0]
    assert plane == "/host:CPU"
    return events


def test_spans_nest_under_the_callers_span_on_the_profilers_clock(
        tiny_model, tmp_path):
    with _session(tiny_model) as sess:
        _warm(sess)
        rids = [sess.submit(_prompt(5, 1), 6), sess.submit(_prompt(20, 2), 6)]
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        jax.profiler.start_trace(str(tmp_path), profiler_options=options)
        try:
            with obs.window() as moved, \
                    jax.profiler.TraceAnnotation("bench.step"):
                sess.step()
        finally:
            jax.profiler.stop_trace()
        assert moved.value("serving.step_s") == 1
        step_s = moved.hist("serving.step_s")["sum"]
        sess.results()

    events = _host_events(tmp_path)
    by_name = {}
    for e in events:
        by_name.setdefault(e[0], []).append(e)
    assert {n: len(v) for n, v in by_name.items()} == {
        "bench.step": 1, "serving.step": 1, "serving.expire": 1,
        "serving.admit": 2, "serving.dispatch": 1, "serving.fetch": 1,
        "serving.fetch_wait": 1, "serving.fetch_copy": 1,
        "serving.deliver": 1}

    def within(inner, outer):
        return outer[1] <= inner[1] and inner[2] <= outer[2]

    bench, = by_name["bench.step"]
    step, = by_name["serving.step"]
    assert within(step, bench)
    order = by_name["serving.expire"] + by_name["serving.admit"] \
        + by_name["serving.dispatch"] + by_name["serving.fetch"] \
        + by_name["serving.deliver"]
    assert all(within(e, step) for e in order)
    # one after the other: the phases are siblings, not nested
    assert all(a[2] <= b[1] for a, b in zip(order, order[1:]))
    # the fetch's two halves are its children: the wait, then the copy
    fetch, = by_name["serving.fetch"]
    wait, = by_name["serving.fetch_wait"]
    copy, = by_name["serving.fetch_copy"]
    assert within(wait, fetch) and within(copy, fetch) and wait[2] <= copy[1]
    assert step[3]["running"] == 0 and step[3]["queued"] == 2
    assert [e[3]["rid"] for e in by_name["serving.admit"]] == rids
    assert [(e[3]["plen"], e[3]["bucket"])
            for e in by_name["serving.admit"]] == [(5, 16), (20, 32)]
    assert by_name["serving.dispatch"][0][3]["slots"] == 2
    # the span and the histogram read the same two instants
    assert abs((step[2] - step[1]) / 1e9 - step_s) < 1e-3


def test_counters_and_timings_equal_the_hand_count(tiny_model):
    """Two slots, blocks of four. r0 (5 -> bucket 16, 6 new), r1 (20 -> 32,
    9 new) and r2 (3 -> 16, 1 new); r3 is cancelled in the queue; r4 is
    shed for r5 (3 -> 16, 2 new) by the priority policy."""
    with _session(tiny_model, max_queue=3, shed_policy="priority") as sess, \
            obs.window() as moved:
        r0 = sess.submit(_prompt(5), 6)
        r1 = sess.submit(_prompt(20), 9)
        r2 = sess.submit(_prompt(3), 1)
        r3 = sess.submit(_prompt(4), 5)
        r4 = sess.submit(_prompt(6), 5)
        r5 = sess.submit(_prompt(3), 2, priority=1)    # evicts r4
        assert sess.cancel(r3)
        res = sess.results()

    # step 1 admits r0, r1: 1 + 4 tokens each. step 2: r0 takes 1 of its
    # lane's 4, r1 all 4. step 3 admits r2, r5: r2 is done with its admit
    # token, r5 takes 1 of 4. Three dispatches of 2 lanes x 4 steps.
    assert moved.value("serving.steps") == 3
    assert moved.value("serving.decode_lane_steps") == 3 * 2 * 4
    assert moved.value("serving.first_tokens") == 4
    assert moved.value("serving.decode_tokens") == 6 + 9 + 1 + 2
    assert moved.value("serving.prefill_tokens") == 5 + 20 + 3 + 3
    assert moved.value("serving.prefill_padded_tokens") == 16 + 32 + 16 + 16
    # histograms: value() is the number of observations
    assert moved.value("serving.step_s") == 3
    assert moved.value("serving.step_host_s") == 3
    assert [moved.value("serving.step_phase_s", phase=p) for p in PHASES] \
        == [4, 3, 3, 3]
    assert moved.value("serving.queue_wait_s") == 4
    assert moved.value("serving.first_token_hold_s") == 4
    assert moved.value("serving.ttft_s") == 4
    assert moved.value("serving.tpot_s") == 3            # r2 has one token
    assert moved.value("serving.request_latency_s") == 4
    # a step is its phases and a little more; its host time leaves out
    # the fetch
    step_s = moved.hist("serving.step_s")["sum"]
    phase_s = {p: moved.hist("serving.step_phase_s", phase=p)["sum"]
               for p in PHASES}
    assert sum(phase_s.values()) <= step_s
    assert moved.hist("serving.step_host_s")["sum"] == pytest.approx(
        step_s - phase_s["fetch"])

    for rid in (r0, r1, r2, r5):
        t = res[rid].timings
        assert res[rid].state is RequestState.DONE
        assert t["submit"] <= t["admit"] <= t["first_token"] <= t["done"]
    assert res[r3].state is RequestState.CANCELLED
    assert res[r4].state is RequestState.REJECTED
    for rid in (r3, r4):
        t = res[rid].timings
        assert t["admit"] is None and t["first_token"] is None
        assert t["submit"] <= t["done"]
    # r0 and r1 got their first tokens from one fetch
    assert res[r0].timings["first_token"] == res[r1].timings["first_token"]


def test_first_token_waits_for_the_whole_block(tiny_model):
    """The admit's token is fetched with the block dispatched after it:
    a request admitted alone holds its first token at least as long as
    that step's fetch blocks."""
    with _session(tiny_model) as sess:
        _warm(sess)
        with obs.window() as moved:
            rid = sess.submit(_prompt(5, 3), 4)
            sess.step()
        assert moved.value("serving.first_token_hold_s") == 1
        hold = moved.hist("serving.first_token_hold_s")["sum"]
        fetch = moved.hist("serving.step_phase_s", phase="fetch")["sum"]
        assert 0 < fetch <= hold
        assert moved.hist("serving.queue_wait_s")["sum"] \
            <= moved.hist("serving.ttft_s")["sum"]
        t = sess.results()[rid].timings
        assert t["first_token"] - t["admit"] == pytest.approx(hold)


def test_metrics_off_is_the_one_switch(tiny_model, tmp_path):
    """No stamp, no observation, no span while metrics are off."""
    with _session(tiny_model) as sess:
        _warm(sess)
        obs.disable()
        try:
            with obs.window() as moved:
                rid = sess.submit(_prompt(5, 4), 6)
                jax.profiler.start_trace(str(tmp_path))
                try:
                    res = sess.results()[rid]
                finally:
                    jax.profiler.stop_trace()
        finally:
            obs.enable()
    assert res.state is RequestState.DONE and len(res.ids) == 5 + 6
    assert res.timings["submit"] is not None
    assert [res.timings[k] for k in ("admit", "first_token", "done")] \
        == [None] * 3
    assert not [d for d in moved.delta.changed()
                if d["name"].startswith("serving.")]
    path, = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    data = jax.profiler.ProfileData.from_file(path)
    assert not [e.name for p in data.planes for line in p.lines
                for e in line.events if e.name.startswith("serving.")]


# ------------------------------------------------ the wall-clock account

def _timed_step(sess, log):
    """``sess.step()`` between two clock reads of the caller's."""
    t0 = time.perf_counter()
    done = sess.step()
    log.append((t0, time.perf_counter()))
    return done


def _parts(moved, name="serving.cycle_s"):
    """Seconds a part (0 for a series the session does not keep: no_work
    and fetch_wait never starve)."""
    return {p: moved.value(name, default=0.0, part=p) for p in PARTS}


def _observed(moved, part):
    """(observations, their sum) of one part's histogram a cycle."""
    h = moved.hist("serving.cycle_part_s", part=part)
    return h["count"], h["sum"]


@pytest.mark.parametrize("mode", MODES)
def test_cycle_parts_sum_to_the_sessions_wall(models, mode):
    """From the first step()'s entry to the last one's return every second
    is in exactly one part: through admits, blocks, a caller that dawdles,
    an empty return and a submit after it."""
    log = []
    with _mode_session(models, mode) as sess, obs.window() as moved:
        for n, new in ((5, 6), (20, 9), (7, 3)):    # three through two slots
            sess.submit(_prompt(n, n), new)
        while sess._queue or sess._running:
            _timed_step(sess, log)
            time.sleep(0.002)
        _timed_step(sess, log)                      # holds no work
        time.sleep(0.01)
        sess.submit(_prompt(4, 3), 3)
        time.sleep(0.005)
        while sess._queue or sess._running:
            _timed_step(sess, log)
    parts = _parts(moved)
    wall = log[-1][1] - log[0][0]
    assert all(v >= 0 for v in parts.values()), parts
    assert sum(parts.values()) == pytest.approx(wall, rel=1e-3)
    # every part saw time but the one no step of this run reaches
    assert all(parts[p] > 0 for p in PARTS), parts
    assert parts["no_work"] >= 0.01 and parts["caller"] >= 0.005
    # the steps' own seconds are the parts that are no gap
    assert sum(v for p, v in parts.items()
               if p not in ("caller", "no_work")) == pytest.approx(
        moved.hist("serving.step_s")["sum"], rel=1e-6)


@pytest.mark.parametrize("mode", MODES)
def test_starved_seconds_are_a_subset_and_name_the_caller(models, mode):
    """A sleep between two step()s with work queued is the caller's and
    starves the chip; the same sleep on a session that holds nothing is
    no_work and starves nobody."""
    nap = 0.03
    with _mode_session(models, mode) as sess:
        _warm(sess)
        with obs.window() as whole:
            sess.submit(_prompt(5, 1), 12)
            sess.step()
            with obs.window() as moved:
                time.sleep(nap)
                sess.step()
            sess.results()
            assert not (sess._queue or sess._running)
            with obs.window() as empty:
                sess.step()
                time.sleep(nap)
                sess.step()
    cycle, starved = _parts(moved), _parts(moved, "serving.starved_s")
    assert nap <= starved["caller"] == cycle["caller"] < nap + 0.5
    assert cycle["no_work"] == 0
    cycle, starved = _parts(empty), _parts(empty, "serving.starved_s")
    assert nap <= cycle["no_work"] < nap + 0.5
    assert sum(starved.values()) == 0 and cycle["caller"] == 0
    cycle, starved = _parts(whole), _parts(whole, "serving.starved_s")
    assert all(starved[p] <= cycle[p] for p in PARTS)
    assert starved["fetch_wait"] == starved["no_work"] == 0
    assert 0 < sum(starved.values()) <= sum(cycle.values()) \
        - cycle["fetch_wait"] - cycle["no_work"]
    # what the session does behind the enqueued block starves nobody
    assert starved["dispatch"] > 0 and starved["fetch_copy"] > 0


@pytest.mark.parametrize("mode", MODES)
def test_a_cycles_observations_are_that_cycles_seconds(models, mode):
    """One cycle (a step()'s return to the next one's return) that
    dispatched a block: its nine parts sum to its wall in the counters, the
    two parts a reader takes a median of (``decode._CYCLE_HIST_PARTS``) are
    observed once each at the counters' seconds, its starved seconds hold
    all of its caller and fetch_copy seconds, and the fetch's histogram and
    the host's read as they did."""
    with _mode_session(models, mode) as sess:
        _warm(sess)
        sess.submit(_prompt(5, 1), 12)
        sess.step()
        t_a = time.perf_counter()
        with obs.window() as moved:
            time.sleep(0.05)
            sess.step()
            t_b = time.perf_counter()
        sess.results()
        with obs.window() as empty:
            sess.step()                 # dispatches nothing
            sess.step()
    seconds = _parts(moved)
    assert sum(seconds.values()) == pytest.approx(t_b - t_a, abs=5e-3)
    assert decode._CYCLE_HIST_PARTS == ("caller", "fetch_copy")
    for p in PARTS:
        # a part no reader reads keeps its counter and no histogram
        assert _observed(moved, p) == (
            (1, pytest.approx(seconds[p]))
            if p in decode._CYCLE_HIST_PARTS else (0, 0)), p
    assert moved.value("serving.cycle_starved_s") == 1
    starved = moved.hist("serving.cycle_starved_s")["sum"]
    assert starved == pytest.approx(
        sum(_parts(moved, "serving.starved_s").values()))
    assert seconds["caller"] + seconds["fetch_copy"] <= starved \
        <= sum(seconds.values()) - seconds["fetch_wait"]
    assert seconds["caller"] >= 0.05 and seconds["no_work"] == 0
    # what was measured is measured as it was: one observation each, the
    # fetch is its two halves, the host's time is the step less the fetch
    assert moved.value("serving.step_s") == 1
    assert moved.value("serving.step_host_s") == 1
    assert moved.value("serving.step_phase_s", phase="fetch") == 1
    fetch = moved.hist("serving.step_phase_s", phase="fetch")["sum"]
    assert fetch == pytest.approx(
        seconds["fetch_wait"] + seconds["fetch_copy"], abs=1e-6)
    assert moved.hist("serving.step_host_s")["sum"] == pytest.approx(
        moved.hist("serving.step_s")["sum"] - fetch)
    # a cycle that dispatched nothing observes neither histogram
    assert moved.value("serving.steps") == 1
    assert empty.value("serving.steps") == 2
    assert [_observed(empty, p)[0] for p in PARTS] == [0] * len(PARTS)
    assert empty.value("serving.cycle_starved_s") == 0
    assert sum(_parts(empty).values()) > 0


@pytest.mark.chaos
@pytest.mark.parametrize("mode", MODES)
def test_a_failed_step_closes_its_parts(models, mode, monkeypatch):
    """The recovery path goes through the same marks: a dispatch that
    fails once (the retry envelope sleeps, then succeeds) and one that
    fails for every subset (the step raises) both leave parts that sum to
    the wall, and the retry's wait is the dispatch's."""
    log = []
    monkeypatch.setenv(_chaos.ENV, "on")
    _chaos.clear()
    try:
        with _mode_session(models, mode, step_backoff_s=0.01) as sess:
            _warm(sess)
            t_warm = time.perf_counter()    # the gap that is open counts
            with obs.window() as moved:
                sess.submit(_prompt(5, 1), 12)
                sess.submit(_prompt(20, 2), 12)
                _timed_step(sess, log)
                _chaos.install("serving.decode_step", times=1)
                _timed_step(sess, log)
                _chaos.install("serving.decode_step")       # every subset
                t0 = time.perf_counter()
                with pytest.raises(decode.ServingStepError):
                    sess.step()
                log.append((t0, time.perf_counter()))
                _chaos.clear()
                _timed_step(sess, log)
    finally:
        _chaos.clear()
    parts = _parts(moved)
    assert moved.value("serving.step_retries") >= 1
    assert parts["dispatch"] >= 0.01
    assert sum(parts.values()) == pytest.approx(
        log[-1][1] - t_warm, rel=1e-3, abs=5e-3)
    assert moved.value("serving.step_s") == 4
    assert moved.value("serving.step_host_s") == 3     # as it was: not the
    assert moved.value("serving.cycle_starved_s") == 3  # step that raised


def test_record_event_keeps_its_chrome_trace_span(tmp_path):
    """Under a running Profiler a RecordEvent, attributes or none, is still
    in the chrome-tracing export; outside one it records nothing and
    raises nothing."""
    with profiler.RecordEvent("outside_any_profiler", rid=3):
        pass
    p = profiler.Profiler()
    p.start()
    with profiler.RecordEvent("plain_region"):
        with profiler.RecordEvent("region_with_attrs", rid="a", slot=1):
            pass
    p.stop()
    path = str(tmp_path / "trace.json")
    p.export(path)
    with open(path) as f:
        spans = {e["name"]: e for e in json.load(f)["traceEvents"]
                 if e["ph"] == "X"}
    assert {"plain_region", "region_with_attrs"} <= set(spans)
    assert "outside_any_profiler" not in spans
    assert spans["plain_region"]["dur"] >= spans["region_with_attrs"]["dur"]


def test_named_scopes_reach_the_lowered_session_programs(tiny_model):
    with _session(tiny_model) as sess:
        state = [t._data for t in sess._state_t]
        active = np.ones((2,), bool)
        block = sess._decode_blk_jit.lower(
            *state, sess._tokens, sess._key, active,
            *sess._cache_arrays).as_text(debug_info=True)
        admit = sess._admit_jit.lower(
            *state, np.zeros((1, 16), np.int32), np.int32(5), np.int32(0),
            sess._tokens, sess._key,
            *sess._cache_arrays).as_text(debug_info=True)
    for path in ("decode_step/cache_attention/write_kv/",
                 "decode_step/cache_attention/bskgd,bckd->bkgsc",
                 "decode_step/sample"):
        assert f"jit(_decode_block_pure)/while/body/{path}" in block, path
    for path in ("admit/cache_attention/write_kv/", "admit/sample"):
        assert f"jit(_admit_pure)/{path}" in admit, path


def test_named_scopes_reach_the_lowered_train_step():
    import jax.numpy as jnp
    from paddle_tpu.models import gpt_hybrid as gh
    cfg = GPTConfig(vocab_size=128, hidden_size=32, num_layers=2,
                    num_heads=2, max_seq_len=32)
    pcfg = gh.ParallelConfig(param_dtype=jnp.float32,
                             compute_dtype=jnp.float32, fused_ce=True)
    mesh = gh.build_mesh(pcfg, jax.devices()[:1])
    params, specs = gh.shard_params(
        gh.init_params(cfg, pcfg, jax.random.PRNGKey(0)), mesh, cfg, pcfg)
    opt = gh.adamw_init(params, pcfg, mesh, specs)
    step = gh.build_train_step(cfg, pcfg, mesh)
    ids = jnp.zeros((2, 32), jnp.int32)
    with mesh:
        text = step.lower(params, opt, (ids, ids)).as_text(debug_info=True)
    # forward, the backward pass's recomputation, loss head and its
    # transpose, optimizer
    for scope in ("checkpoint/block/attend/", "checkpoint/block/mlp/",
                  "checkpoint/rematted_computation/block/attend/",
                  "checkpoint/rematted_computation/block/mlp/",
                  "jvp(lm_head_ce)/", "transpose(jvp(lm_head_ce))/",
                  "jit(train_step)/adamw_update/"):
        assert scope in text, scope
