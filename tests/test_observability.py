"""Unified telemetry layer (ISSUE 4): registry semantics, exporter
formats, disabled-path overhead, compile-cache tracking, and the
instrumented training / serving / loading paths.

Kept cheap per the tier-1 budget: the serving harness is a 4-wide fake
LM (3 tiny compiles total), the training run is a 2-step Linear fit.
"""
import importlib.util
import json
import os
import time
import types

import numpy as np
import pytest

import paddle_tpu as paddle
import paddle_tpu.observability as obs
from paddle_tpu import nn
from paddle_tpu.observability import metrics as met


@pytest.fixture(autouse=True)
def _metrics_on():
    """Every test here runs with metrics enabled and leaves them so
    (the session default); values are NOT reset — assertions use
    deltas or per-test metric names."""
    obs.enable()
    yield
    obs.enable()


# ---------------------------------------------------------------- registry
def test_counter_gauge_histogram_semantics():
    c = obs.counter("t.ctr")
    v0 = c.value
    c.inc()
    c.inc(2.5)
    assert c.value == pytest.approx(v0 + 3.5)

    g = obs.gauge("t.gauge")
    g.set(4)
    g.inc()
    g.dec(2)
    assert g.value == pytest.approx(3.0)

    h = obs.histogram("t.hist")
    for i in range(100):
        h.observe(i / 100)
    assert h.count == 100
    assert h.sum == pytest.approx(sum(i / 100 for i in range(100)))
    assert 0.4 <= h.percentile(0.5) <= 0.6
    snap = h._snapshot()
    assert snap["min"] == 0.0 and snap["max"] == 0.99
    assert snap["p99"] >= snap["p90"] >= snap["p50"]


def test_histogram_reservoir_bounded():
    h = obs.histogram("t.hist_bounded")
    for i in range(5000):
        h.observe(float(i))
    assert h.count == 5000
    assert len(h._reservoir) <= 512
    # reservoir stays a uniform sample: median near 2500
    assert 1500 <= h.percentile(0.5) <= 3500


def test_labels_are_distinct_series_and_types_conflict():
    a = obs.counter("t.lab", op="x")
    b = obs.counter("t.lab", op="y")
    assert a is not b
    a.inc(5)
    assert b.value == 0.0
    assert obs.counter("t.lab", op="x") is a  # cached identity
    with pytest.raises(TypeError):
        obs.gauge("t.lab", op="x")            # same series, other type


def test_registry_same_name_different_label_sets():
    obs.counter("t.multi").inc()
    obs.counter("t.multi", k="1").inc(2)
    vals = {tuple(sorted(d["labels"].items())): d["value"]
            for d in obs.dump() if d["name"] == "t.multi"}
    assert vals[()] == 1.0 and vals[(("k", "1"),)] == 2.0


# ---------------------------------------------------------------- exporters
def test_jsonl_export_parses():
    obs.counter("t.jsonl_probe").inc(7)
    lines = obs.to_jsonl().splitlines()
    parsed = [json.loads(ln) for ln in lines]
    assert len(parsed) == len(obs.dump())
    mine = [d for d in parsed if d["name"] == "t.jsonl_probe"]
    assert mine and mine[0]["value"] == 7.0 and mine[0]["type"] == "counter"
    assert "ts" in mine[0]


def test_prometheus_export_format():
    obs.counter("t.prom_ctr", stage="0").inc(3)
    h = obs.histogram("t.prom_hist")
    h.observe(1.0)
    h.observe(3.0)
    text = obs.to_prometheus()
    assert "# TYPE paddle_tpu_t_prom_ctr counter" in text
    assert 'paddle_tpu_t_prom_ctr{stage="0"} 3' in text
    assert "# TYPE paddle_tpu_t_prom_hist summary" in text
    assert "paddle_tpu_t_prom_hist_count 2" in text
    assert "paddle_tpu_t_prom_hist_sum 4" in text
    assert 'quantile="0.50"' in text


def test_dump_writes_files(tmp_path):
    obs.counter("t.dump_probe").inc()
    p_json = tmp_path / "m.json"
    p_prom = tmp_path / "m.prom"
    snap = obs.dump(str(p_json))
    obs.dump(str(p_prom), format="prom")
    doc = json.loads(p_json.read_text())
    assert any(d["name"] == "t.dump_probe" for d in doc["metrics"])
    assert any(d["name"] == "t.dump_probe" for d in snap)
    assert "paddle_tpu_t_dump_probe" in p_prom.read_text()


# ----------------------------------------- histogram edge cases (ISSUE 13)
def test_histogram_empty_and_single_observation():
    h = obs.histogram("t.hist_edge_empty")
    # empty reservoir: percentile -> None at every q, snapshot stays
    # the minimal {count, sum} form (no percentile keys to lie with)
    for q in (0.0, 0.5, 1.0):
        assert h.percentile(q) is None
    snap = h._snapshot()
    assert snap == {"count": 0, "sum": 0.0}
    # exporters agree at count=0: prometheus emits count/sum, no
    # quantile lines for this series
    text = obs.to_prometheus()
    assert "paddle_tpu_t_hist_edge_empty_count 0" in text
    assert 'paddle_tpu_t_hist_edge_empty{quantile' not in text

    # single observation: every percentile IS that observation, and
    # out-of-range q clamps instead of raising
    h.observe(3.5)
    for q in (-1.0, 0.0, 0.5, 0.99, 1.0, 2.0):
        assert h.percentile(q) == 3.5
    snap = h._snapshot()
    assert snap["count"] == 1 and snap["min"] == snap["max"] == 3.5
    assert snap["p50"] == snap["p90"] == snap["p99"] == 3.5
    assert snap["mean"] == 3.5


# ------------------------------------- label cardinality cap (ISSUE 13)
def test_label_cardinality_cap_drops_and_counts():
    reg = obs.REGISTRY
    old_cap = reg.max_series_per_name
    reg.max_series_per_name = 8
    try:
        dropped0 = obs.counter("metrics.dropped_series").value
        made = [obs.counter("t.cap_probe", rid=str(i)) for i in range(20)]
        for c in made:
            c.inc()
        # only the first 8 label-sets registered; the rest were
        # detached throwaways (call sites keep working) and counted
        series = [d for d in obs.dump() if d["name"] == "t.cap_probe"]
        assert len(series) == 8
        assert obs.counter("metrics.dropped_series").value == \
            dropped0 + 12
        # registered series are stable identities; overflow lookups
        # share ONE detached sink per (name, kind) — no per-call
        # allocation, still invisible to export
        assert obs.counter("t.cap_probe", rid="0") is made[0]
        over = obs.counter("t.cap_probe", rid="19")
        assert over is made[19]            # the shared sink
        assert over is not made[0]         # never a registered series
        over.inc(5)   # works, goes nowhere
        assert len([d for d in obs.dump()
                    if d["name"] == "t.cap_probe"]) == 8
        # the exempt overflow counter itself never drops
        assert any(d["name"] == "metrics.dropped_series"
                   for d in obs.dump())
    finally:
        reg.max_series_per_name = old_cap


# --------------------------------------- snapshot read API (ISSUE 13)
def test_snapshot_delta_window_and_rates():
    obs.counter("t.read_ctr", k="a").inc(10)
    obs.histogram("t.read_hist").observe(2.0)
    obs.gauge("t.read_gauge").set(1.0)
    before = obs.take_snapshot()
    assert before.value("t.read_ctr", k="a") == 10.0
    assert before.get("t.read_ctr", k="missing") is None
    assert "t.read_hist" in before

    obs.counter("t.read_ctr", k="a").inc(30)
    obs.histogram("t.read_hist").observe(4.0)
    obs.histogram("t.read_hist").observe(6.0)
    obs.gauge("t.read_gauge").set(7.5)
    after = obs.take_snapshot()

    d = obs.delta(before, after)
    assert d.value("t.read_ctr", k="a") == 30.0       # counter delta
    assert d.value("t.read_gauge") == 7.5             # gauge end-state
    h = d.hist("t.read_hist")                         # window stats
    assert h["count"] == 2 and h["sum"] == 10.0 and h["mean"] == 5.0
    # registry-only ratio: counter delta per histogram-sum second
    assert d.per("t.read_ctr", "t.read_hist",
                 labels={"k": "a"}) == pytest.approx(3.0)
    # series that moved in the window, and only those
    moved = {(c["name"], tuple(sorted(c["labels"].items())))
             for c in d.changed()}
    assert ("t.read_ctr", (("k", "a"),)) in moved
    assert ("t.read_gauge", ()) in moved

    with obs.window() as w:
        obs.counter("t.read_ctr", k="a").inc(5)
    assert w.value("t.read_ctr", k="a") == 5.0
    assert w.delta.dt >= 0.0

    # from_metrics round-trips a persisted snapshot
    blob = json.loads(json.dumps(after.metrics))
    restored = obs.Snapshot.from_metrics(blob)
    assert restored.value("t.read_ctr", k="a") == 40.0
    d2 = obs.delta(before, restored)
    assert d2.value("t.read_ctr", k="a") == 30.0


# ------------------------------------------------------------- off switch
def test_exporters_valid_when_disabled_mid_session():
    """PADDLE_TPU_METRICS=off / disable() mid-session: the read side
    must keep returning VALID (possibly frozen) output — a scrape or
    dump racing a disable() can never crash a serving process."""
    obs.counter("t.off_probe").inc(3)
    h = obs.histogram("t.off_hist")
    h.observe(1.0)
    obs.disable()
    try:
        snap = obs.dump()
        assert isinstance(snap, list) and snap
        assert any(d["name"] == "t.off_probe" and d["value"] == 3.0
                   for d in snap)
        for ln in obs.to_jsonl().splitlines():
            json.loads(ln)
        text = obs.to_prometheus()
        assert "paddle_tpu_t_off_probe 3" in text
        assert text.endswith("\n")
        # dump-to-file also stays valid
        import tempfile
        with tempfile.TemporaryDirectory() as td:
            p = os.path.join(td, "off.json")
            obs.dump(p)
            assert json.load(open(p))["metrics"]
        # writes are inert while off; the frozen values persist
        obs.counter("t.off_probe").inc(100)
        h.observe(9.0)
        assert obs.counter("t.off_probe").value == 3.0
        assert h.count == 1
    finally:
        obs.enable()


def test_disabled_is_noop_and_near_zero_cost():
    c = obs.counter("t.disabled_probe")
    h = obs.histogram("t.disabled_hist")
    obs.disable()
    try:
        c.inc(100)
        h.observe(1.0)
        g = obs.gauge("t.disabled_gauge")
        g.set(5)
        assert c.value == 0.0 and h.count == 0 and g.value == 0.0
        # micro-benchmark: the disabled mutate path is one branch —
        # generous absolute bound that still catches an accidental
        # lock/time/dict on the disabled path
        n = 50000
        t0 = time.perf_counter()
        for _ in range(n):
            c.inc()
        per_call = (time.perf_counter() - t0) / n
        assert per_call < 2.5e-6, f"disabled inc() costs {per_call:.2e}s"
        # the framework's hot-path guard pattern (module-global bool)
        t0 = time.perf_counter()
        for _ in range(n):
            if met._ENABLED:
                c.inc()
        per_call = (time.perf_counter() - t0) / n
        assert per_call < 1.0e-6, f"guard branch costs {per_call:.2e}s"
    finally:
        obs.enable()
    c.inc()
    assert c.value == 1.0


def test_env_flag_off_disables_at_import():
    spec = importlib.util.spec_from_file_location("_met_env_probe",
                                                  met.__file__)
    old = os.environ.get("PADDLE_TPU_METRICS")
    os.environ["PADDLE_TPU_METRICS"] = "off"
    try:
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        assert mod._ENABLED is False
    finally:
        if old is None:
            del os.environ["PADDLE_TPU_METRICS"]
        else:
            os.environ["PADDLE_TPU_METRICS"] = old


# ------------------------------------------------------ compile tracking
def test_compile_counter_on_toy_jit_fn():
    import jax
    import jax.numpy as jnp

    def f(x):
        return x * 2 + 1

    jf = jax.jit(f)
    with obs.count_compiles() as compiles, obs.count_traces() as traces:
        jf(jnp.ones((3,)))
    assert compiles() >= 1 and traces() >= 1
    # steady state: cache hit, zero events
    with obs.count_compiles() as c2, obs.count_traces() as t2:
        jf(jnp.ones((3,)))
    assert c2() == 0 and t2() == 0
    # liveness: a new shape must be SEEN
    with obs.count_compiles() as c3:
        jf(jnp.ones((4,)))
    assert c3() >= 1


def test_compile_seconds_kept_by_phase():
    """A fresh jit moves the seconds of all three compile phases (what
    jax.monitoring's duration events carry); a second call moves none."""
    import jax
    import jax.numpy as jnp
    names = ("jit.trace_s", "jit.lower_s", "jit.backend_compile_s")

    def read():
        return [obs.counter(n).value for n in names]

    jf = jax.jit(lambda x: jnp.tanh(x) * 3 + 1)
    before = read()
    jf(jnp.ones((5,)))
    first = read()
    assert all(b > a for a, b in zip(before, first)), (before, first)
    jf(jnp.ones((5,)))
    assert read() == first


def test_compile_wall_counts_nested_traces_once():
    """``jit.compile_wall_s`` is the union of the compile events' time
    spans: under the three sums, and strictly under them where a jitted
    function is traced inside another's trace; the table names both."""
    import jax
    import jax.numpy as jnp
    names = ("jit.trace_s", "jit.lower_s", "jit.backend_compile_s")

    def read():
        return (obs.counter("jit.compile_wall_s").value,
                sum(obs.counter(n).value for n in names))

    @jax.jit
    def wall_probe_inner(x):
        return jnp.tanh(x) * 2

    @jax.jit
    def wall_probe_outer(x):
        return wall_probe_inner(x) + 1

    wall0, sums0 = read()
    t0 = time.time()
    wall_probe_outer(jnp.ones((3,)))
    t1 = time.time()
    wall, sums = read()
    assert 0 < wall - wall0 < sums - sums0
    assert wall - wall0 <= t1 - t0
    # the inner trace's seconds are counted twice by the sums, once here
    table = obs.compiled_programs()
    inner, outer = table["wall_probe_inner"], table["wall_probe_outer"]
    assert sums - sums0 - (wall - wall0) >= inner["trace_s"] * 0.99
    assert inner["builds"] == 0 and inner["trace_s"] > 0 \
        and inner["lower_s"] == inner["backend_compile_s"] == 0
    assert outer["builds"] == 1 and outer["cache_hits"] in (0, 1)
    assert min(outer[k] for k in ("trace_s", "lower_s",
                                  "backend_compile_s")) > 0
    assert t0 <= outer["first"] <= inner["first"] <= inner["last"] \
        <= outer["last"] <= t1
    # a second call moves nothing; the table is a copy
    wall_probe_outer(jnp.ones((3,)))
    assert read() == (wall, sums)
    table["wall_probe_outer"]["builds"] = 99
    assert obs.compiled_programs()["wall_probe_outer"]["builds"] == 1


def test_span_union_counts_every_second_once():
    from paddle_tpu.observability import compile_tracker as ct
    u = ct._SpanUnion()
    assert u.add(10.0, 11.0) == 1.0             # alone
    assert u.add(10.2, 10.8) == 0.0             # nested in it
    assert u.add(10.0, 11.0) == 0.0             # repeated
    assert u.add(12.0, 13.0) == 1.0             # apart
    assert u.add(10.5, 12.5) == pytest.approx(1.0)      # bridges the gap
    assert u._ivs == [[10.0, 13.0, pytest.approx(3.0)]]
    # spans arrive at their end: the inner ones, then the one around them
    assert [u.add(20.0 + i, 20.5 + i) for i in range(4)] == [0.5] * 4
    assert u.add(19.0, 25.0) == pytest.approx(6.0 - 2.0)
    assert len(u._ivs) == 2


def test_span_union_stays_short_and_never_overcounts(monkeypatch):
    """Past the cap the oldest intervals are joined: a span that covers
    the joined one whole still adds exactly what was missing, one that
    cuts into it is credited with the whole overlap."""
    from paddle_tpu.observability import compile_tracker as ct
    monkeypatch.setattr(ct, "_MAX_INTERVALS", 4)
    u = ct._SpanUnion()
    total = sum(u.add(float(i), i + 0.25) for i in range(10))
    assert total == 2.5 and len(u._ivs) == 4
    assert u._ivs[0] == [0.0, 6.25, 1.75]       # seven joined, gaps kept
    assert u.add(5.5, 5.75) == 0.0              # cuts in: never over
    assert u.add(-1.0, 10.0) == pytest.approx(11.0 - 2.5)
    assert u._ivs == [[-1.0, 10.0, pytest.approx(11.0)]]


def test_compiled_programs_table_is_bounded(monkeypatch):
    """Names past the bound are summed under ``_other``; ``jit(f)`` and
    ``f`` are one key; a cache hit is the compile's whose span ends next."""
    from paddle_tpu.observability import compile_tracker as ct
    monkeypatch.setattr(ct, "_MAX_PROGRAMS", 2)
    spans = ct._CompileSpans()
    assert spans.add("trace_s", 1.0, 2.0, "f") == 1.0
    assert spans.add("lower_s", 2.0, 2.5, "jit(f)") == 0.5
    spans.cache_hit()
    assert spans.add("backend_compile_s", 2.5, 2.75, "jit(f)") == 0.25
    spans.add("backend_compile_s", 3.0, 4.0, "jit(g)")
    spans.add("trace_s", 5.0, 5.5, "h")
    spans.add("backend_compile_s", 6.0, 6.5, "jit(k)")
    table = spans.table()
    assert sorted(table) == ["_other", "f", "g"]
    assert table["f"] == {"builds": 1, "trace_s": 1.0, "lower_s": 0.5,
                          "backend_compile_s": 0.25, "cache_hits": 1,
                          "first": 1.0, "last": 2.75}
    assert table["g"]["builds"] == 1 and table["g"]["cache_hits"] == 0
    assert table["_other"]["builds"] == 1 \
        and table["_other"]["trace_s"] == 0.5
    # a hit is claimed on the thread it fired on: another thread's compile,
    # whose span may end first, does not take it
    import threading
    spans.cache_hit()
    other = threading.Thread(target=spans.add, args=(
        "backend_compile_s", 7.0, 7.5, "jit(g)"))
    other.start()
    other.join()
    assert spans.table()["g"]["cache_hits"] == 0
    spans.add("backend_compile_s", 7.0, 8.0, "jit(f)")
    assert spans.table()["f"]["cache_hits"] == 2
    spans.clear()
    assert spans.table() == {} and spans.add("trace_s", 1.0, 2.0, "f") == 1.0


def test_reset_forgets_the_compile_spans_with_their_counter():
    """``obs.reset()`` zeroes ``jit.compile_wall_s``; the union and the
    table behind ``compiled_programs()`` go with it, so the counter and the
    table's seconds still say the same after it."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.observability import compile_tracker as ct

    @jax.jit
    def reset_probe(x):
        return jnp.cos(x) - 1

    reset_probe(jnp.ones((5,)))
    assert "reset_probe" in obs.compiled_programs()
    assert obs.counter("jit.compile_wall_s").value > 0
    obs.reset()
    assert obs.compiled_programs() == {} and ct._SPANS._union._ivs == []
    assert obs.counter("jit.compile_wall_s").value == 0

    @jax.jit
    def reset_probe_again(x):
        return jnp.cos(x) - 2

    reset_probe_again(jnp.ones((5,)))
    table = obs.compiled_programs()
    assert "reset_probe" not in table and "reset_probe_again" in table
    assert 0 < obs.counter("jit.compile_wall_s").value <= sum(
        e[k] for e in table.values()
        for k in ("trace_s", "lower_s", "backend_compile_s"))


def test_global_compile_counter_and_static_function_stats():
    before = obs.counter("jit.xla_compiles").value

    @paddle.jit.to_static
    def g(a):
        return a * 3

    x = paddle.to_tensor(np.ones((2,), "f4"))
    g(x)
    g(x)
    assert obs.counter("jit.xla_compiles").value > before
    assert g._m_calls.value >= 2
    assert g._m_builds.value >= 1
    assert g._m_hits.value >= 1
    rep = obs.compile_report()
    mine = [r for r in rep if r["function"].endswith("g")]
    assert mine and mine[0]["xla_executables"] >= 1
    # registry snapshot carries the aggregate gauges via the collector
    snap = {d["name"]: d for d in obs.dump() if not d["labels"]}
    assert snap["jit.static_functions"]["value"] >= 1
    assert snap["jit.xla_executables"]["value"] >= 1


# ------------------------------------------------- pad_mask_arg satellite
def test_pad_mask_arg_unbound_dynamic_dim_raises_clear_error():
    from paddle_tpu.jit import InputSpec

    def step(x, seq_mask):
        return (x * seq_mask).sum()

    st = paddle.jit.to_static(
        step,
        input_spec=[InputSpec([4], "float32"),
                    InputSpec([None], "float32")],
        pad_dynamic_dims=True, pad_mask_arg="seq_mask")
    with pytest.raises(ValueError, match="length is unknown"):
        st(paddle.to_tensor(np.ones((4,), "f4")))


# ------------------------------------------- fleet facade satellite
def test_meta_parallel_defers_schedule_error_to_train_batch():
    from paddle_tpu.distributed.fleet.meta_parallel import (
        PipelineParallel)

    class _Topo:
        def get_hybrid_group_names(self):
            return []

        def get_dim(self, name):
            return 1

    class _Hcg:
        def get_pipe_parallel_world_size(self):
            return 2

        def get_data_parallel_world_size(self):
            return 1

        def get_model_parallel_world_size(self):
            return 1

        def topology(self):
            return _Topo()

    lin = nn.Linear(3, 3)
    strategy = types.SimpleNamespace(
        pipeline_configs={"schedule_mode": "FThenB"})
    pp = PipelineParallel(lin, _Hcg(), strategy)
    # forward/eval-only flow keeps working after the wrap
    x = paddle.to_tensor(np.ones((2, 3), "f4"))
    y = pp(x)
    assert tuple(y.shape) == (2, 3)
    with pytest.raises(ValueError, match="schedule_mode"):
        pp.train_batch((x, x), optimizer=None)


# --------------------------------------------------- training run metrics
def test_training_run_produces_step_metrics():
    x = paddle.to_tensor(np.random.RandomState(0)
                         .rand(8, 4).astype("f4"))
    y = paddle.to_tensor(np.random.RandomState(1)
                         .rand(8, 1).astype("f4"))
    from paddle_tpu.io import TensorDataset
    net = nn.Linear(4, 1)
    model = paddle.Model(net)
    model.prepare(paddle.optimizer.SGD(learning_rate=0.1,
                                       parameters=net.parameters()),
                  nn.MSELoss())
    steps0 = obs.counter("train.steps").value
    fetch0 = obs.histogram("dataloader.fetch_wait_s").count
    model.fit(TensorDataset([x, y]), batch_size=4, epochs=1, verbose=0)
    assert obs.counter("train.steps").value >= steps0 + 2
    assert obs.histogram("train.step_time_s").count >= 2
    assert obs.gauge("train.samples_per_s").value > 0
    assert obs.histogram("dataloader.fetch_wait_s").count >= fetch0 + 2


def test_mfu_gauge_from_configured_flops():
    obs.training.configure(flops_per_token=6e9, peak_flops=1e12)
    try:
        obs.training.record_step(0.01, samples=2, tokens=64)
        mfu = obs.gauge("train.mfu").value
        assert mfu == pytest.approx((64 / 0.01) * 6e9 / 1e12)
    finally:
        obs.training._flops_per_token = None
        obs.training._peak_flops = None


def test_pipeline_bubble_gauge_math():
    from paddle_tpu.parallel.pipeline_1f1b import (
        _record_schedule_metrics, compiled_1f1b_schedule)
    _record_schedule_metrics("t1f1b", compiled_1f1b_schedule, 4, 8)
    bub = obs.gauge("pipeline.bubble_fraction", schedule="t1f1b").value
    mk, want = compiled_1f1b_schedule(4, 8).simulate()
    assert bub == pytest.approx(want)
    assert 0.0 < bub < 1.0
    assert obs.gauge("pipeline.makespan_ticks",
                     schedule="t1f1b").value == pytest.approx(mk)


# --------------------------------------------------- serving run metrics
class _TinyLM(nn.Layer):
    """Minimal cached causal LM for the cb-session harness — one
    embedding + cache attention + head; a few tiny compiles total."""

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
        x = self.emb(ids)                      # [B, S, H]
        q = x.unsqueeze(2)                     # [B, S, 1, H]
        out, c0 = cache_attention(q, q, q, caches[0])
        h = out.reshape([x.shape[0], x.shape[1], self._hidden])
        return self.proj(x + h), [c0]


def test_cb_session_metrics_and_rid_release():
    from paddle_tpu.inference.decode import ContinuousBatchingSession
    paddle.seed(11)
    m = _TinyLM()
    sess = ContinuousBatchingSession(m, max_slots=2, max_length=16)
    lat0 = obs.histogram("serving.request_latency_s").count
    tok0 = obs.counter("serving.decode_tokens").value
    fetch0 = obs.histogram("serving.step_phase_s", phase="fetch").count
    tpot0 = obs.histogram("serving.tpot_s").count
    rng = np.random.RandomState(2)
    rids = [sess.submit(rng.randint(0, 17, (n,)), 4)
            for n in (3, 5, 2)]
    assert obs.gauge("serving.inflight_requests").value == 3
    out = sess.run()
    assert set(out) == set(rids)
    for rid in rids:
        assert out[rid].shape[0] >= 4

    # satellite: delivered rids leave _used_rids -> no leak, id reuse ok
    assert sess._used_rids == set()
    assert obs.gauge("serving.inflight_requests").value == 0
    rid_again = sess.submit(rng.randint(0, 17, (3,)), 2,
                            request_id=rids[0])
    assert rid_again == rids[0]
    out2 = sess.run()
    assert set(out2) == {rids[0]}

    # instrumentation: latency histogram and token counters moved,
    # queue-depth / utilization gauges exist in the snapshot
    assert obs.histogram("serving.request_latency_s").count >= lat0 + 3
    assert obs.counter("serving.decode_tokens").value > tok0
    # the session's own clock: every step's fetch, every request's pace
    assert obs.histogram("serving.step_phase_s",
                         phase="fetch").count > fetch0
    assert obs.histogram("serving.tpot_s").count >= tpot0 + 3
    snap = {d["name"] for d in obs.dump()}
    for name in ("serving.queue_depth", "serving.slot_utilization",
                 "serving.prefill_tokens"):
        assert name in snap, f"missing {name}"


class _CountingClock:
    """The ``time`` module with its clock reads counted."""

    def __init__(self):
        self.reads = 0

    def __getattr__(self, name):
        if name.startswith(("perf_counter", "monotonic", "time")):
            self.reads += 1
        return getattr(time, name)


def test_disabled_session_step_reads_no_clock_for_metrics(monkeypatch):
    """The disabled-cost micro-benchmark, extended to the session's
    step(): with metrics off its spans, stamps and histograms read no
    clock (the deadline scan's one read is all a step makes) and the
    phase wrapper costs a branch each way."""
    from paddle_tpu.inference import decode
    import paddle_tpu.profiler as prof
    paddle.seed(11)
    sess = decode.ContinuousBatchingSession(_TinyLM(), max_slots=2,
                                            max_length=16, decode_block=2)
    sess.submit(np.arange(3), 4)
    sess.run()                                  # compiled
    clock = _CountingClock()
    monkeypatch.setattr(decode, "time", clock)
    monkeypatch.setattr(prof, "time", clock)
    sess.submit(np.arange(4), 6)
    sess.step()
    assert clock.reads > 3, "the counting clock sees the enabled path"
    step_s = obs.histogram("serving.step_s")
    obs.disable()
    try:
        before = (step_s.count, obs.counter("serving.decode_tokens").value)
        clock.reads = 0
        sess.step()
        assert clock.reads == 1, "only _expire_deadlines reads the clock"
        assert before == (step_s.count,
                          obs.counter("serving.decode_tokens").value)
        n = 20000
        t0 = time.perf_counter()
        for _ in range(n):
            with decode._Phase(prof.RecordEvent("t.phase", slot=1), step_s):
                pass
        per_phase = (time.perf_counter() - t0) / n
        assert per_phase < 5e-6, f"a disabled phase costs {per_phase:.2e}s"
        assert clock.reads == 1
    finally:
        obs.enable()
    sess.close()


def test_the_fetch_is_split_only_while_metrics_are_on(monkeypatch):
    """The account's on-cost, counted: a timed step that admits nothing
    reads the session's clock at most four times more than before the
    account (9: the step, the deadline scan, dispatch, fetch, deliver) and
    waits for the block once (the split of the fetch); with metrics off
    there is no wait, no split, and the deadline scan's one read."""
    import jax
    from paddle_tpu.inference import decode
    paddle.seed(11)
    sess = decode.ContinuousBatchingSession(_TinyLM(), max_slots=2,
                                            max_length=16, decode_block=2)
    sess.submit(np.arange(3), 8)
    sess.step()                                 # compiled, admitted
    clock = _CountingClock()
    waits = []
    wait = jax.block_until_ready

    def counted_wait(x):
        waits.append(1)
        return wait(x)

    monkeypatch.setattr(decode, "time", clock)
    monkeypatch.setattr(jax, "block_until_ready", counted_wait)
    starved = obs.histogram("serving.cycle_starved_s")
    seen = starved.count
    sess.step()
    assert 9 < clock.reads <= 9 + 4, clock.reads
    assert len(waits) == 1 and starved.count == seen + 1
    obs.disable()
    try:
        clock.reads = 0
        sess.step()
        assert clock.reads == 1 and len(waits) == 1
        assert sess._account.t is None, "the open part is forgotten"
        assert sess._account.starving, "as a new session's account"
    finally:
        obs.enable()
    assert starved.count == seen + 1
    with obs.window() as moved:
        sess.step()             # timed again: the account starts anew,
    assert moved.value("serving.cycle_starved_s") == 1
    assert moved.hist("serving.cycle_part_s",       # with no gap to count
                      part="caller")["sum"] == 0
    sess.close()


def test_chrome_trace_carries_metric_counter_events(tmp_path):
    obs.counter("t.trace_probe").inc(9)
    import paddle_tpu.profiler as prof
    p = prof.Profiler()
    p.start()
    _ = paddle.to_tensor(np.ones((2, 2), "f4")) * 2
    p.stop()
    path = str(tmp_path / "trace.json")
    p._export_chrome(path)
    events = json.load(open(path))["traceEvents"]
    counters = [e for e in events if e.get("ph") == "C"]
    assert counters, "no counter events in chrome trace"
    names = {e["name"] for e in counters}
    assert "metric::t.trace_probe" in names
    probe = [e for e in counters
             if e["name"] == "metric::t.trace_probe"][0]
    assert probe["args"]["value"] == 9.0
