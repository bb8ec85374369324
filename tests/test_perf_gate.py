"""Perf/footprint regression gate for the flagship training program.

Round 3 shipped a silent moment-dtype regression (f32 moments under the
bf16-param flagship = +5.2 GB = OOM cascade on the 16 GB chip) that the
884-test suite never saw, because nothing constrained the flagship
program's footprint. These gates pin the invariants on CPU, in seconds:

  - optimizer state INHERITS the param dtype under moment_dtype=None
    (the `zeros_like` contract every recorded bench number ran under)
  - total train-state bytes (params + both Adam moments) of the 1.3B
    flagship stay inside a golden budget — eval_shape only, no memory
  - the jitted train step's executable cache stays at ONE entry across
    repeated same-shape steps (recompile = silent 20-40 s/step cliff)
  - the gradient-merge step does not widen the accumulator beyond the
    param dtype (a second place a dtype default could silently double
    HBM)

Reference analog: the CI op-benchmark regression gate
(/root/reference/tools/ci_op_benchmark.sh) — an automated tripwire, not
a human remembering to re-measure.

RATIO-BASED rungs (ISSUE 13): the gate pins WITHIN-WINDOW RATIOS (two
quantities measured in the same capture: s4096/s1024 MFU,
dataloader-fed/pinned, cb/per-step-decode) and telemetry-derived
invariants read from the registry snapshot a bench json embeds under its
``telemetry`` key. Absolute throughputs are reported informationally
only — they are NOT asserted. ``check_ratio_rungs`` is exercised on a
synthetic capture; the chip records the bands were first drawn from
were deleted in PR 21, and the ledger's per-cell bounds are to replace
the bands (ROADMAP).
"""
import json

import jax
import jax.numpy as jnp
import numpy as np

from paddle_tpu.models.gpt import GPTConfig
from paddle_tpu.models import gpt_hybrid as GH
from paddle_tpu.observability import Snapshot

FLAGSHIP = GPTConfig(vocab_size=50304, hidden_size=2048, num_layers=24,
                     num_heads=16, max_seq_len=1024)


def _flagship_pcfg(**over):
    base = dict(dp=1, pp=1, tp=1, remat=True, remat_policy="names",
                scan_unroll=1, param_dtype=jnp.bfloat16,
                compute_dtype=jnp.bfloat16, moment_dtype=None)
    base.update(over)
    return GH.ParallelConfig(**base)


def _state_shapes(pcfg):
    """abstract (params, opt_state) of the flagship — no arrays made."""
    def build():
        params = GH.init_params(FLAGSHIP, pcfg, jax.random.PRNGKey(0))
        # dp==1: adamw_init's zero1 sharding branch is dead, mesh unused
        opt = GH.adamw_init(params, pcfg, mesh=None, specs=None)
        return params, opt
    return jax.eval_shape(build)


def _tree_bytes(tree):
    return sum(int(np.prod(l.shape)) * l.dtype.itemsize
               for l in jax.tree_util.tree_leaves(tree))


def test_moments_inherit_param_dtype():
    params, opt = _state_shapes(_flagship_pcfg())
    pleaves = jax.tree_util.tree_leaves(params)
    for name in ("m", "v"):
        mleaves = jax.tree_util.tree_leaves(opt[name])
        assert len(mleaves) == len(pleaves)
        for p, mo in zip(pleaves, mleaves):
            assert mo.dtype == p.dtype, (
                f"moment '{name}' dtype {mo.dtype} != param dtype "
                f"{p.dtype} under moment_dtype=None — this is the "
                "round-3 +5.2 GB regression")


def test_flagship_state_bytes_within_budget():
    # bf16 1.3B: params ~2.63 GB, m ~2.63, v ~2.63 => ~7.9 GB.
    # f32 moments push this to ~13.2 GB and must FAIL here.
    params, opt = _state_shapes(_flagship_pcfg())
    total = _tree_bytes(params) + _tree_bytes(opt["m"]) + \
        _tree_bytes(opt["v"])
    budget = 8.5e9
    assert total < budget, (
        f"flagship train state {total/1e9:.2f} GB exceeds the golden "
        f"{budget/1e9:.1f} GB budget (param+moment dtype widened?)")
    # and the explicit-f32 config is provably over — the gate is live
    _, opt32 = _state_shapes(_flagship_pcfg(moment_dtype=jnp.float32))
    total32 = _tree_bytes(params) + _tree_bytes(opt32["m"]) + \
        _tree_bytes(opt32["v"])
    assert total32 > budget


def test_train_step_executable_count_stable():
    """Steady-state calls of the jitted train step must neither
    RE-TRACE nor RE-COMPILE (a recompile = silent 20-40 s/step cliff).

    Asserted via the framework's compile-cache tracker over calls
    2..4, NOT via PjitFunction._cache_size(): the C++ fastpath-cache
    entry count measures whether jaxlib *installed its dispatch
    fastpath*, which late in a long test session can legitimately be
    declined (observed deterministically after ~750 suite tests with
    zero retraces, zero recompiles, clean config and an effect-free
    jaxpr — a jaxlib dispatch-layer heuristic, not a program
    regression). Counting actual tracing/compilation events pins the
    invariant that matters and is order-independent. (Formerly used
    jtu.count_jit_*_cache_miss, whose yielded object drifted from a
    callable to a bare list across jax versions —
    observability.count_traces/count_compiles is the stable
    framework-owned surface.)"""
    from paddle_tpu import observability as obs
    cfg = GPTConfig(vocab_size=256, hidden_size=64, num_layers=2,
                    num_heads=2, max_seq_len=64)
    pcfg = _flagship_pcfg(param_dtype=jnp.float32,
                          compute_dtype=jnp.float32)
    mesh, params, opt_state, step = GH.setup(cfg, pcfg, seed=0,
                                             devices=jax.devices()[:1])
    ids = jnp.zeros((2, 32), jnp.int32)
    with mesh:
        # warmup call pays the one allowed trace+compile
        params, opt_state, loss = step(params, opt_state, (ids, ids))
        with obs.count_traces() as traces, \
                obs.count_compiles() as compiles:
            for _ in range(3):
                params, opt_state, loss = step(params, opt_state,
                                               (ids, ids))
    assert traces() == 0 and compiles() == 0, (
        f"steady-state train-step calls re-traced {traces()}x / "
        f"re-compiled {compiles()}x — donation/weak-type/sharding "
        "drift is forcing recompiles")
    # liveness: the counters must SEE a genuine recompile (new shape),
    # or the zero above proves nothing
    with mesh:
        with obs.count_traces() as traces2:
            ids2 = jnp.zeros((4, 32), jnp.int32)
            params, opt_state, loss = step(params, opt_state,
                                           (ids2, ids2))
    assert traces2() > 0, "counter failed to observe a real retrace"


def test_gradient_merge_accumulator_dtype():
    pcfg = _flagship_pcfg(gradient_merge_steps=4)
    params, _ = _state_shapes(pcfg)
    # the merge accumulator is zeros_like(params) inside the scan —
    # assert the public contract at the init helper that feeds the
    # split-engine path (same zeros_like rule)
    acc = jax.eval_shape(
        lambda: GH.init_grad_accum(
            jax.eval_shape(lambda: GH.init_params(
                FLAGSHIP, pcfg, jax.random.PRNGKey(0)))))
    for a, p in zip(jax.tree_util.tree_leaves(acc),
                    jax.tree_util.tree_leaves(params)):
        assert a.dtype == p.dtype
    # decode's executable-count stability is gated in
    # tests/test_decode.py::test_decode_executable_stability


# ===================================================================
# Ratio-based regression rungs (ISSUE 13). The bands below were drawn
# from an earlier chip record, deleted in PR 21 (s4096/s1024 0.870,
# s2048/s1024 0.929, dataloader-fed/pinned 1.007, cb/per-step decode
# 1.83 there); nothing on the current code has been measured against
# them yet.
#   * s4096/s1024 MFU ratio: the blocked kernel only dispatches where
#     it measures faster, so the floor is pre-kernel-minus-margin.
#   * dataloader-fed vs pinned batch: the loader must not throttle the
#     step; the ceiling catches a formula bug (the loader cannot beat a
#     pinned batch by 10%).
#   * cb vs per-step decode, SAME window: the floor only trips when
#     continuous batching falls below the naive path it exists to beat.
RATIO_RUNGS = {
    "train_s4096.mfu_ratio_vs_s1024": (0.82, 1.05),
    "train_s2048.mfu_ratio_vs_s1024": (0.87, 1.10),
    "train_dataloader_fed.vs_pinned_batch": (0.97, 1.10),
    "serve_cb_block16.vs_decode_b8": (0.80, 6.0),
}

#: trace-time analytic bubble fraction ceiling per schedule family
#: (read from the BENCH json's embedded telemetry snapshot)
BUBBLE_CEILING = {"zbh1": 0.2, "zbvpp": 0.2}
BUBBLE_CEILING_DEFAULT = 0.5

S4096_MFU_TARGET = 0.62   # the s4096 roofline question (bench.py)


def check_ratio_rungs(parsed):
    """Gate one parsed BENCH document. Returns (checked, failures,
    missing): ``checked`` maps every rung that was present to its
    value, ``failures`` lists band violations, ``missing`` names rungs
    this capture did not carry (informational — older captures predate
    some rungs). Absolute throughputs are never asserted here."""
    checked, failures, missing = {}, [], []
    rungs = parsed.get("rungs") or {}
    for name, (lo, hi) in RATIO_RUNGS.items():
        rung_name, key = name.split(".")
        v = rungs.get(rung_name) or {}
        v = v.get(key) if isinstance(v, dict) else None
        if v is None:
            missing.append(name)
            continue
        checked[name] = v
        if lo is not None and v < lo:
            failures.append(f"{name}={v} below floor {lo}")
        if hi is not None and v > hi:
            failures.append(f"{name}={v} above ceiling {hi}")

    # --- telemetry-derived rungs: the registry snapshot embedded in
    # the same artifact (bench.py writes it under "telemetry")
    tel = (parsed.get("telemetry") or {}).get("metrics")
    if tel is None:
        missing.append("telemetry")
        return checked, failures, missing
    snap = Snapshot.from_metrics(tel)
    for d in snap.series("pipeline.bubble_fraction"):
        sched = (d.get("labels") or {}).get("schedule", "?")
        name = f"telemetry.bubble_fraction[{sched}]"
        val = d.get("value", 0.0)
        checked[name] = val
        ceil = BUBBLE_CEILING.get(sched, BUBBLE_CEILING_DEFAULT)
        if not (0.0 <= val <= ceil):
            failures.append(f"{name}={val} outside [0, {ceil}]")
    # a measured long-context rung must have gone through the
    # instrumented dispatch chain — the kernel choice is recorded, not
    # inferred
    s4096 = rungs.get("train_s4096") or {}
    if "mfu" in s4096:
        n_disp = sum(d.get("value", 0)
                     for d in snap.series("attn.dispatch"))
        name = "telemetry.attn_dispatches"
        checked[name] = n_disp
        if n_disp <= 0:
            failures.append(
                f"{name}: s4096 measured but no attn.dispatch "
                "counters in the embedded snapshot")
    return checked, failures, missing


def test_ratio_gate_trips_and_passes(tmp_path):
    """The gate logic itself, against a BENCH json on disk (the exact
    read path the real artifacts take): a healthy capture passes every
    rung including the telemetry-derived ones; a regressed capture
    fails on the regressed rungs and ONLY those."""
    telemetry = {"ts": 0.0, "metrics": [
        {"name": "pipeline.bubble_fraction", "type": "gauge",
         "labels": {"schedule": "1f1b"}, "value": 0.27},
        {"name": "pipeline.bubble_fraction", "type": "gauge",
         "labels": {"schedule": "zbh1"}, "value": 0.03},
        {"name": "attn.dispatch", "type": "counter",
         "labels": {"kernel": "blocked_bq512_bkv512"}, "value": 2.0},
        {"name": "train.mfu", "type": "gauge", "labels": {},
         "value": 0.63},
    ]}
    good = {
        "metric": "gpt1.3b_train_tokens_per_sec_per_chip",
        "value": 15736.8, "mfu": 0.6779,
        "rungs": {
            "train_s2048": {"mfu": 0.6295,
                            "mfu_ratio_vs_s1024": 0.9286},
            "train_s4096": {"mfu": 0.63, "mfu_ratio_vs_s1024": 0.9294,
                            "attn_kernel": "blocked_bq512_bkv512"},
            "train_dataloader_fed": {"vs_pinned_batch": 1.0066},
            "serve_cb_block16": {"tokens_per_sec": 423.3,
                                 "vs_decode_b8": 1.832},
            "decode_gpt1.3b_b8": {"tokens_per_sec": 231.1},
        },
        "telemetry": telemetry,
    }
    p = tmp_path / "BENCH_synthetic.json"
    p.write_text(json.dumps({"n": 99, "parsed": good}))
    parsed = json.loads(p.read_text())["parsed"]
    checked, failures, missing = check_ratio_rungs(parsed)
    assert not failures, failures
    assert not missing
    # >= 3 ratio rungs pinned, the headline one among them, plus the
    # telemetry-derived bubble/dispatch invariants
    assert len([k for k in checked if k in RATIO_RUNGS]) >= 3
    assert "train_s4096.mfu_ratio_vs_s1024" in checked
    assert "telemetry.bubble_fraction[1f1b]" in checked
    assert "telemetry.attn_dispatches" in checked

    # regressed capture: s4096 ratio collapses, zbh1 bubble explodes,
    # cb falls below the naive decode path
    bad = json.loads(json.dumps(good))
    bad["rungs"]["train_s4096"]["mfu_ratio_vs_s1024"] = 0.70
    bad["rungs"]["serve_cb_block16"]["vs_decode_b8"] = 0.5
    bad["telemetry"]["metrics"][1]["value"] = 0.35   # zbh1 bubble
    _, failures, _ = check_ratio_rungs(bad)
    assert len(failures) == 3, failures
    assert any("train_s4096" in f for f in failures)
    assert any("vs_decode_b8" in f for f in failures)
    assert any("zbh1" in f for f in failures)

    # a capture missing a rung reports it missing — never a false trip
    sparse = {"rungs": {"train_dataloader_fed":
                        {"vs_pinned_batch": 1.0}}}
    checked, failures, missing = check_ratio_rungs(sparse)
    assert not failures
    assert "train_s4096.mfu_ratio_vs_s1024" in missing
    assert "telemetry" in missing
