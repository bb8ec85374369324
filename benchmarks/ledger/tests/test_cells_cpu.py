"""Every cell of BENCHMARK.json end to end at a tiny size on the CPU, through
``run.main``'s test-only override (the command line cannot reach it, and the
command itself refuses a CPU), and the float32 reference against the program
at that size."""
import json
import os
import subprocess
import sys

import numpy as np
import pytest

import run
from run import HERE, ROOT

TINY = {"sizes": {"vocab_size": 512, "hidden_size": 64, "num_layers": 2,
                  "num_heads": 4, "max_seq_len": 128},
        "build": {"train": {"parallel": {"scan_unroll": 1}},
                  "serve": {"session_kwargs": {"decode_block": 4}}},
        "reference": {"serve_prompt_tokens": 16, "serve_new_tokens": 5}}
TINY_TRAFFIC = {
    "train_steps": {"batch": 4, "seq": 32, "trace_seconds": 1},
    "serve_backlog": {
        "slots": 3, "capacity": 128, "ramp_steps": 2, "trace_seconds": 1,
        "prompt_tokens": {"dist": "uniform", "min": 65, "max": 90},
        "new_tokens": {"dist": "uniform", "min": 8, "max": 16},
        "arrivals": {"process": "backlog", "per_window_second": 600}},
    "serve_open": {
        "slots": 4, "capacity": 128, "trace_seconds": 1,
        "prompt_tokens": {"dist": "lognormal", "median": 24, "sigma": 0.6,
                          "min": 8, "max": 64},
        "new_tokens": {"dist": "lognormal", "median": 12, "sigma": 0.5,
                       "min": 6, "max": 32},
        "arrivals": {"process": "poisson", "rate_per_s": 40, "ramp_s": 0.2,
                     "drain_s": 0.5}},
}

with open(os.path.join(ROOT, "BENCHMARK.json")) as _f:
    BENCH = json.load(_f)
CELLS = [w["name"] for w in BENCH["workloads"]]


def run_cell(name, trace):
    cell = next(w for w in BENCH["workloads"] if w["name"] == name)
    with open(os.path.join(HERE, "traffic", cell["traffic"] + ".json")) as f:
        loop = json.load(f)["loop"]
    return run.main(
        ["--workload", name, "--seed", "4000000007", "--seconds", "2",
         "--trace", str(trace)],
        _test_override={"allow_cpu": True, "config": TINY,
                        "traffic": TINY_TRAFFIC[loop]})


@pytest.mark.parametrize("name", CELLS)
def test_cell_end_to_end_tiny(name, capsys):
    out = run_cell(name, 0)
    last = capsys.readouterr().out.strip().splitlines()[-1]
    assert json.loads(last)["metrics"] == out["metrics"]
    assert out["correct"], out["checks"]
    assert out["attempted"] > 0 and out["failed"] == 0
    want = {m["name"] for m in run.metrics_of(BENCH, "end_to_end",
                                              {"name": name})}
    assert set(out["metrics"]) == want and "setup_s" in want
    assert all(np.isfinite(m["value"]) and m["value"] > 0
               for m in out["metrics"].values())
    assert out["device"]["platform"] == "cpu"      # never a device number


@pytest.mark.parametrize("name", CELLS)
def test_cell_traced_tiny_reports_only_what_it_can_read(name):
    out = run_cell(name, 1)
    assert out["correct"], out["checks"]
    allowed = {m["name"] for m in run.metrics_of(BENCH, "per_layer",
                                                 {"name": name})}
    # no device plane on the CPU: the trace-reading metrics return nothing
    # and are left out; the counting ones are there
    assert set(out["metrics"]) <= allowed
    assert not any("idle_share" in k or "roofline" in k or "collective" in k
                   or "prefill_time" in k for k in out["metrics"])
    assert "busy_s" not in out["device"]


def test_every_per_layer_metric_has_a_reader_and_moves_what_its_cells_report():
    e2e = {m["name"]: m for m in BENCH["end_to_end"]}
    for m in BENCH["per_layer"]:
        assert os.path.exists(os.path.join(HERE, "layer_metrics",
                                           m["name"] + ".py"))
        for cell in m.get("workloads", CELLS):
            moved = e2e[m["moves"]]
            assert cell in moved.get("workloads", CELLS), (m["name"], cell)


def test_command_refuses_a_cpu_and_prints_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, os.path.join(HERE, "run.py"), "--workload", CELLS[0],
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert "{" not in p.stdout
    assert "not 'tpu'" in p.stderr


def test_wrong_tokens_or_a_compile_in_the_window_make_correct_false():
    """The two serving checks that hold a run to its budgets, on the
    bookkeeping alone."""
    import serving

    class R:
        def __init__(self, state, n):
            self.state = type("S", (), {"name": state})
            self.ids = np.zeros(n, np.int32)

    class Session:
        def cancel(self, rid): pass
        def close(self): pass
        def results(self):
            return {0: R("DONE", 14), 1: R("DONE", 13), 2: R("CANCELLED", 11)}

    served = serving.Served.__new__(serving.Served)
    served.session = Session()
    served.req = {i: {"plen": 10, "new": 4, "done": d, "first": 0.5}
                  for i, d in enumerate((1.0, 1.0, None))}
    finished, wrong = served.finish()
    assert finished == [0, 1] and wrong == [1]     # 3 of 4 tokens: wrong


def test_reference_matches_the_program_at_tiny_size():
    """float32 ``reference_logits`` against ``gpt_hybrid.forward`` in float32
    compute: the same mathematics, to float32 rounding."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.models import gpt_hybrid as gh
    from paddle_tpu.models.gpt import GPTConfig
    arch = run.load_module("arch", "gpt_dense")
    cfg = GPTConfig(**TINY["sizes"])
    pcfg = gh.ParallelConfig(param_dtype=jnp.float32,
                             compute_dtype=jnp.float32)
    mesh = gh.build_mesh(pcfg, jax.devices()[:1])
    params = gh.init_params(cfg, pcfg, jax.random.PRNGKey(3))
    ids = np.random.RandomState(0).randint(0, cfg.vocab_size, (2, 48))
    with mesh:
        got = np.asarray(gh.forward(params, jnp.asarray(ids), cfg, pcfg, mesh))
    want = np.asarray(arch.reference_logits(params, ids, cfg.num_heads))
    assert np.max(np.abs(got - want)) / np.max(np.abs(want)) < 1e-5


def test_open_loop_and_chat_mix_kept_for_a_later_cell_run_tiny():
    """``serve-1p3b-chat`` is not in BENCHMARK.json (PERF.md, Open questions);
    its loop, mix and readers stay, and stay working."""
    import jax
    from peaks import peaks_for
    config = run._patched(run.load_json(HERE, "configs", "gpt3-1.3b.json"),
                          TINY)
    traffic = run._patched(run.load_json(HERE, "traffic", "chat-steady.json"),
                           TINY_TRAFFIC["serve_open"])
    ctx = run.Context({"name": "chat-tiny", "chips": 1}, config, traffic, 7,
                      2.0, 0, jax.devices()[:1], peaks_for("TPU v5 lite"))
    out = run.load_module("loops", "serve_open").run(ctx)
    assert all(out["checks"].values()), out["checks"]
    assert out["attempted"] > 20 and out["failed"] == 0
    assert 0 < out["metrics"]["serve_ttft_p90_s"] < 1
    counts = dict(out["counts"], trace_from_s=None)
    for name in ("serve_slot_occupancy", "serve_ttft_p50_s",
                 "gen_lateness_p99_s"):
        value = run.load_module("layer_metrics", name).read(
            None, counts, config, ctx.peaks)
        assert value is not None and value >= 0
