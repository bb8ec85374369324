"""The Jamba cell end to end at a tiny size on the CPU, through
``run.main``'s test-only override: both reference checks (the small session
before the window, the replay of the window's own requests after it), which
a state that forgets its padding must fail; its three readers."""
import json

import numpy as np
import pytest

import run

CELL = "serve-jamba2-3b-reasoning"
TINY = {"sizes": {"vocab_size": 96, "hidden_size": 32,
                  "intermediate_size": 48, "num_layers": 8, "num_heads": 4,
                  "num_key_value_heads": 1, "head_dim": 8,
                  "attn_layer_period": 4, "attn_layer_offset": 1,
                  "mamba_d_state": 4, "mamba_dt_rank": 6,
                  "param_dtype": "float32", "initializer_range": 0.3},
        "build": {"serve": {"weights_dtype": "float",
                            "session_kwargs": {"decode_block": 4}}},
        "reference": {"layers": 8, "sequences": 2,
                      "serve_prompt_tokens": 11, "serve_new_tokens": 9,
                      "serve_margin_tolerance": 1e-4,
                      "replay_requests": 4,
                      "replay_margin_tolerance": 1e-4,
                      "replay_compared_floor": 20,
                      "state": {"prompt_tokens": [11, 9], "new_tokens": 9,
                                "capacity": 32, "slow_rate": 0.02,
                                "tolerance": 1e-4,
                                "slow_tolerance": 1e-4}}}
TINY_TRAFFIC = {
    "slots": 3, "capacity": 64, "ramp_steps": 2, "trace_seconds": 1,
    "prompt_tokens": {"dist": "uniform", "min": 9, "max": 30},
    "new_tokens": {"dist": "uniform", "min": 6, "max": 13},
    "arrivals": {"process": "backlog", "per_window_second": 400}}


def run_cell(trace):
    return run.main(
        ["--workload", CELL, "--seed", "4000000007", "--seconds", "2",
         "--trace", str(trace)],
        _test_override={"allow_cpu": True, "config": TINY,
                        "traffic": TINY_TRAFFIC})


def test_cell_end_to_end_tiny(capsys):
    out = run_cell(0)
    last = capsys.readouterr().out.strip().splitlines()[-1]
    assert json.loads(last)["metrics"] == out["metrics"]
    assert out["correct"], out["checks"]
    assert out["checks"]["matches_reference"]
    assert out["checks"]["replay_matches_reference"]
    assert out["checks"]["state_matches_reference"]
    assert out["attempted"] > 0 and out["failed"] == 0
    assert set(out["metrics"]) == {"serve_tokens_per_s", "serve_tpot_p50_s",
                                   "setup_s"}
    assert all(np.isfinite(m["value"]) and m["value"] > 0
               for m in out["metrics"].values())
    assert out["device"]["platform"] == "cpu"      # never a device number


def test_cell_traced_tiny_reports_what_the_counters_give():
    out = run_cell(1)
    assert out["correct"], out["checks"]
    got = {k: v["value"] for k, v in out["metrics"].items()}
    # no device plane on the CPU: no roofline, no idle, prefill or scan time
    assert set(got) == {"serve_step_host_p50_ms", "serve_decode_useful_share",
                        "serve_prefill_useful_share", "setup_compile_s"}
    assert 0 < got["serve_prefill_useful_share"] < 100


def test_padding_that_reaches_the_state_fails_both_checks(monkeypatch):
    """The session told nothing of the padding: the scan runs over it and
    the window shifts past it, as for a model that keeps no such state."""
    from paddle_tpu.inference import decode
    monkeypatch.setattr(decode.RecurrentCache, "prefilling",
                        lambda self, lens: self)
    out = run_cell(0)
    assert not out["checks"]["matches_reference"]
    assert not out["checks"]["replay_matches_reference"]
    assert not out["checks"]["state_matches_reference"]
    assert out["checks"]["every_request_done_with_its_budget"]


def test_a_state_kept_in_bfloat16_fails_the_state_check(monkeypatch):
    """The scan state rounded to bfloat16 wherever the model returns it,
    as a cache held in bfloat16 would (``reduce_precision``: the TPU
    compiler drops a convert there and back, PERF.md section 6, PR 32)."""
    import jax
    from paddle_tpu.models import jamba

    def rounded(fn):
        def wrapped(*a):
            y, h = fn(*a)
            return y, jax.lax.reduce_precision(h, 8, 7)
        return wrapped
    monkeypatch.setattr(jamba, "_ssm_step", rounded(jamba._ssm_step))
    monkeypatch.setattr(jamba, "_scan", rounded(jamba._scan))
    out = run_cell(0)
    assert not out["checks"]["state_matches_reference"]
    assert out["checks"]["every_request_done_with_its_budget"]


def test_the_cell_names_this_file_as_its_cpu_test():
    import os
    bench = run.load_json(run.ROOT, "BENCHMARK.json")
    _cell, _config, traffic = run.find_cell(bench, CELL)
    assert os.path.samefile(os.path.join(run.HERE, traffic["cpu_test"]),
                            __file__)


def _reduced(module_s, runs, op_s, busy_s=1.0):
    return {"devices": {0: {"module_s": module_s, "module_runs": runs,
                            "op_s": op_s, "busy_s": busy_s}}}


def test_state_roofline_reader_by_hand():
    """A decode block of 16 steps that took exactly the time its bytes
    take at the peak reads 100 %."""
    reader = run.load_module("layer_metrics",
                             "serve_state_decode_step_roofline")
    arch = run.load_module("arch", "jamba")
    config = run.load_json(run.HERE, "configs", "jamba2-3b.json")
    need = arch.decode_step_bytes(config["sizes"], 250.0, 300000.0, 2, 4)
    reduced = _reduced({"jit__decode_block_pure": 10 * 16 * need / 819e9},
                       {"jit__decode_block_pure": 10}, {})
    counts = {"samples": [(0.5, 256, 1e9), (1.5, 256, 290000.0),
                          (2.5, 244, 310000.0)], "trace_from_s": 1.0}
    assert reader.read(reduced, counts, config, {"bytes_per_s": 819e9}) \
        == pytest.approx(100.0)
    # a configuration whose architecture keeps no state: nothing to read
    gpt = run.load_json(run.HERE, "configs", "gpt3-1.3b.json")
    assert reader.read(reduced, counts, gpt, {"bytes_per_s": 819e9}) is None


def test_scan_readers_by_hand():
    """Scans of two buckets that took twice the time their bytes take,
    a quarter of it in the copies beside the kernel, read 50 %; their
    seconds over the busy time are the share."""
    roof = run.load_module("layer_metrics", "serve_scan_kernel_roofline")
    share = run.load_module("layer_metrics", "serve_scan_time_share")
    arch = run.load_module("arch", "jamba")
    config = run.load_json(run.HERE, "configs", "jamba2-3b.json")
    sizes, peaks = config["sizes"], {"bytes_per_s": 819e9}
    t256 = 26 * arch.scan_bytes(sizes, 256) / 819e9
    t1024 = 52 * arch.scan_bytes(sizes, 1024) / 819e9
    op_s = {"selective_scan f32[1,256,40,128]": [1.5 * t256, 26],
            "copy f32[32,8,40,128]": [0.3 * t256, 26],
            "reshape f32[1,256,5120]": [0.2 * t256, 26],
            "selective_scan f32[1,1024,40,128]": [1.5 * t1024, 52],
            "copy f32[128,8,40,128]": [0.5 * t1024, 52],
            "copy f32[64,8,40,128]": [1.0, 3],      # no scan of 512 ran
            "fusion f32[1,1024,40,128]": [1.0, 3]}
    reduced = _reduced({}, {}, op_s, busy_s=8 * (t256 + t1024))
    assert roof.read(reduced, {}, config, peaks) == pytest.approx(50.0)
    assert share.read(reduced, {}, config, peaks) == pytest.approx(25.0)
    # the parent's trace holds no such operation
    nothing = _reduced({}, {}, {"fusion f32[1,1024,40,128]": [1.0, 3],
                                 "copy f32[128,8,40,128]": [1.0, 3]})
    assert roof.read(nothing, {}, config, peaks) is None
    assert share.read(nothing, {}, config, peaks) is None
    assert roof.read(None, {}, config, peaks) is None
