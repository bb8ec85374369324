"""The readers that take their numbers from the program's own registry
(``paddle_tpu.observability``) instead of the loop or the trace: each must
return what the registry holds, on the tiny ``serve_backlog`` and
``serve_open`` cells, and nothing (not an error) where the program has no
such instrument."""
import pytest

import paddle_tpu.observability as obs

import run
from test_cells_cpu import run_cell

PROGRAM_READERS = (
    "serve_step_host_p50_ms", "serve_decode_useful_share",
    "serve_prefill_useful_share", "setup_compile_s",
    "serve_queue_wait_p50_s", "serve_first_token_hold_p50_s",
    "serve_ttft_inside_p50_s")


def read(name):
    return run.load_module("layer_metrics", name).read(None, {}, {}, {})


def p50(name):
    return obs.histogram(name).percentile(0.5)


def value(name):
    return obs.counter(name).value


@pytest.mark.parametrize("cell", ["serve-1p3b-longctx", "serve-1p3b-chat"])
def test_program_readers_give_the_registrys_values(cell):
    obs.reset()
    out = run_cell(cell, 1)
    assert out["correct"], out["checks"]
    got = {name: read(name) for name in PROGRAM_READERS}
    assert all(v is not None and v > 0 for v in got.values()), got

    assert got["serve_step_host_p50_ms"] == 1e3 * p50("serving.step_host_s")
    # a step's host time is under the step's
    assert p50("serving.step_host_s") <= p50("serving.step_s")
    lanes = value("serving.decode_lane_steps")
    slots, block = 3 if "longctx" in cell else 4, 4     # the tiny sizes
    assert lanes and lanes % block == 0
    assert got["serve_decode_useful_share"] == 100.0 * (
        value("serving.decode_tokens") - value("serving.first_tokens")) / lanes
    assert 0 < got["serve_decode_useful_share"] <= 100
    assert got["serve_prefill_useful_share"] == 100.0 * value(
        "serving.prefill_tokens") / value("serving.prefill_padded_tokens")
    assert 0 < got["serve_prefill_useful_share"] <= 100
    assert got["setup_compile_s"] == value("jit.trace_s") \
        + value("jit.lower_s") + value("jit.backend_compile_s")
    assert got["serve_queue_wait_p50_s"] == p50("serving.queue_wait_s")
    assert got["serve_first_token_hold_p50_s"] \
        == p50("serving.first_token_hold_s")
    assert got["serve_ttft_inside_p50_s"] == p50("serving.ttft_s")
    # every admitted request waited, then held: the three histograms are
    # over the same requests
    admits = obs.histogram("serving.queue_wait_s").count
    assert admits == value("serving.admits") > slots
    assert obs.histogram("serving.ttft_s").count \
        == value("serving.first_tokens") <= admits

    # the line of the traced run carries the entries' metrics, each the
    # reader's value at the time the line was made
    for name in ("serve_step_host_p50_ms", "setup_compile_s"):
        assert out["metrics"][name]["value"] > 0
    in_line = "serve_decode_useful_share" in out["metrics"]
    assert in_line == ("longctx" in cell)
    assert ("serve_prefill_useful_share" in out["metrics"]) == in_line


@pytest.mark.parametrize("cell", ["train-1p3b-s1024",
                                  "train-6p7b-s2048-4chip"])
def test_train_cell_reports_its_compile_seconds(cell):
    obs.reset()
    out = run_cell(cell, 1)
    assert out["correct"], out["checks"]
    assert out["metrics"]["setup_compile_s"]["value"] > 0
    assert not any(name.startswith("serve_") for name in out["metrics"])


def test_readers_return_nothing_where_the_program_counts_nothing():
    """As on a program from before these instruments: empty registry, no
    value, no error (the driver runs the readers on the parent too)."""
    obs.reset()
    assert [read(name) for name in PROGRAM_READERS] \
        == [None] * len(PROGRAM_READERS)
