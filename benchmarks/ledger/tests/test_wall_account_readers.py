"""The four readers of the program's wall-clock account (ISSUE 36), on a
registry seeded by hand and on the tiny chat cell: each gives what the
registry holds, in its unit, and nothing (not an error) where the program
keeps no such name, as on a parent from before the account."""
import json
import os

import pytest

import paddle_tpu.observability as obs

import run
from test_cells_cpu import run_cell

READERS = ("serve_starved_p50_ms", "serve_caller_p50_ms",
           "serve_fetch_copy_p50_ms", "setup_compile_wall_s")
SERVING = ("serve-1p3b-longctx", "serve-1p3b-chat",
           "serve-sdar-l6-blockdiff", "serve-jamba2-3b-reasoning")


def read(name):
    return run.load_module("layer_metrics", name).read(None, {}, {}, {})


def test_readers_return_nothing_on_an_empty_registry():
    obs.reset()
    assert [read(name) for name in READERS] == [None] * len(READERS)


def test_readers_give_the_seeded_values_in_their_units():
    obs.reset()
    for starved in (0.001, 0.002, 0.004):
        obs.histogram("serving.cycle_starved_s").observe(starved)
    for part, seconds in (("caller", 0.0005), ("fetch_copy", 0.0002),
                          ("dispatch", 0.3)):
        for k in (1, 2, 3):
            obs.histogram("serving.cycle_part_s", part=part).observe(
                k * seconds)
    obs.counter("jit.compile_wall_s").inc(12.5)
    obs.counter("jit.trace_s").inc(99.0)        # not this reader's
    assert read("serve_starved_p50_ms") == pytest.approx(2.0)
    assert read("serve_caller_p50_ms") == pytest.approx(1.0)
    assert read("serve_fetch_copy_p50_ms") == pytest.approx(0.4)
    assert read("setup_compile_wall_s") == 12.5
    obs.reset()


def test_entries_are_the_issues():
    """Each of the four is an entry of ``per_layer`` with a reader file, in
    a layer the benchmark names; the serving three list the four serving
    cells at the least, the compile wall lists none and so follows every
    cell that reports ``setup_s``, as ``setup_compile_s`` does. Looked up
    by name: what later PRs append moves nothing here."""
    with open(os.path.join(run.ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    by_name = {m["name"]: m for m in bench["per_layer"]}
    assert set(READERS) <= set(by_name)
    cells = {w["name"] for w in bench["workloads"]}
    for name in READERS[:3]:
        m = by_name[name]
        assert (m["unit"], m["better"], m["source"], m["moves"]) \
            == ("ms", "lower", "program_span", "serve_tpot_p50_s")
        assert set(SERVING) <= set(m["workloads"]) <= cells
    wall = by_name["setup_compile_wall_s"]
    assert (wall["unit"], wall["source"], wall["moves"]) \
        == ("s", "program_counter", "setup_s")
    assert "workloads" not in wall
    assert "workloads" not in by_name["setup_compile_s"]
    layers = {m["layer"] for m in bench["per_layer"]
              if m["name"] not in READERS}
    assert all(by_name[name]["layer"] in layers for name in READERS)


def test_traced_chat_cell_prints_the_account():
    """The line of a traced run carries the four; the medians are the
    registry's, a starved cycle holds its caller's and its copy's seconds
    at the least in the sums, and the wall is under the three sums."""
    obs.reset()
    out = run_cell("serve-1p3b-chat", 1)
    assert out["correct"], out["checks"]
    got = {name: out["metrics"][name]["value"] for name in READERS}
    assert all(v > 0 for v in got.values()), got
    assert got["serve_starved_p50_ms"] == 1e3 * obs.histogram(
        "serving.cycle_starved_s").percentile(0.5)
    for name, part in (("serve_caller_p50_ms", "caller"),
                       ("serve_fetch_copy_p50_ms", "fetch_copy")):
        assert got[name] == 1e3 * obs.histogram(
            "serving.cycle_part_s", part=part).percentile(0.5)
        assert obs.counter("serving.starved_s", part=part).value \
            == pytest.approx(obs.counter("serving.cycle_s",
                                         part=part).value)
    assert got["setup_compile_wall_s"] \
        <= out["metrics"]["setup_compile_s"]["value"]
    # the open loop sleeps while the session holds nothing: that is no_work
    assert obs.counter("serving.cycle_s", part="no_work").value > 0
    # a cycle that dispatched nothing is not observed
    assert obs.histogram("serving.cycle_starved_s").count \
        <= obs.histogram("serving.step_s").count


@pytest.mark.parametrize("module, was", [
    ("test_blocks_cell_cpu",
     {"serve_tokens_per_lane_pass", "serve_moe_busiest_expert_load",
      "serve_step_host_p50_ms", "serve_prefill_useful_share",
      "setup_compile_s"}),
    ("test_jamba_cell_cpu",
     {"serve_step_host_p50_ms", "serve_decode_useful_share",
      "serve_prefill_useful_share", "setup_compile_s"})])
def test_block_and_state_cells_lines_hold_the_four_names(module, was):
    """The two cells that bring their own tiny sizes, both generation modes
    and a recurrent cache: the traced line still holds what their own test
    files pin (``test_cell_traced_tiny_reports_what_the_counters_give``, an
    exact set that a ``benchmark`` PR has to widen: PERF.md, Open
    questions) and the four readers, each over 0 and the wall under the
    sums. Subset checks: a later PR's metric in these cells fails nothing
    here."""
    import importlib
    obs.reset()
    out = importlib.import_module(module).run_cell(1)
    assert out["correct"], out["checks"]
    got = {name: m["value"] for name, m in out["metrics"].items()}
    assert was | set(READERS) <= set(got)
    assert all(got[name] > 0 for name in READERS), got
    assert got["setup_compile_wall_s"] <= got["setup_compile_s"]


def test_traced_train_cell_prints_the_compile_wall_alone():
    obs.reset()
    out = run_cell("train-1p3b-s1024", 1)
    assert out["correct"], out["checks"]
    assert 0 < out["metrics"]["setup_compile_wall_s"]["value"] \
        <= out["metrics"]["setup_compile_s"]["value"]
    assert not any(name.startswith("serve_") for name in out["metrics"])
