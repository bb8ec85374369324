"""The block-diffusion cell end to end at a tiny size on the CPU, through
``run.main``'s test-only override; its replay against the reference, which a
dropped expert or a coarser product must fail; its three readers."""
import json

import numpy as np
import pytest

import run
import serving_blocks

CELL = "serve-sdar-l6-blockdiff"
TINY = {"vocab_size": 160, "hidden_size": 32, "moe_intermediate_size": 16,
        "num_hidden_layers": 2, "num_attention_heads": 4,
        "num_key_value_heads": 2, "head_dim": 8, "num_experts": 8,
        "num_experts_per_tok": 2, "max_position_embeddings": 128,
        "initializer_range": 0.2,
        "generation": {"mask_token_id": 159},
        "build": {"serve": {"weights_dtype": "float32"}},
        "reference": {"replay_requests": 4,
                      "tolerances": {"logit_margin": 1e-4,
                                     "confidence_gap": 1e-4,
                                     "router_margin": 1e-4},
                      "compared_floor": {"tokens": 20, "pairs": 4},
                      "differing_share": {
                          "token": {"over": 1e-4, "at_most": 0.0},
                          "pair": {"over": 1e-4, "at_most": 0.0}}}}
TINY_TRAFFIC = {
    "slots": 3, "capacity": 64, "ramp_steps": 2, "trace_seconds": 1,
    "prompt_tokens": {"dist": "uniform", "min": 9, "max": 30},
    "new_tokens": {"dist": "uniform", "min": 10, "max": 10},
    "arrivals": {"process": "backlog", "per_window_second": 400}}


def run_cell(trace, traffic=None, floor=None):
    config = run._patched(TINY, {"reference": {"compared_floor": floor}}) \
        if floor else TINY
    return run.main(
        ["--workload", CELL, "--seed", "4000000007", "--seconds", "2",
         "--trace", str(trace)],
        _test_override={"allow_cpu": True, "config": config,
                        "traffic": dict(TINY_TRAFFIC, **(traffic or {}))})


@pytest.mark.parametrize("traffic, floor", [
    ({}, None),
    # every confidence is over this threshold: a pass fixes all that is
    # open and leaves no pair to compare
    ({"remasking": "low_confidence_dynamic", "denoising_steps": 4,
      "confidence_threshold": 0.008}, {"tokens": 20, "pairs": 0})],
    ids=["static2", "dynamic4"])
def test_cell_end_to_end_tiny(traffic, floor, capsys):
    out = run_cell(0, traffic, floor)
    last = capsys.readouterr().out.strip().splitlines()[-1]
    assert json.loads(last)["metrics"] == out["metrics"]
    assert out["correct"], out["checks"]
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
    # no device plane on the CPU: no roofline, no idle or prefill time share
    assert set(got) == {"serve_tokens_per_lane_pass",
                        "serve_moe_busiest_expert_load",
                        "serve_step_host_p50_ms",
                        "serve_prefill_useful_share", "setup_compile_s"}
    assert 0 < got["serve_tokens_per_lane_pass"] <= 4 / 3
    assert 1.0 <= got["serve_moe_busiest_expert_load"] <= 8 / 2


def test_the_cell_names_this_file_as_its_cpu_test():
    """``test_cells_cpu.py``'s tables of tiny sizes are GPT-shaped; the
    ledger's ``conftest.py`` skips its cases for a cell that names its own
    test file, which has to be this one and to run the cell both ways."""
    import os
    bench = run.load_json(run.ROOT, "BENCHMARK.json")
    _cell, _config, traffic = run.find_cell(bench, CELL)
    assert os.path.samefile(os.path.join(run.HERE, traffic["cpu_test"]),
                            __file__)


def test_roofline_reader_by_hand():
    """A dispatch of 3 passes that took exactly the time its bytes take at
    the peak reads 100 %."""
    reader = run.load_module("layer_metrics", "serve_block_forward_roofline")
    arch = run.load_module("arch", "sdar_moe")
    config = run.load_json(run.HERE, "configs", "sdar-30b-a3b-l6.json")
    import paddle_tpu.observability as obs
    passes, seen = (obs.counter("moe.layer_passes").value,
                    obs.counter("moe.experts_touched").value)
    touched = seen / passes if passes else None   # an earlier test's, or none
    need = 2 * arch.forward_bytes(config, 128, 20000.0, 2, 4,
                                  touched=touched) \
        + arch.forward_bytes(config, 128, 20000.0, 2, 4, head=False,
                             touched=touched)
    peaks = {"bytes_per_s": 819e9}
    reduced = {"devices": {0: {
        "module_s": {"jit__block_pure": 10 * need / 819e9},
        "module_runs": {"jit__block_pure": 10}}}}
    counts = {"samples": [(0.5, 32, 1e9), (1.5, 32, 19000.0),
                          (2.5, 32, 21000.0)],
              "trace_from_s": 1.0, "slots": 32, "passes": 3}
    assert reader.read(reduced, counts, config, peaks) == pytest.approx(100.0)
    assert reader.read(None, counts, config, peaks) is None
    gpt = run.load_json(run.HERE, "configs", "gpt3-1.3b.json")
    assert reader.read(reduced, counts, gpt, peaks) is None
    reduced["devices"][0]["module_runs"] = {}
    assert reader.read(reduced, counts, config, peaks) is None


# ------------------------------------------------- the replay's teeth

@pytest.fixture(scope="module")
def generated():
    """A tiny session's requests, the reference's parameters and the
    arguments of the replay."""
    import jax
    from peaks import peaks_for
    bench = run.load_json(run.ROOT, "BENCHMARK.json")
    cell, config, traffic = run.find_cell(bench, CELL)
    config = run._patched(config, TINY)
    ctx = run.Context(cell, config, traffic, 11, 2.0, 0, jax.devices()[:1],
                      peaks_for("TPU v5 lite"))
    model, cfg = serving_blocks.build_model(ctx)
    rng = np.random.RandomState(5)
    prompts = [rng.randint(0, 159, n).astype(np.int32)
               for n in (8, 9, 10, 11)]
    session, _ = serving_blocks.open_session(ctx, model, 4, 48)
    with session:
        rids = [session.submit(p, 24 - len(p)) for p in prompts]
        results = session.results()
    requests = [(p, results[r].ids[len(p):], results[r].commit_steps)
                for p, r in zip(prompts, rids)]
    params = ctx.arch.from_serving_state(model.state_dict(), 2)
    static = ctx.arch.static_config(dict(config, **config["generation"]))
    args = (4, traffic["denoising_steps"], traffic["remasking"], 0.9,
            TINY["reference"]["tolerances"], 24,
            {"token": 1e-4, "pair": 1e-4})
    return ctx.arch, params, static, requests, args


def test_reference_passes_are_each_row_s_whole_forward(generated):
    """``reference_passes`` (every sequence once, the rows' blocks beside
    it) against ``reference_forward`` of each row's own whole sequence
    with its block partly open and nothing after it seen."""
    arch, params, static, requests, _args = generated
    seqs = np.stack([np.concatenate([p, g]) for p, g, _c in requests])
    rng = np.random.RandomState(0)
    starts = np.array([[0, 8, 20], [4, 12, 16], [8, 8, 12], [20, 4, 0]])
    opens = rng.rand(4, 3, 4) < 0.5
    hidden, router = arch.reference_passes(params, seqs, starts, opens, 4,
                                           static)
    x0, margin, conf = arch.pass_stats(params, hidden, static)
    for n in range(3):
        is_open = np.zeros(seqs.shape, bool)
        for i, start in enumerate(starts[:, n]):
            is_open[i, start:start + 4] = opens[i, n]
            is_open[i, start + 4:] = True
        logits, want_router = arch.reference_forward(params, seqs, is_open,
                                                     4, static)
        for i, start in enumerate(starts[:, n]):
            want = np.array(logits[i, start:start + 4])
            want[:, static[-1]] = -np.inf
            top2 = np.sort(want, -1)[:, -2:]
            np.testing.assert_array_equal(x0[i, n], want.argmax(-1))
            np.testing.assert_allclose(margin[i, n], top2[:, 1] - top2[:, 0],
                                       atol=1e-5)
            np.testing.assert_allclose(
                conf[i, n], 1 / np.exp(want - top2[:, 1:]).sum(-1), rtol=1e-4)
            np.testing.assert_allclose(
                router[i, n], np.asarray(want_router)[i, start:start + 4],
                atol=1e-5)


def test_replay_passes_what_the_session_generated(generated):
    arch, params, static, requests, args = generated
    counts, widest = serving_blocks.replay(arch, params, static, requests,
                                           *args)
    assert counts.get("tokens_differ", 0) == 0
    assert counts.get("choices_differ", 0) == 0
    assert counts["tokens_compared"] == sum(len(g) for _p, g, _c in requests)
    assert counts.get("pairs_differ", 0) == 0
    assert counts["choices_compared"] > 0 and counts["pairs_compared"] > 0
    assert counts["tokens_over"] == counts["tokens_compared"]
    assert serving_blocks.judge(counts, TINY["reference"])
    assert widest == {"logit_margin_at_a_differing_token": 0.0,
                      "confidence_gap_at_a_differing_pair": 0.0}


@pytest.mark.parametrize("fault", ["dropped_expert", "coarse_product"])
def test_replay_fails_a_dropped_expert_and_a_coarser_product(generated,
                                                            fault):
    """A reference that lacks one expert of a layer, or whose expert
    weights are rounded to 3 bits of mantissa, is as far from the session as
    a session with that fault is from the reference."""
    import jax.numpy as jnp
    arch, params, static, requests, args = generated
    layers = [dict(layer) for layer in params["layers"]]
    for layer in layers:
        if fault == "dropped_expert":
            layer["down_proj"] = layer["down_proj"].at[3].set(0.0)
        else:
            for name in ("gate_proj", "up_proj", "down_proj"):
                layer[name] = layer[name].astype(jnp.float8_e4m3fn) \
                    .astype(jnp.float32)
    counts, _widest = serving_blocks.replay(
        arch, dict(params, layers=layers), static, requests, *args)
    assert counts.get("tokens_differ", 0) + counts.get("pairs_differ", 0) \
        > 0
    assert counts["tokens_over_differ"] + counts["pairs_over_differ"] > 0
    assert not serving_blocks.judge(counts, TINY["reference"])
    # the statistical form alone tells it too
    assert not serving_blocks.judge(
        {k: v for k, v in counts.items() if not k.endswith("s_differ")},
        TINY["reference"])


def test_replay_skips_what_the_reference_is_not_sure_of(generated):
    arch, params, static, requests, args = generated
    loose = dict(args[-3], logit_margin=1e9)
    counts, _ = serving_blocks.replay(arch, params, static, requests,
                                      *args[:-3], loose, *args[-2:])
    assert counts.get("tokens_compared", 0) == 0
    assert counts["tokens_skipped"] == sum(len(g) for _p, g, _c in requests)
