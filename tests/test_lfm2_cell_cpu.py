"""The LFM2 training cell end to end at a tiny size on the CPU, through
``run.main``'s test-only override (the command itself refuses a CPU): every
check of ``loops/train_steps_experts.py``, the readers that count, and the
faults ``grads_match_reference``, ``step_matches_reference`` and
``held_share_in_band`` exist to refuse. Named by the cell's traffic file
(``cpu_test``)."""
import dataclasses
import json
import os
import sys

import numpy as np
import pytest

LEDGER = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmarks", "ledger")
if LEDGER not in sys.path:
    sys.path.insert(0, LEDGER)

import run  # noqa: E402

CELL = "train-lfm2-ep4-l5-s8192"
LEAVES = ["embed", "layers.0.in_proj", "layers.0.conv", "layers.1.q_norm",
          "layers.1.router", "layers.1.gate_up", "layers.1.down",
          "layers.2.router", "layers.2.gate_up", "layers.2.conv"]
TINY = {"sizes": {"vocab_size": 96, "hidden_size": 32,
                  "intermediate_size": 48, "moe_intermediate_size": 16,
                  "num_attention_heads": 4, "num_key_value_heads": 2,
                  "num_experts": 8, "num_local_experts": 4,
                  "num_experts_per_tok": 2, "initializer_range": 0.3},
        "build": {"train": {"parallel": {"param_dtype": "float32",
                                         "compute_dtype": "float32"}}},
        "reference": {"init_std": 0.3, "train_logits_tolerance": 1e-4,
                      "grads": {"seq": 16, "sequences": 2, "leaves": LEAVES,
                                "tolerance": 1e-3, "loss_tolerance": 1e-4,
                                "margin": 1e-4, "clear_floor": 0.9,
                                "routing_mismatch_tolerance": 0.0},
                      # the warm-up over 8 steps (``tiny_hyper``)
                      "step": {"adamw": {"lr": 3e-4 / 8},
                               "loss_tolerance": 1e-4, "tolerance": 1e-3,
                               "routed_tolerance": 1e-3,
                               "change_tolerance": 1e-2,
                               "load_mismatch_tolerance": 0.0},
                      "held_share_band": [0.3, 0.7]}}
TINY_TRAFFIC = {"batch": 2, "seq": 32, "trace_seconds": 1}


def run_cell(trace, **reference):
    config = dict(TINY, reference={**TINY["reference"], **reference})
    return run.main(
        ["--workload", CELL, "--seed", "4000000007", "--seconds", "2",
         "--trace", str(trace)],
        _test_override={"allow_cpu": True, "config": config,
                        "traffic": TINY_TRAFFIC})


@pytest.fixture(autouse=True)
def tiny_hyper(monkeypatch):
    """The reference's published hyper-parameters are not the tiny
    model's: two experts a token; a warm-up a 2 s run gets through."""
    import byname
    from paddle_tpu.models import lfm2_moe
    monkeypatch.setattr(lfm2_moe, "LR_WARMUP_STEPS", 8)
    load = byname.load_module

    def tiny(kind, name):
        mod = load(kind, name)
        if (kind, name) == ("arch", "lfm2_moe"):
            mod.HYPER = dict(mod.HYPER, num_experts_per_tok=2)
        return mod
    monkeypatch.setattr(run, "load_module", tiny)


def test_cell_end_to_end_tiny(capsys):
    out = run_cell(0)
    last = capsys.readouterr().out.strip().splitlines()[-1]
    assert json.loads(last)["metrics"] == out["metrics"]
    assert out["correct"], out["checks"]
    assert out["checks"]["matches_reference"]
    assert out["checks"]["grads_match_reference"]
    assert out["checks"]["routing_matches_reference"]
    assert out["checks"]["step_matches_reference"]
    assert out["checks"]["held_share_in_band"]
    assert out["checks"]["probe_loss_fell"]
    assert out["attempted"] > 0 and out["failed"] == 0
    assert set(out["metrics"]) == {"train_tokens_per_s", "setup_s"}
    assert all(np.isfinite(m["value"]) and m["value"] > 0
               for m in out["metrics"].values())
    assert out["device"]["platform"] == "cpu"      # never a device number


def test_cell_traced_tiny_reports_what_the_counters_give():
    out = run_cell(1)
    assert out["correct"], out["checks"]
    got = {k: v["value"] for k, v in out["metrics"].items()}
    # no device plane on the CPU: no roofline, no time share, no idle share
    assert set(got) == {"train_dispatch_ms", "train_mfu", "setup_compile_s",
                        "setup_compile_wall_s",
                        "train_moe_busiest_expert_load",
                        "train_moe_held_share"}
    assert 0 < got["setup_compile_wall_s"] <= got["setup_compile_s"]
    assert got["train_moe_busiest_expert_load"] >= 1.0
    assert 0 < got["train_moe_held_share"] < 100


FAULTS = ["a_held_experts_weight_gradient_dropped", "choice_without_the_bias",
          "grouped_products_in_8_bits"]


@pytest.mark.parametrize("fault", FAULTS)
def test_a_fault_in_the_program_alone_fails_the_gradient_check(
        fault, monkeypatch):
    import jax
    import jax.numpy as jnp
    from paddle_tpu.models import lfm2_moe, sdar_moe
    if fault == "a_held_experts_weight_gradient_dropped":
        grouped = sdar_moe._grouped

        @jax.custom_vjp
        def forget_first(w):
            return w

        forget_first.defvjp(lambda w: (w, None),
                            lambda _res, g: (g.at[0].set(0),))
        monkeypatch.setattr(
            sdar_moe, "_grouped",
            lambda lhs, rhs, *a, **kw: grouped(lhs, forget_first(rhs),
                                               *a, **kw))
    elif fault == "choice_without_the_bias":
        route = lfm2_moe.route
        monkeypatch.setattr(
            lfm2_moe, "route",
            lambda h, w, bias, *a: route(h, w, jnp.zeros_like(bias), *a))
    else:
        grouped = sdar_moe._grouped

        def low(x):
            return jax.lax.reduce_precision(x, 4, 3)
        monkeypatch.setattr(
            sdar_moe, "_grouped",
            lambda lhs, rhs, *a, **kw: grouped(low(lhs), low(rhs), *a, **kw))
    out = run_cell(0)
    # the routing is compared first and the gradients along the program's:
    # a wrong choice fails the one, a wrong product the other
    failed = "routing_matches_reference" \
        if fault == "choice_without_the_bias" else "grads_match_reference"
    assert not out["checks"][failed]
    assert not out["correct"]


STEP_FAULTS = ["half_the_batch_dropped", "attention_without_its_key_gradient",
               "rate_constant_from_the_first_step",
               "bias_moved_against_the_rule"]


@pytest.mark.parametrize("fault", STEP_FAULTS)
def test_a_fault_in_the_timed_step_alone_fails_the_step_check(
        fault, monkeypatch):
    """The faults the 1,024-token gradient check cannot see: it jits its own
    gradient, and these are in the engine's step, its batch, its attention
    path, its schedule and the model's own update."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.models import gpt_hybrid, lfm2_moe
    if fault == "half_the_batch_dropped":
        loss = lfm2_moe.loss_and_routing

        def first_sequence_twice(params, batch, *rest):
            ids = jnp.concatenate([batch[0][:1]] * batch[0].shape[0])
            got, routing = loss(params, (ids, ids), *rest)
            _, real = loss(params, batch, *rest)
            return got, jax.lax.stop_gradient(real)
        monkeypatch.setattr(lfm2_moe, "TRAIN_MODEL", dataclasses.replace(
            lfm2_moe.TRAIN_MODEL, loss_fn=first_sequence_twice))
    elif fault == "attention_without_its_key_gradient":
        attend = gpt_hybrid._attend
        monkeypatch.setattr(
            gpt_hybrid, "_attend",
            lambda q, k, v, *rest: attend(q, jax.lax.stop_gradient(k), v,
                                          *rest))
    elif fault == "rate_constant_from_the_first_step":
        monkeypatch.setattr(lfm2_moe, "learning_rate", 3e-4)
    else:
        update = lfm2_moe.update_routing
        monkeypatch.setattr(lfm2_moe, "TRAIN_MODEL", dataclasses.replace(
            lfm2_moe.TRAIN_MODEL,
            update_frozen=lambda frozen, routing: update(
                frozen, routing, rate=-lfm2_moe.EXPERT_BIAS_UPDATE_RATE)))
    out = run_cell(0)
    assert not out["checks"]["step_matches_reference"]
    # (off the TPU both checks attend through the same XLA attention)
    assert out["checks"]["grads_match_reference"] \
        or fault == "attention_without_its_key_gradient"
    assert not out["correct"]


def test_a_run_whose_router_left_the_experts_held_is_refused():
    out = run_cell(0, held_share_band=[0.8, 0.9])
    assert not out["checks"]["held_share_in_band"]
    assert not out["correct"]
