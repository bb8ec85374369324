"""chip_smoke.py's control flow at tiny size on the CPU mesh, plus the
start-up contracts it rests on: no CPU branch in the script, a compile
cache placed from outside, imports that create no backend, peaks that
refuse an unknown device. The full-width run needs a chip
(``python chip_smoke.py`` through the chip tool)."""
import json
import os
import subprocess
import sys

import pytest

import chip_smoke
from paddle_tpu.models.gpt import GPTConfig

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

TINY = GPTConfig(vocab_size=256, hidden_size=64, num_layers=2, num_heads=2,
                 max_seq_len=64)


def _python(*args, env_extra=None, drop=()):
    """A fresh CPU-backend python at the repo root, started, not waited."""
    env = {k: v for k, v in os.environ.items() if k not in drop}
    env["JAX_PLATFORMS"] = "cpu"
    env.update(env_extra or {})
    return subprocess.Popen([sys.executable, *args], cwd=ROOT, env=env,
                            text=True, stdout=subprocess.PIPE,
                            stderr=subprocess.PIPE)


def test_train_phase_tiny():
    # every earlier check (loss start/fall, no compile after warm-up) must
    # hold for the phase to reach its last one: on the CPU no Pallas kernel
    # is routed, so asking for one is what fails
    with pytest.raises(AssertionError, match="expected the 'simple'"):
        chip_smoke.train_phase(TINY, batch=2, seq=32, steps=2,
                               scan_unroll=2, expect_kernel="simple")


def test_multichip_phase_tiny():
    rs = chip_smoke.multichip_phase(
        TINY, batch=8, seq=32, steps=1, scan_unroll=1, n_devices=4,
        layouts=({"dp": 4, "zero1": True}, {"dp": 2, "tp": 2, "sp": True}))
    assert [r["param_devices"] for r in rs] == [4, 4]
    assert all(r["losses"][-1] < r["losses"][0] and not r["dispatch"]
               for r in rs)
    # the compiled step's permutes and all-to-alls are read and printed;
    # no tp rank has to be sent another's heads of q, k or v
    assert all(isinstance(r["collectives"], dict) for r in rs)
    assert not [k for k in rs[1]["collectives"] if k.endswith("[4,32,64]")]
    # both layouts shard the moments inside the layer; the two [L, 3h|4h]
    # biases that tp holds have no dim of their own left (and two layers
    # do not divide by dp=4)
    assert [r["moments"] for r in rs] == [
        {"zero1.moment_shard{dim=in_layer}": 14,
         "zero1.moment_shard{dim=none}": 2},
        {"zero1.moment_shard{dim=in_layer}": 14,
         "zero1.moment_shard{dim=layer}": 2}]


def test_train_phase_refuses_moments_over_the_layer_dim(monkeypatch):
    import jax
    from jax.sharding import PartitionSpec as P
    from paddle_tpu.models import gpt_hybrid

    def over_layers(params, pcfg, specs):
        return jax.tree_util.tree_map(
            lambda x, s: P("dp", *tuple(s)[1:]) if x.ndim >= 3 else s,
            params, specs)
    monkeypatch.setattr(gpt_hybrid, "moment_specs", over_layers)
    with pytest.raises(AssertionError, match="dp on the layer dim of "
                       r"\['fc1_w', 'fc2_w', 'proj_w', 'qkv_w'\]"):
        chip_smoke.train_phase(TINY, batch=8, seq=32, steps=1,
                               scan_unroll=1, dp=2, tp=2, sp=True,
                               devices=jax.devices()[:4])


def test_serve_phase_tiny():
    r = chip_smoke.serve_phase(TINY, max_slots=2, max_length=64,
                               decode_block=4, n_requests=4,
                               prompt_range=(4, 12), budget_range=(4, 8))
    assert r["requests"] == 4 and r["first_diff"] != 0
    assert r["dispatch"] == {}      # no kernel, and no fallback, on the CPU
    assert set(r["writes"]) == {"cache.write_dispatch{kernel=update_slice}"}


def test_serve_phase_tiny_asks_for_its_kernel():
    # as in the train phase: every other check holds, and on the CPU the
    # one-token step runs the einsums, so asking for the kernel fails
    with pytest.raises(AssertionError, match="expected the 'decode_ragged'"):
        chip_smoke.serve_phase(TINY, max_slots=2, max_length=64,
                               decode_block=4, n_requests=2,
                               prompt_range=(4, 12), budget_range=(4, 8),
                               expect_kernel="decode_ragged")


def test_serve_phase_tiny_asks_for_its_cache_write():
    # likewise: on the CPU the cache is written by dynamic_update_slice
    with pytest.raises(AssertionError, match="written through 'row_dma'"):
        chip_smoke.serve_phase(TINY, max_slots=2, max_length=64,
                               decode_block=4, n_requests=2,
                               prompt_range=(4, 12), budget_range=(4, 8),
                               expect_write="row_dma")


@pytest.mark.parametrize("s", [1, 4])
def test_cache_write_check_interpret(s):
    assert chip_smoke.check_cache_write((4, 64, 2, 128), s=s,
                                        interpret=True) == 0


def test_cache_write_check_catches_a_wrong_row(monkeypatch):
    from paddle_tpu.ops.pallas import cache_write
    write_rows = cache_write.write_rows

    def off_by_one(kbuf, vbuf, kn, vn, lens, interpret):
        return write_rows(kbuf, vbuf, kn, vn, lens + 1, interpret=interpret)
    monkeypatch.setattr(cache_write, "write_rows", off_by_one)
    with pytest.raises(AssertionError, match="differ from dynamic_update"):
        chip_smoke.check_cache_write((4, 64, 2, 128), interpret=True)


def test_decode_kernel_check_interpret():
    assert chip_smoke.check_decode_kernel((4, 64, 2, 128),
                                          interpret=True) <= 1e-5
    with pytest.raises(AssertionError, match="off the float32 einsums"):
        chip_smoke.check_decode_kernel((4, 64, 2, 128), interpret=True,
                                       tol=0.0)


EXPERT_ROWS_TINY = (1024, 32, 16, 8, 2, 2)


def test_expert_rows_check_tiny():
    errs = chip_smoke.check_expert_rows(EXPERT_ROWS_TINY, dtype="float32",
                                        tol=1e-5)
    assert max(errs) <= 1e-5


def test_expert_rows_check_refuses_a_run_of_two_trips(monkeypatch):
    """A buffer too small for the held pairs sends the layer on a second
    trip: the result is right and the check still refuses it."""
    from paddle_tpu.models import sdar_moe
    monkeypatch.setattr(sdar_moe, "_HELD_ROWS_SLACK", 0.5)
    with pytest.raises(AssertionError, match="is not what ran"):
        chip_smoke.check_expert_rows(EXPERT_ROWS_TINY, dtype="float32")


def test_kernel_phase_interpret():
    cases = (("simple", (1, 2, 128, 64), True, None),
             ("causal_skip", (1, 1, 256, 64), True, None),
             ("qblock", (1, 1, 256, 64), False, None),
             ("blocked", (1, 1, 256, 64), True, (128, 128)),
             ("blocked", (1, 1, 256, 64), False, (128, 128)),
             # the tiny twin of the LFM2 cell's call: head width 64, a
             # batch of heads, bf16 (check_kernel's default dtype)
             ("blocked", (2, 4, 256, 64), True, (128, 128)))
    assert len(chip_smoke.kernel_phase(cases, interpret=True)) == len(cases)
    with pytest.raises(AssertionError, match="off its float32 reference"):
        chip_smoke.check_kernel(*cases[0], interpret=True, tol=0.0)


def test_blocked_flash_passes_dimension_semantics():
    from paddle_tpu.ops.pallas import blocked_flash
    params = blocked_flash._compiler_params()
    # (b, h, tile): the walk over a slice's tiles carries the scratch
    assert tuple(str(s).lower().rsplit(".", 1)[-1]
                 for s in params.dimension_semantics) == (
        "parallel", "parallel", "arbitrary")


def test_unknown_device_kind_raises():
    from paddle_tpu import cost_model
    assert cost_model.spec_for_device_kind("TPU v5 lite")["flops"] == 197e12
    with pytest.raises(KeyError, match="no peak spec"):
        cost_model.spec_for_device_kind("cpu")
    with pytest.raises(KeyError):
        cost_model.attached_chip_spec()        # the CPU test backend


def test_start_up_contracts_in_subprocesses():
    """Three fresh processes, run side by side: the smoke on a CPU backend;
    every root script and the launcher imported, then the cache placed with
    the variable unset; the cache placed with the variable set."""
    report = ("import json, jax, jax._src.xla_bridge as xb; "
              "from paddle_tpu import compile_cache; "
              "d = compile_cache.enable(); "
              "print(json.dumps({'dir': d, "
              "'cfg': jax.config.jax_compilation_cache_dir, "
              "'backends': sorted(xb._backends)}))")
    smoke = _python("chip_smoke.py")
    unset = _python("-c", "import paddle_tpu, chip_smoke, "
                    "__graft_entry__, paddle_tpu.distributed.launch.main; "
                    + report, drop=("JAX_COMPILATION_CACHE_DIR",))
    given = _python("-c", report,
                    env_extra={"JAX_COMPILATION_CACHE_DIR": "/x/cache"})

    out, err = smoke.communicate(timeout=120)
    assert smoke.returncode not in (0, None), out
    assert "default_backend=cpu" in out and '"ok"' not in out
    assert "only runs on a chip" in err

    out, err = unset.communicate(timeout=120)
    assert unset.returncode == 0, err[-2000:]
    got = json.loads(out.strip().splitlines()[-1])
    assert got["dir"] == got["cfg"] == os.path.join(ROOT, ".jax_cache")
    assert got["backends"] == [], "an import created a jax backend"

    out, err = given.communicate(timeout=120)
    assert given.returncode == 0, err[-2000:]
    got = json.loads(out.strip().splitlines()[-1])
    assert got["dir"] == got["cfg"] == "/x/cache"
