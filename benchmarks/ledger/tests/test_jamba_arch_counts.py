"""``arch/jamba.py``'s counts against the table of ISSUE 32, at the published
sizes."""
import pytest

import run

arch = run.load_module("arch", "jamba")
CFG = run.load_json(run.HERE, "configs", "jamba2-3b.json")
SIZES = CFG["sizes"]


def test_layer_kinds_from_offset_and_period():
    kinds = [arch.layer_kind(SIZES, i) for i in range(28)]
    assert [i for i, k in enumerate(kinds) if k == "attention"] == [7, 21] \
        == CFG["derived"]["attention_layers"]
    assert arch.layer_counts(SIZES) == (26, 2)


def test_weight_counts_by_hand():
    # in_proj 2560x10240; conv 5120x4 + 5120; x_proj 5120x192; the three
    # norms 192; dt_proj 160x5120 + 5120; A_log 5120x16; D; out_proj
    assert arch.mamba_weight_count(SIZES) == 26214400 + 25600 + 983040 \
        + 192 + 824320 + 81920 + 5120 + 13107200 == 41241792 \
        == CFG["derived"]["mamba_mixer_parameters"]
    # q and o 2560x2560, k and v 2560x128
    assert arch.attention_weight_count(SIZES) == 2 * 6553600 + 2 * 327680 \
        == 13762560 == CFG["derived"]["attention_mixer_parameters"]
    assert arch.mlp_weight_count(SIZES) == 3 * 2560 * 8192 + 5120 \
        == 62919680 == CFG["derived"]["mlp_and_norm_parameters"]
    assert arch.weight_count(SIZES) == 26 * 104161472 + 2 * 76682240 \
        + 167772160 + 2560 == 3029337472 == CFG["derived"]["parameters"]
    assert CFG["derived"]["weight_bytes"] == 2 * 3029337472


def test_published_keys_and_sizes_agree():
    for ours, theirs in (("num_layers", "num_hidden_layers"),
                         ("num_heads", "num_attention_heads")):
        assert SIZES[ours] == CFG[theirs]
    for key in ("vocab_size", "hidden_size", "intermediate_size",
                "num_key_value_heads", "attn_layer_period",
                "attn_layer_offset", "mamba_d_state", "mamba_d_conv",
                "mamba_dt_rank", "mamba_expand", "rms_norm_eps"):
        assert SIZES[key] == CFG[key]
    assert CFG["reduced"] == []


def test_state_and_cache_bytes():
    # 5120 x 16 of scan state and 3 x 5120 of window, float32, 26 layers
    assert arch.state_bytes_per_slot(SIZES) == 26 * (327680 + 61440) \
        == 10117120 == CFG["derived"]["state_bytes_per_slot"]
    # K and V of one head of 128, float32, two layers
    assert arch.cache_bytes_per_token(SIZES, 4) == 2 * 2 * 128 * 4 == 2048 \
        == CFG["derived"]["cache_bytes_per_token"]
    assert 256 * arch.state_bytes_per_slot(SIZES) \
        == pytest.approx(2.59e9, rel=1e-3)
    assert 256 * 3072 * 2048 == pytest.approx(1.61e9, rel=1e-3)


def test_a_step_of_the_cell_moves_about_twelve_gigabytes():
    # 256 slots, a mean of 1,000 valid tokens each
    need = arch.decode_step_bytes(SIZES, 256, 256 * 1000, 2, 4)
    assert need == 6058674944 + 2 * 256 * 10117120 + 256 * 1000 * 2048
    assert need == pytest.approx(11.76e9, rel=1e-3)
    assert 2 * 256 * 10117120 / need == pytest.approx(0.44, abs=0.005)


def test_scan_bytes_by_hand():
    # xc, delta, y: 3 x 1024 x 5120; B, C: 2 x 1024 x 16; h in and out
    assert arch.scan_bytes(SIZES, 1024) == 4 * (
        3 * 1024 * 5120 + 2 * 1024 * 16 + 2 * 5120 * 16) \
        == pytest.approx(63.7e6, rel=1e-2)
