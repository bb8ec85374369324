"""``arch/gpt_dense.py``'s operation and byte counts against hand
arithmetic, for both configurations."""
import json
import os

import pytest

from run import HERE, load_module

arch = load_module("arch", "gpt_dense")


def sizes(name):
    with open(os.path.join(HERE, "configs", name + ".json")) as f:
        return json.load(f)["sizes"]


def test_gpt3_1p3b_train_flops_at_s1024():
    # per layer, forward: QKV 6h^2 + proj 2h^2 + FFN 16h^2 = 24 * 2048^2
    # = 100,663,296; attention 4h(S+1)/2 = 2 * 2048 * 1025 = 4,198,400
    # head 2hV = 2 * 2048 * 50304 = 206,045,184
    forward = 24 * (100_663_296 + 4_198_400) + 206_045_184
    assert forward == 2_722_725_888
    s = sizes("gpt3-1.3b")
    assert arch.forward_flops_per_token(s, 1024) == forward
    assert arch.train_flops_per_token(s, 1024) == 3 * forward  # 8.168 GFLOP


def test_gpt3_6p7b_16_layers_train_flops_at_s2048():
    # 24 * 4096^2 = 402,653,184; attention 2 * 4096 * 2049 = 16,785,408
    # head 2 * 4096 * 50304 = 412,090,368
    forward = 16 * (402_653_184 + 16_785_408) + 412_090_368
    assert arch.forward_flops_per_token(sizes("gpt3-6.7b-4chip"), 2048) \
        == forward


def test_weights_and_decode_bytes_1p3b():
    s = sizes("gpt3-1.3b")
    h, f = 2048, 8192
    layer = (3 * h * h + 3 * h) + (h * h + h) + (h * f + f) + (f * h + h) \
        + 4 * h
    assert layer == 50_358_272
    weights = 24 * layer + 50304 * h + 2 * h
    assert arch.weight_count(s) == weights == 1_311_625_216
    # K and V, 24 layers, 2048 wide, float32: 393,216 B a cached token
    assert arch.cache_bytes_per_token(s, 4) == 2 * 24 * 2048 * 4 == 393_216
    assert arch.decode_step_bytes(s, 10_000, 2, 4) \
        == 2 * weights + 10_000 * 393_216


def test_parameters_written_in_the_configs():
    for name in ("gpt3-1.3b", "gpt3-6.7b-4chip"):
        with open(os.path.join(HERE, "configs", name + ".json")) as f:
            cfg = json.load(f)
        s = cfg["sizes"]
        # weights read by a forward pass, plus the position table
        assert cfg["derived"]["parameters"] == arch.weight_count(s) \
            + s["max_seq_len"] * s["hidden_size"]
        assert cfg["derived"]["head_dim"] * s["num_heads"] == s["hidden_size"]
        assert cfg["derived"]["ffn_size"] == s["ffn_mult"] * s["hidden_size"]


def test_peaks_refuse_an_unknown_kind():
    from peaks import peaks_for
    assert peaks_for("TPU v5 lite")["flops_per_s"] == 197e12
    with pytest.raises(KeyError):
        peaks_for("TPU v9 imaginary")
