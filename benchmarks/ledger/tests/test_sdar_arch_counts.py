"""``arch/sdar_moe.py``'s bytes and operations by hand, for one layer at
the published widths."""
import pytest

import run

arch = run.load_module("arch", "sdar_moe")
CFG = run.load_json(run.HERE, "configs", "sdar-30b-a3b-l6.json")
ONE = dict(CFG, num_hidden_layers=1)


def test_weight_counts_of_one_layer():
    # q 2048x4096, k and v 2048x512, o 4096x2048, two norms of 2048, two of
    # 128
    assert arch.attention_weight_count(CFG) == 2048 * 4096 * 2 \
        + 2 * 2048 * 512 + 2 * 2048 + 2 * 128
    assert arch.expert_weight_count(CFG) == 3 * 2048 * 768
    assert CFG["derived"]["parameters"] == 6 * (
        arch.attention_weight_count(CFG) + 2048 * 128
        + 128 * arch.expert_weight_count(CFG)) + 2 * 151936 * 2048 + 2048
    assert CFG["derived"]["weight_bytes"] == pytest.approx(8.72e9, rel=1e-3)
    assert arch.cache_bytes_per_token(CFG, 4) == 6 * 4096 \
        == CFG["derived"]["cache_bytes_per_token"]


def test_experts_touched():
    assert arch.experts_touched(CFG, 1) == pytest.approx(8.0)
    assert arch.experts_touched(CFG, 128) == pytest.approx(128 * (1 - (15 / 16) ** 128))
    assert 127.9 < arch.experts_touched(CFG, 128) < 128.0


def test_forward_bytes_of_one_layer_by_hand():
    # one token: 8 experts, the attention and router weights, one embedding
    # row, the final norm, the head; 100 cached tokens of 2 x 4 x 128 floats
    want = (arch.attention_weight_count(CFG) + 2048 * 128
            + 8 * 3 * 2048 * 768 + 2048 + 2048 + 2048 * 151936) * 2 \
        + 100 * 4096
    assert arch.forward_bytes(ONE, 1, 100, 2, 4) == pytest.approx(want)
    assert arch.forward_bytes(ONE, 1, 100, 2, 4, head=False) \
        == pytest.approx(want - 2 * 2048 * 151936)


def test_a_pass_of_the_cell_reads_about_nine_gigabytes():
    # ISSUE 28's reckoning: 7.25 GB of experts, 0.23 of attention, 0.62 of
    # head, at most 1.0 of cache
    full = arch.forward_bytes(CFG, 128, 32 * 1280, 2, 4)
    assert 9.0e9 < full < 9.2e9
    experts = 6 * arch.experts_touched(CFG, 128) * arch.expert_weight_count(CFG) * 2
    assert experts == pytest.approx(7.25e9, rel=2e-3)


def test_forward_flops_count_routed_products_only():
    # one token, no cache: projections 2 x 2048 x (2 x 4096 + 2 x 512), the
    # router, 8 experts of three 2048 x 768 products, attention to itself
    want = 2 * 2048 * (2 * 4096 + 2 * 512) + 2 * 2048 * 128 \
        + 8 * 6 * 2048 * 768 + 4 * 4096 * 1 + 2 * 2048 * 151936
    assert arch.forward_flops(ONE, 1, 0) == want
    dense = 128 * 6 * 2048 * 768          # every expert over the token
    assert arch.forward_flops(ONE, 1, 0, head=False) < dense / 8


def test_num_transfer_tokens_is_the_published_schedule():
    assert arch.num_transfer_tokens(4, 2) == [2, 2]
    assert arch.num_transfer_tokens(4, 3) == [2, 1, 1]
    assert arch.num_transfer_tokens(8, 8) == [1] * 8
