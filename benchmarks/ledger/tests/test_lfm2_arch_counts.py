"""``arch/lfm2_moe.py``'s counts: the parameters to the parameter, the
operations a token against a count by hand, the grouped products' operations
linear in the pairs; and the readers that divide by them."""
import json
import os

import pytest

import run
from run import HERE

arch = run.load_module("arch", "lfm2_moe")
with open(os.path.join(HERE, "configs", "lfm2-24b-a2b-ep4-l5.json")) as _f:
    CONFIG = json.load(_f)
SIZES = CONFIG["sizes"]
PUBLISHED = dict(SIZES, num_layers=40, num_hidden_layers=40,
                 layer_types=CONFIG["published"]["layer_types"],
                 num_dense_layers=2, num_local_experts=64, vocab_size=65536)


def test_weight_count_is_the_tables_to_the_parameter():
    assert arch.operator_weight_count(SIZES, "conv") == 16_783_360
    assert arch.operator_weight_count(SIZES, "full_attention") == 10_485_888
    assert arch.ff_weight_count(SIZES, "dense") == 72_351_744
    assert arch.ff_weight_count(SIZES, "experts") == 131_136 + 16 * 9_437_184
    assert arch.weight_count(SIZES) == 788_052_352 \
        == CONFIG["derived"]["parameters"]
    assert arch.weight_count(PUBLISHED) == 23_843_661_440 \
        == CONFIG["derived"]["parameters_published_whole"]


def test_weight_count_is_the_programs_tree():
    """At a tiny size: the leaves ``lfm2_moe.init_params`` makes, the running
    counts aside (the bias counts, as the table's router + bias)."""
    import jax
    from paddle_tpu.models import gpt_hybrid as gh
    from paddle_tpu.models import lfm2_moe as lm
    cfg = lm.LFM2MoeConfig.tiny(num_local_experts=3, expert_offset=1)
    shapes = jax.eval_shape(
        lambda k: lm.init_params(cfg, gh.ParallelConfig(), k),
        jax.random.PRNGKey(0))
    held = sum(leaf.size for path, leaf
               in jax.tree_util.tree_leaves_with_path(shapes)
               if not any(count in jax.tree_util.keystr(path)
                          for count in ("expert_load", "expert_peak")))
    sizes = {k: getattr(cfg, k) for k in (
        "vocab_size", "hidden_size", "intermediate_size",
        "moe_intermediate_size", "num_attention_heads",
        "num_key_value_heads", "layer_types", "num_layers",
        "num_dense_layers", "num_experts", "num_local_experts",
        "num_experts_per_tok", "conv_L_cache")}
    assert arch.weight_count(sizes) == held


def test_train_flops_per_token_is_the_count_by_hand():
    """One conv + dense layer, one attention + experts layer; h 8, 2 heads
    of 4 over 1 KV head, dense width 6, 4 experts of width 3, 2 a token, 1
    held; 5 rows of vocabulary; sequence 7."""
    sizes = {"hidden_size": 8, "num_attention_heads": 2,
             "num_key_value_heads": 1, "intermediate_size": 6,
             "moe_intermediate_size": 3, "num_experts": 4,
             "num_local_experts": 1, "num_experts_per_tok": 2,
             "layer_types": ["conv", "full_attention"], "num_layers": 2,
             "num_dense_layers": 1, "vocab_size": 5, "conv_L_cache": 3}
    conv = 2 * 8 * 24 + 2 * 8 * 8 + 2 * 3 * 8 + 2 * 8      # in, out, taps, gates
    dense = 3 * 2 * 8 * 6
    attention = 2 * (2 * 8 * 8) + 2 * (2 * 8 * 4) \
        + 2 * (2 * 8) * (7 + 1) / 2                         # q o, k v, scores and p.v
    experts = 2 * 8 * 4 + 2 * (1 / 4) * 3 * 2 * 8 * 3       # router, held share
    head = 2 * 8 * 5
    by_hand = 3 * (conv + dense + attention + experts + head)
    assert arch.train_flops_per_token(sizes, 7) == pytest.approx(by_hand)


def test_train_flops_at_the_cells_sizes_is_the_issues_arithmetic():
    per_token = arch.train_flops_per_token(SIZES, 8192)
    assert per_token == pytest.approx(1.43e9, rel=0.01)
    assert per_token * 16384 == pytest.approx(23.4e12, rel=0.01)


def test_grouped_flops_is_linear_in_the_pairs():
    one = arch.grouped_flops(SIZES, 1)
    assert one == 3 * 6 * 2048 * 1536
    assert arch.grouped_flops(SIZES, 16384) == 16384 * one
    assert arch.grouped_flops(SIZES, 0) == 0


def _reduced(op_s, busy_s=2.0):
    return {"devices": {0: {"op_s": op_s, "busy_s": busy_s}}}


def test_grouped_readers_divide_the_kernels_seconds():
    peaks = {"flops_per_s": 197e12}
    roofline = run.load_module("layer_metrics", "train_moe_grouped_roofline")
    share = run.load_module("layer_metrics", "train_moe_grouped_time_share")
    # 3 layer-steps: 6 tgmm calls; a remat'd forward gmm adds time only
    ops = {"gmm f32[65536,3072]": [0.03, 6], "gmm bf16[65536,2048]": [0.02, 3],
           "tgmm bf16[16,2048,3072]": [0.02, 3],
           "tgmm bf16[16,1536,2048]": [0.01, 3], "fusion f32[8]": [1.0, 9]}
    counts = {"held_pairs_per_layer_step": 16384.0}
    need = 3 * arch.grouped_flops(SIZES, 16384.0)
    assert roofline.read(_reduced(ops), counts, CONFIG, peaks) \
        == pytest.approx(100 * need / 197e12 / 0.08)
    assert share.read(_reduced(ops), counts, CONFIG, peaks) \
        == pytest.approx(100 * 0.08 / 2.0)


@pytest.mark.parametrize("name", ["train_moe_grouped_roofline",
                                  "train_moe_grouped_time_share"])
def test_grouped_readers_find_nothing_in_another_program(name):
    """No trace; a trace without the kernels (the parent's program); a
    configuration that names no grouped operation."""
    reader = run.load_module("layer_metrics", name)
    peaks = {"flops_per_s": 197e12}
    other = {"fusion f32[8]": [1.0, 9]}
    gpt = run.load_json(HERE, "configs", "gpt3-1.3b.json")
    assert reader.read(None, {}, CONFIG, peaks) is None
    assert reader.read(_reduced(other), {}, CONFIG, peaks) is None
    assert reader.read(_reduced(other), {}, gpt, peaks) is None


@pytest.mark.parametrize("name", ["train_moe_busiest_expert_load",
                                  "train_moe_held_share"])
def test_counter_readers_find_nothing_where_nothing_was_counted(name):
    import paddle_tpu.observability as obs
    reader = run.load_module("layer_metrics", name)
    if obs.counter("moe.assignments").value == 0:
        assert reader.read(None, {}, CONFIG, {}) is None
    gpt = run.load_json(HERE, "configs", "gpt3-1.3b.json")
    if name == "train_moe_busiest_expert_load":
        assert reader.read(None, {}, gpt, {}) is None
