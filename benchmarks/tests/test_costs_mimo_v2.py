"""``costs_mimo_v2.py`` against hand-worked lines (PERF.md section 3 and
ISSUE 42 repeat them), on the committed configuration."""
import json
import os

import pytest

from benchmarks.harness import costs_mimo_v2 as costs

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def c():
    with open(os.path.join(BENCH, "configs", "mimo-v2-flash.json")) as f:
        return json.load(f)


def test_the_published_widths_are_unchanged(c):
    assert (c["hidden_size"], c["num_attention_heads"], c["head_dim"],
            c["v_head_dim"]) == (4096, 64, 192, 128)
    assert (c["swa_num_key_value_heads"], c["num_key_value_heads"],
            c["sliding_window"]) == (8, 4, 128)
    assert int(c["head_dim"] * c["partial_rotary_factor"]) == 64
    assert (c["swa_rope_theta"], c["rope_theta"]) == (10000, 5000000)
    assert (c["intermediate_size"], c["moe_intermediate_size"],
            c["experts_published"], c["num_experts_per_tok"]) == (16384, 2048, 256, 8)
    assert c["attention_value_scale"] == 0.707
    assert c["reduced"] == ["num_hidden_layers", "n_routed_experts",
                            "vocab_size", "max_position_embeddings"]
    assert set(c["reduced_why"]) == set(c["reduced"]) == set(c["published"])
    assert len(c["hybrid_layer_pattern"]) == len(c["moe_layer_freq"]) == 48
    assert costs.layer_kinds(c) == [(False, False), (True, True), (True, True),
                                    (True, True), (True, True), (False, True),
                                    (True, True)]


def test_decode_round_bytes(c):
    # a sliding layer's attention: Wq 4096 x 12,288 = 50,331,648; Wk 4096 x
    # 1,536 = 6,291,456; Wv 4096 x 1,024 = 4,194,304; Wo 8,192 x 4,096 =
    # 33,554,432; 64 sinks
    assert costs.attention_params(c, True) == 94_371_904
    # a full layer's: Wk 4096 x 768, Wv 4096 x 512, no sink
    assert costs.attention_params(c, False) == 89_128_960
    # one expert 3 x 4096 x 2048 = 25,165,824 values, 50,331,648 B in bf16
    assert costs.expert_bytes(c, 2) == 50_331_648
    # layer 0: 89,128,960 + dense 3 x 4096 x 16,384 = 201,326,592
    # a routed layer's fixed part: router 4096 x 256 + a bias of 256
    # head 4096 x 19,072 = 78,118,912; 15 norm vectors 61,440
    values = (89_128_960 + 201_326_592 + 5 * (94_371_904 + 1_048_832)
              + 89_128_960 + 1_048_832 + 78_118_912 + 61_440)
    assert values == 935_917_376
    assert costs.decode_fixed_bytes(c, 2) == 2 * values == 1_871_834_752
    # a full layer caches 4 x (192 + 128) lanes, a sliding one 8 x 320
    assert costs.kv_bytes_per_position_layer(c, False, 2) == 2560
    assert costs.kv_bytes_per_position_layer(c, True, 2) == 5120
    # 100 rounds that hit 84 experts each (14 a routed layer), 64 slots of
    # which 24 see 25,000 positions and 40 see 500; windows all full
    ctx = 100 * (24 * 25_000 + 40 * 500)
    parts = costs.decode_parts_bytes(c, 2, 100, 8_400, ctx, 100 * 64 * 128)
    assert parts == {"fixed": 187_183_475_200, "experts": 422_785_843_200,
                     "full_kv": 2 * ctx * 2560, "window_kv": 5 * 819_200 * 5120}
    assert parts["full_kv"] == 317_440_000_000
    least = costs.decode_least_bytes(c, 2, 100, 8_400, ctx, 100 * 64 * 128)
    assert least == sum(parts.values())
    assert least / 100 / 819e9 == pytest.approx(0.011578, rel=1e-3)  # 11.6 ms


def test_prefill_operations(c):
    # a 2,048-token chunk behind a prefix of 4,096
    p, t = 4096, 2048
    causal = t * p + t * (t + 1) // 2
    windowed = costs.windowed_pairs(p, t, 128)
    assert (causal, windowed) == (10_486_784, 2048 * 128)
    assert costs.windowed_pairs(0, 200, 128) == 128 * 129 // 2 + 72 * 128
    per_token = 2 * (5 * (50_331_648 + 6_291_456 + 4_194_304 + 33_554_432)
                     + 2 * (50_331_648 + 3_145_728 + 2_097_152 + 33_554_432)
                     + 3 * 4096 * 16_384 + 6 * 4096 * 256)
    flops = (t * per_token + 2 * 64 * 320 * (2 * causal + 5 * windowed)
             + 2 * 4096 * 19_072)
    assert costs.prefill_flops(c, [(p, t)], 0) == flops
    # every assignment that lands: 3 matmuls of 4096 x 2048, x 2
    assert costs.prefill_flops(c, [(p, t)], 1000) - flops == 1000 * 50_331_648
    assert costs.prefill_flops(c, [(0, 100)], 0) < costs.prefill_flops(c, [(0, 101)], 0)
