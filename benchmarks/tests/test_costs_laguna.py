"""``costs_laguna.py`` against hand-worked lines (PERF.md section 3 and ISSUE
26 repeat them), on the committed configuration."""
import json
import os

import pytest

from benchmarks import run
from benchmarks.harness import costs_laguna as costs

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def c():
    with open(os.path.join(BENCH, "configs", "laguna-s-2.1.json")) as f:
        return json.load(f)


def test_decode_round_bytes(c):
    # attention, 48 heads: q and o 2 x 3072 x 6144 = 37,748,736; k and v
    # 2 x 3072 x 1024 = 6,291,456; gate 3072 x 48 = 147,456: 44,187,648
    assert costs._attention_params(c, 48) == 44_187_648
    # 72 heads: 2 x 3072 x 9216 + 6,291,456 + 221,184
    assert costs._attention_params(c, 72) == 63_135_744
    # one expert: 3 x 3072 x 1024 = 9,437,184 values, 18,874,368 B in bf16
    assert costs.expert_bytes(c, 2) == 18_874_368
    # layer 0: 44,187,648 + dense MLP 3 x 3072 x 12,288 = 113,246,208
    # layers 1-3: 63,135,744 + shared 9,437,184 + router 786,432 = 73,359,360
    # layer 4: 44,187,648 + 9,437,184 + 786,432 = 54,411,264
    # head 3072 x 50,176 = 154,140,672; 11 norm vectors 33,792
    values = (44_187_648 + 113_246_208 + 3 * 73_359_360 + 54_411_264
              + 154_140_672 + 33_792)
    assert values == 586_097_664
    assert costs.decode_fixed_bytes(c, 2) == 2 * values == 1_172_195_328
    # 8 K/V heads x 128 x (k, v) x 2 B
    assert costs.kv_bytes_per_position_layer(c, 2) == 4096
    # 100 rounds that hit 368 experts each (92 a sparse layer), 32 tokens a
    # round at a context of 1,000: 2 full layers see 1,000, 3 sliding see 512
    least = costs.decode_least_bytes(c, 2, 100, 36_800, 3_200_000, 32 * 512 * 100)
    assert least == (117_219_532_800 + 694_576_742_400
                     + 4096 * (2 * 3_200_000 + 3 * 1_638_400))
    assert least / 100 / 819e9 == pytest.approx(0.010478, rel=1e-3)  # 10.5 ms


def test_prefill_operations(c):
    t = 2048
    causal, windowed = 2048 * 2049 // 2, 512 * 513 // 2 + 1536 * 512
    assert (causal, windowed) == (2_098_176, 917_760)
    flops = (2 * 3072 * 50_176                                   # head, once
             + 2 * t * (2 * 44_187_648 + 3 * 63_135_744)         # projections
             + 4 * 128 * (48 * 2 * causal + 72 * 3 * windowed)   # attention
             + 6 * t * 3072 * 12_288                             # dense MLP
             + 4 * (2 * t * 3072 * 256 + 6 * t * 3072 * 1024))   # routers, shared
    assert costs.prefill_flops(c, t, 0) == flops
    # every assignment that lands: 3 matmuls of 3072 x 1024, x 2
    assert costs.prefill_flops(c, t, 1000) - flops == 1000 * 18_874_368
    assert costs.prefill_flops(c, 100, 0) < costs.prefill_flops(c, 101, 0)


def test_the_readers_on_a_recording(c):
    rec = {"config": c, "device_kind": "TPU v5 lite", "chips": 1,
           "counters": {"lm.traced_moe_experts_hit": 36_800,
                        "lm.decode_context_tokens": 3_200_000,
                        "lm.decode_window_tokens": 1_638_400,
                        "lm.traced_prefill_tokens": [2048, 256],
                        "lm.traced_prefill_moe_assignments": 46_080},
           "trace": {"modules": {"jit__decode_fn": {"calls": 100.0, "device_s": 2.0},
                                 "jit__prefill_fn": {"calls": 2.0, "device_s": 0.1}}}}
    # 1.0478 s least over 2.0 s on the device
    assert run.read_layer_metric(BENCH, "laguna_decode_hbm_roofline", rec) \
        == pytest.approx(52.39, abs=0.05)
    flops = (costs.prefill_flops(c, 2048, 0) + costs.prefill_flops(c, 256, 0)
             + 46_080 * 18_874_368)
    assert run.read_layer_metric(BENCH, "laguna_prefill_mfu", rec) \
        == pytest.approx(flops / 197e12 / 0.1 * 100)
    # a program without the counters (the parent commit): nothing, no raise
    bare = dict(rec, counters={})
    assert run.read_layer_metric(BENCH, "laguna_decode_hbm_roofline", bare) is None
    assert run.read_layer_metric(BENCH, "laguna_prefill_mfu", bare) is None
    assert run.read_layer_metric(BENCH, "moe_experts_hit_pct", bare) is None
