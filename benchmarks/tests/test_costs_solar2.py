"""``costs_solar2.py`` against hand-worked lines (PERF.md section 3 and ISSUE
33 repeat them), on the committed configuration."""
import json
import os

import pytest

from benchmarks import run
from benchmarks.harness import costs_solar2 as costs

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def c():
    with open(os.path.join(BENCH, "configs", "solar-open2-250b.json")) as f:
        return json.load(f)


def test_decode_round_bytes(c):
    # softmax layer: q, o, gate 3 x 4096 x 8192 = 100,663,296; k and v
    # 2 x 4096 x 1024 = 8,388,608
    assert costs.softmax_params(c) == 109_051_904
    # KDA layer: q, k, v, o 4 x 33,554,432 = 134,217,728; two low-rank pairs
    # 2 x (4096 x 128 + 128 x 8192) = 3,145,728; beta 4096 x 64 = 262,144
    assert costs.kda_matmul_params(c) == 137_625_600
    # conv 4 x 24,576 = 98,304; A_log 64 + dt_bias 8,192 + norm 128 = 8,384
    assert costs.kda_params(c) == 137_732_288
    # router 4096 x 320 = 1,310,720; shared 3 x 4096 x 1280 = 15,728,640;
    # two norms 8,192
    assert costs.ffn_fixed_params(c) == 17_047_552
    # one expert: 15,728,640 values, 31,457,280 B in bf16
    assert costs.expert_bytes(c, 2) == 31_457_280
    # 109,051,904 + 3 x 137,732,288 + 4 x 17,047,552 + head 4096 x 24,576 =
    # 100,663,296 + final norm 4,096
    values = 109_051_904 + 413_196_864 + 68_190_208 + 100_663_296 + 4_096
    assert values == 691_106_368
    assert costs.decode_fixed_bytes(c, 2) == 2 * values == 1_382_212_736
    # 8 K/V heads x 128 x (k, v) x 2 B, on the ONE softmax layer
    assert costs.kv_bytes_per_position_layer(c, 2) == 4096
    # a slot's row in a KDA layer, read and written: the state 64 x 128 x 128
    # x 4 B = 4,194,304; the tail 3 x 24,576 x 2 B = 147,456
    assert costs.state_row_bytes(c, 2) == 2 * (4_194_304 + 147_456) == 8_683_520
    # 100 rounds of 128 active slots at a context of 1,000 that hit all 40
    # experts in each of the 4 layers: 384 state rows a round
    least = costs.decode_least_bytes(c, 2, 100, 16_000, 12_800_000, 38_400)
    assert least == (138_221_273_600 + 503_316_480_000 + 4096 * 12_800_000
                     + 333_447_168_000)
    assert least / 100 / 819e9 == pytest.approx(0.012543, rel=1e-3)  # 12.5 ms
    # an idle slot counts for nothing: half the slots, half the state's bytes
    assert (least - costs.decode_least_bytes(c, 2, 100, 16_000, 12_800_000, 19_200)
            == 19_200 * 8_683_520)


def test_prefill_operations(c):
    t = 1024
    causal = 1024 * 1025 // 2
    assert causal == 524_800
    flops = (2 * 4096 * 24_576                                    # head, once
             + 2 * t * (109_051_904 + 3 * 137_625_600)            # projections
             + 4 * 64 * 128 * causal                              # one softmax layer
             + 3 * t * (2 * 4 * 24_576 + 7 * 64 * 128 * 128)      # conv, recurrence
             + 4 * 2 * t * (1_310_720 + 15_728_640))              # routers, shared
    assert costs.prefill_flops(c, t, 0) == flops
    # the recurrence's own count: 7 x 128 x 128 x 64 heads = 7,340,032 a
    # position and layer, 2.6% of a KDA layer's projections (275,251,200)
    assert 7 * 64 * 128 * 128 == 7_340_032
    # every assignment that lands: 3 matmuls of 4096 x 1280, x 2
    assert costs.prefill_flops(c, t, 1000) - flops == 1000 * 31_457_280
    assert costs.prefill_flops(c, 100, 0) < costs.prefill_flops(c, 101, 0)


def _recording(c, **kw):
    rec = {"config": c, "device_kind": "TPU v5 lite", "chips": 1,
           "counters": {"lm.decode_context_tokens": 12_800_000,
                        "lm.traced_moe_experts_hit": 16_000,
                        "lm.traced_state_rows": 38_400,
                        "lm.traced_decode_rounds": 100,
                        "lm.traced_prefill_tokens": [1024, 128],
                        "lm.traced_prefill_moe_assignments": 1152 * 4},
           "trace": {"modules": {
               "jit__decode_fn": {"calls": 100.0, "device_s": 3.0},
               "jit__prefill_fn(1)": {"calls": 1.0, "device_s": 0.05},
               "jit__prefill_fn(2)": {"calls": 1.0, "device_s": 0.01}}}}
    rec.update(kw)
    return rec


def test_readers_on_a_recording(c):
    rec = _recording(c)
    # 1,027.4 GB least / 819 GB/s = 1.2543 s, over 3.0 s on the device
    assert run.read_layer_metric(BENCH, "solar2_decode_hbm_roofline", rec) \
        == pytest.approx(41.81, abs=0.02)
    # the state's share of those bytes: 333.4 of 1,027.4 GB
    assert run.read_layer_metric(BENCH, "state_bytes_pct.solar2", rec) \
        == pytest.approx(32.46, abs=0.02)
    flops = (costs.prefill_flops(c, 1024, 0) + costs.prefill_flops(c, 128, 0)
             + 4608 * 31_457_280)
    assert run.read_layer_metric(BENCH, "solar2_prefill_mfu", rec) \
        == pytest.approx(flops / 197e12 / 0.06 * 100, rel=1e-6)
    assert 0 < run.read_layer_metric(BENCH, "solar2_prefill_mfu", rec) < 100


def test_readers_find_nothing_on_a_program_without_the_state(c):
    """On the parent commit (no state arena, no ``state_rows`` on the round's
    span) and on a run without a device trace the readers return None and do
    not raise."""
    bare = {"config": c, "device_kind": "TPU v5 lite", "chips": 1,
            "counters": {}, "trace": {"modules": {}}}
    for name in ("solar2_decode_hbm_roofline", "solar2_prefill_mfu",
                 "state_bytes_pct.solar2"):
        assert run.read_layer_metric(BENCH, name, bare) is None
    rec = _recording(c)
    rec["counters"]["lm.traced_state_rows"] = 0
    assert run.read_layer_metric(BENCH, "state_bytes_pct.solar2", rec) is None
    assert run.read_layer_metric(BENCH, "solar2_decode_hbm_roofline", rec) is None
