"""``costs_ling3.py`` against hand-worked lines (PERF.md section 3 and ISSUE
35 repeat them), on the committed configuration."""
import json
import os

import pytest

from benchmarks import run
from benchmarks.harness import costs_ling3 as costs

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def c():
    with open(os.path.join(BENCH, "configs", "ling-3.0-flash-vl.json")) as f:
        return json.load(f)


def test_decode_round_bytes(c):
    # KDA layer: q, k, v, o, the decay's and the gate's full-rank projections
    # 6 x 2560 x 4096 = 62,914,560; beta 2560 x 32 = 81,920
    assert costs.kda_matmul_params(c) == 62_996_480
    # conv 4 x 12,288 = 49,152; A_log 32 + dt_bias 4,096 + norm 128 = 4,256
    assert costs.kda_params(c) == 63_049_888
    # MLA layer: Wq 2560 x 6144 = 15,728,640; W_dkv 2560 x 576 = 1,474,560;
    # W_ukv 512 x 8192 = 4,194,304; gate 81,920; Wo 10,485,760; the norm 512
    assert costs.mla_matmul_params(c) == 31_965_184
    assert costs.mla_params(c) == 31_965_696
    # a dense layer's SwiGLU 3 x 2560 x 6144; a routed layer's router
    # 2560 x 512 = 1,310,720 and shared expert 3 x 2560 x 768 = 5,898,240
    assert costs.ffn_fixed_params(c, True) == 47_185_920
    assert costs.ffn_fixed_params(c, False) == 7_208_960
    # one expert: 5,898,240 values, 11,796,480 B in bf16
    assert costs.expert_bytes(c, 2) == 11_796_480
    # 7 x 63,049,888 + 31,965,696 + 2 x 47,185,920 + 6 x 7,208,960 + 8 x 2
    # norms of 2,560 + head 2560 x 19,648 = 50,298,880 + final norm 2,560
    values = (441_349_216 + 31_965_696 + 94_371_840 + 43_253_760 + 40_960
              + 50_298_880 + 2_560)
    assert values == 661_282_912
    assert costs.decode_fixed_bytes(c, 2) == 2 * values == 1_322_565_824
    # ONE row a position on the ONE MLA layer: (512 + 64) x 2 B
    assert costs.latent_bytes_per_position_layer(c, 2) == 1152
    # a slot's row in a KDA layer, read and written: the state 32 x 128 x 128
    # x 4 B = 2,097,152; the tail 3 x 12,288 x 2 B = 73,728
    assert costs.state_row_bytes(c, 2) == 2 * (2_097_152 + 73_728) == 4_341_760
    # 100 rounds of 32 active slots at 830,000 live positions that hit 25 of
    # the 64 experts in each of the 6 routed layers: 224 state rows a round
    parts = costs.decode_parts_bytes(c, 2, 100, 15_000, 83_000_000, 22_400)
    assert parts == {"fixed": 132_256_582_400, "experts": 176_947_200_000,
                     "latent": 95_616_000_000, "state": 97_255_424_000}
    assert sum(parts.values()) / 100 / 819e9 == pytest.approx(0.006130, rel=1e-3)
    # positions, not whole blocks; an idle slot counts for nothing
    assert costs.decode_parts_bytes(c, 2, 100, 15_000, 83_000_001, 11_200) == dict(
        parts, latent=95_616_001_152, state=48_627_712_000)


def test_decode_round_operations(c):
    # a decoded token, outside the routed experts: seven KDA layers'
    # projections, convolution (2 x 4 x 12,288) and recurrence (7 x 32 x 128 x
    # 128 = 3,670,016); the MLA layer's projections with W_ukv as the fold and
    # the unfold (2 x 32 x 512 x 256: the same count); two dense and six
    # routed layers' fixed halves; the head
    per_token = (7 * (2 * 62_996_480 + 98_304 + 3_670_016) + 2 * 31_965_184
                 + 2 * 2 * 47_185_920 + 6 * 2 * 7_208_960 + 2 * 2560 * 19_648)
    assert per_token == 1_348_108_288
    assert costs.decode_least_flops(c, 1, 0, 0) == per_token
    # an assignment that lands: 3 matmuls of 2560 x 768, x 2
    assert (costs.decode_least_flops(c, 32, 100, 0)
            == 32 * per_token + 100 * 11_796_480)
    # a live position of the absorbed attention: 32 heads x (576 + 512) x 2
    assert costs.decode_least_flops(c, 0, 0, 1) == 69_632
    # at 830,000 live positions: 57.8 GFLOP, over the 43.1 of 32 tokens
    assert costs.decode_least_flops(c, 0, 0, 830_000) / 1e9 == pytest.approx(
        57.79, abs=0.01)
    assert 32 * per_token / 1e9 == pytest.approx(43.14, abs=0.01)


def _recording(c, **kw):
    rec = {"config": c, "device_kind": "TPU v5 lite", "chips": 1,
           "counters": {"lm.traced_moe_experts_hit": 15_000,
                        "lm.traced_moe_assignments": 19_200,
                        "lm.traced_state_rows": 22_400,
                        "lm.traced_latent_positions": 83_000_000,
                        "lm.traced_active_slots": 3_200,
                        "lm.traced_decode_rounds": 100},
           "trace": {"modules": {
               "jit__decode_fn": {"calls": 100.0, "device_s": 2.0}}}}
    rec.update(kw)
    return rec


def test_readers_on_a_recording(c):
    rec = _recording(c)
    # 502.1 GB least / 819 GB/s = 0.6130 s, over 2.0 s on the device
    assert run.read_layer_metric(BENCH, "ling3_decode_hbm_roofline", rec) \
        == pytest.approx(30.65, abs=0.02)
    # 95.6 and 97.3 of 502.1 GB
    assert run.read_layer_metric(BENCH, "latent_bytes_pct.ling3", rec) \
        == pytest.approx(19.04, abs=0.02)
    assert run.read_layer_metric(BENCH, "state_bytes_pct.ling3", rec) \
        == pytest.approx(19.37, abs=0.02)
    flops = (3_200 * 1_348_108_288 + 19_200 * 11_796_480 + 83_000_000 * 69_632)
    assert run.read_layer_metric(BENCH, "ling3_decode_mfu", rec) \
        == pytest.approx(flops / 197e12 / 2.0 * 100, rel=1e-6)
    assert 0 < run.read_layer_metric(BENCH, "ling3_decode_mfu", rec) < 100


def test_readers_find_nothing_on_a_program_without_the_latent_counters(c):
    """On the parent commit (no ``latent_positions`` on the round's span) and
    on a run without a device trace the readers return None and do not
    raise."""
    bare = {"config": c, "device_kind": "TPU v5 lite", "chips": 1,
            "counters": {}, "trace": {"modules": {}}}
    names = ("ling3_decode_hbm_roofline", "ling3_decode_mfu",
             "latent_bytes_pct.ling3", "state_bytes_pct.ling3")
    for name in names:
        assert run.read_layer_metric(BENCH, name, bare) is None
    rec = _recording(c)
    rec["counters"]["lm.traced_latent_positions"] = 0
    for name in names:
        assert run.read_layer_metric(BENCH, name, rec) is None
