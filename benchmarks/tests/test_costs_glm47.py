"""``costs_glm47.py`` against hand-worked lines (PERF.md section 3 and ISSUE 40
repeat them), on the committed configuration."""
import json
import os

import pytest

from benchmarks.harness import costs_glm47 as costs

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def c():
    with open(os.path.join(BENCH, "configs", "glm-4.7-flash.json")) as f:
        return json.load(f)


def test_a_blocks_parameters(c):
    # W_qa 2,048 x 768 = 1,572,864; W_qb 768 x 20 x 256 = 3,932,160; W_dkv
    # 2,048 x 576 = 1,179,648; W_ukv 512 x 20 x 448 = 4,587,520; Wo 5,120 x
    # 2,048 = 10,485,760; the two norms 768 + 512
    assert costs.mla_matmul_params(c) == 21_757_952
    assert costs.mla_params(c) == 21_759_232
    # layer 0's SwiGLU 3 x 2,048 x 10,240; a routed layer's router 2,048 x 64
    # = 131,072 and shared expert 3 x 2,048 x 1,536 = 9,437,184
    assert costs.ffn_fixed_params(c, True) == 62_914_560
    assert costs.ffn_fixed_params(c, False) == 9_568_256
    # ... with two norm vectors of 2,048 (the selection bias, 64 float32, aside)
    assert costs.block_fixed_params(c, True) == 84_677_888
    assert costs.block_fixed_params(c, False) == 31_331_584
    # one expert: 9,437,184 values, 18,874,368 B in bf16; the head 2,048 x 154,880
    assert costs.expert_bytes(c, 2) == 18_874_368
    assert costs.head_bytes(c, 2) == 634_388_480


def test_a_rounds_least_bytes(c):
    # outside the experts and the head: layer 0, four routed blocks, the final
    # norm: 84,677,888 + 4 x 31,331,584 + 2,048 = 210,006,272 values
    assert costs.round_fixed_bytes(c, 2, drafted=False) == 420_012_544
    # the prediction module's block, eh_proj 4,096 x 2,048, its three norms
    assert (costs.round_fixed_bytes(c, 2) - 420_012_544
            == 2 * (31_331_584 + 8_388_608 + 3 * 2048) == 79_452_672)
    # ONE row a position and arena layer: (512 + 64) x 2 B
    assert costs.latent_bytes_per_position_layer(c, 2) == 1152
    # 100 self-drafting rounds of 64 slots at 340,000 live positions over six
    # arena layers that hit all 64 experts of the five routed blocks
    parts = costs.round_parts_bytes(c, 2, 100, 100, 100 * 5 * 64,
                                    100 * 6 * 340_000)
    assert parts == {"fixed": 49_946_521_600, "head": 126_877_696_000,
                     "experts": 603_979_776_000, "latent": 235_008_000_000}
    # ISSUE 40's line: 10.4 GB, 12.7 ms a round at the roofline
    assert sum(parts.values()) / 100 == pytest.approx(10.16e9, rel=1e-2)
    assert sum(parts.values()) / 100 / 819e9 == pytest.approx(0.0124, rel=1e-2)
    # a round with the drafter off reads the head once and no module
    plain = costs.round_parts_bytes(c, 2, 100, 0, 100 * 4 * 64, 100 * 5 * 340_000)
    assert plain["head"] == 100 * 634_388_480
    assert plain["fixed"] == 100 * 420_012_544
    # positions, not whole blocks
    assert costs.round_parts_bytes(c, 2, 100, 100, 32_000, 204_000_001)[
        "latent"] == 235_008_001_152


def test_a_prefills_operations(c):
    # a computed token: five mixers 5 x 2 x 21,757,952; layer 0's SwiGLU 2 x
    # 62,914,560; four routed layers' routers and shared experts 4 x 2 x
    # 9,568,256 and 4 experts each 4 x 4 x 6 x 2,048 x 1,536; the module's
    # ROWS: eh_proj 2 x 8,388,608 and W_dkv 2 x 1,179,648
    per_token = (217_579_520 + 125_829_120 + 76_546_048 + 301_989_888
                 + 16_777_216 + 2_359_296)
    assert costs.prefill_flops(c, 1, 0, 0) == per_token == 741_081_088
    # a (query, key) pair: 20 heads x (256 score lanes + 256 value lanes) x 2
    # on each of the five layers
    assert costs.prefill_flops(c, 0, 1, 0) == 2 * 20 * 512 * 5 == 102_400
    # the head, one position a program
    assert costs.prefill_flops(c, 0, 0, 1) == 2 * 2048 * 154_880
    # a suffix of 512 tokens over a matched prefix of 4,096, and the one-token
    # pass before it: 513 tokens, 512 x (4,096 + 256.5) + 4,095 pairs
    pairs = 512 * (4096 + 256.5) + 4095
    flops = costs.prefill_flops(c, 513, pairs, 2)
    assert flops == pytest.approx(6.1e11, rel=2e-2)
    assert flops / 197e12 == pytest.approx(3.1e-3, rel=2e-2)    # 3 ms at the peak


def test_the_readers_read_a_recordings_counters(c):
    rec = {"config": c, "device_kind": "TPU v5 lite", "counters": {
        "lm.traced_rounds": 100, "lm.traced_draft_rounds": 100,
        "lm.traced_moe_experts_hit": 32_000,
        "lm.traced_latent_positions": 204_000_000},
        "trace": {"modules": {"jit__selfdraft_fn(3)": {"calls": 100.0,
                                                       "device_s": 2.5},
                              "jit__prefix_prefill_fn(9)": {"calls": 8.0,
                                                            "device_s": 0.2}}}}
    parts = costs.traced_rounds(rec)
    assert sum(parts.values()) == pytest.approx(1.0158e12, rel=1e-3)
    assert costs.modules_device_s(rec, costs.ROUND_MODULES) == 2.5
    assert costs.modules_device_s(rec, costs.PREFILL_MODULES) == 0.2
    assert costs.traced_rounds(dict(rec, counters={})) is None
    from benchmarks import run
    bench = os.path.join(BENCH)
    roof = run.read_layer_metric(bench, "glm47_round_hbm_roofline", rec)
    assert roof == pytest.approx(1.0158e12 / 819e9 / 2.5 * 100, rel=1e-3)
    share = run.read_layer_metric(bench, "latent_bytes_pct.glm47", rec)
    assert share == pytest.approx(23.1, rel=1e-2)
    rec["counters"].update({"lm.traced_prefill_tokens": 8 * 513,
                            "lm.traced_prefill_pairs": 8 * 2_232_575.0,
                            "lm.traced_prefill_chunks": 16})
    mfu = run.read_layer_metric(bench, "glm47_prefill_mfu", rec)
    assert 10 < mfu < 15
    # a program without the spans and counters: nothing to read, no error
    empty = dict(rec, counters={}, trace={"modules": {}})
    for name in ("glm47_round_hbm_roofline", "glm47_prefill_mfu",
                 "latent_bytes_pct.glm47"):
        assert run.read_layer_metric(bench, name, empty) is None
