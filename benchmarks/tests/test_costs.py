"""``costs.py`` against hand-worked lines (PERF.md section 3 repeats them)."""
import json
import os

import pytest

from benchmarks.harness import costs, peaks, stats

CONFIGS = os.path.join(os.path.dirname(os.path.dirname(__file__)), "configs")


def _config(name):
    with open(os.path.join(CONFIGS, name + ".json")) as f:
        return json.load(f)


def test_gpt2_xl_decode_round_bytes():
    c = _config("gpt2-xl")
    # per layer 12 h^2 + 13 h = 12 * 2,560,000 + 20,800 = 30,740,800
    # 48 layers 1,475,558,400; + final norm 3,200; + head 50257 * 1600 = 80,411,200
    assert costs.gpt2_decode_weight_bytes(c, 1) == 1_555_972_800
    assert costs.gpt2_decode_weight_bytes(c, 2) == 3_111_945_600
    # 48 layers x (k, v) x 1600 x 2 B
    assert costs.gpt2_kv_bytes_per_position(c, 2) == 307_200


def test_peaks_table_refuses_an_unknown_device():
    assert peaks.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        peaks.peaks("TPU v9")


def test_percentile_and_spread():
    xs = list(range(1, 101))
    assert stats.percentile(xs, 95) == pytest.approx(95.05)
    assert stats.median([3, 1, 2]) == 2
    assert stats.quartile_spread([10, 10, 10, 10, 11, 9]) == pytest.approx(0.05)
