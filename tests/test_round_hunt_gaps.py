"""``scripts/lm_round_hunt.py --trace`` holds every device idle gap against the
engine's starved account: ``gap_cover`` on made-up intervals (one clock)."""
import importlib.util
import os

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def hunt():
    spec = importlib.util.spec_from_file_location(
        "lm_round_hunt", os.path.join(ROOT, "scripts", "lm_round_hunt.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


LEAVES = [("lm/decode_wait", 0.0, 1.0), ("lm/emit", 1.0, 1.2),
          ("lm/sched", 1.2, 1.3), ("lm/decode_dispatch", 1.3, 1.6),
          ("lm/decode_wait", 1.6, 3.0)]
ENVELOPES = [("lm/starved", 1.0, 1.6), ("lm/idle", 5.0, 9.0)]


def _approx(d):
    return {k: pytest.approx(v) for k, v in d.items()}


@pytest.mark.parametrize("gap,covered,uncovered", [
    # inside one envelope
    ((1.1, 1.5), {"lm/starved": 0.4}, {}),
    # the tail of a wait before it, the enqueue's latency after it
    ((0.9, 1.8), {"lm/starved": 0.6}, {"lm/decode_wait": 0.1 + 0.2}),
    # under a wait alone: the device done and the host not yet told
    ((2.0, 2.5), {}, {"lm/decode_wait": 0.5}),
    # where no leaf lies either
    ((3.5, 5.5), {"lm/idle": 0.5}, {"no leaf": 1.5}),
    # two envelopes and what lies between
    ((1.5, 6.0), {"lm/starved": 0.1, "lm/idle": 1.0},
     {"lm/decode_wait": 1.4, "no leaf": 2.0}),
])
def test_gap_cover_splits_a_gap_between_envelopes_and_leaves(
        hunt, gap, covered, uncovered):
    (row,) = hunt.gap_cover([gap], ENVELOPES, LEAVES)
    assert row["at"] == gap[0] and row["gap"] == pytest.approx(gap[1] - gap[0])
    assert row["covered"] == _approx(covered)
    assert row["uncovered"] == _approx(uncovered)
    assert sum(row["covered"].values()) + sum(row["uncovered"].values()) \
        == pytest.approx(row["gap"])


def test_gap_cover_keeps_the_gaps_in_order_and_apart(hunt):
    rows = hunt.gap_cover([(1.1, 1.5), (2.0, 2.5)], ENVELOPES, LEAVES)
    assert [r["at"] for r in rows] == [1.1, 2.0]
    assert rows[0]["uncovered"] == {} and rows[1]["covered"] == {}
