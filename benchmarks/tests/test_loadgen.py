"""The load generator: seeded, weighted, the same work for every seed."""
import collections
import threading
import time

import numpy as np
import pytest

from benchmarks.harness import loadgen, stats

MIX = {"kind": "poisson", "rate_rps": 5.0,
       "prompt_lens": [32, 64, 128, 256, 512],
       "prompt_weights": [0.20, 0.30, 0.25, 0.15, 0.10],
       "output_lens": [16, 32, 64, 128, 256],
       "output_weights": [0.30, 0.30, 0.20, 0.15, 0.05]}


def test_same_seed_same_schedule_and_large_seeds():
    a = loadgen.schedule(MIX, 2 ** 31 + 12345, 40, 50257)
    b = loadgen.schedule(MIX, 2 ** 31 + 12345, 40, 50257)
    assert [x.due_s for x in a] == [x.due_s for x in b]
    assert all(np.array_equal(x.prompt, y.prompt) for x, y in zip(a, b))
    assert all(x.prompt.min() >= 1 and x.prompt.max() <= 50257 for x in a)


def test_weighted_menus_and_the_same_work_for_every_seed():
    a = loadgen.schedule(MIX, 1, 40, 50257)
    b = loadgen.schedule(MIX, 2, 40, 50257)
    assert len(a) == len(b) == 200                      # 5 a second for 40 s
    count = collections.Counter(len(x.prompt) for x in a)
    assert count == {32: 40, 64: 60, 128: 50, 256: 30, 512: 20}
    assert count == collections.Counter(len(x.prompt) for x in b)
    assert (collections.Counter(x.max_new for x in a)
            == {16: 60, 32: 60, 64: 40, 128: 30, 256: 10})
    gaps = lambda s: sorted(np.round(np.diff([x.due_s for x in s]), 9))  # noqa: E731
    # the same gaps (but the two that straddle the first arrival) reordered
    assert len(set(gaps(a)) & set(gaps(b))) >= 190
    assert [x.due_s for x in a] != [x.due_s for x in b]
    assert 0 <= a[0].due_s and a[-1].due_s < 40
    assert loadgen.apportion([0.5, 0.3, 0.2], 7) == [4, 2, 1]


def test_order_seed_fixes_the_order_and_leaves_the_ids_to_the_seed():
    mix = dict(MIX, order_seed=7)
    a, b = (loadgen.schedule(mix, s, 40, 50257) for s in (1, 2))
    assert [(x.due_s, len(x.prompt), x.max_new) for x in a] \
        == [(x.due_s, len(x.prompt), x.max_new) for x in b]
    assert not all(np.array_equal(x.prompt, y.prompt) for x, y in zip(a, b))
    assert (collections.Counter(x.max_new for x in a)
            == {16: 60, 32: 60, 64: 40, 128: 30, 256: 10})


def test_bursty_keeps_the_mean_rate_and_bursts():
    mix = dict(MIX, kind="bursty", rate_rps=9.0, burst_factor=3,
               burst_period_s=8, burst_duty=0.3)
    a = loadgen.schedule(mix, 3, 32, 50257)
    assert len(a) == 288                                # 9 a second for 32 s
    in_burst = sum((x.due_s % 8) < 2.4 for x in a)
    assert in_burst / len(a) == pytest.approx(0.9, abs=0.02)
    with pytest.raises(ValueError):
        loadgen.schedule(dict(mix, burst_factor=4), 3, 32, 50257)


def test_fire_is_open_loop_and_reports_lateness():
    mix = dict(MIX, rate_rps=50.0, prompt_lens=[4], prompt_weights=[1],
               output_lens=[2], output_weights=[1])
    arrivals = loadgen.schedule(mix, 4, 0.5, 100)
    fired, slow = [], {"n": 0}

    def submit(a):
        if a.index == 3:                 # a stalled submit delays what follows
            time.sleep(0.08)
        if a.index == 5:
            raise RuntimeError("refused")
        return a.index

    t_open = time.perf_counter() + 0.01
    loadgen.fire(arrivals, submit, t_open, fired.append, threading.Event())
    assert [f.arrival.index for f in fired] == list(range(len(arrivals)))
    assert fired[5].handle is None and "refused" in fired[5].error
    # every firing is timed from the instant it was DUE, so the stall shows
    # as lateness of the arrivals behind it, not as a shorter schedule
    late = [f.fired_at - f.due_at for f in fired]
    assert all(f.due_at == pytest.approx(t_open + f.arrival.due_s) for f in fired)
    assert max(late[4:8]) > 0.03 and min(late) > -1e-3


def test_emission_rate_counts_whole_rounds_wherever_the_edges_fall():
    # 16 slots emit together every 0.25 s, each client a hair later; the
    # plain count over a fixed window moves by a round with the edges' phase
    rounds = [0.25 * k for k in range(-8, 200)]
    stamps = [r + 1e-4 * s for r in rounds for s in range(16)]
    plain, rates = set(), set()
    for phase in (0.0005, 0.01, 0.1, 0.2, 0.2495):
        lo, hi = phase, phase + 40.0
        plain.add(sum(lo <= t < hi for t in stamps))
        rates.add(round(stats.emission_rate(stamps, lo, hi), 9))
    assert rates == {64.0}
    # an edge inside a round's trail of stamps belongs to that emission
    assert stats.emission_rate(stamps, 0.0005, 40.0005) == pytest.approx(64.0)
    # a pause between the two emissions counts against the rate
    paused = [t if t < 20 else t + 2.0 for t in stamps]
    assert stats.emission_rate(paused, 0.1, 40.1) == pytest.approx(
        16 * 152 / 40.0)
    # no stamp before the opening: the edge stays, and idle time counts
    assert stats.emission_rate([1.0, 2.0, 3.0], 0.0, 4.0) == pytest.approx(1.0)
    assert stats.emission_rate([], 0.0, 4.0) == 0.0
