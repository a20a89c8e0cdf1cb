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


@pytest.mark.parametrize("round_ms", [2, 5, 12, 20, 33, 240])
def test_emission_rate_of_a_saturated_stream_at_any_round_length(round_ms):
    """16 tokens a round, each reaching its client up to 1 ms after the
    round's end: 16 / r tokens a second wherever the edges fall, whether a
    round is 2 ms or 240 (a settle of 20 ms chained rounds under 20 ms apart
    into one emission and read 0 at 12 ms)."""
    r, rng = round_ms / 1e3, np.random.RandomState(round_ms)
    rounds = np.arange(-2.0, 43.0, r)
    stamps = (rounds[:, None] + rng.uniform(0, 1e-3, (len(rounds), 16))).ravel()
    for phase in (0.0, 0.0004, 0.31 * r, 0.5 * r, 0.97 * r):
        rate = stats.emission_rate(stamps, phase, phase + 40.0)
        assert rate == pytest.approx(16 / r, rel=2e-3), (round_ms, phase)


def test_emission_rate_edges_move_forward_inside_one_group_only():
    """An edge inside a round's trail goes to that group's end and no
    further: not into the next round however close it follows, and never by
    more than four settles."""
    rounds = [0.002 * k for k in range(-500, 1500)]
    stamps = [t + 6e-5 * s for t in rounds for s in range(16)]      # 0.9 ms
    xs, ends, settle = stats.emission_groups(stamps)
    assert settle == 0.0005                     # a 1.1-ms gap: the floor's
    assert len(ends) == len(rounds)             # a group is a round, not two
    assert np.all(np.diff(ends) == 16)
    # opening edge in the middle of round 0's trail, closing edge after
    # round 500's: 500 whole rounds between the two group ends
    assert stats.emission_rate(stamps, 0.0004, 1.00095) == pytest.approx(8000.0)
    # a stream with no gap of a settle at all: the edge goes four settles on
    dense = list(np.arange(0.0, 3.0, 1e-4))
    assert stats.emission_rate(dense, 1.0, 2.0) == pytest.approx(1e4, rel=1e-3)


def test_emission_rate_edge_in_a_pause_stays_where_it_is():
    """Bursts of rounds 10 ms apart with pauses of seconds between them: an
    edge in a pause stays (the reading is the plain count), one inside a
    burst moves back one round's gap at most."""
    burst = [0.010 * k + 4e-5 * s for k in range(100) for s in range(16)]
    stamps = [t + 5.0 * b for b in range(-1, 10) for t in burst]
    # bursts end 1.0 s after they start: both edges 2.5 s into a pause
    assert stats.emission_rate(stamps, 3.5, 43.5) == pytest.approx(
        8 * 1600 / 40.0)
    # a faster drain of the same bursts reads the same
    fast = [t * 0.25 + 5.0 * b for b in range(-1, 10) for t in burst]
    assert stats.emission_rate(fast, 3.5, 43.5) == pytest.approx(8 * 1600 / 40.0)
    # the closing edge inside a burst, 4 ms after round 50's trail
    rate = stats.emission_rate(stamps, 3.5, 40.0 + 0.5046)
    assert rate == pytest.approx((7 * 1600 + 51 * 16) / (40.5006 - 3.5))


SATURATED = {"kind": "bursty", "rate_rps": 1.8, "burst_factor": 3,
             "burst_period_s": 8, "burst_duty": 0.3, "order_seed": 23,
             **{k: MIX[k] for k in ("prompt_lens", "prompt_weights",
                                    "output_lens", "output_weights")}}


def test_emission_rate_of_the_retired_cell_does_not_read_the_drain_speed():
    """``gpt2xl.saturated`` (retired, PR 28: 1.8 requests/s in bursts, a 16-s
    pre-roll) replayed through a toy round loop.  It offered 94.4 tokens/s;
    moving each edge back to the last emission however long the engine had
    been drained read 101.2 / 95.5 / 91.6 at rounds of 33 / 12 / 5 ms and
    refused PR 27's faster round.  Now the reading is the plain count to
    within one group at any round, and the same wherever the count is."""
    from benchmarks.tests import replay
    arrivals = [(a.due_s - 16.0, len(a.prompt), a.max_new)
                for a in loadgen.schedule(SATURATED, 1, 56.0, 50257)]
    rates = {}
    for round_ms in (33, 20, 12, 5):
        stamps, _ = replay.round_loop(arrivals, round_ms / 1e3, 40.0)
        plain = sum(0.0 <= t < 40.0 for t in stamps) / 40.0
        rates[round_ms] = stats.emission_rate(stamps, 0.0, 40.0)
        assert rates[round_ms] == pytest.approx(plain, rel=5e-3), round_ms
    # the 33-ms engine carries 3% more tokens over the window's edges (the
    # plain count says so too); from 20 ms down the window holds the offered
    # 94.4 tokens/s at every speed
    assert rates[33] == pytest.approx(97.3, abs=0.2)
    for round_ms in (20, 12, 5):
        assert rates[round_ms] == pytest.approx(94.4, rel=5e-3), round_ms


def test_emission_groups_follow_the_stream_not_a_stated_round():
    """What the chip showed at a 33-ms round (PR 28): the worker's 16 picks
    and emits take 1.6-2.6 ms and leave gaps of up to 1.2 ms in a round's
    trail.  A quarter of the round's gap holds such a trail together; an edge
    that falls into the trail's own gap still counts the round whole."""
    trail = [1e-4 * s for s in range(10)] + [2.1e-3 + 1e-4 * s for s in range(6)]
    rounds = [0.035 * k for k in range(-100, 1300)]
    stamps = [r + t for r in rounds for t in trail]
    xs, ends, settle = stats.emission_groups(stamps)
    assert len(ends) == len(rounds) and np.all(np.diff(ends) == 16)
    assert settle == pytest.approx(0.25 * (0.035 - 2.6e-3))
    for phase in (0.0, 0.0015, 0.0023, 0.02):
        assert stats.emission_rate(stamps, phase, phase + 40.0) \
            == pytest.approx(16 / 0.035), phase
