"""The arithmetic between samples and reported numbers."""
import math
import statistics
from typing import Sequence

import numpy as np


def percentile(values: Sequence[float], q: float) -> float:
    """Linear interpolation between closest ranks (numpy's default)."""
    xs = sorted(values)
    if not xs:
        raise ValueError("percentile of no samples")
    k = (len(xs) - 1) * q / 100.0
    lo = math.floor(k)
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (k - lo)


def median(values: Sequence[float]) -> float:
    return percentile(values, 50.0)


def describe(values: Sequence[float], q: float = 95.0) -> dict:
    """What goes on the earlier line beside each percentile."""
    return {"n": len(values), "median": median(values),
            "mean": sum(values) / len(values),
            f"p{q:g}": percentile(values, q), "max": max(values)}


def quartile_spread(values: Sequence[float]) -> float:
    """Distance between the first and third quartile as a share of the
    median, quartiles as ``statistics.quantiles(values, n=4)`` gives them
    (the driver's spread)."""
    q1, _, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / statistics.median(values)


def emission_groups(stamps: Sequence[float], settle: float = 0.0005,
                    share: float = 0.25):
    """The sorted stamps, the index of each emission group's last stamp, and
    the settle that separates the groups.  A group is what ONE round's tokens
    reach their clients in: stamps under a settle apart, where the settle is
    ``share`` of the upper quartile of the gaps between the groups that the
    floor of ``settle`` seconds finds, and never under that floor.  So it
    follows the stream and not a stated round length: at GPT-2 XL's 33-ms
    round the worker's 16 picks and emits take 1.6-2.6 ms with gaps of up to
    1.2 ms among them (my chip run, PR 28), which the floor alone splits in a
    third of the rounds and a quarter of the round's gap (8 ms) does not; at a
    2-ms round the floor decides.  Rounds chain into one group only where
    they come under half a millisecond apart."""
    xs = np.sort(np.asarray(stamps, np.float64))
    if not len(xs):
        return xs, np.zeros((0,), np.int64), settle
    gaps = np.diff(xs)
    between = gaps[gaps >= settle]
    if len(between):
        settle = max(settle, share * float(np.percentile(between, 75)))
    return xs, np.append(np.flatnonzero(gaps >= settle), len(xs) - 1), settle


def emission_rate(stamps: Sequence[float], t_open: float, t_close: float,
                  settle: float = 0.0005, reach: float = 3.0) -> float:
    """Events a second over the window ``[t_open, t_close)``, each edge moved
    back to the emission it follows.

    A server that emits a token for every slot at the end of each round makes
    the plain count over a fixed window move in steps of a whole round (16
    tokens of some 2,500: 0.64%), by where in a round the edges happen to
    fall.  So an edge is moved back to the end of the emission group
    (``emission_groups``) that holds the last stamp at or before it, and the
    rate is the stamps after the opening emission up to the closing one, over
    the time between the two: every round between the two emissions is
    counted whole, and nothing else is.  Two limits keep the reading free of
    the round's length and of how fast a burst drains:

    - an edge inside a group's trail goes FORWARD to the group's end, by at
      most four of the groups' settles, never into the next group;
    - an edge moves back by about one gap between groups at most: one that
      lies further behind the last emission than ``reach`` times the median
      gap between the (up to 8 + 8) groups around it has fallen into a
      pause, and stays where it is, as does one that no stamp precedes or
      that has no gap around it to be measured against.  So a stream with
      pauses reads its plain count to within one group.
    """
    xs, ends, settle = emission_groups(stamps, settle)
    closes = xs[ends]

    def edge(t: float) -> float:
        i = int(np.searchsorted(xs, t, side="right")) - 1
        if i < 0:
            return t
        g = int(np.searchsorted(ends, i))       # the group stamp i is in
        e = min(closes[g],
                xs[int(np.searchsorted(xs, t + 4 * settle, side="right")) - 1])
        around = np.concatenate([np.diff(closes[max(g - 8, 0):g + 1]),
                                 np.diff(closes[g + 1:g + 10])])
        if not len(around) or t - e > reach * np.median(around):
            return t
        return float(e)

    lo, hi = edge(t_open), edge(t_close)
    if hi <= lo:
        return 0.0
    return float(np.searchsorted(xs, hi, side="right")
                 - np.searchsorted(xs, lo, side="right")) / (hi - lo)
