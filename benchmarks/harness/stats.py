"""The arithmetic between samples and reported numbers."""
import bisect
import math
import statistics
from typing import Sequence


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


def emission_rate(stamps: Sequence[float], t_open: float, t_close: float,
                  settle: float = 0.02) -> float:
    """Events a second over the window ``[t_open, t_close)``, each edge moved
    back to the emission it follows.

    A server that emits a token for every slot at the end of each round makes
    the plain count over a fixed window move in steps of a whole round (16
    tokens of some 2,500: 0.64%), by where in a round the edges happen to
    fall.  So an edge is moved back to the last stamp at or before it (with
    the stamps that trail that one by under ``settle`` seconds each: one
    round's tokens reach their clients within a millisecond or two), and the
    rate is the stamps after the opening emission up to the closing one, over
    the time between the two: every round and every pause between the two
    emissions is counted whole, and nothing else is.  The window keeps its
    length to within one gap between emissions at each end.  An edge that no
    stamp precedes stays where it is."""
    xs = sorted(stamps)

    def edge(t: float) -> float:
        i = bisect.bisect_right(xs, t) - 1
        if i < 0:
            return t
        while i + 1 < len(xs) and xs[i + 1] - xs[i] < settle:
            i += 1
        return xs[i]

    lo, hi = edge(t_open), edge(t_close)
    if hi <= lo:
        return 0.0
    return (bisect.bisect_right(xs, hi) - bisect.bisect_right(xs, lo)) / (hi - lo)
