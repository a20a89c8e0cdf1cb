"""The host's part of a plain decode round, from the program's round spans."""
import bisect

from benchmarks.harness import stats


def read(rec: dict):
    lo, hi = rec["window"]
    rounds = [(s, s + d) for n, s, d in rec["spans"]
              if n == "lm/round" and lo <= s < hi]
    admits = sorted(s for n, s, _ in rec["spans"] if n == "lm/admit")
    waits = sorted((s, d) for n, s, d in rec["spans"] if n == "lm/decode_wait")
    starts = [s for s, _ in waits]
    host = []
    for a, b in rounds:
        i = bisect.bisect_left(admits, a)
        if i < len(admits) and admits[i] < b:
            continue                    # an admission interrupted it
        inside = waits[bisect.bisect_left(starts, a):bisect.bisect_left(starts, b)]
        if inside:                      # it decoded
            host.append((b - a) - sum(d for _, d in inside))
    return stats.median(host) if host else None
