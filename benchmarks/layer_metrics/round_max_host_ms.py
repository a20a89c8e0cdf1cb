"""What the host added to the window's longest round: its time outside the two
phases that wait for the device."""
WAITS = ("lm/decode_wait", "lm/first_token")


def read(rec: dict):
    lo, hi = rec["window"]
    rounds = [(d, s) for n, s, d in rec["spans"]
              if n == "lm/round" and lo <= s < hi]
    if not rounds:
        return None
    dur, start = max(rounds)
    waited = sum(d for n, s, d in rec["spans"]
                 if n in WAITS and start <= s < start + dur)
    return dur - waited
