"""A toy round loop for the estimator's tests: an arrival schedule replayed
through ``slots`` decode slots with no chip and no program.  One admission a
round (its prefill holds the round back and emits the request's first token),
then one decode round in which every seated request emits a token; the token
stamps are what ``serve_lm`` would hand ``stats.emission_rate``."""
import bisect

#: seconds a prefill holds the rounds, by the bucket the prompt falls in
PREFILL_S = ((64, 0.006), (128, 0.008), (256, 0.011), (512, 0.018))


def round_loop(arrivals, round_s, until_s, slots=16, host_s=0.0012,
               trail_s=4e-5):
    """``arrivals``: (due_s, prompt_len, max_new) in due order.  Returns the
    stamps, and the share of slot-rounds that decoded a request."""
    queue, stamps, seated = list(arrivals), [], []
    t, nxt, active_rounds, rounds = queue[0][0], 0, 0, 0
    while t < until_s:
        if nxt < len(queue) and queue[nxt][0] <= t and len(seated) < slots:
            _, prompt_len, max_new = queue[nxt]
            nxt += 1
            t += PREFILL_S[bisect.bisect_left([b for b, _ in PREFILL_S],
                                              prompt_len)][1]
            stamps.append(t)
            if max_new > 1:
                seated.append(max_new - 1)
        if not seated:
            if nxt == len(queue):
                break
            t = max(t, queue[nxt][0])
            continue
        t += round_s + host_s
        rounds += 1
        active_rounds += len(seated)
        stamps += [t + trail_s * k for k in range(len(seated))]
        seated = [left - 1 for left in seated if left > 1]
    return stamps, active_rounds / max(rounds * slots, 1)
