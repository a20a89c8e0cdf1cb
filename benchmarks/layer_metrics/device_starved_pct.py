"""Share of the measured window in which the engine had work and the device,
by the engine's own account, had none: seconds of the program's ``lm/starved``
envelopes (a wait on the newest enqueued output proved the device empty; the
next enqueue ended it) over the part of the window that the profiler does not
touch, which is the part BEFORE the profiled sub-window.  Under the profiler
the worker's Python runs many times slower, so those seconds read the
instrument; and what follows the profile is left out whole, because
``stop_trace`` (called at ``traced_window[1]``) stops every thread for 0.45 s
and then writes the trace out for 7-33 s on its caller's thread, past the
window's close in the busiest cell, while the worker's host phases read a
third longer (``gpt2xl.backlog``: 7.6-8.1% starved after the profile, 5.6-6.1
before it, 5.5 with no tracer at all; my chip run, PR 37)."""
#: left out before ``traced_window[0]``, which is stamped when
#: ``jax.profiler.start_trace`` has returned: the call takes 0.04-0.06 s
#: (0.58 once; my chip run, PR 37)
MARGIN_BEFORE_S = 0.75


def overlap(spans, names, parts) -> float:
    """Seconds of the spans called one of ``names`` inside the intervals
    ``parts`` (disjoint ``(lo, hi)``)."""
    return sum(max(0.0, min(s + d, hi) - max(s, lo))
               for n, s, d in spans if n in names for lo, hi in parts)


def read(rec: dict):
    traced = rec.get("traced_window")
    if not traced or traced[1] is None:
        return None                     # no profiled sub-window: not a --trace 1 run
    rounds = [s for n, s, _ in rec["spans"] if n == "lm/round"]
    if not rounds:
        return None                     # the tracer was off, or is not this program's
    # from the window's start, or the first round a full ring still holds
    lo = max(rec["window"][0], min(rounds))
    hi = min(rec["window"][1], traced[0] - MARGIN_BEFORE_S)
    if hi <= lo:
        return None
    return overlap(rec["spans"], ("lm/starved",), [(lo, hi)]) / (hi - lo) * 100.0
