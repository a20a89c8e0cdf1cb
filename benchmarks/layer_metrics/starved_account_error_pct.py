"""The shared-clock check of the engine's device account: inside the profiled
sub-window the program's ``lm/starved`` and ``lm/idle`` spans (device proven
empty with work to do; no request) against the device trace's own idle time
(``window_s`` - ``busy_s``), as a share of the sub-window.  Both sides sit
under the same profiler there, so the account has to reproduce the device's
idle seconds, the instrument's inflation of the host's phases and all.  What
it cannot see by construction: the tail of a wait (the device done, the host
not yet told), the gap between two modules, device copies outside the stamped
enqueue phases."""


def read(rec: dict):
    traced, trace = rec.get("traced_window"), rec.get("trace")
    if not traced or traced[1] is None or not trace or not trace.get("window_s"):
        return None
    if not any(n == "lm/round" for n, _, _ in rec["spans"]):
        return None
    lo, hi = traced
    account = sum(max(0.0, min(s + d, hi) - max(s, lo))
                  for n, s, d in rec["spans"] if n in ("lm/starved", "lm/idle"))
    return (abs(account - (trace["window_s"] - trace["busy_s"]))
            / trace["window_s"] * 100.0)
