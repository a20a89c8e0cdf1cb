"""From a profiler trace (``*.xplane.pb``) to numbers: the device's busy
union and idle share, time per XLA module, the operations that took most
time, and the idle gaps labelled by what the host was doing.

Anatomy of a TPU trace as jax 0.9 writes it (looked at by hand, PR 23): one
plane per chip named ``/device:TPU:<n>`` with the lines ``XLA Modules`` (one
event per executed program, named ``jit_<fn>(<hash>)``) and ``XLA Ops`` (one
event per HLO operation, named by its HLO text); the host is ``/host:CPU``,
whose ``python3`` line carries ``jax.profiler.TraceAnnotation`` spans.  Event
times are nanoseconds from the start of the profile.  The device's clock
runs about a millisecond off the host's, so a gap's label is right for gaps
of several milliseconds and a guess below that.
"""
import glob
import os
import re
from typing import Iterable, List, Optional, Tuple

SYNC = "bench/clock_sync"


def merge(intervals: Iterable[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[Tuple[float, float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1] = (out[-1][0], b)
        else:
            out.append((a, b))
    return out


def _clip(a: float, b: float, lo: float, hi: float):
    a, b = max(a, lo), min(b, hi)
    return (a, b) if b > a else None


def module_name(event_name: str) -> str:
    """``jit__decode_fn(123456)`` -> ``jit__decode_fn``."""
    return re.sub(r"\(\d+\)$", "", event_name)


def op_name(event_name: str) -> str:
    """``%fusion.3 = bf16[...] fusion(...)`` -> ``fusion.3``."""
    return event_name.split(" = ", 1)[0].lstrip("%")[:64]


def find_xplane(trace_dir: str) -> str:
    paths = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    if not paths:
        raise FileNotFoundError(f"no xplane.pb under {trace_dir}")
    return max(paths, key=os.path.getmtime)


def reduce_trace(path: str, window: Optional[Tuple[float, float]] = None,
                 host_spans: Optional[list] = None,
                 sync_perf: Optional[float] = None) -> dict:
    """``window`` clips everything to a window (default: first to last device
    event); ``host_spans`` are ``(name, start, end)`` and label the idle gaps.
    Both are nanoseconds on the trace's own clock -- or, with ``sync_perf``,
    seconds on the ``perf_counter`` clock, ``sync_perf`` being its reading
    when the ``bench/clock_sync`` annotation was entered."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    chips, sync_ns = [], None
    for plane in data.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name == SYNC:
                        sync_ns = ev.start_ns
        if not plane.name.startswith("/device:TPU:"):
            continue
        modules, ops = [], []
        for line in plane.lines:
            if line.name == "XLA Modules":
                modules = [(ev.name, ev.start_ns, ev.start_ns + ev.duration_ns)
                           for ev in line.events]
            elif line.name == "XLA Ops":
                ops = [(ev.name, ev.start_ns, ev.start_ns + ev.duration_ns)
                       for ev in line.events]
        if modules or ops:
            chips.append((plane.name, modules, ops))
    if not chips:
        raise ValueError(f"{path}: no device plane with XLA events")
    if sync_perf is not None:
        if sync_ns is None:
            raise ValueError(f"{path}: no {SYNC} marker on the host plane")
        to_ns = lambda t: (t - sync_perf) * 1e9 + sync_ns      # noqa: E731
        window = window and tuple(to_ns(t) for t in window)
        host_spans = [(n, to_ns(a), to_ns(b)) for n, a, b in host_spans or []]
    every = [e for _, m, o in chips for e in (o or m)]
    lo, hi = window or (min(e[1] for e in every), max(e[2] for e in every))
    busy, per_module, per_op, gaps = [], {}, {}, {}
    for _, modules, ops in chips:
        clipped = [c for c in (_clip(a, b, lo, hi) for _, a, b in (ops or modules))
                   if c]
        merged = merge(clipped)
        busy.append(sum(b - a for a, b in merged))
        for name, a, b in modules:
            c = _clip(a, b, lo, hi)
            if c:
                m = per_module.setdefault(module_name(name), [0, 0.0])
                m[0] += 1
                m[1] += (c[1] - c[0]) * 1e-9
        for name, a, b in ops:
            c = _clip(a, b, lo, hi)
            if c:
                per_op[op_name(name)] = (per_op.get(op_name(name), 0.0)
                                         + (c[1] - c[0]) * 1e-9)
        edges = [lo] + [x for iv in merged for x in iv] + [hi]
        for a, b in zip(edges[0::2], edges[1::2]):
            if b > a:
                label = _covering((a + b) / 2, host_spans or [])
                gaps[label] = gaps.get(label, 0.0) + (b - a) * 1e-9
    n = len(chips)
    top = lambda d: sorted(([k, v / n] for k, v in d.items()),  # noqa: E731
                           key=lambda kv: -kv[1])[:10]
    return {
        "chips": n, "window_s": (hi - lo) * 1e-9,
        "busy_s": sum(busy) / n * 1e-9,
        "modules": {k: {"calls": v[0] / n, "device_s": v[1] / n}
                    for k, v in per_module.items()},
        "device_ops": top(per_op), "idle_gaps": top(gaps),
    }


def _covering(t_ns: float, spans: list) -> str:
    """The shortest host span that covers ``t_ns`` (the innermost one)."""
    best, best_len = "no host span", None
    for name, a, b in spans:
        if a <= t_ns < b and (best_len is None or b - a < best_len):
            best, best_len = name, b - a
    return best
