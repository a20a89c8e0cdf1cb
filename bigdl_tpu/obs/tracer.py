"""Span tracing: one trace spine for training steps and serving requests.

The reference's observability is per-phase wall-clock counters summed on
the Spark driver (optim/Metrics.scala); a counter tells you the *mean*
cost of a phase, never which iteration or which request was slow.  This
module is the missing timeline: a thread-safe span API whose events
export as Chrome trace-event JSON (loadable in Perfetto / chrome://
tracing).

Design constraints, in order:

1. near-zero overhead when disabled — every instrumented hot path
   (batcher dispatch, per-chunk uploads, the training loop) calls
   ``span()`` unconditionally, so the disabled path must be one
   attribute check returning a shared no-op context manager;
2. thread-safe and allocation-bounded — events land in a ring buffer
   (``collections.deque`` with ``maxlen``), so a week-long serving
   process can keep tracing without growing; what a full ring pushes
   out is counted (``Tracer.dropped``), so a reader of the whole window
   knows when its start is gone;
3. retroactive spans — the batcher learns a request's queue wait only
   at dispatch time, so ``add_complete`` accepts an explicit start
   timestamp instead of requiring a context manager around the wait.

Toggled by the ``BIGDL_TPU_TRACE`` env var (read at import for the
process-wide tracer; ``enable()``/``disable()`` flip it at runtime).
Timestamps are ``time.perf_counter`` microseconds relative to the
tracer's epoch — monotonic, immune to NTP steps, and exactly what the
Chrome ``ts``/``dur`` fields want.

Request-scoped tracing rides the same buffer: serving entry points mint
an id with :func:`mint_request_id`, stamp it into span ``args``
(``request_id`` for per-request events, ``request_ids`` for batch-level
events that cover several), and :meth:`Tracer.span_tree` /
:meth:`Tracer.export_request` reassemble one request's timeline from
the ring.  ``BIGDL_TPU_TRACE_SAMPLE`` (0..1, default 1) decides — by a
deterministic hash of the id, so every layer agrees without passing a
flag — which requests record their per-round events, keeping tracing
cheap at high QPS.
"""
from __future__ import annotations

import itertools
import json
import os
import threading
import time
import zlib
from collections import deque
from typing import Optional


def _env_enabled() -> bool:
    return os.environ.get("BIGDL_TPU_TRACE", "0").lower() in ("1", "true", "on")


def _env_sample_rate() -> float:
    try:
        rate = float(os.environ.get("BIGDL_TPU_TRACE_SAMPLE", "1"))
    except ValueError:
        return 1.0
    return min(max(rate, 0.0), 1.0)


#: process-wide request-id sequence; ids stay unique across engines and
#: batchers inside one process, and the pid prefix disambiguates merged
#: multi-process traces
_REQ_SEQ = itertools.count(1)


def mint_request_id() -> str:
    """A fresh request id (``r<pid>-<seq>``).  Always cheap, always
    minted — the flight recorder lists active ids even when tracing is
    off; sampling only gates what the *tracer* records for the id."""
    return "r%d-%d" % (os.getpid(), next(_REQ_SEQ))


# -- request context ---------------------------------------------------- #
# The batcher knows which requests are in the batch it is dispatching;
# the layers below it (ReplicaSet failover, engine run_batch) only see a
# padded array.  A thread-local carries the ids across that call so the
# failover hop can stamp them without widening every run_batch signature.
_REQCTX = threading.local()


def set_request_context(request_ids) -> None:
    """Bind the given request ids to the current thread (the dispatch
    thread) until cleared; tuple-copied so callers can reuse the list."""
    _REQCTX.rids = tuple(request_ids)


def get_request_context() -> tuple:
    """Request ids bound to the current thread (empty when none)."""
    return getattr(_REQCTX, "rids", ())


def clear_request_context() -> None:
    _REQCTX.rids = ()


class _NullSpan:
    """Shared no-op context manager for the disabled path."""
    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


_NULL_SPAN = _NullSpan()


class _Span:
    """One live span; records a Chrome 'X' (complete) event on exit."""
    __slots__ = ("_tracer", "name", "cat", "args", "_t0")

    def __init__(self, tracer: "Tracer", name: str, cat: str, args: dict):
        self._tracer = tracer
        self.name = name
        self.cat = cat
        self.args = args
        self._t0 = time.perf_counter()

    def __enter__(self):
        return self

    def __exit__(self, exc_type, exc, tb):
        t1 = time.perf_counter()
        if exc_type is not None:
            self.args = dict(self.args)
            self.args["error"] = f"{exc_type.__name__}: {exc}"
        self._tracer.add_complete(self.name, self._t0, t1 - self._t0,
                                  cat=self.cat, args=self.args)
        return False


class Tracer:
    """Ring-buffered trace-event collector.

    One process normally uses the module-level tracer (``get_tracer()``);
    private instances exist for tests and for tools that want an
    isolated buffer.
    """

    def __init__(self, capacity: int = 65536,
                 enabled: Optional[bool] = None,
                 sample_rate: Optional[float] = None):
        self.enabled = _env_enabled() if enabled is None else bool(enabled)
        self.sample_rate = (_env_sample_rate() if sample_rate is None
                            else min(max(float(sample_rate), 0.0), 1.0))
        self._events: deque = deque(maxlen=int(capacity))
        #: events a full ring pushed out since the last ``clear()``
        self.dropped = 0
        self._lock = threading.Lock()
        # perf_counter epoch; the unix pair stamps exports with wall time
        self._epoch_perf = time.perf_counter()
        self._epoch_unix = time.time()
        self._pid = os.getpid()

    # -- control -------------------------------------------------------- #
    def enable(self) -> None:
        self.enabled = True

    def disable(self) -> None:
        self.enabled = False

    def set_sample_rate(self, rate: float) -> None:
        self.sample_rate = min(max(float(rate), 0.0), 1.0)

    def sampled(self, request_id: Optional[str]) -> bool:
        """Whether per-round events should be recorded for this request.

        Deterministic on the id (crc32 fraction vs ``sample_rate``), so
        admission, prefill, decode and failover all make the same call
        without coordinating — a sampled request traces end to end, an
        unsampled one costs nothing anywhere."""
        if not self.enabled or not request_id:
            return False
        if self.sample_rate >= 1.0:
            return True
        if self.sample_rate <= 0.0:
            return False
        frac = (zlib.crc32(request_id.encode()) & 0xFFFFFFFF) / 2.0 ** 32
        return frac < self.sample_rate

    def clear(self) -> None:
        with self._lock:
            self._events.clear()
            self.dropped = 0

    def __len__(self) -> int:
        with self._lock:
            return len(self._events)

    # -- recording ------------------------------------------------------ #
    def span(self, name: str, cat: str = "obs", **args):
        """Context manager timing a section.  Disabled: a shared no-op."""
        if not self.enabled:
            return _NULL_SPAN
        return _Span(self, name, cat, args)

    def annotation(self, name: str):
        """An entered ``jax.profiler.TraceAnnotation`` (None when
        disabled): the same label on the host plane of a device profile,
        on the profiler's own clock, so a reader of the ``.xplane.pb``
        needs no ``perf_counter`` mapping between a program phase and
        the device ops under it.  The caller ends it with
        ``__exit__(None, None, None)``.  jax is imported here, on first
        use, so that this module stays free of it at import."""
        if not self.enabled:
            return None
        from jax.profiler import TraceAnnotation
        ann = TraceAnnotation(name)
        ann.__enter__()
        return ann

    def _ts_us(self, t_perf: float) -> float:
        return (t_perf - self._epoch_perf) * 1e6

    def add_complete(self, name: str, t0_perf: float, dur_s: float,
                     cat: str = "obs", args: Optional[dict] = None,
                     tid: Optional[int] = None) -> None:
        """Record a finished span retroactively (``t0_perf`` from
        ``time.perf_counter``) — how the batcher reports a request's
        queue wait it only knows at dispatch time."""
        if not self.enabled:
            return
        ev = {"name": name, "cat": cat, "ph": "X",
              "ts": self._ts_us(t0_perf), "dur": max(dur_s, 0.0) * 1e6,
              "pid": self._pid,
              "tid": tid if tid is not None else threading.get_ident()}
        if args:
            ev["args"] = args
        self._push(ev)

    def _push(self, ev: dict) -> None:
        with self._lock:
            if len(self._events) == self._events.maxlen:
                self.dropped += 1
            self._events.append(ev)

    def instant(self, name: str, cat: str = "obs", **args) -> None:
        """Point-in-time event (Chrome ph='i', thread scope)."""
        if not self.enabled:
            return
        ev = {"name": name, "cat": cat, "ph": "i", "s": "t",
              "ts": self._ts_us(time.perf_counter()),
              "pid": self._pid, "tid": threading.get_ident()}
        if args:
            ev["args"] = args
        self._push(ev)

    # -- reading / export ---------------------------------------------- #
    def events(self) -> list:
        """A snapshot of the ring, ordered by start timestamp.

        Events land in the ring at *completion* time, so under
        concurrent writers the raw append order interleaves
        arbitrarily; sorting by ``ts`` (stable, so equal-ts events keep
        completion order) gives every reader — exports, the flight
        recorder, tests — one canonical ordering.  Each event dict is
        copied under the lock, so a reader never sees a span another
        thread is still assembling."""
        with self._lock:
            evs = [dict(e) for e in self._events]
        evs.sort(key=lambda e: e.get("ts", 0.0))
        return evs

    @staticmethod
    def _matches_request(ev: dict, request_id: str) -> bool:
        args = ev.get("args")
        if not isinstance(args, dict):
            return False
        if args.get("request_id") == request_id:
            return True
        rids = args.get("request_ids")
        return isinstance(rids, (list, tuple)) and request_id in rids

    def request_events(self, request_id: str) -> list:
        """Every buffered event stamped with this request id — directly
        (``args.request_id``) or as a member of a batch-level event's
        ``args.request_ids`` list."""
        return [e for e in self.events()
                if self._matches_request(e, request_id)]

    def span_tree(self, request_id: str) -> dict:
        """One request's events assembled into a phase tree.

        Spans nest by interval containment (a span whose ``[ts,
        ts+dur]`` lies inside another's is its child), which
        reconstructs the request's lifecycle — queue wait, prefill
        chunks, per-round decode/verify, failover hops — from the flat
        ring without the recorders ever coordinating.  Instants join as
        zero-duration leaves.  Returns ``{"request_id", "span_count",
        "spans": [...]}`` where each span is ``{"name", "cat", "ph",
        "ts", "dur", "args", "children"}``."""
        nodes = []
        for e in sorted(self.request_events(request_id),
                        key=lambda e: (e.get("ts", 0.0),
                                       -e.get("dur", 0.0))):
            nodes.append({"name": e.get("name"), "cat": e.get("cat"),
                          "ph": e.get("ph"), "ts": e.get("ts", 0.0),
                          "dur": e.get("dur", 0.0),
                          "args": e.get("args", {}), "children": []})
        roots: list = []
        stack: list = []
        for n in nodes:
            end = n["ts"] + n["dur"]
            # a nanosecond of slack: a phase and its envelope can end on
            # ONE clock read, and ts + dur then differ by rounding alone
            while stack and not (n["ts"] >= stack[-1]["ts"]
                                 and end <= stack[-1]["ts"]
                                 + stack[-1]["dur"] + 1e-3):
                stack.pop()
            (stack[-1]["children"] if stack else roots).append(n)
            if n["ph"] == "X":
                stack.append(n)
        return {"request_id": request_id, "span_count": len(nodes),
                "spans": roots}

    def export_request(self, request_id: str,
                       path: Optional[str] = None) -> dict:
        """One request's events as a Chrome trace-event document —
        the same format ``export_chrome`` writes, filtered to the
        request — written atomically to ``path`` when given."""
        events = self.request_events(request_id)
        doc = {
            "traceEvents": self._thread_metadata(events) + events,
            "displayTimeUnit": "ms",
            "otherData": {
                "producer": "bigdl_tpu.obs",
                "epoch_unix": self._epoch_unix,
                "request_id": request_id,
            },
        }
        if path:
            tmp = f"{path}.tmp"
            with open(tmp, "w") as f:
                json.dump(doc, f)
            os.replace(tmp, path)
        return doc

    def _thread_metadata(self, events: list) -> list:
        """Chrome 'M' thread_name rows so Perfetto shows thread names
        instead of bare idents."""
        names = {t.ident: t.name for t in threading.enumerate()
                 if t.ident is not None}
        rows = []
        for tid in sorted({e["tid"] for e in events}):
            rows.append({"name": "thread_name", "ph": "M", "pid": self._pid,
                         "tid": tid,
                         "args": {"name": names.get(tid, f"thread-{tid}")}})
        return rows

    def export_chrome(self, path: Optional[str] = None) -> dict:
        """The buffered events as a Chrome trace-event document
        (``{"traceEvents": [...]}``); written to ``path`` when given.
        Loadable as-is in Perfetto / chrome://tracing."""
        events = self.events()
        doc = {
            "traceEvents": self._thread_metadata(events) + events,
            "displayTimeUnit": "ms",
            "otherData": {
                "producer": "bigdl_tpu.obs",
                "epoch_unix": self._epoch_unix,
                "dropped": self.dropped,
            },
        }
        if path:
            tmp = f"{path}.tmp"
            with open(tmp, "w") as f:
                json.dump(doc, f)
            os.replace(tmp, path)
        return doc


#: process-wide tracer — instrumented modules bind this once at import.
#: Its ring holds a whole traced run of the benchmark's busiest cell
#: (`gpt2xl.backlog`: 100,000 events in 56 s with every request sampled;
#: at 65,536 the window's first seconds were pushed out)
_GLOBAL = Tracer(capacity=131072)


def get_tracer() -> Tracer:
    return _GLOBAL
