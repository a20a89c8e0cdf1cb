"""SLOController: windowed-p99 guardrails that actuate capacity, then
admission.

The controller closes the loop between the obs plane and the serving
plane.  It reads latency from the LIFETIME histograms engines already
publish (``serving/lm/ttft`` etc.) — no second bookkeeping in the hot
path — by snapshotting :meth:`Histogram.counts` every tick and
differencing against a snapshot from ``window_intervals`` ticks ago:
the delta IS the histogram of just that sliding window, and
:func:`~bigdl_tpu.obs.registry.percentile_from_counts` turns it into a
windowed p99.

Policy is the classic two-stage ladder:

1. **Scale** while there is headroom: ``hot_streak`` consecutive ticks
   over target call ``scale_up()`` (more decode slots, more replicas —
   whatever the caller wired in).
2. **Admission control** once scaling is exhausted: step down the
   ``admission_levels`` ladder (smaller enqueue bound), trading typed
   sheds (:class:`ServingOverloaded`, counted in
   ``serving/rejected_total``) for a bounded queue.  Shedding the
   excess keeps p99 for ACCEPTED requests under target past the
   saturation knee; the alternative — an unbounded queue — takes every
   request's latency to infinity together.

``cool_streak`` consecutive ticks under target walk back up: relax
admission first, shrink capacity last.  Streak hysteresis (not a
single-tick threshold) is what keeps a noisy p99 from flapping the
actuators.  A windowed p99 under target is NOT sufficient to relax,
though: under a tight admission bound the accepted requests are fast
*because* the excess is being shed — p99 looks healthy precisely when
admission is doing its job.  So relaxing additionally requires a
shed-free window (``rejections`` wired): rejections in the window mean
offered load still exceeds capacity, and opening the gate would only
convert typed sheds into queue delay for everyone.

Deliberately sans thread in the core: :meth:`tick` is a pure
read-decide-actuate step, so tests drive it with a fake clock and
hand-fed histograms.  :meth:`start`/:meth:`stop` wrap it in a daemon
thread for production use.
"""
from __future__ import annotations

import threading
import time
from collections import deque
from typing import Callable, List, Optional, Sequence

from bigdl_tpu.obs.registry import Histogram, percentile_from_counts


class SLOController:
    """Watch one latency histogram; hold its windowed p99 under target.

    Args:
        histogram: live :class:`~bigdl_tpu.obs.registry.Histogram` (the
            engine's own object, e.g. ``LMMetrics.ttft`` — registered in
            obs as ``serving/lm/ttft``).
        target_p99_s: the SLO.
        interval_s: tick period when threaded (``start``).
        window_intervals: sliding window length, in ticks.
        scale_up / scale_down: capacity actuators; ``scale_up`` returns
            truthy if it actually added capacity (falsy means exhausted
            — the controller moves to admission control).  Optional:
            ``None`` skips straight to admission.  Capacity must be
            REAL: wire device-aware hooks —
            ``ReplicaSet.try_scale_up`` (refuses when the
            :class:`~bigdl_tpu.serving.placement.PlacementPolicy` has
            no free mesh slot) or an LM hook gated on
            ``kvcache_headroom()`` — never a bare ``scale_to(n+1)``,
            which would happily stack replicas onto already-busy
            devices and convert overload into slower everything.
        admission_levels: enqueue bounds, loosest first (e.g.
            ``[64, 32, 16, 8]``).  ``set_admission(level_value)`` is
            called whenever the controller moves along the ladder.
        hot_streak / cool_streak: consecutive over/under-target ticks
            before acting.  Cool is slower than hot on purpose —
            overload hurts more than spare capacity.
        start_level: initial index into ``admission_levels``.  The
            default 0 starts loosest (fail-open); passing
            ``len(levels) - 1`` starts at the tightest bound
            (fail-closed) and lets cool ticks relax it — the right
            posture when the first seconds of a load burst would
            otherwise fill a deep queue and blow the p99 budget before
            the controller's window even sees it.  A non-zero
            ``start_level`` with ``set_admission`` wired applies the
            starting bound immediately so engine state and controller
            state agree.
        ledger: optional
            :class:`~bigdl_tpu.obs.ledger.MemoryLedger` (or anything
            with ``over_watermark() -> bool``).  When wired, a tick
            that would scale up first consults the ledger's byte-level
            headroom: past the ``BIGDL_TPU_MEM_WATERMARK``
            used-fraction watermark the controller REFUSES to add
            capacity (new slots/replicas would only hasten
            RESOURCE_EXHAUSTED) and falls through to admission
            control; a later cool window re-arms scaling as usual.
            This replaces ad-hoc per-subsystem checks inside
            ``scale_up`` hooks with the process-wide attribution
            plane.
        rejections: optional callable returning the CUMULATIVE shed
            count (e.g. the ``serving/rejected_total`` counter's
            value).  When wired, the controller refuses to relax while
            the shed window saw any sheds ("hold_shedding") — see the
            module docstring for why a healthy p99 alone is a trap.
        shed_free_intervals: length of the shed window, in ticks
            (default: ``window_intervals``).  Under on/off bursty
            arrivals this must cover at least a full burst period:
            queues drain between bursts, so a shed window shorter than
            the quiet gap reopens the gate just in time for the next
            burst to fill a deep queue — and a deep queue sheds
            nothing until it is already full of doomed-latency
            requests.
    """

    def __init__(self, *, histogram: Histogram, target_p99_s: float,
                 interval_s: float = 0.25,
                 window_intervals: int = 8,
                 scale_up: Optional[Callable[[], object]] = None,
                 scale_down: Optional[Callable[[], object]] = None,
                 set_admission: Optional[Callable[[int], object]] = None,
                 admission_levels: Sequence[int] = (),
                 hot_streak: int = 2,
                 cool_streak: int = 4,
                 start_level: int = 0,
                 ledger=None,
                 rejections: Optional[Callable[[], float]] = None,
                 shed_free_intervals: Optional[int] = None):
        if target_p99_s <= 0:
            raise ValueError("target_p99_s must be > 0")
        if window_intervals < 1:
            raise ValueError("window_intervals must be >= 1")
        self.histogram = histogram
        self.target_p99_s = float(target_p99_s)
        self.interval_s = float(interval_s)
        self.scale_up = scale_up
        self.scale_down = scale_down
        self.set_admission = set_admission
        self.ledger = ledger
        self.admission_levels = [int(v) for v in admission_levels]
        self.hot_streak = int(hot_streak)
        self.cool_streak = int(cool_streak)

        self.rejections = rejections
        self._snaps: deque = deque(maxlen=window_intervals + 1)
        self._snaps.append(histogram.counts())
        shed_win = (int(shed_free_intervals) if shed_free_intervals
                    else window_intervals)
        self._rej: deque = deque(maxlen=max(1, shed_win) + 1)
        if rejections is not None:
            self._rej.append(float(rejections()))
        self._hot = 0
        self._cool = 0
        # index into admission_levels; 0=loosest
        self._level = (min(max(0, int(start_level)),
                           len(self.admission_levels) - 1)
                       if self.admission_levels else 0)
        if self.set_admission is not None and self._level > 0:
            self.set_admission(self.admission_levels[self._level])
        self._scaling_exhausted = False
        self.actions: List[dict] = []
        self.ticks = 0
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None

    # -- observation ----------------------------------------------------- #
    def window_p99(self) -> Optional[float]:
        """p99 over the current sliding window; None if the window saw
        no observations (idle is not hot)."""
        new, old = self._snaps[-1], self._snaps[0]
        delta = [max(0, a - b) for a, b in zip(new, old)]
        return percentile_from_counts(delta, 99.0)

    # -- decide + actuate ------------------------------------------------ #
    def tick(self) -> dict:
        """One read-decide-actuate step; returns what it saw and did."""
        self._snaps.append(self.histogram.counts())
        if self.rejections is not None:
            self._rej.append(float(self.rejections()))
        self.ticks += 1
        p99 = self.window_p99()
        action = "none"
        if p99 is not None and p99 > self.target_p99_s:
            self._hot += 1
            self._cool = 0
            if self._hot >= self.hot_streak:
                action = self._tighten()
                self._hot = 0
        elif p99 is not None:
            self._cool += 1
            self._hot = 0
            if self._cool >= self.cool_streak:
                action = self._relax()
                self._cool = 0
        out = {"tick": self.ticks, "p99_s": p99, "action": action,
               "admission_level": self._level,
               "scaling_exhausted": self._scaling_exhausted}
        if action != "none":
            self.actions.append(out)
        return out

    def _mem_denied(self) -> bool:
        """True when the memory ledger reads the device past its
        used-fraction watermark — adding capacity under byte pressure
        trades a latency miss for an OOM kill."""
        if self.ledger is None:
            return False
        try:
            return bool(self.ledger.over_watermark())
        except Exception:
            return False

    def _tighten(self) -> str:
        if not self._scaling_exhausted and self.scale_up is not None:
            if self._mem_denied():
                # refuse to add slots below the byte watermark; a cool
                # window's rearm_scaling retries once pressure clears
                self._scaling_exhausted = True
            elif self.scale_up():
                return "scale_up"
            else:
                self._scaling_exhausted = True  # fall through to admission
        if self.set_admission is not None and \
                self._level < len(self.admission_levels) - 1:
            self._level += 1
            self.set_admission(self.admission_levels[self._level])
            return "admission_tighten"
        return "saturated"   # nothing left to pull — sheds do the work

    def _shedding(self) -> bool:
        """True if the current window saw any rejections."""
        return len(self._rej) >= 2 and self._rej[-1] > self._rej[0]

    def _relax(self) -> str:
        if self.rejections is not None and self._shedding():
            # accepted-request p99 is healthy BECAUSE the gate is shut;
            # opening it now would trade typed sheds for queue delay
            return "hold_shedding"
        if self.set_admission is not None and self._level > 0:
            self._level -= 1
            self.set_admission(self.admission_levels[self._level])
            return "admission_relax"
        if self._scaling_exhausted:
            # capacity may have freed up; allow scale_up to retry later
            self._scaling_exhausted = False
            return "rearm_scaling"
        if self.scale_down is not None:
            self.scale_down()
            return "scale_down"
        return "none"

    # -- threading ------------------------------------------------------- #
    def start(self) -> "SLOController":
        if self._thread is not None:
            return self
        self._stop.clear()
        self._thread = threading.Thread(target=self._loop,
                                        name="slo-controller", daemon=True)
        self._thread.start()
        return self

    def _loop(self) -> None:
        while not self._stop.wait(self.interval_s):
            self.tick()

    def stop(self) -> None:
        self._stop.set()
        t, self._thread = self._thread, None
        if t is not None:
            t.join(timeout=5.0)

    def __enter__(self) -> "SLOController":
        return self.start()

    def __exit__(self, *exc) -> None:
        self.stop()

    def summary(self) -> dict:
        return {"ticks": self.ticks,
                "actions": [a["action"] for a in self.actions],
                "admission_level": self._level,
                "admission_value": (self.admission_levels[self._level]
                                    if self.admission_levels else None),
                "scaling_exhausted": self._scaling_exhausted}


def detect_knee(rows: Sequence[dict], *,
                offered_key: str = "offered_rps",
                goodput_key: str = "goodput_rps",
                efficiency: float = 0.85) -> dict:
    """Find the saturation knee in a goodput-vs-offered-load curve.

    Below the knee the server keeps up: goodput tracks offered load
    (within ``efficiency``).  The knee is the LAST load point where
    ``goodput >= efficiency * offered``; everything past it is the
    saturated regime where extra offered load buys sheds, not goodput.
    Returns ``{knee_rps, peak_goodput_rps, saturated}`` —
    ``saturated`` is True only if the sweep actually drove past the
    knee (a curve that never bends just wasn't pushed hard enough).
    """
    pts = sorted(
        ((float(r[offered_key]), float(r[goodput_key])) for r in rows
         if r.get(offered_key) is not None
         and r.get(goodput_key) is not None),
        key=lambda p: p[0])
    if not pts:
        return {"knee_rps": None, "peak_goodput_rps": None,
                "saturated": False}
    knee = None
    for off, good in pts:
        if good >= efficiency * off:
            knee = off
    peak = max(g for _, g in pts)
    return {"knee_rps": knee,
            "peak_goodput_rps": peak,
            "saturated": knee is not None and knee < pts[-1][0]}
