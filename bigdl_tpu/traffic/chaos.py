"""Chaos replay: turn an incident list into a live fault schedule and
fire it mid-load.

The default incident list is SYNTHETIC and seeded
(:func:`synthetic_incidents`): a mix of probe, transfer-heavy and serving
stage deaths with gaps of several minutes, the shape a wedged or lost
backend produces.  A deployment that keeps a real incident ledger
(:mod:`bigdl_tpu.traffic.incidents`) passes its ``path`` and replays what
it has actually seen instead.

Two halves:

- :func:`build_schedule` — deterministic (seeded) bootstrap resample
  of the inter-incident gaps, compressed onto the requested chaos
  window, each event mapped to an existing ``fault_point`` site by what
  the incident's stage was exercising when it died.
- :class:`ChaosReplayer` — arms an (initially empty) FaultInjector and
  appends each event's parsed spec at its scheduled offset, so faults
  land mid-load exactly like a backend death does: while requests are in
  flight, not between runs.  The safety interlock is preserved —
  arming sets ``BIGDL_TPU_FAULTS`` (to the full schedule, so a ``ps
  e`` or log line shows precisely what chaos is active) and refuses to
  clobber an operator's explicit spec.

The harness contract asserted on top of this (tests/test_traffic.py):
ZERO ACCEPTED-REQUEST LOSS — every request the
server accepted before or during the chaos window completes with exact
results; only typed sheds at admission are allowed to increase.
"""
from __future__ import annotations

import os
import random
import threading
import time
from typing import List, Optional

from bigdl_tpu.resilience.faults import (ENV_SPEC, FaultInjector, active,
                                         install, parse_spec)
from bigdl_tpu.traffic.incidents import inter_incident_gaps, load_incidents

#: fallback inter-incident gap (seconds) when a ledger is empty or has a
#: single row — the middle of the synthetic list's 420-1040 s spread.
DEFAULT_GAP_S = 600.0

#: (stage, rc) mix of the synthetic incident list: hard deaths (rc 124, a
#: ``timeout`` reaping a hung stage) of probes and transfer-heavy stages,
#: and absorbed wobbles (rc 0) of the serving stages
_SYNTHETIC_STAGES = (
    ("probe", 124), ("bench", 124), ("attention", 124), ("pipeline", 124),
    ("lm", 124), ("serve_lm", 0), ("slo", 0), ("prefix", 0), ("mesh", 0),
    ("disconnect", 0),
)


def synthetic_incidents(seed: int = 0, n: int = 48) -> List[dict]:
    """A seeded synthetic incident list in the ledger's schema: ``n``
    rows drawn from ``_SYNTHETIC_STAGES`` with gaps uniform in
    420-1040 s.  Deterministic in (seed, n)."""
    rng = random.Random(int(seed) * 7919 + 17)
    t, rows = 0.0, []
    for _ in range(int(n)):
        t += rng.uniform(420.0, 1040.0)
        stage, rc = rng.choice(_SYNTHETIC_STAGES)
        rows.append({"ts_unix": round(t, 1), "stage": stage, "rc": rc})
    return rows


def _map_incident(incident: dict) -> tuple:
    """(site, kind) an incident replays at.

    The mapping follows what the dying stage was doing: a clean-exit
    row (rc=0, a wobble the tooling absorbed) replays as a transient at
    admission; an LM-serving stage death lands mid-dispatch; every
    other hard death (bench/attention/pipeline/profile, rc=124) died
    moving bytes to the device, so it replays on the transfer
    path.  Probe/init deaths replay at engine bring-up."""
    stage = str(incident.get("stage", "")).lower()
    rc = int(incident.get("rc", 1))
    if "probe" in stage or "init" in stage:
        return "engine.init", "transient"
    if "deadline" in stage or "cancel" in stage or "disconnect" in stage:
        # lifecycle-stage incidents replay as client disconnects: the
        # serving.cancel site turns any injected fault into a
        # cooperative stream.cancel at the next scheduler round
        return "serving.cancel", "transient"
    if rc == 0:
        return "serving.enqueue", "transient"
    if "lm" in stage or "serv" in stage:
        return "serving.dispatch", "transient"
    return "transfer.chunk", "transient"


def build_schedule(duration_s: float, *,
                   incidents: Optional[List[dict]] = None,
                   path: Optional[str] = None,
                   seed: int = 0,
                   min_events: int = 2,
                   max_events: int = 16) -> List[dict]:
    """Seeded chaos schedule for a ``duration_s`` window.

    ``incidents`` wins; else the ledger at ``path``; else the seeded
    synthetic list.  Gaps are bootstrap-resampled from the inter-incident
    distribution and compressed onto the window preserving their
    relative structure (a run of short real gaps stays a burst of
    chaos events); each event inherits (site, kind) from a resampled
    incident via :func:`_map_incident`.  Deterministic in
    (incident log, duration, seed)."""
    if duration_s <= 0:
        raise ValueError("duration_s must be > 0")
    if incidents is None:
        incidents = (load_incidents(path) if path is not None
                     else synthetic_incidents(seed))
    gaps = inter_incident_gaps(incidents) or [DEFAULT_GAP_S]
    rng = random.Random(int(seed))
    mean_gap = sum(gaps) / len(gaps)
    n = int(round(duration_s / mean_gap)) if mean_gap > 0 else 0
    n = max(min_events, min(max_events, n if n > 0 else min_events))
    drawn_gaps = [rng.choice(gaps) for _ in range(n)]
    drawn_rows = ([rng.choice(incidents) for _ in range(n)]
                  if incidents else [{"stage": "bench", "rc": 124}] * n)
    # compress: n gaps + a tail gap span the window, so every event
    # lands strictly inside it
    total = sum(drawn_gaps) + rng.choice(gaps)
    events, at = [], 0.0
    for gap, row in zip(drawn_gaps, drawn_rows):
        at += gap * duration_s / total
        site, kind = _map_incident(row)
        events.append({
            "at_s": round(at, 4),
            "site": site,
            "kind": kind,
            "spec": f"{site}:{kind}:count=1",
            "source_stage": row.get("stage"),
            "source_rc": row.get("rc"),
        })
    return events


class ChaosReplayer:
    """Fire a :func:`build_schedule` schedule against the live process.

    ``start()`` arms an empty injector (honouring the ``BIGDL_TPU_FAULTS``
    interlock) and a daemon thread appends each event's spec at its
    scheduled offset; ``stop()`` disarms and restores the env.  Specs
    land with ``count=1``, so each event injects exactly one fault at
    the next matching hook-point crossing — a dead window (no traffic
    at that site) leaves the spec armed, just like a real backend death
    waits for the next transfer to surface.
    """

    def __init__(self, schedule: List[dict], *, seed: int = 0):
        self.schedule = sorted(schedule, key=lambda e: e["at_s"])
        self.seed = int(seed)
        self.injector: Optional[FaultInjector] = None
        self.armed_events: List[dict] = []
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None
        self._env_was_set = False

    def start(self) -> "ChaosReplayer":
        if self._thread is not None:
            return self
        if os.environ.get(ENV_SPEC):
            raise RuntimeError(
                f"{ENV_SPEC} is already set — refusing to replace an "
                "explicit fault spec with a chaos schedule")
        if active() is not None:
            raise RuntimeError("a FaultInjector is already installed")
        # the env var shows the FULL schedule: chaos is visible, and the
        # install() interlock stays honest
        os.environ[ENV_SPEC] = ";".join(e["spec"] for e in self.schedule) \
            or "serving.enqueue:transient:count=0"
        self.injector = FaultInjector([], seed=self.seed)
        install(self.injector)
        self._stop.clear()
        self._thread = threading.Thread(target=self._loop,
                                        name="chaos-replayer", daemon=True)
        self._thread.start()
        return self

    def _loop(self) -> None:
        t0 = time.monotonic()
        for ev in self.schedule:
            lag = ev["at_s"] - (time.monotonic() - t0)
            if lag > 0 and self._stop.wait(lag):
                return
            if self._stop.is_set():
                return
            # appending to the live spec list is how events "happen":
            # the next matching fault_point crossing fires them
            self.injector.specs.extend(parse_spec(ev["spec"]))
            self.armed_events.append(
                dict(ev, armed_at_s=round(time.monotonic() - t0, 4)))

    def stop(self) -> "ChaosReplayer":
        self._stop.set()
        t, self._thread = self._thread, None
        if t is not None:
            t.join(timeout=5.0)
        if self.injector is not None and active() is self.injector:
            install(None)
        os.environ.pop(ENV_SPEC, None)
        return self

    def __enter__(self) -> "ChaosReplayer":
        return self.start()

    def __exit__(self, *exc) -> None:
        self.stop()

    def summary(self) -> dict:
        inj = self.injector
        return {
            "scheduled": len(self.schedule),
            "armed": len(self.armed_events),
            "fired": (sum(v["fired"] for v in inj.stats().values())
                      if inj else 0),
            "events": [{k: e.get(k) for k in
                        ("at_s", "site", "kind", "source_stage")}
                       for e in self.schedule],
        }
