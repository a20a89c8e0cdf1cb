"""bigdl_tpu.traffic — production traffic harness.

Three pieces that close the serving loop the way production does:

- :mod:`~bigdl_tpu.traffic.loadgen` — open-loop, deterministic,
  seeded arrival traces (bursty Poisson, diurnal ramp, mixed
  prompt/output lengths) replayed against a serving engine; arrivals
  never wait on completions, so the saturation knee is observable.
- :mod:`~bigdl_tpu.traffic.slo` — SLOController: windowed p99 read
  out of the obs histograms, scale-then-shed actuation ladder, plus
  :func:`~bigdl_tpu.traffic.slo.detect_knee` for goodput curves.
- :mod:`~bigdl_tpu.traffic.chaos` — replay of an incident list (a
  seeded synthetic one, or a recorded ledger) as a seeded fault schedule
  through the existing ``fault_point`` sites, mid-load.
"""
from bigdl_tpu.traffic.chaos import ChaosReplayer, build_schedule
from bigdl_tpu.traffic.incidents import (append_incident,
                                         inter_incident_gaps,
                                         load_incidents)
from bigdl_tpu.traffic.loadgen import (Arrival, LoadReport,
                                       TraceLoadGenerator)
from bigdl_tpu.traffic.slo import SLOController, detect_knee

__all__ = [
    "Arrival", "LoadReport", "TraceLoadGenerator",
    "SLOController", "detect_knee",
    "ChaosReplayer", "build_schedule",
    "load_incidents", "append_incident", "inter_incident_gaps",
]
