"""bigdl_tpu.obs — unified observability: tracing, telemetry, forensics.

Six pieces, one spine:

- :mod:`~bigdl_tpu.obs.tracer` — thread-safe span API (context manager
  + decorator) over a ring buffer, exported as Chrome trace-event JSON
  (Perfetto-loadable); there is no other exporter.  Request-scoped:
  every serving submission is minted a ``request_id``
  (:func:`mint_request_id`), propagated through batch assembly,
  prefill, decode/verify rounds, and failover re-dispatch, and
  assembled back into a per-request span tree
  (:meth:`Tracer.span_tree` / :meth:`Tracer.export_request`).
  Enabled via ``BIGDL_TPU_TRACE=1``; sampled per request via
  ``BIGDL_TPU_TRACE_SAMPLE``; near-zero overhead when off.
- :mod:`~bigdl_tpu.obs.registry` — process-wide MetricRegistry of
  counters/gauges/histograms (cardinality-capped;
  ``BIGDL_TPU_REGISTRY_MAX``); ``optim.Metrics`` and
  ``serving.ServingMetrics`` publish into it, and one
  ``export_to_summary`` path writes everything through the
  ``visualization`` tfevents writers.
- :mod:`~bigdl_tpu.obs.timeseries` — TimeSeriesSampler: a background
  thread snapshotting the registry at a fixed interval into bounded
  rings — gauge values, counter deltas, windowed histogram p50/p99 —
  the time axis the SLO controller and post-mortems read.
- :mod:`~bigdl_tpu.obs.flight` — FlightRecorder: on a watchdog stall,
  a classified backend-lost, a fault-injector fire, or a shed burst,
  atomically dump ONE correlated bundle (last spans + time-series
  window + ``Engine.diagnose_tpu()`` + serving state + active request
  ids) to ``FLIGHT_<ts>.json`` and append a pointer into the incident
  ledger.  Armed via ``BIGDL_TPU_FLIGHT=1``.
- :mod:`~bigdl_tpu.obs.watchdog` — StallWatchdog: rolling-median step
  cadence; a hung step captures ``Engine.diagnose_tpu()`` + all-thread
  stacks into the trace before the process looks merely "slow".
- :mod:`~bigdl_tpu.obs.ledger` — MemoryLedger: process-wide HBM byte
  attribution (params / KV arenas / drafter / kvtier / executables),
  per-executable roofline costs captured at AOT-lower time,
  ``headroom(device)`` + reconciliation drift vs
  ``device.memory_stats()``, and a ``mem_pressure`` flight trigger at
  the ``BIGDL_TPU_MEM_WATERMARK`` used-fraction watermark.

Quickstart::

    import os; os.environ["BIGDL_TPU_TRACE"] = "1"   # before import
    from bigdl_tpu import obs

    tr = obs.get_tracer()
    with tr.span("my_phase", cat="app", rows=1024):
        ...
    tr.export_chrome("TRACE_app.json")               # open in Perfetto

    reg = obs.get_registry()
    reg.counter("app/requests").add(1)
    print(reg.snapshot())
"""
from bigdl_tpu.obs.ledger import MemoryLedger, get_ledger, set_ledger
from bigdl_tpu.obs.registry import (Counter, FnGauge, Gauge, Histogram,
                                    MetricRegistry, get_registry,
                                    percentile_from_counts)
from bigdl_tpu.obs.timeseries import (TimeSeriesSampler, get_sampler,
                                      set_sampler)
from bigdl_tpu.obs.tracer import (Tracer, get_tracer, mint_request_id,
                                  set_request_context,
                                  get_request_context,
                                  clear_request_context)
from bigdl_tpu.obs.watchdog import (StallWatchdog, env_watchdog_enabled,
                                    env_watchdog_kwargs, shared_watchdog,
                                    thread_stacks)

# Flight names resolve lazily (PEP 562): an eager `from ...flight
# import` here would put bigdl_tpu.obs.flight in sys.modules before
# runpy executes it, so every `python -m bigdl_tpu.obs.flight dump`
# (an external incident recorder) logged a RuntimeWarning about
# the double import.  Everything else in the tree already imports
# flight lazily; the package facade now does too.
_FLIGHT_NAMES = ("FlightRecorder", "get_flight_recorder", "note_shed")


def __getattr__(name):
    if name in _FLIGHT_NAMES:
        from bigdl_tpu.obs import flight
        return getattr(flight, name)
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")


__all__ = [
    "Tracer", "get_tracer", "mint_request_id",
    "set_request_context", "get_request_context", "clear_request_context",
    "Counter", "Gauge", "FnGauge", "Histogram", "MetricRegistry",
    "get_registry", "percentile_from_counts",
    "TimeSeriesSampler", "get_sampler", "set_sampler",
    "FlightRecorder", "get_flight_recorder", "note_shed",
    "MemoryLedger", "get_ledger", "set_ledger",
    "StallWatchdog", "env_watchdog_enabled", "env_watchdog_kwargs",
    "shared_watchdog", "thread_stacks",
]
