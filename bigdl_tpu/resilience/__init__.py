"""bigdl_tpu.resilience — fault injection, retrying transfers, and
serving failover.

The reference's fault story came free from Spark lineage (a lost task
is recomputed, arXiv 1804.05839); under JAX nothing is free, so this
package supplies the pieces explicitly:

- :mod:`~bigdl_tpu.resilience.errors` — the transient / backend-lost /
  fatal failure classification (``classify_error``);
- :mod:`~bigdl_tpu.resilience.retry` — ``with_backoff``, the bounded
  exponential-backoff policy wired into ``chunked_device_put`` (with
  automatic chunk-size downshift toward an 8 MB floor);
- :mod:`~bigdl_tpu.resilience.faults` — the deterministic
  ``FaultInjector`` behind the ``BIGDL_TPU_FAULTS`` env spec (inert
  unless that variable is explicitly set);
- :mod:`~bigdl_tpu.resilience.replicaset` — ``ReplicaSet``, N serving
  replicas behind one batcher with least-loaded dispatch, circuit
  breakers, and bounded re-dispatch.

Training-side resilience (emergency checkpoint on failure,
``Optimizer.resume_from``) lives on the optimizers themselves —
see ``bigdl_tpu.optim.optimizer``.

``ReplicaSet`` is imported lazily: the error/retry/fault layers must
stay importable from low-level modules (``utils.transfer``,
``utils.engine``) without dragging the serving stack in.
"""
from __future__ import annotations

from bigdl_tpu.resilience.errors import (BackendLostError,
                                         ServingOverloaded,
                                         TransientBackendError,
                                         classify_error)
from bigdl_tpu.resilience.faults import (FaultInjector, fault_point,
                                         refresh_from_env)
from bigdl_tpu.resilience.retry import with_backoff

__all__ = [
    "BackendLostError", "TransientBackendError", "ServingOverloaded",
    "classify_error",
    "FaultInjector", "fault_point", "refresh_from_env",
    "with_backoff", "ReplicaSet",
]


def __getattr__(name):
    if name == "ReplicaSet":
        from bigdl_tpu.resilience.replicaset import ReplicaSet
        return ReplicaSet
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")
