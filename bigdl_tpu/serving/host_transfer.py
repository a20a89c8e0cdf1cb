"""Host->device staging for serving batches.

Thin serving-side veneer over ``utils.transfer.chunked_device_put``:
every batch is staged in <=32 MB slices with one slice in flight at a
time, each under the resilience layer's retry and classification.
The stager also keeps byte/chunk counters so the serving metrics can
report transfer pressure per engine.
"""
from __future__ import annotations

import threading
from typing import Optional

import numpy as np

from bigdl_tpu.utils.transfer import DEFAULT_CHUNK_BYTES, chunked_device_put


class HostStager:
    """Stages host batches onto the device with chunking + counters."""

    def __init__(self, dtype=None, *,
                 chunk_bytes: int = DEFAULT_CHUNK_BYTES, device=None):
        self.dtype = dtype
        self.chunk_bytes = int(chunk_bytes)
        self.device = device
        self._lock = threading.Lock()
        self.bytes_staged = 0
        self.batches_staged = 0

    def stage(self, x_host):
        """Upload one batch; returns the ready device array."""
        import jax.numpy as jnp

        x_host = np.asarray(x_host)
        out = chunked_device_put(x_host, self.dtype,
                                 chunk_bytes=self.chunk_bytes,
                                 device=self.device)
        wire = jnp.dtype(self.dtype) if self.dtype is not None \
            else x_host.dtype
        with self._lock:
            self.bytes_staged += int(x_host.size) * jnp.dtype(wire).itemsize
            self.batches_staged += 1
        return out

    def stats(self) -> dict:
        with self._lock:
            return {"bytes_staged": self.bytes_staged,
                    "batches_staged": self.batches_staged,
                    "chunk_bytes": self.chunk_bytes}
