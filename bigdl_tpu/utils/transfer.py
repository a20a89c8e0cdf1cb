"""Chunked host->device staging — the shared transfer discipline.

Serving stages real batches, KV chains and quantized payloads by slicing
the upload along the leading dim into <=32 MB pieces with exactly one
slice in flight at a time, then assembling on device: each slice is a
retry unit and a byte counter, and a fault lands on one slice instead of
the whole payload.  The pattern lives here once.

Resilience (bigdl_tpu.resilience): each slice upload runs under
``with_backoff`` — a transient backend wobble retries with exponential
backoff AND halves the chunk size toward an 8 MB floor (a flaky link
degrades to smaller frames instead of dying), while a lost backend
surfaces as a classified ``BackendLostError`` after bounded attempts
instead of an indefinite hang.

One devicewise concat costs a copy.
"""
from __future__ import annotations

from bigdl_tpu.resilience.faults import fault_point
from bigdl_tpu.resilience.retry import with_backoff

#: Per-transfer ceiling: the retry unit and the granularity of the
#: staged-bytes counters.
DEFAULT_CHUNK_BYTES = 32 << 20

#: Downshift floor: halving below 8 MB buys no more safety and
#: multiplies per-slice dispatch overhead.
MIN_CHUNK_BYTES = 8 << 20


def chunked_device_put(x_host, dtype=None, *,
                       chunk_bytes: int = DEFAULT_CHUNK_BYTES,
                       device=None,
                       max_retries: int = 4,
                       min_chunk_bytes: int = MIN_CHUNK_BYTES):
    """Stage ``x_host`` onto the device in <= ``chunk_bytes`` slices
    along the leading dim, one in flight at a time, and return the
    assembled (blocked-until-ready) device array.

    ``dtype`` is the wire/device dtype (chunk sizing uses it — a f64
    host batch uploaded as bf16 moves a quarter of the bytes).  Arrays
    that fit in one chunk take the single device_put fast path; 0-d
    arrays always do.

    ``device`` may be a ``jax.sharding.Sharding`` (e.g. a placement
    slice's ``NamedSharding``): each chunk then lands pre-sharded —
    dtype conversion happens host-side and ``jax.device_put`` goes
    straight to the sharded layout, never materializing the dense
    array on one device first.  When dim 0 is itself sharded, chunk
    row counts are rounded to a multiple of the dim-0 shard count so
    every slice splits evenly.

    A slice that fails transiently retries up to ``max_retries`` times
    with backoff, halving the working chunk size toward
    ``min_chunk_bytes`` before each retry; exhausted retries and dead
    backends raise :class:`~bigdl_tpu.resilience.errors.BackendLostError`.
    """
    import jax
    import jax.numpy as jnp
    import numpy as np

    from bigdl_tpu.obs.tracer import get_tracer
    _tr = get_tracer()

    x_host = np.asarray(x_host)
    target = jnp.dtype(dtype) if dtype is not None else x_host.dtype
    is_sharding = isinstance(device, jax.sharding.Sharding)

    def _put(a):
        if is_sharding:
            # host-side dtype conversion (ml_dtypes covers bf16), then
            # one device_put directly onto the sharded layout — going
            # through jnp.asarray would stage the dense array on the
            # default device first, the detour this path exists to avoid
            arr = np.asarray(a, target)
            return jax.device_put(arr, device)
        arr = jnp.asarray(a, target)
        if device is not None:
            arr = jax.device_put(arr, device)
        return arr

    if x_host.ndim == 0 or x_host.size == 0:
        def _small():
            fault_point("transfer.chunk", rows=0, bytes=0)
            out = _put(x_host)
            out.block_until_ready()
            return out
        return with_backoff(_small, retries=max_retries, label="h2d put")

    itemsize = jnp.dtype(target).itemsize
    per_row = max(1, int(x_host[0:1].size) * itemsize)
    n = x_host.shape[0]
    # dim-0 shard count: chunks must split evenly across it
    shard0 = 1
    if is_sharding:
        try:
            shard0 = max(1, n // device.shard_shape(x_host.shape)[0])
        except Exception:  # noqa: BLE001 — unsized/indivisible: single put
            shard0 = n if n > 0 else 1
    # mutable so the on_transient hook below downshifts mid-transfer;
    # later slices keep the reduced size (the link stays flaky)
    state = {"chunk": max(int(chunk_bytes), per_row * shard0)}
    floor = max(1, min(int(min_chunk_bytes), state["chunk"]))

    def _downshift(attempt, exc):
        new = max(floor, state["chunk"] // 2)
        if new < state["chunk"]:
            state["chunk"] = new
            from bigdl_tpu.obs import get_registry
            get_registry().counter("resilience/transfer_downshifts").add(1)
            _tr.instant("h2d/downshift", cat="transfer", chunk_bytes=new)

    parts = []
    i = 0
    while i < n:
        def _stage(i=i):
            rows = max(1, state["chunk"] // per_row)
            if shard0 > 1:
                rows = max(shard0, rows - rows % shard0)
            piece = x_host[i:i + rows]
            with _tr.span("h2d/chunk", cat="transfer", offset_rows=i,
                          rows=int(piece.shape[0]),
                          bytes=int(piece.size) * itemsize):
                fault_point("transfer.chunk", offset_rows=i,
                            rows=int(piece.shape[0]),
                            bytes=int(piece.size) * itemsize)
                p = _put(piece)
                # one in-flight slice at a time — device_put is async,
                # so building the list without blocking would enqueue
                # every slice at once, recreating the oversized burst
                p.block_until_ready()
            return p, int(piece.shape[0])
        p, took = with_backoff(_stage, retries=max_retries,
                               on_transient=_downshift, label="h2d chunk")
        parts.append(p)
        i += took
    if len(parts) == 1:
        return parts[0]
    with _tr.span("h2d/assemble", cat="transfer", chunks=len(parts)):
        out = jnp.concatenate(parts, axis=0)
        if is_sharding:
            # re-commit: concatenation of sharded parts lets XLA pick
            # the output layout; the caller was promised ``device``.
            # Device-to-device only — no further host transfer.
            out = jax.device_put(out, device)
        out.block_until_ready()
    del parts  # don't hold a second copy of the batch alive
    return out
