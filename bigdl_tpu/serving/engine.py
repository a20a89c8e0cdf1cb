"""ServingEngine: a built ``nn.Module`` as a servable endpoint.

The inference analog of the training-side DistriOptimizer: a frozen
params/buffers pytree shared by every request (BigDL's serving model,
arXiv 1804.05839 — batched forward passes over a shared immutable
model), with

- ``apply`` always under ``jit`` with ``training=False`` (no buffer
  writes, no dropout), ahead-of-time compiled per shape bucket through
  the explicit :class:`~bigdl_tpu.serving.compile_cache.CompileCache`;
- a :class:`~bigdl_tpu.serving.batcher.DynamicBatcher` gathering
  requests into bucket-padded batches (sync ``predict`` rides the same
  queue as async ``submit`` — one dispatch path, one ordering);
- chunked host->device staging (``host_transfer.HostStager``) so a big
  batch is staged in bounded slices with byte counters;
- ``metrics.ServingMetrics`` splitting latency into queue wait vs
  device time, exportable through the visualization tfevents writers.

The served model's output may be a single array or any pytree of
arrays (multi-headed models, Tables); every leaf must carry the batch
dim first — the batcher slices requests back out leaf-wise.
"""
from __future__ import annotations

from concurrent.futures import Future
from typing import Optional, Sequence

import numpy as np

from bigdl_tpu.obs import (env_watchdog_enabled, env_watchdog_kwargs,
                           get_registry, get_tracer, shared_watchdog)
from bigdl_tpu.serving.batcher import DynamicBatcher, power_of_two_buckets
from bigdl_tpu.serving.compile_cache import CompileCache
from bigdl_tpu.serving.host_transfer import HostStager
from bigdl_tpu.serving.metrics import ServingMetrics
from bigdl_tpu.utils.engine import Engine, configure_compile_cache
from bigdl_tpu.utils.transfer import DEFAULT_CHUNK_BYTES

_tracer = get_tracer()


class ServingEngine:
    """Serve a built module.

    Args:
        module: a built ``nn.Module`` (``build()`` already called —
            the engine freezes the params/buffers it finds).
        input_shape: per-example input shape (no batch dim); needed by
            ``warmup`` before the first request arrives, else inferred
            from traffic.
        buckets: batch-dim shape buckets; default powers of two up to
            ``max_batch_size``.
        max_batch_size: device batch ceiling; default ``max(buckets)``
            or 32.
        max_wait_ms: how long a partial batch waits for company.
        max_queue: bounded queue depth (backpressure beyond it).
        dtype: wire/device input dtype (default float32).
        donate_x: donate the input buffer to the compiled executable.
        use_shared_pool: run the batching worker on the shared Engine
            host pool instead of a private thread.
        name: label for traces, metrics, and fault-injection filters
            (``resilience.ReplicaSet`` names its members r0..rN-1).
        with_batcher: when False the engine is built WITHOUT its own
            DynamicBatcher — submit/predict are disabled and batches
            arrive through ``_run_batch`` from an external dispatcher
            (the ReplicaSet mode: one queue fronting N engines).
        placement: optional
            :class:`~bigdl_tpu.serving.placement.MeshSlice` — the
            engine's device slot.  Params land sharded across the
            slot's devices (tensor-parallel over its ``model`` axis),
            staged inputs land replicated on the slot, and compiled
            entries are keyed by the slot tag.  None keeps the classic
            single-device behavior bit-for-bit.
        tp_rules: optional ``rules(path, leaf) -> NamedSharding|None``
            overriding the derived
            :func:`~bigdl_tpu.serving.placement.serving_tp_rules` for
            custom module trees.
    """

    def __init__(self, module, *,
                 input_shape: Optional[tuple] = None,
                 buckets: Optional[Sequence[int]] = None,
                 max_batch_size: Optional[int] = None,
                 max_wait_ms: float = 5.0,
                 max_queue: int = 256,
                 dtype="float32",
                 donate_x: bool = False,
                 max_cache_entries: int = 16,
                 chunk_bytes: int = DEFAULT_CHUNK_BYTES,
                 use_shared_pool: bool = True,
                 name: str = "engine",
                 with_batcher: bool = True,
                 placement=None,
                 tp_rules=None):
        configure_compile_cache()
        import jax
        import jax.numpy as jnp

        module._built()
        self.module = module
        self.name = name
        self.placement = placement
        # freeze: the engine holds its own references; later training
        # steps rebind module.params and never touch these
        self._params = module.params
        self._buffers = module.buffers
        self._dtype = jnp.dtype(dtype)
        self.input_shape = tuple(input_shape) if input_shape else None

        # quantized replica (module.quantize()): re-stage the int8
        # payload through the shared 32 MB chunked-transfer discipline
        # (~4x fewer bytes on the wire than f32) and
        # publish the wire win as quant/* gauges
        from bigdl_tpu.quant import (params_dtype_tag, params_nbytes,
                                     stage_quantized_params)
        self.quant_dtype = params_dtype_tag(self._params)
        self._quant_bytes_staged = 0
        if placement is not None:
            # one chunked pass straight to the sharded layout — staging
            # dense-on-one-device first and resharding would push the
            # payload host->device twice
            from bigdl_tpu.serving.placement import (serving_tp_rules,
                                                     shard_params_chunked)
            if tp_rules is None and placement.tp > 1:
                tp_rules = serving_tp_rules(module, placement.mesh)
            rules = tp_rules if tp_rules is not None else (lambda p, l: None)
            self._params = shard_params_chunked(
                self._params, rules, placement.mesh, chunk_bytes=chunk_bytes)
            rep = placement.replicated()
            self._buffers = jax.tree_util.tree_map(
                lambda b: jax.device_put(b, rep), self._buffers)
            if self.quant_dtype == "int8":
                self._quant_bytes_staged = params_nbytes(self._params)
                get_registry().gauge("quant/serving_bytes_staged", unit="B") \
                    .set(self._quant_bytes_staged)
        elif self.quant_dtype == "int8":
            self._params, self._quant_bytes_staged = stage_quantized_params(
                self._params, chunk_bytes=chunk_bytes)
            get_registry().gauge("quant/serving_bytes_staged", unit="B") \
                .set(self._quant_bytes_staged)

        if max_batch_size is None:
            max_batch_size = max(buckets) if buckets else 32
        if buckets is None:
            buckets = power_of_two_buckets(max_batch_size)
        if max(buckets) < max_batch_size:
            raise ValueError(
                f"largest bucket {max(buckets)} < max_batch_size "
                f"{max_batch_size}: every dispatch must fit a bucket")
        # kept on the engine (not just the batcher): batcher-less
        # replica members still need them for warmup, and an external
        # dispatcher (ReplicaSet) reads them to configure its own queue
        self.max_batch_size = int(max_batch_size)
        self.buckets = tuple(sorted(set(int(b) for b in buckets)))

        _rng = jax.random.PRNGKey(0)  # inert: training=False paths
        _module = module

        _out_sharding = (placement.replicated()
                         if placement is not None and placement.tp > 1
                         else None)

        def _infer(params, buffers, x):
            # inside the trace: expand non-native QTensors (identity
            # for f32 replicas); native ones dequant in their kernels
            from bigdl_tpu.quant import dequantize_entry
            y, _ = _module.apply(dequantize_entry(params), x,
                                 buffers=buffers,
                                 training=False, rng=_rng)
            if _out_sharding is not None:
                # a col-parallel tail would leave the output sharded on
                # its last dim; pin it replicated so the host pull is
                # one clean gather instead of per-shard fetches
                y = jax.lax.with_sharding_constraint(y, _out_sharding)
            return y

        self.cache = CompileCache(
            _infer, max_entries=max_cache_entries, donate_x=donate_x,
            placement_tag=placement.tag if placement is not None else "",
            name=f"serve/{name}/infer")
        self.stager = HostStager(
            self._dtype, chunk_bytes=chunk_bytes,
            device=placement.input_sharding() if placement is not None
            else None)
        # live metrics, published into the process-wide obs registry
        # (latest engine owns the serving/* names)
        self.metrics = ServingMetrics().publish_to(get_registry())
        # memory-ledger attribution: staged params per placement slot
        # plus the stager's cumulative transfer traffic
        self._ledger_keys = []
        try:
            import weakref as _weakref

            from bigdl_tpu.obs.ledger import get_ledger
            from bigdl_tpu.quant import params_nbytes as _pnb
            led = get_ledger()
            _dev = placement.tag if placement is not None else None
            self._ledger_keys.append(led.register(
                "params", f"{name}/staged", _pnb(self._params),
                device=_dev, note=f"quant={self.quant_dtype}"))
            _stager_ref = _weakref.ref(self.stager)

            def _staged_bytes():
                st = _stager_ref()
                return st.bytes_staged if st is not None else None

            self._ledger_keys.append(led.register(
                "host_stager", f"{name}/bytes_staged", _staged_bytes,
                device=_dev, note="cumulative h2d traffic"))
        except Exception:
            pass
        # dispatch-cadence stall detection: a device call that hangs
        # (a wedged backend) fires diagnose_tpu + stack dumps
        # into the trace instead of silently stalling every client
        self.watchdog = (shared_watchdog("serve_dispatch")
                         .reset(**env_watchdog_kwargs())
                         if env_watchdog_enabled() else None)
        self.batcher = None
        if with_batcher:
            self.batcher = DynamicBatcher(
                self._run_batch,
                max_batch_size=max_batch_size,
                max_wait_ms=max_wait_ms,
                max_queue=max_queue,
                buckets=buckets,
                metrics=self.metrics,
                pool=Engine.default_or_create() if use_shared_pool else None)
        self._closed = False

    # ------------------------------------------------------------------ #
    def _run_batch(self, x_padded: np.ndarray):
        """Batcher callback: stage, run the bucket executable, sync."""
        if self.watchdog is not None:
            self.watchdog.step_started()
        try:
            # resilience hook: replica death / latency spikes inject
            # here (filtered by this engine's name), before any device
            # work — exactly where a dead backend would first surface
            from bigdl_tpu.resilience.faults import fault_point
            fault_point("serving.dispatch", name=self.name,
                        rows=int(x_padded.shape[0]))
            misses0 = (self.cache.stats()["misses"] if _tracer.enabled
                       else 0)
            with _tracer.span("serve/h2d", cat="serve",
                              rows=int(x_padded.shape[0])):
                xd = self.stager.stage(x_padded)
            y = self.cache(self._params, self._buffers, xd)
            if _tracer.enabled:
                miss = self.cache.stats()["misses"] > misses0
                _tracer.instant(
                    "serve/cache_miss" if miss else "serve/cache_hit",
                    cat="serve", bucket=int(x_padded.shape[0]))
            # single array or pytree of arrays — every leaf must carry
            # the batch dim first or the batcher's slice-back would
            # silently hand requests the wrong rows
            import jax
            rows = int(x_padded.shape[0])
            leaves = jax.tree_util.tree_leaves(y)
            if not leaves:
                raise TypeError("model output has no array leaves")
            for leaf in leaves:
                if not hasattr(leaf, "shape") or leaf.ndim < 1 \
                        or int(leaf.shape[0]) != rows:
                    raise TypeError(
                        f"every output leaf needs a leading batch dim of "
                        f"{rows}; got {getattr(leaf, 'shape', type(leaf))}")
            # host pull doubles as the device sync
            return jax.tree_util.tree_map(np.asarray, y)
        finally:
            if self.watchdog is not None:
                self.watchdog.step_finished()

    def _coerce(self, x, batched: bool) -> np.ndarray:
        x = np.asarray(x, self._dtype)
        if not batched:
            x = x[None]
        if self.input_shape is None and x.ndim >= 1:
            self.input_shape = tuple(x.shape[1:])
        return x

    # ------------------------------------------------------------------ #
    def warmup(self, input_shape: Optional[tuple] = None) -> int:
        """Pre-compile one executable per configured bucket so the
        first real request pays no XLA compile; returns how many were
        compiled.  After a full warmup a bucketed workload's cache
        hit rate is 1.0."""
        shape = tuple(input_shape) if input_shape else self.input_shape
        if shape is None:
            raise ValueError("warmup needs input_shape (none configured "
                             "and no request seen yet)")
        self.input_shape = shape
        shapes = [(b,) + shape for b in self.buckets]
        if self.placement is not None:
            # AOT executables bake in committed-input shardings: warmup
            # inputs must arrive exactly like traffic does — through the
            # stager onto the slot — or the compiled entries would
            # expect default-device inputs and recompile on first hit
            inputs = [self.stager.stage(np.zeros(s, self._dtype))
                      for s in shapes]
            return self.cache.warmup_inputs(self._params, self._buffers,
                                            inputs)
        return self.cache.warmup(self._params, self._buffers, shapes,
                                 self._dtype)

    def submit(self, x, *, batched: bool = True) -> Future:
        """Async: enqueue a request (a batch by default), get a Future
        of the output batch.  Raises ServingQueueFull on backpressure."""
        if self._closed:
            from bigdl_tpu.serving.batcher import ServingClosed
            raise ServingClosed("engine is closed")
        if self.batcher is None:
            raise RuntimeError(
                "this engine has no batcher (with_batcher=False): it is "
                "a ReplicaSet member — submit through the ReplicaSet")
        return self.batcher.submit(self._coerce(x, batched))

    def predict(self, x, *, timeout: Optional[float] = None) -> np.ndarray:
        """Sync: serve one batch through the same queue as submit()."""
        return self.submit(x).result(timeout=timeout)

    def predict_one(self, x, *,
                    timeout: Optional[float] = None) -> np.ndarray:
        """Sync single example: adds and strips the batch dim."""
        fut = self.submit(self._coerce(x, batched=False), batched=True)
        y = fut.result(timeout=timeout)
        if hasattr(y, "shape"):
            return y[0]
        import jax
        return jax.tree_util.tree_map(lambda a: a[0], y)

    # ------------------------------------------------------------------ #
    def stats(self) -> dict:
        out = {
            "name": self.name,
            "pending": self.batcher.pending() if self.batcher else 0,
            "buckets": list(self.buckets),
            "quant_dtype": self.quant_dtype,
            "quant_bytes_staged": self._quant_bytes_staged,
            "placement": (self.placement.describe()
                          if self.placement is not None else None),
            "compile_cache": self.cache.stats(),
            "host_transfer": self.stager.stats(),
            "metrics": self.metrics.snapshot(self.cache.stats()),
        }
        if self.watchdog is not None:
            out["watchdog"] = {"stalls": self.watchdog.stall_count,
                               "median_dispatch_s": self.watchdog.median()}
        return out

    def export_metrics(self, summary, step: int) -> None:
        """Write the current snapshot through a visualization Summary."""
        self.metrics.export_to_summary(summary, step, self.cache.stats())

    def close(self, timeout: Optional[float] = 30.0) -> None:
        self._closed = True
        if self.batcher is not None:
            self.batcher.close(timeout=timeout)
        try:
            from bigdl_tpu.obs.ledger import get_ledger
            led = get_ledger()
            for sub, nm in getattr(self, "_ledger_keys", []):
                led.release(sub, nm)
        except Exception:
            pass

    def __enter__(self) -> "ServingEngine":
        return self

    def __exit__(self, *exc) -> None:
        self.close()
