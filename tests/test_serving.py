"""bigdl_tpu.serving: dynamic batcher, compile cache, engine, transfer.

Fast tests run in tier-1 (the smoke test pushes a single request
through the FULL engine on CPU); the soak/latency tests are marked
slow.
"""
import os
import threading
import time
from concurrent.futures import Future

import numpy as np
import pytest

from bigdl_tpu import nn
from bigdl_tpu.serving import (CompileCache, DynamicBatcher, ServingEngine,
                               ServingClosed, ServingQueueFull,
                               power_of_two_buckets)
from bigdl_tpu.serving.metrics import LatencyHistogram, ServingMetrics


def _tiny_model():
    return nn.Sequential(nn.Linear(8, 4), nn.LogSoftMax()).build(seed=0)


# --------------------------------------------------------------------------- #
# batcher edge cases (no jax involved: fake run_batch)                        #
# --------------------------------------------------------------------------- #

def test_power_of_two_buckets():
    assert power_of_two_buckets(32) == (1, 2, 4, 8, 16, 32)
    assert power_of_two_buckets(24) == (1, 2, 4, 8, 16, 24)
    assert power_of_two_buckets(1) == (1,)


def test_batcher_empty_queue_timeout_flush():
    """A lone request must flush when its wait budget expires, not sit
    until a full batch arrives."""
    b = DynamicBatcher(lambda x: x * 2, max_batch_size=64, max_wait_ms=20)
    try:
        t0 = time.perf_counter()
        y = b.submit(np.ones((3, 2), np.float32)).result(timeout=10)
        dt = time.perf_counter() - t0
        np.testing.assert_allclose(y, 2 * np.ones((3, 2)))
        assert y.shape == (3, 2)
        assert dt < 5.0  # flushed by timeout, not stuck
    finally:
        b.close()


def test_batcher_pads_to_buckets_and_slices_back():
    shapes = []

    def run(x):
        shapes.append(x.shape)
        return x + 1

    b = DynamicBatcher(run, max_batch_size=16, max_wait_ms=1)
    try:
        for n in (1, 3, 5, 7, 11):
            y = b.submit(np.full((n, 4), n, np.float32)).result(timeout=10)
            assert y.shape == (n, 4)
            np.testing.assert_allclose(y, n + 1)
        assert all(s[0] in (1, 2, 4, 8, 16) for s in shapes), shapes
    finally:
        b.close()


def test_batcher_request_larger_than_max_batch():
    """An oversized request is served alone, chunked into bucket-shaped
    slices, with the reassembled output matching."""
    shapes = []

    def run(x):
        shapes.append(x.shape)
        return x * 10

    b = DynamicBatcher(run, max_batch_size=8, max_wait_ms=1)
    try:
        x = np.arange(20 * 3, dtype=np.float32).reshape(20, 3)
        y = b.submit(x).result(timeout=10)
        np.testing.assert_allclose(y, x * 10)
        assert all(s[0] <= 8 and s[0] in (1, 2, 4, 8) for s in shapes)
    finally:
        b.close()


def test_batcher_queue_full_rejection():
    """Backpressure: a full bounded queue rejects with an error instead
    of growing without bound."""
    release = threading.Event()
    entered = threading.Event()

    def run(x):
        entered.set()
        release.wait(timeout=30)
        return x

    m = ServingMetrics()
    b = DynamicBatcher(run, max_batch_size=1, max_wait_ms=0,
                       max_queue=4, metrics=m)
    try:
        first = b.submit(np.ones((1, 2), np.float32))
        assert entered.wait(timeout=10)  # worker is now blocked in run()
        held = [b.submit(np.ones((1, 2), np.float32)) for _ in range(4)]
        with pytest.raises(ServingQueueFull):
            b.submit(np.ones((1, 2), np.float32))
        assert m.rejected == 1 and m.requests == 5
        release.set()
        for f in [first] + held:
            f.result(timeout=10)
    finally:
        release.set()
        b.close()


def test_batcher_response_order_matches_submission_order():
    done_order = []
    b = DynamicBatcher(lambda x: x, max_batch_size=4, max_wait_ms=5)
    try:
        futs = []
        for i in range(24):
            f = b.submit(np.full((1, 2), i, np.float32))
            f.add_done_callback(lambda _f, i=i: done_order.append(i))
            futs.append(f)
        outs = [f.result(timeout=10) for f in futs]
        for i, y in enumerate(outs):  # payload routed to the right caller
            np.testing.assert_allclose(y, i)
        assert done_order == sorted(done_order)  # FIFO completion
    finally:
        b.close()


def test_batcher_close_rejects_new_and_drains_pending():
    b = DynamicBatcher(lambda x: x, max_batch_size=4, max_wait_ms=1)
    f = b.submit(np.ones((2, 2), np.float32))
    b.close()
    assert f.result(timeout=10).shape == (2, 2)  # drained, not dropped
    with pytest.raises(ServingClosed):
        b.submit(np.ones((1, 2), np.float32))


def test_batcher_close_timeout_resolves_inflight_and_queued():
    """Regression (resilience): close() against a WEDGED dispatch must
    not leave any accepted future hanging — queued and in-flight
    requests all resolve with ServingClosed within the timeout, and the
    late worker completion afterwards is a harmless no-op."""
    release = threading.Event()
    served = []

    def wedged(x):
        release.wait(20)  # the dead-backend stand-in: a stuck device call
        served.append(x.shape)
        return x

    b = DynamicBatcher(wedged, max_batch_size=2, max_wait_ms=1)
    try:
        futs = [b.submit(np.ones((1, 3), np.float32)) for _ in range(5)]
        t0 = time.perf_counter()
        b.close(timeout=0.3)
        assert time.perf_counter() - t0 < 10.0
        for f in futs:  # every accepted request resolved, none hang
            with pytest.raises(ServingClosed):
                f.result(timeout=5)
    finally:
        release.set()  # unwedge; the late result must not blow up
        time.sleep(0.05)


def test_batcher_run_error_propagates_to_futures():
    def run(x):
        raise RuntimeError("device fell over")

    b = DynamicBatcher(run, max_batch_size=4, max_wait_ms=1)
    try:
        f = b.submit(np.ones((1, 2), np.float32))
        with pytest.raises(RuntimeError, match="device fell over"):
            f.result(timeout=10)
    finally:
        b.close()


# --------------------------------------------------------------------------- #
# compile cache                                                               #
# --------------------------------------------------------------------------- #

def test_compile_cache_counters_and_warmup():
    model = _tiny_model()

    def infer(params, buffers, x):
        y, _ = model.apply(params, x, buffers=buffers, training=False)
        return y

    cache = CompileCache(infer, max_entries=8)
    import jax.numpy as jnp
    compiled = cache.warmup(model.params, model.buffers,
                            [(1, 8), (4, 8)], jnp.float32)
    assert compiled == 2 and len(cache) == 2
    # warmup counts neither hits nor misses
    assert cache.stats()["hits"] == 0 and cache.stats()["misses"] == 0

    x = jnp.ones((4, 8), jnp.float32)
    y = cache(model.params, model.buffers, x)
    assert y.shape == (4, 4)
    assert cache.stats() == {"entries": 2, "hits": 1, "misses": 0,
                             "evictions": 0, "hit_rate": 1.0,
                             "ledger_tag": "infer"}
    cache(model.params, model.buffers, jnp.ones((2, 8), jnp.float32))
    s = cache.stats()
    assert s["misses"] == 1 and s["entries"] == 3


def test_compile_cache_lru_eviction():
    model = _tiny_model()

    def infer(params, buffers, x):
        y, _ = model.apply(params, x, buffers=buffers, training=False)
        return y

    cache = CompileCache(infer, max_entries=2)
    import jax.numpy as jnp
    for n in (1, 2, 4):
        cache(model.params, model.buffers, jnp.ones((n, 8), jnp.float32))
    s = cache.stats()
    assert s["entries"] == 2 and s["evictions"] == 1
    # (1, 8) was evicted: serving it again is a miss
    cache(model.params, model.buffers, jnp.ones((1, 8), jnp.float32))
    assert cache.stats()["misses"] == 4


# --------------------------------------------------------------------------- #
# engine (full path) — the tier-1 smoke test                                  #
# --------------------------------------------------------------------------- #

def test_smoke_single_request_through_full_engine():
    """Tier-1 smoke: one request through warmup -> batcher -> compile
    cache -> chunked staging -> device -> response, on CPU."""
    model = _tiny_model()
    with ServingEngine(model, input_shape=(8,), max_batch_size=8,
                       max_wait_ms=2.0) as eng:
        assert eng.warmup() == len(eng.batcher.buckets)
        x = np.random.RandomState(0).randn(3, 8).astype(np.float32)
        y = eng.predict(x, timeout=60)
        ref = np.asarray(model.evaluate().forward(x))
        np.testing.assert_allclose(y, ref, atol=1e-5)
        one = eng.predict_one(x[0], timeout=60)
        np.testing.assert_allclose(one, ref[0], atol=1e-5)
        st = eng.stats()
        assert st["compile_cache"]["hit_rate"] == 1.0  # warm: no compiles
        assert st["metrics"]["examples"] == 4
        assert st["host_transfer"]["batches_staged"] >= 2
    with pytest.raises(ServingClosed):
        eng.submit(x)


def test_engine_mixed_sizes_hit_rate_after_warmup():
    model = _tiny_model()
    with ServingEngine(model, input_shape=(8,), max_batch_size=16,
                       max_wait_ms=1.0) as eng:
        eng.warmup()
        rng = np.random.RandomState(1)
        futs = [eng.submit(rng.randn(n, 8).astype(np.float32))
                for n in (1, 3, 5, 7, 9, 16, 2, 11, 4, 8)]
        for f in futs:
            assert f.result(timeout=60).shape[1] == 4
        s = eng.stats()
        assert s["compile_cache"]["hit_rate"] > 0.9
        occ = s["metrics"]["batch_occupancy"]
        assert occ is not None and 0 < occ <= 1.0


def test_module_serve_convenience():
    eng = _tiny_model().serve(input_shape=(8,), max_batch_size=4,
                              max_wait_ms=1.0)
    try:
        y = eng.predict(np.zeros((2, 8), np.float32), timeout=60)
        assert y.shape == (2, 4)
    finally:
        eng.close()


# --------------------------------------------------------------------------- #
# Module.forward bucket fast path                                             #
# --------------------------------------------------------------------------- #

def test_module_forward_bucket_reuse_no_retrace():
    traces = [0]

    class Counting(nn.Module):
        def f(self, params, x, *, training=False, rng=None):
            traces[0] += 1
            return x * 2.0

    m = Counting().build().evaluate().register_batch_buckets([8, 16])
    for n in (3, 5, 8, 2, 7):
        y = m.forward(np.ones((n, 4), np.float32))
        assert y.shape == (n, 4)
        np.testing.assert_allclose(np.asarray(y), 2.0)
    assert traces[0] == 1  # one trace serves every size within bucket 8
    m.forward(np.ones((12, 4), np.float32))   # next bucket: second trace
    m.forward(np.ones((99, 4), np.float32))   # beyond buckets: exact path
    assert traces[0] == 3


def test_module_forward_buckets_ignored_in_training():
    traces = [0]

    class Counting(nn.Module):
        def f(self, params, x, *, training=False, rng=None):
            traces[0] += 1
            return x + 1.0

    m = Counting().build().register_batch_buckets([8])  # train mode
    for n in (3, 5):
        assert m.forward(np.ones((n, 2), np.float32)).shape == (n, 2)
    assert traces[0] == 2  # exact shapes: padding never touches training


# --------------------------------------------------------------------------- #
# chunked transfer                                                            #
# --------------------------------------------------------------------------- #

def test_chunked_device_put_matches_direct():
    from bigdl_tpu.utils.transfer import chunked_device_put
    x = np.random.RandomState(0).randn(64, 7).astype(np.float32)
    # tiny chunk budget forces many slices; content must be identical
    y = chunked_device_put(x, chunk_bytes=7 * 4 * 5)
    np.testing.assert_array_equal(np.asarray(y), x)
    assert tuple(y.shape) == x.shape
    # dtype conversion on the wire + single-chunk fast path + 0-d
    y16 = chunked_device_put(np.float64(x), "bfloat16", chunk_bytes=1 << 30)
    assert str(y16.dtype) == "bfloat16"
    assert float(chunked_device_put(np.float32(3.5))) == 3.5


# --------------------------------------------------------------------------- #
# metrics                                                                     #
# --------------------------------------------------------------------------- #

def test_latency_histogram_percentiles():
    h = LatencyHistogram()
    assert h.percentile(50) is None
    for ms in range(1, 101):
        h.observe(ms / 1000.0)
    p50, p99 = h.percentile(50), h.percentile(99)
    assert 0.045 <= p50 <= 0.06, p50
    assert 0.09 <= p99 <= 0.115, p99
    snap = h.snapshot()
    assert snap["count"] == 100 and snap["max_s"] == pytest.approx(0.1)


def test_metrics_export_through_visualization(tmp_path):
    from bigdl_tpu.visualization import ServingSummary
    m = ServingMetrics()
    m.record_submit()
    m.record_batch(3, 4, [0.001, 0.002, 0.003], 0.01)
    m.record_done(0.012)
    s = ServingSummary(str(tmp_path), "serve_app")
    assert s.folder.endswith(os.path.join("serve_app", "serving"))
    m.export_to_summary(s, step=1, cache_stats={"hit_rate": 1.0,
                                                "hits": 3, "misses": 0})
    rows = s.read_scalar("Serving/ThroughputEPS")
    assert len(rows) == 1
    assert s.read_scalar("Serving/CacheHitRate")[0][1] == 1.0
    assert s.read_scalar("Serving/LatencyP50")[0][1] == pytest.approx(
        0.012, rel=0.2)
    s.close()


# --------------------------------------------------------------------------- #
# soak + CLI (slow)                                                           #
# --------------------------------------------------------------------------- #

@pytest.mark.slow
def test_serving_soak_concurrent_clients():
    """Many threads hammering one engine: every response correct, no
    deadlock, throughput accounted."""
    model = _tiny_model()
    errs = []
    with ServingEngine(model, input_shape=(8,), max_batch_size=16,
                       max_wait_ms=2.0, max_queue=1024) as eng:
        eng.warmup()

        def client(seed):
            rng = np.random.RandomState(seed)
            try:
                for _ in range(40):
                    n = int(rng.randint(1, 9))
                    x = rng.randn(n, 8).astype(np.float32)
                    y = eng.predict(x, timeout=120)
                    assert y.shape == (n, 4)
            except Exception as e:  # pragma: no cover
                errs.append(e)

        threads = [threading.Thread(target=client, args=(i,))
                   for i in range(8)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=300)
        assert not errs, errs
        snap = eng.stats()
        assert snap["metrics"]["examples"] >= 8 * 40
        assert snap["compile_cache"]["hit_rate"] > 0.9
        assert snap["metrics"]["throughput_eps"] > 0


# --------------------------------------------------------------------------- #
# pytree outputs (multi-headed models) through batcher + engine               #
# --------------------------------------------------------------------------- #

class _TwoHeaded:
    """Duck-typed built module with a pytree output: the multi-headed
    model case the batcher's leaf-wise slice-back exists for."""

    def __init__(self):
        self._inner = _tiny_model()
        self.params = self._inner.params
        self.buffers = self._inner.buffers

    def _built(self):
        return True

    def apply(self, params, x, buffers=None, training=False, rng=None):
        import jax.numpy as jnp
        y, buffers = self._inner.apply(params, x, buffers=buffers,
                                       training=training, rng=rng)
        return {"cls": y, "reg": (y[:, :2] * 2.0, jnp.sum(y, axis=1))}, \
            buffers


def _two_headed_ref(model, x):
    import jax
    y, _ = model._inner.apply(model.params, x, buffers=model.buffers,
                              training=False,
                              rng=jax.random.PRNGKey(0))
    y = np.asarray(y)
    return {"cls": y, "reg": (y[:, :2] * 2.0, y.sum(axis=1))}


def test_batcher_pytree_output_slice_back():
    """Fake run_batch returning a dict of heads: every leaf is sliced
    back per request, including the oversized chunked path."""

    def run(x):
        return {"a": x + 1, "b": (x[:, :1] * 2, x.sum(axis=1))}

    b = DynamicBatcher(run, max_batch_size=8, max_wait_ms=1)
    try:
        for n in (1, 3, 20):  # 20 > max_batch_size: chunk + concat
            x = np.arange(n * 4, dtype=np.float32).reshape(n, 4)
            y = b.submit(x).result(timeout=10)
            assert set(y) == {"a", "b"}
            np.testing.assert_allclose(y["a"], x + 1)
            np.testing.assert_allclose(y["b"][0], x[:, :1] * 2)
            np.testing.assert_allclose(y["b"][1], x.sum(axis=1))
    finally:
        b.close()


def test_engine_pytree_outputs_end_to_end():
    """Two-headed module through the full ServingEngine: per-request
    slice-back of every leaf, mixed sizes, oversized chunking, and
    predict_one's leaf-wise batch-dim strip."""
    model = _TwoHeaded()
    with ServingEngine(model, input_shape=(8,), max_batch_size=8,
                       max_wait_ms=1.0) as eng:
        eng.warmup()
        rng = np.random.RandomState(0)
        for n in (1, 5, 20):  # 20 > max_batch_size
            x = rng.randn(n, 8).astype(np.float32)
            y = eng.predict(x, timeout=120)
            ref = _two_headed_ref(model, x)
            assert set(y) == {"cls", "reg"}
            assert isinstance(y["cls"], np.ndarray)
            np.testing.assert_allclose(y["cls"], ref["cls"], rtol=1e-5)
            np.testing.assert_allclose(y["reg"][0], ref["reg"][0],
                                       rtol=1e-5)
            np.testing.assert_allclose(y["reg"][1], ref["reg"][1],
                                       rtol=1e-5)
        one = eng.predict_one(rng.randn(8).astype(np.float32),
                              timeout=120)
        assert one["cls"].shape == (4,) and one["reg"][1].shape == ()


def test_engine_rejects_output_leaf_without_batch_dim():
    """The slice-back contract is validated: a head whose leading dim
    is not the batch dim fails loudly instead of shuffling rows."""

    class _Bad(_TwoHeaded):
        def apply(self, params, x, buffers=None, training=False,
                  rng=None):
            import jax.numpy as jnp
            out, b = super().apply(params, x, buffers=buffers,
                                   training=training, rng=rng)
            return {"ok": out["cls"], "scalar": jnp.sum(out["cls"])}, b

    model = _Bad()
    with ServingEngine(model, input_shape=(8,), max_batch_size=4,
                       max_wait_ms=1.0) as eng:
        with pytest.raises(TypeError, match="leading batch dim"):
            eng.predict(np.zeros((3, 8), np.float32), timeout=120)
