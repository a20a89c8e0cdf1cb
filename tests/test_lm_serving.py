"""LM serving: continuous batching, slot KV cache, bucketed prefill.

Fast tier-1 tests cover the scheduler mechanics (slot insert/free,
bucket selection, EOS early-exit), the donation contract (the decode
loop reuses the resident cache buffers — no realloc per step), the
prefill compile-count contract (executables == distinct buckets), and
small-scale token-exactness vs offline ``generate``.  The slow soak
replays a staggered-arrival, mixed-length workload and asserts
bit-exact agreement for EVERY request.
"""
import time

import numpy as np
import pytest

from bigdl_tpu.models.transformer import TransformerLM
from bigdl_tpu.models.transformer.generate import generate
from bigdl_tpu.serving import (CompileCache, LMServingEngine,
                               ServingClosed, ServingQueueFull,
                               prefill_bucket_lengths)
from bigdl_tpu.serving.lm_engine import LMMetrics


def _wait(pred, timeout=30.0):
    """Streams resolve a beat before the worker frees slots / bumps
    counters — poll instead of asserting the instant result() returns."""
    deadline = time.perf_counter() + timeout
    while time.perf_counter() < deadline:
        if pred():
            return True
        time.sleep(0.005)
    return False


def _lm(vocab=31, hidden=16, heads=2, layers=1, max_len=32, seed=0,
        pos="rope"):
    return TransformerLM(vocab_size=vocab, hidden_size=hidden,
                         n_head=heads, n_layers=layers, max_len=max_len,
                         pos_encoding=pos).build(seed=seed)


@pytest.fixture(scope="module")
def lm_model():
    return _lm()


@pytest.fixture(scope="module")
def lm_engine(lm_model):
    """One shared engine for the read-only fast tests (each engine
    compiles prefill buckets + decode + insert; sharing keeps tier-1
    inside budget)."""
    eng = LMServingEngine(lm_model, slots=2, cache_len=24,
                          max_new_tokens=6, prefill_buckets=(4, 8, 16))
    eng.warmup()
    yield eng
    eng.close()


# --------------------------------------------------------------------------- #
# buckets                                                                     #
# --------------------------------------------------------------------------- #

def test_prefill_bucket_lengths():
    assert prefill_bucket_lengths(64) == (8, 16, 32, 64)
    assert prefill_bucket_lengths(48) == (8, 16, 32, 48)
    assert prefill_bucket_lengths(8) == (8,)
    assert prefill_bucket_lengths(5) == (5,)


def test_bucket_selection_and_overflow(lm_engine, lm_model):
    assert lm_engine.bucket_for(1) == 4
    assert lm_engine.bucket_for(4) == 4
    assert lm_engine.bucket_for(5) == 8
    assert lm_engine.bucket_for(16) == 16
    # ACCEPTANCE: a prompt longer than the largest prefill bucket (the
    # old per-slot cache region) is admitted — chunked paged prefill —
    # and served bit-exact vs offline generate
    p = np.arange(1, 19)  # 18 > largest bucket 16
    out = lm_engine.generate(p, max_new_tokens=6, timeout=120)
    ref = np.asarray(generate(lm_model, lm_model.params,
                              p[None].astype(np.int32), 6))
    np.testing.assert_array_equal(out, ref[0])


def test_submit_rejects_over_cache_len(lm_engine):
    with pytest.raises(ValueError):
        lm_engine.submit(np.arange(1, 11), max_new_tokens=15)  # 10+15>24


# --------------------------------------------------------------------------- #
# compile cache: pytree keys, prefill compile-count contract                  #
# --------------------------------------------------------------------------- #

def test_compile_cache_pytree_inputs():
    """The generalized cache keys on per-leaf (shape, dtype) + treedef:
    multi-tensor inputs (the prefill case) hit and miss correctly."""
    calls = []

    def fn(params, buffers, x):
        calls.append(1)
        return x["ids"] * params + x["len"]

    cache = CompileCache(fn, max_entries=4)
    import jax.numpy as jnp
    p = jnp.float32(2.0)
    a = {"ids": np.ones((1, 8), np.float32), "len": np.float32(3)}
    b = {"ids": np.ones((1, 8), np.float32), "len": np.float32(9)}
    c = {"ids": np.ones((1, 16), np.float32), "len": np.float32(3)}
    y = np.asarray(cache(p, None, a))
    np.testing.assert_allclose(y, 2.0 + 3.0)
    cache(p, None, b)  # same signature, new values: HIT
    cache(p, None, c)  # new leaf shape: MISS
    st = cache.stats()
    assert st["misses"] == 2 and st["hits"] == 1 and st["entries"] == 2
    # warmup_inputs pre-compiles without counting traffic
    d = {"ids": np.ones((1, 32), np.float32), "len": np.float32(0)}
    assert cache.warmup_inputs(p, None, [d, d]) == 1
    st = cache.stats()
    assert st["entries"] == 3 and st["misses"] == 2


def test_prefill_compiles_equal_distinct_buckets(lm_model):
    """Acceptance: prefill executable count == distinct (bucket, dtype)
    pairs, and warmed traffic is all hits."""
    eng = LMServingEngine(lm_model, slots=2, cache_len=24,
                          max_new_tokens=4, prefill_buckets=(4, 8, 16))
    try:
        assert eng.warmup() == 3  # one per bucket
        st = eng.prefill_cache.stats()
        assert st["entries"] == 3 and st["misses"] == 0
        # traffic across all three buckets: hits only, no new compiles
        for t in (2, 4, 6, 9, 16):
            eng.generate(np.arange(1, t + 1) % 30 + 1, timeout=60,
                         max_new_tokens=3)
        st = eng.prefill_cache.stats()
        assert st["entries"] == 3
        assert st["misses"] == 0 and st["hits"] == 5
    finally:
        eng.close()


# --------------------------------------------------------------------------- #
# slots: insert/free, EOS early-exit, donation                                #
# --------------------------------------------------------------------------- #

def test_slot_insert_free_and_exactness(lm_engine, lm_model):
    """More requests than slots: continuous admission recycles freed
    slots and every stream matches offline generate bit-for-bit."""
    prompts = [np.arange(1, 5), np.arange(2, 9), np.arange(3, 7),
               np.arange(1, 8)]
    streams = [lm_engine.submit(p, max_new_tokens=3) for p in prompts]
    for p, s in zip(prompts, streams):
        out = s.result(timeout=120)
        ref = np.asarray(generate(lm_model, lm_model.params,
                                  p[None].astype(np.int32), 3))
        np.testing.assert_array_equal(out, ref[0])
    assert _wait(lambda: sorted(lm_engine._free) == [0, 1])  # recycled
    st = lm_engine.stats()
    assert st["active"] == 0 and st["queued"] == 0


def test_decode_reuses_donated_cache_buffers(lm_engine):
    """Acceptance: the decode loop never reallocates the resident k/v
    caches — the donated output IS the input buffer, so the device
    addresses stay fixed across steps and requests."""
    lm_engine.generate(np.arange(1, 6), timeout=60)  # ensure warm+used
    p0 = lm_engine.cache_buffer_pointers()
    assert all(p is not None for p in p0)
    for t in (3, 7, 11):
        lm_engine.generate(np.arange(1, t + 1), max_new_tokens=4,
                           timeout=60)
    assert lm_engine.cache_buffer_pointers() == p0


def test_eos_early_exit_frees_slot(lm_engine):
    """A request hitting EOS stops streaming immediately (its tokens
    are the offline prefix through the first EOS) and its slot is
    reusable; completion is counted."""
    done0 = lm_engine.metrics.completed
    p = np.arange(1, 5)
    full = lm_engine.generate(p, max_new_tokens=6, timeout=60)
    gen = full[len(p):]
    eos = int(gen[2])  # stop at the 3rd token's value
    first_hit = int(np.argmax(gen == eos))  # may appear earlier
    out = lm_engine.generate(p, max_new_tokens=6, eos_id=eos, timeout=60)
    np.testing.assert_array_equal(out, full[:len(p) + first_hit + 1])
    assert out[-1] == eos
    # the slot is free again and serves the next request
    assert _wait(lambda: lm_engine.stats()["active"] == 0)
    assert lm_engine.generate(p, max_new_tokens=2,
                              timeout=60).shape == (6,)
    assert _wait(lambda: lm_engine.metrics.completed == done0 + 3)


def test_first_token_eos_never_occupies_slot(lm_engine, lm_model):
    """max_new=1 (and first-token EOS) complete from prefill alone —
    no insert, no decode step."""
    steps0 = lm_engine.metrics.decode_steps
    out = lm_engine.generate(np.arange(1, 5), max_new_tokens=1,
                             timeout=60)
    assert out.shape == (5,)
    assert lm_engine.metrics.decode_steps == steps0
    ref = np.asarray(generate(lm_model, lm_model.params,
                              np.arange(1, 5)[None].astype(np.int32), 1))
    np.testing.assert_array_equal(out, ref[0])


# --------------------------------------------------------------------------- #
# sampling parity, streaming, lifecycle                                       #
# --------------------------------------------------------------------------- #

def test_sampled_parity_with_offline(lm_model):
    """temperature > 0: the engine replays offline generate()'s exact
    key chain, so sampled streams are bit-exact too."""
    import jax
    eng = LMServingEngine(lm_model, slots=2, cache_len=24,
                          temperature=0.7, prefill_buckets=(8,))
    try:
        p = np.arange(1, 6)
        for seed in (0, 3):  # same shapes: the 2nd seed reuses compiles
            out = eng.generate(p, max_new_tokens=3, rng=seed, timeout=60)
            ref = np.asarray(generate(
                lm_model, lm_model.params, p[None].astype(np.int32), 3,
                temperature=0.7, rng=jax.random.PRNGKey(seed)))
            np.testing.assert_array_equal(out, ref[0])
    finally:
        eng.close()


def test_prefix_sharing_greedy_and_sampled_exact(lm_model):
    """ACCEPTANCE: with paging + radix sharing ON and a prefix actually
    reused (hit rate > 0), greedy AND sampled streams stay bit-exact vs
    offline generate — sharing changes memory traffic, never tokens."""
    import jax
    eng = LMServingEngine(lm_model, slots=2, cache_len=24, block_len=4,
                          prefill_buckets=(4, 8, 16))
    try:
        p = np.arange(1, 13)  # 12 tokens = 3 full blocks, 2 matchable
        ref = np.asarray(generate(lm_model, lm_model.params,
                                  p[None].astype(np.int32), 6))[0]
        np.testing.assert_array_equal(
            eng.generate(p, max_new_tokens=6, timeout=120), ref)
        hits0 = eng.radix.hits
        # identical prompt: served THROUGH the shared chain, still exact
        np.testing.assert_array_equal(
            eng.generate(p, max_new_tokens=6, timeout=120), ref)
        assert eng.radix.hits == hits0 + 1
        assert eng.radix.matched_tokens >= 8
        # sampled: the replayed key chain survives the prefix-hit path
        sref = np.asarray(generate(
            lm_model, lm_model.params, p[None].astype(np.int32), 6,
            temperature=0.7, rng=jax.random.PRNGKey(7)))[0]
        out = eng.generate(p, max_new_tokens=6, temperature=0.7, rng=7,
                           timeout=120)
        np.testing.assert_array_equal(out, sref)
        assert eng.radix.hits == hits0 + 2
    finally:
        eng.close()


def test_stream_tokens_iterator(lm_engine):
    s = lm_engine.submit(np.arange(1, 5), max_new_tokens=4)
    toks = list(s.tokens(timeout=60))
    assert len(toks) == 4
    np.testing.assert_array_equal(toks, s.result(timeout=60)[4:])
    assert s.ttft_s is not None and s.ttft_s >= 0


def test_queue_full_and_closed(lm_model):
    eng = LMServingEngine(lm_model, slots=1, cache_len=24, max_queue=0,
                          max_new_tokens=4, prefill_buckets=(8,))
    try:
        with pytest.raises(ServingQueueFull):
            eng.submit(np.arange(1, 4))
        assert eng.metrics.rejected == 1
    finally:
        eng.close()
    with pytest.raises(ServingClosed):
        eng.submit(np.arange(1, 4))


def test_close_resolves_streams(lm_model):
    """close() drains accepted work; a stream submitted before close
    still resolves (with tokens, since drain finishes it)."""
    eng = LMServingEngine(lm_model, slots=1, cache_len=24,
                          prefill_buckets=(8,))
    s = eng.submit(np.arange(1, 5), max_new_tokens=4)
    eng.close(timeout=60)
    assert s.result(timeout=5).shape == (8,)


def test_lm_metrics_snapshot_and_registry():
    from bigdl_tpu.obs import get_registry
    m = LMMetrics(slots=4).publish_to(get_registry())
    m.record_submit()
    m.record_first_token(0.010)
    m.record_step(2, [0.002, 0.003])
    m.record_complete()
    snap = m.snapshot()
    assert snap["tokens"] == 3 and snap["completed"] == 1
    assert snap["slot_occupancy"] == 0.5  # 2 of 4 slots decoded
    assert snap["ttft"]["count"] == 1 and snap["itl"]["count"] == 2
    reg = get_registry().snapshot()
    assert "serving/lm/tokens_per_s" in reg
    assert reg["serving/lm/slot_occupancy"]["value"] == 0.5


def test_learned_pos_exactness():
    """Per-slot learned position embeddings (not just RoPE) stay exact
    through padded prefill + slot decode."""
    model = _lm(pos="learned", max_len=24, seed=2)
    eng = LMServingEngine(model, slots=2, cache_len=20,
                          prefill_buckets=(8,))
    try:
        p = np.arange(1, 7)  # bucket-padded to 8: pos rows must align
        out = eng.generate(p, max_new_tokens=4, timeout=60)
        ref = np.asarray(generate(model, model.params,
                                  p[None].astype(np.int32), 4))
        np.testing.assert_array_equal(out, ref[0])
    finally:
        eng.close()


# --------------------------------------------------------------------------- #
# slow: mixed-length staggered soak + bench CLI                               #
# --------------------------------------------------------------------------- #

@pytest.mark.slow
def test_soak_continuous_batching_token_exact():
    """THE acceptance soak: staggered arrivals, mixed prompt lengths,
    mixed budgets, EOS early-exit — every request's streamed tokens are
    bit-exact vs offline generate, under real slot churn."""
    model = _lm(vocab=61, hidden=32, heads=2, layers=2, max_len=64,
                seed=5)
    eng = LMServingEngine(model, slots=3, cache_len=48,
                          prefill_buckets=(4, 8, 16, 32))
    rng = np.random.RandomState(0)
    try:
        eng.warmup()
        work = []
        for i in range(24):
            t = int(rng.choice((2, 5, 9, 14, 23, 32)))
            m = int(rng.choice((3, 8, 15)))
            work.append((rng.randint(1, 62, size=t).astype(np.int32), m,
                         int(rng.randint(1, 62)) if i % 3 == 0 else None))
        streams = []
        for prompt, m, eos in work:
            streams.append(eng.submit(prompt, max_new_tokens=m,
                                      eos_id=eos))
            time.sleep(float(rng.exponential(0.004)))
        for (prompt, m, eos), s in zip(work, streams):
            out = s.result(timeout=300)
            ref = np.asarray(generate(model, model.params, prompt[None],
                                      m))[0]
            gen = out[len(prompt):]
            if eos is not None and eos in ref[len(prompt):]:
                stop = int(np.argmax(ref[len(prompt):] == eos))
                assert len(gen) == stop + 1 and gen[-1] == eos
                np.testing.assert_array_equal(out, ref[:len(prompt)
                                                       + stop + 1])
            else:
                assert len(gen) == m
                np.testing.assert_array_equal(out, ref)
        assert _wait(lambda: eng.metrics.completed == len(work))
        st = eng.stats()
        assert st["prefill_cache"]["misses"] == 0  # warmup covered all
        assert st["metrics"]["slot_occupancy"] > 0.3
    finally:
        eng.close()


def test_int8_lm_serves_and_generates_exactly(lm_model):
    """An int8 Module.quantize() clone both serves through the slot
    engine AND runs offline generate (the jit-entry dequant seam covers
    generate's prefill/decode too), bit-exact with each other."""
    qlm = lm_model.quantize("int8")
    assert qlm.quant_report["bytes_saved"] > 0  # really quantized
    eng = LMServingEngine(qlm, slots=2, cache_len=24,
                          prefill_buckets=(8,))
    try:
        p = np.arange(1, 7)
        out = eng.generate(p, max_new_tokens=4, timeout=120)
        ref = np.asarray(generate(qlm, qlm.params,
                                  p[None].astype(np.int32), 4))
        np.testing.assert_array_equal(out, ref[0])
    finally:
        eng.close()
