"""Paged KV cache: BlockPool + RadixCache units, pool-pressure faults.

Covers the host-side memory plane of LM serving: refcounted block
allocation, the radix trie's retain/insert/evict protocol (LRU of
unreferenced tails, referenced chains never evict), the two typed
exhaustion outcomes (permanent ``RequestExceedsPool`` rejection vs
transient deferral that completes exactly), shared-prefix slot-recycle
exactness when one of two sharing streams hits EOS, and the
``kvcache/arena_bytes`` gauge the SLO controller's headroom check
reads through ``ObsSummary``.
"""
import time

import numpy as np
import pytest

from bigdl_tpu.models.transformer import TransformerLM
from bigdl_tpu.models.transformer.generate import generate
from bigdl_tpu.obs import get_registry
from bigdl_tpu.serving import LMServingEngine
from bigdl_tpu.serving.kvcache import (SCRATCH_BLOCK, BlockPool,
                                       PoolExhausted, RadixCache,
                                       RequestExceedsPool)


def _lm(vocab=31, hidden=16, heads=2, layers=1, max_len=32, seed=0):
    return TransformerLM(vocab_size=vocab, hidden_size=hidden,
                         n_head=heads, n_layers=layers,
                         max_len=max_len).build(seed=seed)


def _pool(num_blocks=8, block_len=2):
    return BlockPool(n_layers=1, n_heads=1, head_dim=2,
                     block_len=block_len, num_blocks=num_blocks)


def _rejected():
    snap = get_registry().snapshot()
    return snap.get("serving/rejected_total", {"value": 0})["value"] or 0


def _wait(pred, timeout=30.0):
    deadline = time.perf_counter() + timeout
    while time.perf_counter() < deadline:
        if pred():
            return True
        time.sleep(0.005)
    return False


# --------------------------------------------------------------------------- #
# BlockPool                                                                   #
# --------------------------------------------------------------------------- #

def test_block_pool_alloc_release_refcount():
    pool = _pool(num_blocks=5)
    assert pool.capacity == 4 and pool.free_count == 4
    a = pool.alloc(2)
    assert len(a) == 2 and SCRATCH_BLOCK not in a  # scratch reserved
    assert all(pool.refcount(b) == 1 for b in a)
    pool.retain(a)
    assert all(pool.refcount(b) == 2 for b in a)
    pool.release(a)
    assert pool.free_count == 2  # still held once
    pool.release(a)
    assert pool.free_count == 4  # back on the free list
    with pytest.raises(ValueError):
        pool.release(a)  # double free
    with pytest.raises(ValueError):
        pool.retain(a)   # retain of free block


def test_block_pool_alloc_is_all_or_nothing():
    pool = _pool(num_blocks=4)
    a = pool.alloc(2)
    with pytest.raises(PoolExhausted):
        pool.alloc(2)  # only 1 free: nothing handed out
    assert pool.free_count == 1
    pool.release(a)
    assert len(pool.alloc(3)) == 3


def test_block_pool_stats_and_sizing():
    pool = _pool(num_blocks=8, block_len=4)
    assert pool.blocks_for(1) == 1
    assert pool.blocks_for(4) == 1
    assert pool.blocks_for(5) == 2
    pool.alloc(3)
    st = pool.stats()
    assert st["used_blocks"] == 3 and st["free_blocks"] == 4
    assert st["utilization"] == pytest.approx(3 / 7)
    # (L, N, B, W) f32 k + v arenas; one head of 2 values pads to 128 lanes
    assert pool.shape == (1, 8, 4, 128)
    assert st["arena_bytes"] == 2 * (1 * 8 * 4 * 128) * 4
    with pytest.raises(ValueError):
        BlockPool(n_layers=1, n_heads=1, head_dim=2, block_len=2,
                  num_blocks=1)  # no room for scratch + data


# --------------------------------------------------------------------------- #
# the arena layout (PR 25): block-contiguous, lane-padded rows                 #
# --------------------------------------------------------------------------- #

#: (heads, head_dim): H * D = 320 needs lane padding (-> 384), 256 does not
LAYOUT_GEOMETRIES = [(5, 64), (4, 64)]
LAYOUT_KINDS = ["bfloat16", "float32", "int8"]


def _layout_pool(heads, head_dim, kind, layers=2, block_len=4, num_blocks=12):
    import jax.numpy as jnp
    return BlockPool(n_layers=layers, n_heads=heads, head_dim=head_dim,
                     block_len=block_len, num_blocks=num_blocks,
                     dtype=None if kind == "int8" else jnp.dtype(kind),
                     kv_quant="int8" if kind == "int8" else None)


def _arenas(pool):
    return ((pool.k, pool.v) if pool.kv_quant is None
            else (pool.k, pool.v, pool.ks, pool.vs))


@pytest.mark.parametrize("kind", LAYOUT_KINDS)
@pytest.mark.parametrize("geom", LAYOUT_GEOMETRIES, ids=["hd320", "hd256"])
def test_layout_shape_follows_geometry(geom, kind):
    """The lane padding comes from the shape the pool is built with: 320
    -> 384 lanes, 256 stays 256; the scale arena holds a block's B * H
    scales in one padded row; the wire shape never pads."""
    h, d = geom
    pool = _layout_pool(h, d, kind)
    assert pool.shape == (2, 12, 4, 384 if h * d == 320 else 256)
    assert pool.k.shape == pool.v.shape == pool.shape
    assert pool.wire_shape == (2, h, 4, d)
    assert pool.block_bytes == 2 * h * 4 * d * pool.dtype.itemsize
    if kind == "int8":
        assert pool.ks.shape == pool.vs.shape == pool.scale_shape == (2, 12, 128)
    else:
        assert pool.ks is None and pool.scale_arena_bytes == 0


@pytest.mark.parametrize("kind", LAYOUT_KINDS)
@pytest.mark.parametrize("geom", LAYOUT_GEOMETRIES, ids=["hd320", "hd256"])
def test_layout_insert_then_read_chain_bit_exact(geom, kind):
    """``_insert_blocks`` then ``read_chain`` gives back the chunk's rows
    bit for bit (a quantized pool: its own int8 rows and scales), the
    padding lanes stay zero and no other block is touched."""
    import jax
    import jax.numpy as jnp
    from bigdl_tpu.models.transformer.generate import (_insert_blocks,
                                                       _kv_quantize_rows)
    from bigdl_tpu.serving.kvcache.blocks import read_chain
    h, d = geom
    pool = _layout_pool(h, d, kind)
    L, _, B, W = pool.shape
    tb, ids = 10, jnp.asarray([7, 2, 9], jnp.int32)   # 10 rows: 3 blocks of 4
    dt = jnp.float32 if kind == "int8" else pool.dtype
    keys = jax.random.split(jax.random.PRNGKey(h), 2)
    k_new = jax.random.normal(keys[0], (L, 1, h, tb, d), jnp.float32).astype(dt)
    v_new = jax.random.normal(keys[1], (L, 1, h, tb, d), jnp.float32).astype(dt)
    out = _insert_blocks(pool.k, pool.v, k_new, v_new, ids, pool.ks, pool.vs)
    block = (B, h, d)
    for arena, new, scale in ((out[0], k_new, out[2:] and out[2]),
                              (out[1], v_new, out[2:] and out[3])):
        got = read_chain(arena, slice(None), ids, block)[:, :tb]  # (L, tb, H, D)
        want = new[:, 0].transpose(0, 2, 1, 3)
        if kind == "int8":
            want, want_s = _kv_quantize_rows(want)
            got_s = read_chain(scale, slice(None), ids, block[:2])[:, :tb]
            np.testing.assert_array_equal(np.asarray(got_s), np.asarray(want_s))
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
        a = np.asarray(arena)
        assert not a[..., h * d:].any()                 # padding lanes
        untouched = [b for b in range(pool.num_blocks) if b not in (7, 2, 9)]
        assert not a[:, untouched].any()


@pytest.mark.parametrize("kind", LAYOUT_KINDS)
@pytest.mark.parametrize("geom", LAYOUT_GEOMETRIES, ids=["hd320", "hd256"])
def test_layout_write_rows_lands_at_block_and_offset(geom, kind):
    """``write_rows`` at (layer, block, offset), the decode step's write:
    the row reads back at position ``blk_index * B + offset`` of the chain
    and its neighbours stay as they were."""
    import jax.numpy as jnp
    from bigdl_tpu.serving.kvcache.blocks import read_chain, write_rows
    h, d = geom
    pool = _layout_pool(h, d, kind)
    B = pool.block_len
    blk, off = jnp.asarray([[3], [5]]), jnp.asarray([[1], [3]])    # (S, 1)
    tables = jnp.asarray([[3, 4], [6, 5]], jnp.int32)
    rows = (jnp.arange(2 * h * d).reshape(2, 1, h, d) % 100 + 1).astype(pool.dtype)
    k = write_rows(pool.k, 1, blk, off, rows)
    got = np.asarray(read_chain(k, 1, tables, (B, h, d)))          # (S, 2B, H, D)
    want = np.zeros_like(got)
    want[0, 0 * B + 1], want[1, 1 * B + 3] = rows[0, 0], rows[1, 0]
    np.testing.assert_array_equal(got, want)
    assert not np.asarray(k[0]).any()                              # other layer
    if kind == "int8":
        srows = jnp.arange(2 * h, dtype=jnp.float32).reshape(2, 1, h) + 0.5
        ks = write_rows(pool.ks, 1, blk, off, srows)
        got = np.asarray(read_chain(ks, 1, tables, (B, h)))        # (S, 2B, H)
        want = np.zeros_like(got)
        want[0, 1], want[1, B + 3] = srows[0, 0], srows[1, 0]
        np.testing.assert_array_equal(got, want)
        assert not np.asarray(ks[0]).any()


@pytest.mark.parametrize("kind", LAYOUT_KINDS)
@pytest.mark.parametrize("geom", LAYOUT_GEOMETRIES, ids=["hd320", "hd256"])
def test_layout_export_adopt_round_trip_keeps_the_wire(geom, kind):
    """The wire and the host tier keep the per-block (L, H, B, D) payload:
    what ``export_chain`` gives is the rows as written, in that shape, and
    a chain adopted into another pool exports the same bytes."""
    import jax.numpy as jnp
    from bigdl_tpu.serving.kvcache.blocks import write_rows
    h, d = geom
    src, dst = _layout_pool(h, d, kind), _layout_pool(h, d, kind)
    L, _, B, _ = src.shape
    ids = src.alloc(3)
    idx = jnp.asarray(ids, jnp.int32)
    rng = np.random.default_rng(h)
    rows = (rng.integers(-100, 100, (L, 3, B, h, d))).astype(src.dtype)
    src.k = write_rows(src.k, slice(None), idx, None, jnp.asarray(rows))
    src.v = write_rows(src.v, slice(None), idx, None, jnp.asarray(-rows))
    if kind == "int8":
        srows = rng.random((L, 3, B, h)).astype(np.float32)
        src.ks = write_rows(src.ks, slice(None), idx, None, jnp.asarray(srows))
        src.vs = write_rows(src.vs, slice(None), idx, None, jnp.asarray(2 * srows))
    wire = src.export_chain(ids)
    assert wire["k"].shape == (3, L, h, B, d) and wire["k"].dtype == src.dtype
    np.testing.assert_array_equal(wire["k"], rows.transpose(1, 0, 3, 2, 4))
    np.testing.assert_array_equal(wire["v"], -rows.transpose(1, 0, 3, 2, 4))
    if kind == "int8":
        assert wire["ks"].shape == (3, L, h, B)
        np.testing.assert_array_equal(wire["ks"], srows.transpose(1, 0, 3, 2))
    dst.alloc(2)                                    # land on other block ids
    fresh = dst.adopt_chain(wire["k"], wire["v"], wire.get("ks"), wire.get("vs"))
    assert fresh != ids
    back = dst.export_chain(fresh)
    for key in wire:
        np.testing.assert_array_equal(back[key], wire[key])


# --------------------------------------------------------------------------- #
# RadixCache                                                                  #
# --------------------------------------------------------------------------- #

def test_radix_match_caps_before_last_token():
    """The final prompt token is never served from cache — a full-prefix
    hit would leave no position to compute first-token logits from."""
    pool = _pool(num_blocks=8, block_len=2)
    rc = RadixCache(pool)
    toks = np.arange(10, 16)  # 3 full blocks
    chain = pool.alloc(3)
    rc.insert(toks, chain)
    assert rc.nodes == 3
    m = rc.match(toks)  # t=6: cap = (6-1)//2 = 2 of the 3 blocks
    assert m == chain[:2]
    assert all(pool.refcount(b) == 3 for b in m)  # seq + trie + caller
    assert rc.matched_tokens == 4 and rc.hits == 1
    pool.release(m)
    # a diverging prompt matches only the shared head
    other = np.array([10, 11, 99, 98, 97, 96])
    assert rc.match(other) == chain[:1]
    pool.release(chain[:1])


def test_radix_insert_keeps_existing_nodes():
    """Re-inserting a cached prefix adopts nothing new: the trie's
    blocks stay authoritative, the caller's duplicates stay private."""
    pool = _pool(num_blocks=8, block_len=2)
    rc = RadixCache(pool)
    toks = np.arange(4)
    first = pool.alloc(2)
    assert rc.insert(toks, first) == 2
    dup = pool.alloc(2)
    assert rc.insert(toks, dup) == 0  # nodes exist: nothing adopted
    assert pool.refcount(dup[0]) == 1  # still only the caller's
    assert rc.match(toks) == first[:1]
    pool.release(first[:1])


def test_radix_evicts_lru_unreferenced_tails_only():
    """Satellite: eviction frees LRU leaves at refcount 1 (trie-only);
    chains referenced by a live sequence never evict."""
    pool = _pool(num_blocks=16, block_len=2)
    rc = RadixCache(pool)
    cold = np.arange(20, 26)
    cold_chain = pool.alloc(3)
    rc.insert(cold, cold_chain)
    pool.release(cold_chain)          # trie is the only holder
    hot = np.arange(40, 44)
    hot_chain = pool.alloc(2)
    rc.insert(hot, hot_chain)         # live: sequence still holds it
    warm = np.arange(60, 64)
    warm_chain = pool.alloc(2)
    rc.insert(warm, warm_chain)
    pool.release(warm_chain)          # trie-only, but touched later
    m = rc.match(warm)                # refresh warm's LRU stamp
    pool.release(m)
    free0 = pool.free_count
    freed = rc.evict(3)
    # the cold chain is strictly older: it evicts leaves-first
    assert freed == 3 and rc.evictions == 3
    assert pool.free_count == free0 + 3
    assert rc.match(cold) == []       # gone
    # live chain untouched even under a huge target
    rc.evict(100)
    assert all(pool.refcount(b) >= 2 for b in hot_chain)
    m = rc.match(hot)
    assert m == hot_chain[:1]
    pool.release(m)


# --------------------------------------------------------------------------- #
# engine: prefix sharing + slot recycle under EOS                             #
# --------------------------------------------------------------------------- #

def test_shared_prefix_eos_recycle_exact():
    """Satellite: two live streams share a prefix chain; the one that
    hits EOS frees its slot and refs while the survivor keeps decoding
    bit-exact, and a third request still hits the (intact) prefix."""
    model = _lm()
    eng = LMServingEngine(model, slots=2, cache_len=24, block_len=4,
                          prefill_buckets=(4, 8, 16))
    try:
        eng.warmup()
        p = np.arange(1, 13)  # 12 tokens = 3 full blocks; 2 matchable
        ref = np.asarray(generate(model, model.params,
                                  p[None].astype(np.int32), 8))[0]
        eos = int(ref[len(p) + 1])  # second generated token
        stop = int(np.argmax(ref[len(p):] == eos))
        s_eos = eng.submit(p, max_new_tokens=8, eos_id=eos)
        s_full = eng.submit(p, max_new_tokens=8)  # admitted 2nd: shares
        out_eos = s_eos.result(timeout=120)
        out_full = s_full.result(timeout=120)
        np.testing.assert_array_equal(out_eos, ref[:len(p) + stop + 1])
        np.testing.assert_array_equal(out_full, ref)
        assert eng.radix.hits >= 1  # the 2nd stream reused the chain
        assert _wait(lambda: eng.stats()["active"] == 0)
        # chain survived both releases: a 3rd request hits it too
        hits0 = eng.radix.hits
        np.testing.assert_array_equal(
            eng.generate(p, max_new_tokens=8, timeout=120), ref)
        assert eng.radix.hits == hits0 + 1
        assert eng.radix.matched_tokens >= 16  # 2 hits x 2 blocks x 4
    finally:
        eng.close()


def test_identical_prompt_reprefills_after_eviction():
    """Satellite: after its chain is evicted, an identical prompt is a
    cold miss that re-prefills correctly (no stale-table reuse)."""
    model = _lm()
    eng = LMServingEngine(model, slots=1, cache_len=24, block_len=4,
                          prefill_buckets=(4, 8, 16))
    try:
        p = np.arange(1, 13)
        ref = np.asarray(generate(model, model.params,
                                  p[None].astype(np.int32), 4))[0]
        np.testing.assert_array_equal(
            eng.generate(p, max_new_tokens=4, timeout=120), ref)
        assert _wait(lambda: eng.stats()["active"] == 0)
        assert eng.radix.evict(100) == 3  # drop the whole cached chain
        np.testing.assert_array_equal(
            eng.generate(p, max_new_tokens=4, timeout=120), ref)
    finally:
        eng.close()


# --------------------------------------------------------------------------- #
# pool pressure: typed rejection vs deferral (the faults gate)                #
# --------------------------------------------------------------------------- #

@pytest.mark.faults
def test_request_exceeds_pool_typed_rejection():
    """A request whose TOTAL block need exceeds the whole pool gets the
    permanent typed error, counted in serving/rejected_total."""
    model = _lm()
    eng = LMServingEngine(model, slots=1, cache_len=24, block_len=4,
                          num_blocks=4, prefill_buckets=(4, 8, 16))
    try:
        before = _rejected()
        rej0 = eng.metrics.rejected
        with pytest.raises(RequestExceedsPool):
            eng.submit(np.arange(1, 11), max_new_tokens=6)  # 4 blocks > 3
        assert isinstance(RequestExceedsPool("x"), ValueError)  # fatal class
        assert eng.metrics.rejected == rej0 + 1
        assert _rejected() == before + 1
        # a request that fits the pool is served fine
        assert eng.generate(np.arange(1, 7), max_new_tokens=4,
                            timeout=120).shape == (10,)
    finally:
        eng.close()


@pytest.mark.faults
def test_pool_pressure_defers_then_completes_exact():
    """Transient exhaustion: more concurrent requests than the pool can
    hold defer (requeue, FIFO kept) instead of failing, and every
    stream still matches offline generate bit-for-bit."""
    model = _lm()
    # capacity 8 at block_len 4: two worst-case requests in flight,
    # while 3 slots invite a third admission that must defer
    eng = LMServingEngine(model, slots=3, cache_len=16, block_len=4,
                          num_blocks=9, prefill_buckets=(4, 8, 16))
    try:
        eng.warmup()
        work = [(np.arange(1, t + 1), m)
                for t, m in ((6, 6), (9, 6), (5, 6), (8, 6), (7, 6), (4, 6))]
        streams = [eng.submit(p, max_new_tokens=m) for p, m in work]
        for (p, m), s in zip(work, streams):
            out = s.result(timeout=300)
            ref = np.asarray(generate(model, model.params,
                                      p[None].astype(np.int32), m))
            np.testing.assert_array_equal(out, ref[0])
        assert _wait(lambda: eng.metrics.completed == len(work))
    finally:
        eng.close()


# --------------------------------------------------------------------------- #
# observability: arena gauge reaches the summary plane                        #
# --------------------------------------------------------------------------- #

def test_arena_bytes_gauge_in_registry_and_summary(tmp_path):
    """Satellite: kvcache/arena_bytes is a registry gauge (so the SLO
    controller's headroom check can price cache memory) and flows into
    ObsSummary via the standard export."""
    from bigdl_tpu.visualization import ObsSummary

    model = _lm()
    eng = LMServingEngine(model, slots=1, cache_len=16, block_len=4,
                          prefill_buckets=(8, 16))
    try:
        snap = get_registry().snapshot()
        assert snap["kvcache/arena_bytes"]["value"] == \
            eng.pool.arena_bytes > 0
        assert snap["kvcache/arena_bytes"]["unit"] == "bytes"
        s = ObsSummary(str(tmp_path), "kv")
        get_registry().export_to_summary(s, step=1)
        vals = s.read_scalar("Obs/kvcache/arena_bytes")
        assert vals and vals[0][1] == eng.pool.arena_bytes
        s.close()
        assert eng.kvcache_headroom() == eng.pool.free_count // 4
    finally:
        eng.close()
