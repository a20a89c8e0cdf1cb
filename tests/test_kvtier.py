"""Host-tier KV offload + session hibernation (serving/kvtier).

Tier-1 coverage for the memory hierarchy below the HBM arena:

- HostBlockStore mechanics: put/get roundtrip, global LRU across the
  host and disk tiers, spill + CRC-verified rehydrate, corrupted spill
  degrading to a counted miss, scale-atomicity of quantized payloads.
- The single radix eviction funnel: every drop fires ``on_evict(path,
  block)`` before release, and a raising hook degrades to a plain drop.
- Chain demote -> promote bit-identity at the pool level, f32 AND int8
  (scales travel in the same payload).
- Session hibernation: a mid-decode stream swaps out of its slot (HBM
  chain -> host tier), its slot frees, and it resumes BIT-EXACTLY —
  both over the fast payload path and the payload-lost fallback
  (prompt re-prefill + decode-path replay), greedy and sampled.
- A 10-session oversubscribed trace over a ~2-chain pool: evicted
  prefix tails survive in the tier and returning sessions re-admit
  them with a nonzero tier hit rate.
"""
import os
import time

import numpy as np
import pytest

from bigdl_tpu.models.transformer import TransformerLM
from bigdl_tpu.serving import (BlockPool, HostBlockStore, LMServingEngine,
                               RadixCache)
from bigdl_tpu.serving.kvtier import block_path


def _payload(n, seed=0, L=1, H=2, B=4, D=3):
    rng = np.random.default_rng(seed)
    return {"k": rng.standard_normal((n, L, H, B, D)).astype(np.float32),
            "v": rng.standard_normal((n, L, H, B, D)).astype(np.float32)}


# --------------------------------------------------------------------------- #
# HostBlockStore                                                              #
# --------------------------------------------------------------------------- #

def test_store_put_get_roundtrip_and_pop():
    s = HostBlockStore(host_bytes=1 << 20, name="t-rt")
    p = _payload(2)
    s.put(("a",), p)
    got = s.get(("a",))
    assert np.array_equal(got["k"], p["k"])
    assert np.array_equal(got["v"], p["v"])
    assert s.get(("a",), pop=True) is not None
    assert s.get(("a",)) is None            # popped; now a miss
    st = s.stats()
    assert st["hits"] == 2 and st["misses"] == 1
    assert st["demotions"] == 1


def test_store_lru_spill_order_and_rehydrate(tmp_path):
    one = _payload(1)["k"].nbytes * 2       # bytes per 1-block payload
    s = HostBlockStore(host_bytes=3 * one, spill_dir=str(tmp_path),
                       name="t-spill")
    for i in range(5):
        s.put(("b", i), _payload(1, seed=i))
    st = s.stats()
    # host tier holds the 3 newest; the 2 OLDEST spilled, none dropped
    assert st["spills"] == 2 and st["drops"] == 0
    spilled = [k for k, e in s._entries.items() if e.where == "disk"]
    assert spilled == [("b", 0), ("b", 1)]
    # rehydrate verifies the CRC and returns the exact demoted bytes
    got = s.get(("b", 0))
    assert np.array_equal(got["k"], _payload(1, seed=0)["k"])
    assert s.stats()["corrupt_reads"] == 0


def test_store_drop_without_spill_dir():
    one = _payload(1)["k"].nbytes * 2
    s = HostBlockStore(host_bytes=2 * one, name="t-drop")
    for i in range(4):
        s.put(("c", i), _payload(1, seed=i))
    st = s.stats()
    assert st["drops"] == 2 and st["spills"] == 0
    assert s.get(("c", 0)) is None          # oldest went first
    assert s.get(("c", 3)) is not None


def test_store_corrupt_spill_reads_as_miss(tmp_path):
    one = _payload(1)["k"].nbytes * 2
    s = HostBlockStore(host_bytes=one, spill_dir=str(tmp_path),
                       name="t-crc")
    s.put(("d", 0), _payload(1))
    s.put(("d", 1), _payload(1, seed=1))    # forces ("d",0) to disk
    entry = s._entries[("d", 0)]
    assert entry.where == "disk"
    with open(entry.path, "wb") as f:
        f.write(b"not a kv block")
    assert s.get(("d", 0)) is None          # corrupt -> incident + miss
    assert s.stats()["corrupt_reads"] == 1
    assert ("d", 0) not in s._entries       # forgotten, not retried


def test_store_scales_demote_atomically():
    s = HostBlockStore(host_bytes=1 << 20, name="t-atomic")
    p = _payload(1)
    with pytest.raises(ValueError, match="atomically"):
        s.put(("e",), {"k": p["k"], "v": p["v"],
                       "ks": np.ones((1, 1, 2, 4), np.float32)})


def test_block_path_matches_radix_keys():
    toks = [3, 1, 4, 1, 5, 9, 2, 6]
    assert block_path(toks, 4, 2) == ((3, 1, 4, 1), (5, 9, 2, 6))


# --------------------------------------------------------------------------- #
# the single eviction funnel                                                  #
# --------------------------------------------------------------------------- #

def test_radix_on_evict_fires_before_release():
    pool = BlockPool(n_layers=1, n_heads=2, head_dim=4, block_len=4,
                     num_blocks=8)
    cache = RadixCache(pool)
    seen = []

    def hook(path, block):
        # the block must still be allocated (gatherable) in the hook
        seen.append((path, block, pool.refcount(block)))
    cache.on_evict = hook
    toks = list(range(8))
    blocks = pool.alloc(2)
    cache.insert(toks, blocks)
    pool.release(blocks)                    # trie holds the only refs
    freed = cache.evict(2)
    assert freed == 2
    assert len(seen) == 2
    # leaves-first: the deeper block evicts first, full path attached
    assert seen[0][0] == ((0, 1, 2, 3), (4, 5, 6, 7))
    assert seen[1][0] == ((0, 1, 2, 3),)
    assert all(rc >= 1 for _, _, rc in seen)


def test_radix_on_evict_raising_hook_degrades_to_drop():
    pool = BlockPool(n_layers=1, n_heads=2, head_dim=4, block_len=4,
                     num_blocks=8)
    cache = RadixCache(pool, on_evict=lambda p, b: 1 / 0)
    blocks = pool.alloc(1)
    cache.insert(list(range(4)), blocks)
    pool.release(blocks)
    assert cache.evict(1) == 1              # eviction proceeded
    assert cache.nodes == 0


# --------------------------------------------------------------------------- #
# demote -> promote bit-identity (pool level, f32 + int8)                     #
# --------------------------------------------------------------------------- #

@pytest.mark.parametrize("quant", [None, "int8"])
def test_export_tier_adopt_roundtrip_bit_identical(quant):
    import jax.numpy as jnp
    geom = dict(n_layers=2, n_heads=2, head_dim=4, block_len=4,
                num_blocks=8, kv_quant=quant)
    src, dst = BlockPool(**geom), BlockPool(**geom)
    from bigdl_tpu.serving.kvcache.blocks import write_rows
    ids = src.alloc(3)
    shape = (2, 3, 4, 2, 4)                 # whole blocks: (L, n, B, H, D)
    fill = jnp.arange(np.prod(shape)).reshape(shape)
    idx = jnp.asarray(ids, jnp.int32)

    def put(arena, rows):
        return write_rows(arena, slice(None), idx, None, rows)

    if quant:
        src.k = put(src.k, (fill % 127).astype(jnp.int8))
        src.v = put(src.v, (-fill % 127).astype(jnp.int8))
        sfill = jnp.arange(np.prod(shape[:4]), dtype=jnp.float32)
        src.ks = put(src.ks, sfill.reshape(shape[:4]) * 0.25)
        src.vs = put(src.vs, sfill.reshape(shape[:4]) * 0.5)
    else:
        src.k = put(src.k, fill.astype(jnp.float32))
        src.v = put(src.v, -fill.astype(jnp.float32))
    wire = src.export_chain(ids)
    # the wire keeps its per-block (L, H, B, D) payload whatever the arena
    assert wire["k"].shape == (3, 2, 2, 4, 4)
    assert np.array_equal(
        wire["k"], np.asarray(src.k[:, idx, :, :8]).reshape(shape)
        .transpose(1, 0, 3, 2, 4))
    if quant:                               # scales rode the payload
        assert wire["ks"].shape == (3, 2, 2, 4)
        assert wire["vs"].dtype == np.float32
    tier = HostBlockStore(host_bytes=1 << 20, name=f"t-rt-{quant}")
    tier.put(("chain",), wire)
    back = tier.get(("chain",), pop=True)
    fresh = dst.adopt_chain(back["k"], back["v"],
                            back.get("ks"), back.get("vs"))
    assert np.array_equal(np.asarray(src.k[:, ids]),
                          np.asarray(dst.k[:, fresh]))
    assert np.array_equal(np.asarray(src.v[:, ids]),
                          np.asarray(dst.v[:, fresh]))
    if quant:
        assert np.array_equal(np.asarray(src.ks[:, ids]),
                              np.asarray(dst.ks[:, fresh]))
        assert np.array_equal(np.asarray(src.vs[:, ids]),
                              np.asarray(dst.vs[:, fresh]))


# --------------------------------------------------------------------------- #
# session hibernation                                                         #
# --------------------------------------------------------------------------- #

@pytest.fixture(scope="module")
def kv_model():
    return TransformerLM(vocab_size=31, hidden_size=16, n_head=2,
                         n_layers=1, max_len=64,
                         pos_encoding="rope").build(seed=0)


_PROMPT = np.arange(1, 9, dtype=np.int32)
_ENG_KW = dict(slots=2, cache_len=56, max_new_tokens=40,
               prefill_buckets=(8,), block_len=4)


@pytest.fixture(scope="module")
def reference_runs(kv_model):
    """Uninterrupted outputs the hibernated runs must match exactly."""
    eng = LMServingEngine(kv_model, **_ENG_KW)
    greedy = eng.generate(_PROMPT, max_new_tokens=40)
    sampled = eng.generate(_PROMPT, max_new_tokens=40,
                           temperature=0.7, rng=7)
    eng.close()
    return greedy, sampled


def test_hibernate_resume_bit_exact(kv_model, reference_runs):
    tier = HostBlockStore(host_bytes=64 << 20, name="t-hib")
    eng = LMServingEngine(kv_model, kvtier=tier, **_ENG_KW)
    try:
        st = eng.submit(_PROMPT, max_new_tokens=40)
        next(st.tokens())
        assert eng.hibernate(st), "stream not seated (finished early?)"
        stats = eng.stats()
        assert stats["hibernated"] == 1 and stats["hibernations"] == 1
        # the slot and its HBM blocks actually freed
        assert len(eng._free) == eng.slots
        # ... and the stream is genuinely paused, not decoding
        frozen = len(st.generated)
        time.sleep(0.15)
        assert len(st.generated) == frozen
        assert tier.contains(("session", st.request_id))
        assert eng.resume(st)
        out = st.result(timeout=120)
        assert np.array_equal(out, reference_runs[0])
        assert eng.resumes == 1 and eng.resume_re_prefills == 0
        ts = tier.stats()
        assert ts["promotions"] >= 1
        assert ts["promote_bandwidth_mbs"] is None \
            or ts["promote_bandwidth_mbs"] > 0
        # double-hibernate of a finished stream is a clean refusal
        assert not eng.hibernate(st)
    finally:
        eng.close()


def test_hibernate_lost_payload_replays_bit_exact(kv_model,
                                                  reference_runs):
    """The tier dropped the session chain: resume re-prefills the
    PROMPT through the deterministic prefill path and force-replays
    the already-emitted tokens through the decode path — no token is
    re-emitted, and the continuation is still bit-exact (sampled)."""
    tier = HostBlockStore(host_bytes=64 << 20, name="t-lost")
    eng = LMServingEngine(kv_model, kvtier=tier, **_ENG_KW)
    try:
        st = eng.submit(_PROMPT, max_new_tokens=40,
                        temperature=0.7, rng=7)
        it = st.tokens()
        for _ in range(3):
            next(it)
        assert eng.hibernate(st)
        emitted_before = np.asarray(st.generated)
        assert len(emitted_before) >= 3
        # poison: consume the session payload out from under resume
        assert tier.get(("session", st.request_id), pop=True) is not None
        assert eng.resume(st)
        out = st.result(timeout=120)
        assert np.array_equal(out, reference_runs[1])
        # the replayed head was never re-emitted
        assert np.array_equal(np.asarray(st.generated)[:len(emitted_before)],
                              emitted_before)
        assert eng.resume_re_prefills == 1
    finally:
        eng.close()


def test_hibernate_resume_int8_scales_survive(kv_model):
    """int8 engine: the hibernated chain demotes WITH its scales and
    resumes bit-exactly vs an uninterrupted int8 run."""
    kw = dict(_ENG_KW, max_new_tokens=24, kv_quant="int8")
    ref_eng = LMServingEngine(kv_model, **kw)
    ref = ref_eng.generate(_PROMPT, max_new_tokens=24)
    ref_eng.close()
    tier = HostBlockStore(host_bytes=64 << 20, name="t-hib8")
    eng = LMServingEngine(kv_model, kvtier=tier, **kw)
    try:
        st = eng.submit(_PROMPT, max_new_tokens=24)
        next(st.tokens())
        assert eng.hibernate(st)
        payload = tier.get(("session", st.request_id))
        assert "ks" in payload and "vs" in payload   # scales demoted too
        assert eng.resume(st)
        assert np.array_equal(st.result(timeout=120), ref)
    finally:
        eng.close()


def test_close_resolves_hibernated_streams(kv_model):
    tier = HostBlockStore(host_bytes=64 << 20, name="t-close")
    eng = LMServingEngine(kv_model, kvtier=tier, **_ENG_KW)
    st = eng.submit(_PROMPT, max_new_tokens=40)
    next(st.tokens())
    assert eng.hibernate(st)
    eng.close()
    from bigdl_tpu.serving import ServingClosed
    with pytest.raises(ServingClosed):
        st.result(timeout=10)


# --------------------------------------------------------------------------- #
# oversubscribed session trace                                                #
# --------------------------------------------------------------------------- #

def test_oversubscribed_trace_reuses_tier(kv_model):
    """10 sessions over a pool that holds ~3 chains (>3x oversubscribed
    working set, 10x in sessions-per-slot): round 1 populates and the
    radix tail-evicts through the demote hook; round 2 replays the
    trace and returning prompts re-admit demoted blocks from the tier
    with a NONZERO hit rate."""
    tier = HostBlockStore(host_bytes=64 << 20, name="t-over")
    eng = LMServingEngine(kv_model, slots=2, cache_len=32,
                          max_new_tokens=4, prefill_buckets=(32,),
                          block_len=4, num_blocks=1 + 3 * 8,
                          kvtier=tier)
    try:
        rng = np.random.default_rng(0)
        head = rng.integers(1, 31, 8)
        # 17-token prompts: cap=(17-1)//4=4 blocks, so the evictable
        # leaf block is inside the matchable range on the return visit
        prompts = [np.concatenate(
            [head, rng.integers(1, 31, 9)]).astype(np.int32)
            for _ in range(10)]
        for _ in range(2):
            streams = [eng.submit(p) for p in prompts]
            for s in streams:
                s.result(timeout=120)
        ts = tier.stats()
        assert ts["demotions"] > 0, "oversubscription never demoted"
        assert ts["hits"] > 0 and ts["promotions"] > 0, \
            "returning sessions never reused the tier"
        assert ts["hit_rate"] > 0
        # engine-level stats surface the tier
        assert eng.stats()["kvtier"]["demotions"] == ts["demotions"]
        rs = eng.stats()["kvcache"]["prefix_cache"]
        assert rs["evictions"] >= ts["demotions"]
    finally:
        eng.close()


# --------------------------------------------------------------------------- #
# metrics surface                                                             #
# --------------------------------------------------------------------------- #

def test_tier_metrics_publish_to_registry():
    from bigdl_tpu.obs import get_registry
    s = HostBlockStore(host_bytes=1 << 20, name="t-reg")
    s.put(("m",), _payload(1))
    s.get(("m",))
    s.get(("nope",))
    snap = get_registry().snapshot()
    assert snap["kvtier/t-reg/demotions"]["value"] == 1
    assert snap["kvtier/t-reg/hits"]["value"] == 1
    assert snap["kvtier/t-reg/misses"]["value"] == 1
    assert snap["kvtier/t-reg/host_bytes"]["value"] > 0
    # a SECOND store under the same name starts from zero (private
    # counters re-registered, not shared)
    s2 = HostBlockStore(host_bytes=1 << 20, name="t-reg")
    snap2 = get_registry().snapshot()
    assert snap2["kvtier/t-reg/demotions"]["value"] == 0
