"""Prefix-affinity routing (serving/router).

Tier-1 coverage for the cache-aware dispatch plane:

- prefix_signatures: deterministic 64-bit block fingerprints, capped
  exactly like RadixCache.match (the final token is never cached).
- RadixSummary: O(1) incremental maintenance under the trie hooks —
  inserts, evictions, and the attach-time replay of an existing trie.
- RadixRouter scoring: longest-prefix wins, exact ties break
  least-loaded by (inflight, dispatched), affinity_weight trades
  affinity against load, cold prompts decline to the caller's
  least-loaded fallback, and an evicted chain is NEVER dispatched to
  on a stale summary (the double-prefill hazard).
- SessionTable: sticky lookup, hibernation markers, bounded LRU.
- LMReplicaSet end-to-end: sticky sessions return to their replica
  bit-exactly, stickiness survives a hibernate/resume round-trip, and
  (faults) a replica killed mid-stream or mid-hibernation re-routes
  with zero accepted loss and byte-identical output.
"""
import time

import numpy as np
import pytest

from bigdl_tpu.models.transformer import TransformerLM
from bigdl_tpu.serving import (BlockPool, HostBlockStore, LMServingEngine,
                               RadixCache)
from bigdl_tpu.serving.kvcache.radix import (_SIG_ROOT, _sig_extend,
                                             prefix_signatures)
from bigdl_tpu.serving.router import (LMReplicaSet, RadixRouter,
                                      RadixSummary, SessionTable)


def _pool(num_blocks=8, block_len=2):
    return BlockPool(n_layers=1, n_heads=1, head_dim=2,
                     block_len=block_len, num_blocks=num_blocks)


class _FakeReplica:
    """The _Replica protocol the router scores: name + load counters."""

    def __init__(self, name, inflight=0, dispatched=0):
        self.name = name
        self.inflight = inflight
        self.dispatched = dispatched


# --------------------------------------------------------------------------- #
# prefix signatures                                                           #
# --------------------------------------------------------------------------- #

def test_prefix_signatures_deterministic_and_capped():
    toks = np.arange(10, 20)            # t=10, block_len=2
    a = prefix_signatures(toks, 2)
    b = prefix_signatures(toks.copy(), 2)
    assert a == b and len(a) == (10 - 1) // 2   # match()'s cap: 4, not 5
    # the chain hash is the FNV fold of the root->node block keys
    sig = _sig_extend(_SIG_ROOT, (10, 11))
    assert a[0] == sig
    assert a[1] == _sig_extend(sig, (12, 13))
    # a diverging block changes that signature and every one after it
    other = toks.copy()
    other[2] = 99
    c = prefix_signatures(other, 2)
    assert c[0] == a[0] and c[1] != a[1]


def test_prefix_signatures_short_prompt_is_empty():
    assert prefix_signatures(np.array([5, 6]), 2) == []    # cap = 0
    assert prefix_signatures(np.array([], dtype=np.int32), 2) == []


# --------------------------------------------------------------------------- #
# RadixSummary maintenance                                                    #
# --------------------------------------------------------------------------- #

def test_summary_tracks_insert_and_evict():
    pool = _pool()
    rc = RadixCache(pool)
    summ = RadixSummary("r0")
    rc.attach_summary(summ)
    toks = np.arange(10, 16)            # 3 full blocks
    chain = pool.alloc(3)
    rc.insert(toks, chain)
    assert len(summ) == rc.nodes == 3
    sigs = prefix_signatures(np.arange(10, 17), 2)   # 7 toks -> cap 3
    assert summ.match_blocks(sigs) == 3
    pool.release(chain)                  # trie-only refs: evictable
    v0 = summ.version
    rc.evict(99)                         # leaves-first: whole chain goes
    assert rc.nodes == 0 and len(summ) == 0
    assert summ.match_blocks(sigs) == 0
    assert summ.evicts == 3 and summ.version > v0


def test_summary_attach_replays_existing_trie():
    pool = _pool()
    rc = RadixCache(pool)
    toks = np.arange(20, 26)
    chain = pool.alloc(3)
    rc.insert(toks, chain)
    summ = RadixSummary("late")
    rc.attach_summary(summ)              # one walk, then O(1) hooks
    assert len(summ) == 3
    assert summ.match_blocks(prefix_signatures(np.arange(20, 27), 2)) == 3


def test_summary_match_stops_at_first_gap():
    summ = RadixSummary()
    sigs = prefix_signatures(np.arange(0, 9), 2)     # 4 sigs
    for s in (sigs[0], sigs[1], sigs[3]):            # hole at depth 2
        summ.on_insert(s)
    assert summ.match_blocks(sigs) == 2  # ancestor gap ends the prefix


# --------------------------------------------------------------------------- #
# RadixRouter scoring                                                         #
# --------------------------------------------------------------------------- #

def _router_with(matches):
    """Router whose summaries match the canonical prompt to the given
    depth per replica name; returns (router, prompt_sigs)."""
    sigs = prefix_signatures(np.arange(100, 117), 4)  # 4 block sigs
    r = RadixRouter(affinity_weight=0.7)
    for name, depth in matches.items():
        s = RadixSummary(name)
        for sg in sigs[:depth]:
            s.on_insert(sg)
        r.register(name, s)
    return r, sigs


def test_router_prefers_longest_prefix():
    router, sigs = _router_with({"a": 1, "b": 3})
    a, b = _FakeReplica("a"), _FakeReplica("b", inflight=1)
    # b matches deeper; its one in-flight request doesn't flip w=0.7
    pick = router.pick([a, b], {"prompt_sigs": sigs})
    assert pick is b
    assert router.affinity_hits == 1


def test_router_tie_breaks_least_loaded():
    router, sigs = _router_with({"a": 2, "b": 2, "c": 2})
    a = _FakeReplica("a", inflight=2, dispatched=9)
    b = _FakeReplica("b", inflight=1, dispatched=5)
    c = _FakeReplica("c", inflight=1, dispatched=4)
    # equal match + equal inflight: dispatched breaks the tie, exactly
    # the breaker core's least-loaded key
    assert router.pick([a, b, c], {"prompt_sigs": sigs}) is c


def test_router_cold_prompt_declines():
    router, sigs = _router_with({"a": 0, "b": 0})
    a, b = _FakeReplica("a"), _FakeReplica("b")
    assert router.pick([a, b], {"prompt_sigs": sigs}) is None
    assert router.pick([a, b], {"prompt_sigs": []}) is None
    assert router.cold_dispatches == 1   # no-sigs dispatch isn't "cold"
    assert router.affinity_hits == 0


def test_router_affinity_weight_trades_against_load():
    sigs = prefix_signatures(np.arange(100, 117), 4)
    full = RadixSummary("hot")
    for sg in sigs:
        full.on_insert(sg)
    part = RadixSummary("idle")
    part.on_insert(sigs[0])
    hot = _FakeReplica("hot", inflight=10)
    idle = _FakeReplica("idle", inflight=0)
    for w, want in ((0.95, "hot"), (0.2, "idle")):
        r = RadixRouter(affinity_weight=w)
        r.register("hot", full)
        r.register("idle", part)
        assert r.pick([hot, idle], {"prompt_sigs": sigs}).name == want


def test_router_never_dispatches_to_evicted_chain():
    """The staleness hazard: a chain the trie just evicted must not
    attract its session back (dead sticky cache -> double prefill).
    The summary hook fires under the trie lock, so right after the
    eviction the router already declines."""
    pool = _pool()
    rc = RadixCache(pool)
    summ = RadixSummary("r0")
    rc.attach_summary(summ)
    toks = np.arange(30, 36)
    chain = pool.alloc(3)
    rc.insert(toks, chain)
    router = RadixRouter()
    router.register("r0", summ)
    rep = _FakeReplica("r0")
    sigs = prefix_signatures(np.arange(30, 37), 2)
    assert router.pick([rep], {"prompt_sigs": sigs}) is rep
    pool.release(chain)
    rc.evict(99)
    # evicted everywhere -> cold dispatch (least-loaded fallback), not
    # a stale affinity pick
    assert router.pick([rep], {"prompt_sigs": sigs}) is None
    assert router.cold_dispatches == 1


# --------------------------------------------------------------------------- #
# SessionTable                                                                #
# --------------------------------------------------------------------------- #

def test_session_table_record_lookup_hibernate():
    t = SessionTable()
    assert t.lookup("s1") is None and t.lookup(None) is None
    t.record("s1", "r0")
    assert t.lookup("s1") == "r0"
    t.mark_hibernated("s1", "r1")        # tier entry lives on r1 now
    assert t.lookup("s1") == "r1"
    t.record("s1", "r2")                 # re-dispatch clears the marker
    assert t.lookup("s1") == "r2"
    t.forget("s1")
    assert t.lookup("s1") is None


def test_session_table_bounded_lru():
    t = SessionTable(max_sessions=2)
    t.record("a", "r0")
    t.record("b", "r0")
    assert t.lookup("a") == "r0"         # refreshes a's LRU position
    t.record("c", "r1")                  # evicts b, the oldest
    assert t.lookup("b") is None
    assert t.lookup("a") == "r0" and t.lookup("c") == "r1"
    assert t.evicted == 1


# --------------------------------------------------------------------------- #
# LMReplicaSet end-to-end                                                     #
# --------------------------------------------------------------------------- #

@pytest.fixture(scope="module")
def rt_model():
    return TransformerLM(vocab_size=31, hidden_size=16, n_head=2,
                         n_layers=1, max_len=64,
                         pos_encoding="rope").build(seed=0)


_PROMPT = np.arange(1, 9, dtype=np.int32)
_ENG_KW = dict(slots=2, cache_len=56, max_new_tokens=24,
               prefill_buckets=(8, 16), block_len=4)


@pytest.fixture(scope="module")
def rt_reference(rt_model):
    """Uninterrupted single-engine outputs the routed runs must match
    exactly — same prompt, seed, temperature on every arm."""
    eng = LMServingEngine(rt_model, **_ENG_KW)
    turn1 = eng.generate(_PROMPT, max_new_tokens=6,
                         temperature=0.7, rng=7)
    prompt2 = np.concatenate([turn1, [3, 5, 2]]).astype(np.int32)
    turn2 = eng.generate(prompt2, max_new_tokens=6,
                         temperature=0.7, rng=8)
    sampled_long = eng.generate(_PROMPT, max_new_tokens=12,
                                temperature=0.7, rng=5)
    eng.close()
    return {"turn1": turn1, "prompt2": prompt2, "turn2": turn2,
            "sampled_long": sampled_long}


def test_routed_set_sticky_session_bit_exact(rt_model, rt_reference):
    rs = LMReplicaSet(rt_model, 2, router=RadixRouter(), name="t-sticky",
                      **_ENG_KW)
    try:
        t1 = rs.submit(_PROMPT, session_id="chat", max_new_tokens=6,
                       temperature=0.7, rng=7)
        out1 = t1.result(timeout=60)
        assert np.array_equal(out1, rt_reference["turn1"])
        first = t1.replica_name
        t2 = rs.submit(rt_reference["prompt2"], session_id="chat",
                       max_new_tokens=6, temperature=0.7, rng=8)
        out2 = t2.result(timeout=60)
        assert np.array_equal(out2, rt_reference["turn2"])
        # the returning turn stuck to its replica and reused the chain
        assert t2.replica_name == first
        st = rs.stats()
        assert st["sessions"]["sticky_hits"] >= 1
        assert st["prefix_cache"]["hits"] >= 1
        assert st["prefix_cache"]["prefill_tokens_saved"] > 0
    finally:
        rs.close()


def test_stickiness_survives_hibernation_roundtrip(rt_model, rt_reference):
    rs = LMReplicaSet(
        rt_model, 2, router=RadixRouter(),
        kvtier_factory=lambda n: HostBlockStore(host_bytes=32 << 20,
                                                name=n),
        name="t-hib", **_ENG_KW)
    try:
        st = rs.submit(_PROMPT, session_id="hib", max_new_tokens=12,
                       temperature=0.7, rng=5)
        it = st.tokens(timeout=60)
        next(it)
        assert rs.hibernate(st), "stream not seated (finished early?)"
        # the session remembers which replica's tier holds its chain
        assert rs.sessions.lookup("hib") == st.replica_name
        assert rs.stats()["hibernations"] == 1
        assert rs.resume(st) is True     # fast path: same replica
        out = st.result(timeout=60)
        assert np.array_equal(out, rt_reference["sampled_long"])
        assert rs.stats()["resumes"] == 1
        assert rs.stats()["resume_re_routes"] == 0
    finally:
        rs.close()


def test_router_fallback_when_all_summaries_cold(rt_model):
    """A router with nothing to say never owns liveness: cold prompts
    dispatch least-loaded and still complete."""
    rs = LMReplicaSet(rt_model, 2, router=RadixRouter(), name="t-cold",
                      **_ENG_KW)
    try:
        outs = [rs.submit(np.arange(1 + i, 9 + i, dtype=np.int32),
                          max_new_tokens=4)
                for i in range(3)]
        for s in outs:
            assert s.result(timeout=60).shape[0] == 12
        assert rs.router.cold_dispatches >= 1
    finally:
        rs.close()


# --------------------------------------------------------------------------- #
# faults: chaos replica death                                                 #
# --------------------------------------------------------------------------- #

@pytest.mark.faults
def test_kill_replica_mid_stream_replays_bit_exact(rt_model, rt_reference):
    rs = LMReplicaSet(rt_model, 2, router=RadixRouter(), name="t-chaos",
                      **_ENG_KW)
    try:
        st = rs.submit(_PROMPT, session_id="doomed", max_new_tokens=12,
                       temperature=0.7, rng=5)
        it = st.tokens(timeout=60)
        next(it)
        next(it)
        victim = st.replica_name
        rs.kill_replica(victim)
        # zero accepted loss: the stream re-prefills on the survivor,
        # replays the two emitted tokens, and finishes byte-identical
        out = st.result(timeout=60)
        assert np.array_equal(out, rt_reference["sampled_long"])
        assert st.re_dispatches == 1
        assert st.replica_name != victim
        reps = rs.stats()["replicas"]
        assert reps[victim]["state"] == "draining"
        assert rs.stats()["sessions"]["re_routes"] >= 1
    finally:
        rs.close()


@pytest.mark.faults
def test_kill_hibernation_holder_resume_re_routes(rt_model, rt_reference):
    rs = LMReplicaSet(
        rt_model, 2, router=RadixRouter(),
        kvtier_factory=lambda n: HostBlockStore(host_bytes=32 << 20,
                                                name=n),
        name="t-chaos-hib", **_ENG_KW)
    try:
        st = rs.submit(_PROMPT, session_id="hib2", max_new_tokens=12,
                       temperature=0.7, rng=5)
        it = st.tokens(timeout=60)
        next(it)
        assert rs.hibernate(st)
        victim = st.replica_name
        rs.kill_replica(victim)          # tier entry dies with it
        # _fail_all woke the forwarder; give it a beat to re-dispatch
        deadline = time.perf_counter() + 30
        while st.re_dispatches == 0 and time.perf_counter() < deadline:
            time.sleep(0.01)
        assert rs.resume(st) is True     # degraded: already re-routed
        out = st.result(timeout=60)
        assert np.array_equal(out, rt_reference["sampled_long"])
        assert st.replica_name != victim
        assert rs.stats()["resume_re_routes"] + \
            rs.stats()["sessions"]["re_routes"] >= 1
    finally:
        rs.close()


@pytest.mark.faults
def test_kill_last_replica_fails_streams_typed(rt_model):
    from bigdl_tpu.resilience.errors import BackendLostError
    rs = LMReplicaSet(rt_model, 2, router=RadixRouter(), name="t-doom",
                      **_ENG_KW)
    try:
        st = rs.submit(_PROMPT, max_new_tokens=12, temperature=0.7,
                       rng=5)
        next(st.tokens(timeout=60))
        for name in list(rs.stats()["replicas"]):
            rs.kill_replica(name)
        with pytest.raises(BackendLostError):
            st.result(timeout=60)
    finally:
        rs.close()
