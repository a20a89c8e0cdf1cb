"""Disaggregated prefill/decode serving: KV-chain migration, chunked
prefill, phase-tagged placement, and the serving.migrate fault site.

Unit tier covers BlockPool.export_chain/adopt_chain (content fidelity,
refcount conservation, all-or-nothing under pressure, typed
PoolExhausted) and PlacementPolicy phase tags.  E2E tier asserts the
disaggregated coordinator and the chunked-prefill engine stream
BIT-EXACT vs the co-located engine — greedy and sampled, radix sharing
on, int8 target — and that the two serving.migrate fault kinds resolve
to retry / re-prefill with zero accepted-request loss.
"""
import numpy as np
import pytest

from bigdl_tpu.models.transformer import TransformerLM
from bigdl_tpu.obs import get_registry
from bigdl_tpu.serving import (DisaggCoordinator, LMServingEngine,
                               PlacementPolicy)
from bigdl_tpu.serving.kvcache import BlockPool, PoolExhausted
from bigdl_tpu.serving.placement import DeviceTopology


def _lm(vocab=31, hidden=16, heads=2, layers=1, max_len=64, seed=0):
    return TransformerLM(vocab_size=vocab, hidden_size=hidden,
                         n_head=heads, n_layers=layers, max_len=max_len,
                         pos_encoding="rope").build(seed=seed)


@pytest.fixture(scope="module")
def lm_model():
    return _lm()


def _prompts(sizes=(5, 12, 23, 9, 17, 30), seed=7, vocab=31):
    rng = np.random.default_rng(seed)
    return [rng.integers(1, vocab, size=n).astype(np.int32)
            for n in sizes]


def _serve_all(target, prompts, max_new=8):
    """Submit every prompt (alternating greedy/sampled) and collect the
    full streams."""
    streams = [target.submit(p, temperature=0.7 if i % 2 else 0.0, rng=i)
               for i, p in enumerate(prompts)]
    return [s.result(timeout=120) for s in streams]


@pytest.fixture(scope="module")
def colocated_ref(lm_model):
    """The co-located engine's streams — the exactness oracle every
    disaggregated/chunked variant must reproduce bit-for-bit."""
    prompts = _prompts()
    with LMServingEngine(lm_model, slots=2, cache_len=48,
                         max_new_tokens=8,
                         prefill_buckets=(4, 8, 16)) as eng:
        outs = _serve_all(eng, prompts)
    return prompts, outs


# --------------------------------------------------------------------------- #
# BlockPool migration primitives                                              #
# --------------------------------------------------------------------------- #

def _pool(num_blocks=8, block_len=4):
    return BlockPool(n_layers=2, n_heads=2, head_dim=3,
                     block_len=block_len, num_blocks=num_blocks)


def _fill(pool, ids, seed=0):
    """Write distinct recognisable rows into ``ids`` and return the
    host copies."""
    import jax.numpy as jnp
    from bigdl_tpu.serving.kvcache.blocks import write_rows
    rng = np.random.default_rng(seed)
    L, H, B, D = pool.wire_shape
    k = rng.standard_normal((L, len(ids), H, B, D)).astype(pool.dtype)
    v = rng.standard_normal((L, len(ids), H, B, D)).astype(pool.dtype)
    idx = jnp.asarray(ids, jnp.int32)
    # whole blocks through the pool's own writer: (L, n, B, H, D) rows
    pool.k = write_rows(pool.k, slice(None), idx, None,
                        jnp.asarray(k).swapaxes(2, 3))
    pool.v = write_rows(pool.v, slice(None), idx, None,
                        jnp.asarray(v).swapaxes(2, 3))
    return k, v


def test_export_adopt_roundtrip_exact_and_refcounts():
    """Contents survive the hop bit-for-bit; the source pool's
    refcounts are untouched and adopted blocks arrive at refcount 1."""
    src, dst = _pool(), _pool()
    ids = src.alloc(3)
    k, v = _fill(src, ids)
    wire = src.export_chain(ids)
    assert wire["blocks"] == 3
    assert wire["k"].shape == (3,) + src.wire_shape == (3, 2, 2, 4, 3)
    np.testing.assert_array_equal(wire["k"],
                                  np.moveaxis(k, 0, 1))
    assert all(src.refcount(b) == 1 for b in ids)  # export never refs

    new = dst.adopt_chain(wire["k"], wire["v"], extra_blocks=2)
    assert len(new) == 5
    assert all(dst.refcount(b) == 1 for b in new)
    assert dst.free_count == dst.capacity - 5
    got = dst.export_chain(new[:3])
    np.testing.assert_array_equal(got["k"], wire["k"])
    np.testing.assert_array_equal(got["v"], wire["v"])


def test_export_chunked_slices_match_one_shot():
    """A chunk ceiling smaller than one block still yields the same
    payload — the slicer just walks block-by-block."""
    src = _pool()
    ids = src.alloc(4)
    _fill(src, ids, seed=3)
    one = src.export_chain(ids)
    sliced = src.export_chain(ids, chunk_bytes=1)  # floor: 1 block/slice
    np.testing.assert_array_equal(one["k"], sliced["k"])
    np.testing.assert_array_equal(one["v"], sliced["v"])


def test_adopt_all_or_nothing_under_pressure():
    """A destination pool that cannot seat the whole chain + tail
    raises the TRANSIENT type and is left exactly as found."""
    src, dst = _pool(num_blocks=8), _pool(num_blocks=4)  # dst capacity 3
    ids = src.alloc(3)
    _fill(src, ids)
    wire = src.export_chain(ids)
    free_before = dst.free_count
    with pytest.raises(PoolExhausted):
        dst.adopt_chain(wire["k"], wire["v"], extra_blocks=1)  # needs 4
    assert dst.free_count == free_before  # nothing leaked


def test_adopt_releases_on_transfer_failure(monkeypatch):
    """A mid-transfer error releases every allocated block before
    propagating — a half-migrated chain never strands pool memory."""
    import bigdl_tpu.utils.transfer as transfer
    src, dst = _pool(), _pool()
    ids = src.alloc(2)
    _fill(src, ids)
    wire = src.export_chain(ids)

    def _boom(*a, **kw):
        raise RuntimeError("wire died")

    monkeypatch.setattr(transfer, "chunked_device_put", _boom)
    free_before = dst.free_count
    with pytest.raises(RuntimeError, match="wire died"):
        dst.adopt_chain(wire["k"], wire["v"], extra_blocks=2)
    assert dst.free_count == free_before


def test_adopt_rejects_mismatched_wire():
    dst = _pool()
    k = np.zeros((2, 2, 2, 4, 3), np.float32)
    v = np.zeros((1, 2, 2, 4, 3), np.float32)
    with pytest.raises(ValueError, match="wire shapes differ"):
        dst.adopt_chain(k, v)


def test_adopt_empty_wire_reserves_tail_only():
    """A fully radix-matched migration wires zero blocks but still
    atomically reserves the generation tail."""
    dst = _pool()
    empty = np.zeros((0,) + dst.wire_shape, dst.dtype)
    ids = dst.adopt_chain(empty, empty, extra_blocks=2)
    assert len(ids) == 2 and all(dst.refcount(b) == 1 for b in ids)


# --------------------------------------------------------------------------- #
# PlacementPolicy phase tags                                                  #
# --------------------------------------------------------------------------- #

def test_placement_phase_tags_and_gauges():
    pol = PlacementPolicy(DeviceTopology(), slots=4, tp=1)
    a = pol.acquire(phase="prefill")
    b = pol.acquire(phase="decode")
    c = pol.acquire(phase="decode")
    d = pol.acquire()  # untagged keeps the original contract
    assert pol.phase_of(a) == "prefill" and pol.phase_of(c) == "decode"
    assert pol.phase_of(d) is None
    assert pol.phase_counts() == {"prefill": 1, "decode": 2,
                                  "untagged": 1}
    snap = get_registry().snapshot()
    assert snap["serving/placement/phase/prefill"]["value"] == 1
    assert snap["serving/placement/phase/decode"]["value"] == 2
    st = pol.stats()
    assert st["phase_counts"]["decode"] == 2
    assert {s["phase"] for s in st["slots"]} == {"prefill", "decode", None}
    pol.release(b)
    pol.release(c)
    assert pol.phase_counts() == {"prefill": 1, "untagged": 1}
    snap = get_registry().snapshot()
    assert snap["serving/placement/phase/decode"]["value"] == 0  # zeroed
    # a released slot re-acquires under a new phase cleanly
    e = pol.acquire(phase="prefill")
    assert pol.phase_counts()["prefill"] == 2
    for s in (a, d, e):
        pol.release(s)


# --------------------------------------------------------------------------- #
# chunked-prefill interleaving (co-located fallback)                          #
# --------------------------------------------------------------------------- #

def test_chunked_prefill_exact_and_itl_split(lm_model, colocated_ref):
    """max_prefill_chunk_tokens bounds the per-round prefill stall
    without changing a single token; the per-phase ITL histograms
    split decode-only gaps from prefill-interrupted ones."""
    prompts, ref = colocated_ref
    with LMServingEngine(lm_model, slots=2, cache_len=48, block_len=4,
                         max_new_tokens=8, prefill_buckets=(4, 8, 16),
                         max_prefill_chunk_tokens=8) as eng:
        outs = _serve_all(eng, prompts)
        snap = eng.metrics.snapshot()
        st = eng.stats()
    for got, want in zip(outs, ref):
        np.testing.assert_array_equal(got, want)
    assert st["max_prefill_chunk_tokens"] == 8
    # every ITL lands in exactly one split histogram
    assert (snap["itl_decode"]["count"]
            + snap["itl_prefill_gap"]["count"]) == snap["itl"]["count"]
    assert snap["itl_decode"]["count"] > 0
    assert snap["itl_prefill_gap"]["count"] > 0  # interleaving happened


def test_chunk_cap_must_fit_a_block(lm_model):
    """Sub-block buckets cannot chunk — typed at construction."""
    with pytest.raises(ValueError, match="block-aligned"):
        LMServingEngine(lm_model, slots=1, cache_len=48, block_len=16,
                        prefill_buckets=(4, 8),
                        max_prefill_chunk_tokens=8)


# --------------------------------------------------------------------------- #
# end-to-end migration exactness                                              #
# --------------------------------------------------------------------------- #

def test_disagg_streams_bit_exact(lm_model, colocated_ref):
    """Greedy AND sampled streams through the disaggregated pools match
    the co-located engine token-for-token; every request migrated."""
    prompts, ref = colocated_ref
    with DisaggCoordinator(lm_model, prefill_replicas=1,
                           decode_replicas=1, slots=2, cache_len=48,
                           max_new_tokens=8,
                           prefill_buckets=(4, 8, 16)) as co:
        outs = _serve_all(co, prompts)
        st = co.stats()
    for got, want in zip(outs, ref):
        np.testing.assert_array_equal(got, want)
    assert st["migrations"] == len(prompts)
    assert st["adopted"] == len(prompts)
    assert st["lost_payloads"] == 0
    assert st["decode"]["completed"] == len(prompts)


def test_disagg_int8_radix_sharing_survives_hop(lm_model):
    """int8 target, radix on: repeated prompts dedupe against the
    DECODE replica's trie, so repeats wire fewer blocks than the first
    pass — prefix sharing survives the migration — and the streams
    stay exact vs the co-located int8 engine."""
    qlm = lm_model.quantize("int8")
    assert qlm.quant_report["bytes_saved"] > 0
    base = np.asarray([3, 9, 27, 14, 8, 26, 11, 5, 19, 22, 7, 30],
                      np.int32)
    prompts = [base, base.copy(),                    # identical head
               np.concatenate([base, [4, 17, 2]])]   # shared prefix
    kw = dict(slots=2, cache_len=48, block_len=4, max_new_tokens=6,
              prefill_buckets=(4, 8, 16), enable_prefix_cache=True)
    with LMServingEngine(qlm, **kw) as eng:
        ref = _serve_all(eng, prompts, max_new=6)
    with DisaggCoordinator(qlm, prefill_replicas=1, decode_replicas=1,
                           **kw) as co:
        # serial submission so radix insertion precedes the re-match
        outs = []
        for i, p in enumerate(prompts):
            s = co.submit(p, temperature=0.7 if i % 2 else 0.0, rng=i)
            outs.append(s.result(timeout=120))
        st = co.stats()
    for got, want in zip(outs, ref):
        np.testing.assert_array_equal(got, want)
    assert st["migrations"] == 3
    # 12-token prompt at block_len 4 = 3 blocks.  The radix match caps
    # at (t-1)//B blocks (at least one token must prefill), so the
    # identical repeat matches 2 and wires only its last block, and
    # the extended prompt (4 blocks) matches 3 and wires its tail —
    # 5 total vs 10 without sharing
    per_prompt_blocks = [3, 1, 1]
    assert st["migrated_blocks"] == sum(per_prompt_blocks)


def test_disagg_defers_under_pool_pressure(lm_model):
    """A decode pool that can only seat one chain at a time defers
    adoptions (typed, FIFO) instead of failing them — every accepted
    stream still completes exactly."""
    prompts = _prompts(sizes=(20, 24, 22), seed=3)
    kw = dict(slots=2, cache_len=32, block_len=4, max_new_tokens=6,
              prefill_buckets=(4, 8, 16), enable_prefix_cache=False,
              num_blocks=1 + 2 * 8)  # two worst-case chains, tight
    with LMServingEngine(lm_model, **kw) as eng:
        ref = _serve_all(eng, prompts, max_new=6)
    with DisaggCoordinator(lm_model, prefill_replicas=1,
                           decode_replicas=1, **kw) as co:
        outs = _serve_all(co, prompts, max_new=6)
        st = co.stats()
    for got, want in zip(outs, ref):
        np.testing.assert_array_equal(got, want)
    assert st["migrations"] == len(prompts)


def test_prefill_replica_cannot_speculate(lm_model):
    from bigdl_tpu.serving.spec import SpecConfig
    with pytest.raises(ValueError, match="cannot speculate"):
        LMServingEngine(lm_model, slots=1, cache_len=48,
                        prefill_buckets=(8,), migrate=lambda *a: None,
                        spec=SpecConfig(k=2))


# --------------------------------------------------------------------------- #
# independent phase scaling                                                   #
# --------------------------------------------------------------------------- #

def test_try_scale_up_gates_on_placement(lm_model):
    """Scale-up adds a replica to ONE phase, tagged on the placement
    policy; a full device set refuses (falsy) — the SLO ladder's
    fall-through-to-admission contract."""
    pol = PlacementPolicy(DeviceTopology(), slots=3, tp=1)
    with DisaggCoordinator(lm_model, prefill_replicas=1,
                           decode_replicas=1, placement=pol,
                           slots=2, cache_len=48, max_new_tokens=8,
                           prefill_buckets=(4, 8, 16)) as co:
        assert pol.phase_counts() == {"prefill": 1, "decode": 1}
        assert co.try_scale_up("decode") is True
        assert len(co.decode) == 2
        assert pol.phase_counts() == {"prefill": 1, "decode": 2}
        assert co.try_scale_up("prefill") is False  # device set full
        assert len(co.prefill) == 1
        # the grown pool still serves exactly
        prompts, _ = _prompts(sizes=(6, 14)), None
        outs = _serve_all(co, prompts)
        assert all(len(o) for o in outs)
        with pytest.raises(ValueError, match="unknown phase"):
            co.try_scale_up("verify")
    assert pol.headroom() == 3  # close released every slot


def test_slo_controllers_watch_per_phase_histograms(lm_model):
    """The two ladders actuate their own phase: hot TTFT grows the
    prefill pool, hot decode-ITL grows the decode pool."""
    with DisaggCoordinator(lm_model, prefill_replicas=1,
                           decode_replicas=1, max_replicas_per_phase=2,
                           slots=2, cache_len=48, max_new_tokens=8,
                           prefill_buckets=(4, 8, 16)) as co:
        ttft_ctl, itl_ctl = co.slo_controllers(
            ttft_target_s=0.5, itl_target_s=0.05,
            window_intervals=2, hot_streak=2)
        assert ttft_ctl.histogram is co.prefill_metrics.ttft
        assert itl_ctl.histogram is co.decode_metrics.itl_decode
        for _ in range(4):  # hot TTFT window
            co.prefill_metrics.ttft.observe(2.0)
            ttft_ctl.tick()
        assert len(co.prefill) == 2 and len(co.decode) == 1
        for _ in range(4):
            co.decode_metrics.itl_decode.observe(1.0)
            itl_ctl.tick()
        assert len(co.decode) == 2
        # both phases now at the ceiling
        assert co.try_scale_up("prefill") is False
        assert co.try_scale_up("decode") is False


# --------------------------------------------------------------------------- #
# the serving.migrate fault site                                              #
# --------------------------------------------------------------------------- #

@pytest.mark.faults
@pytest.mark.parametrize("spec,expect", [
    ("serving.migrate:transient:count=2", "retried"),
    ("serving.migrate:backend_lost:p=0.5", "re_prefilled"),
])
def test_migrate_fault_matrix_zero_accepted_loss(lm_model, colocated_ref,
                                                 monkeypatch, spec,
                                                 expect):
    """Transients retry the chain export under with_backoff; a lost
    backend drops the payload and the decode replica re-prefills —
    either way every accepted stream completes BIT-EXACT (zero loss)
    and the outcome is counted."""
    from bigdl_tpu.resilience import faults
    prompts, ref = colocated_ref
    monkeypatch.setenv(faults.ENV_SPEC, spec)
    monkeypatch.setenv("BIGDL_TPU_FAULTS_SEED", "3")
    faults.refresh_from_env()
    try:
        before = (get_registry().snapshot()
                  .get("resilience/faults_injected", {}).get("value")
                  or 0)
        with DisaggCoordinator(lm_model, prefill_replicas=1,
                               decode_replicas=1, slots=2, cache_len=48,
                               max_new_tokens=8, migrate_base_delay_s=0.01,
                               prefill_buckets=(4, 8, 16)) as co:
            outs = _serve_all(co, prompts)
            st = co.stats()
        for got, want in zip(outs, ref):
            np.testing.assert_array_equal(got, want)
        assert st["migrations"] == len(prompts)       # zero loss
        assert st["decode"]["completed"] == len(prompts)
        snap = get_registry().snapshot()
        assert snap["resilience/faults_injected"]["value"] > before
        if expect == "retried":
            assert st["lost_payloads"] == 0 == st["re_prefills"]
        else:
            assert st["lost_payloads"] > 0
            assert st["re_prefills"] == st["lost_payloads"]
    finally:
        monkeypatch.delenv(faults.ENV_SPEC, raising=False)
        faults.refresh_from_env()
