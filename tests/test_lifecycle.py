"""Request lifecycle: end-to-end deadlines, cooperative cancellation,
hedged dispatch.

Tier-1 coverage for the lifecycle layer: pre-admission deadline sheds
are typed (ServingDeadlineExceeded, a ServingOverloaded — every
existing shed accounting path stays honest), mid-stream expiry and
client cancels finish streams with a typed truncation whose tokens are
the bit-exact prefix of the uninterrupted answer, and the freed
slot+blocks are reusable within one scheduler round with BlockPool
refcounts conserved.  The two cancel races the close/EOS machinery can
hit are pinned as regressions: a future cancelled BEFORE the batcher
drains it, and a cancel landing the same round as EOS/slot-recycle.
"""
import threading
import time

import numpy as np
import pytest

from bigdl_tpu.models.transformer import TransformerLM
from bigdl_tpu.resilience.errors import (ServingDeadlineExceeded,
                                         ServingOverloaded)
from bigdl_tpu.resilience.replicaset import HedgePolicy
from bigdl_tpu.serving import DynamicBatcher, LMServingEngine
from bigdl_tpu.serving.router import LMReplicaSet


def _wait(pred, timeout=30.0):
    deadline = time.perf_counter() + timeout
    while time.perf_counter() < deadline:
        if pred():
            return True
        time.sleep(0.005)
    return False


@pytest.fixture(scope="module")
def lc_model():
    return TransformerLM(vocab_size=31, hidden_size=16, n_head=2,
                         n_layers=1, max_len=64,
                         pos_encoding="rope").build(seed=0)


_ENG_KW = dict(slots=2, cache_len=56, max_new_tokens=12,
               prefill_buckets=(8, 16), block_len=4)


@pytest.fixture(scope="module")
def lc_engine(lc_model):
    eng = LMServingEngine(lc_model, **_ENG_KW)
    eng.warmup()
    yield eng
    eng.close()


_PROMPT = np.arange(1, 9, dtype=np.int32)


# --------------------------------------------------------------------------- #
# deadlines                                                                   #
# --------------------------------------------------------------------------- #

def test_deadline_typed_classification():
    """A blown deadline IS an overload shed: the SLO ladder and loadgen
    shed accounting must keep working unchanged."""
    assert issubclass(ServingDeadlineExceeded, ServingOverloaded)


def test_deadline_preadmission_shed_is_typed(lc_engine):
    with pytest.raises(ServingDeadlineExceeded):
        lc_engine.submit(_PROMPT, deadline_s=0.0)
    assert lc_engine.lifecycle_stats()["expired_preadmission"] >= 1


def test_deadline_generous_budget_completes_exact(lc_engine, lc_model):
    from bigdl_tpu.models.transformer.generate import generate
    s = lc_engine.submit(_PROMPT, max_new_tokens=4, deadline_s=60.0)
    out = s.result(timeout=60)
    ref = np.asarray(generate(lc_model, lc_model.params,
                              _PROMPT[None].astype(np.int32), 4))
    np.testing.assert_array_equal(out, ref[0])
    assert s.truncation is None


def test_deadline_midstream_truncates_prefix_exact(lc_model):
    """A budget that expires mid-decode finishes the stream CLEANLY
    (typed truncation, no error) and the emitted tokens are the exact
    prefix of the uninterrupted answer."""
    eng = LMServingEngine(lc_model, **_ENG_KW)
    try:
        eng.warmup()
        full = eng.generate(_PROMPT, max_new_tokens=12, timeout=60)
        # slow the decode down so a ~50 ms budget dies mid-stream
        s = eng.submit(_PROMPT, max_new_tokens=12, deadline_s=0.05)
        out = s.result(timeout=60)   # truncation is NOT an error
        assert s.truncation is not None
        assert s.truncation.reason == "deadline"
        assert s.truncation.at_tokens == len(s.generated)
        np.testing.assert_array_equal(out, full[:len(out)])
        assert _wait(lambda: eng.stats()["active"] == 0)
        assert eng.lifecycle_stats()["expired_midstream"] >= 1 or \
            eng.lifecycle_stats()["expired_preadmission"] >= 1
    finally:
        eng.close()


def test_deadline_expires_while_queued_typed_shed(lc_model):
    """Requests stuck behind a full house whose budget dies in the
    queue resolve with the typed shed BEFORE any prefill is spent."""
    eng = LMServingEngine(lc_model, **_ENG_KW)
    try:
        eng.warmup()
        # occupy both slots with long decodes
        busy = [eng.submit(_PROMPT, max_new_tokens=48) for _ in range(2)]
        s = eng.submit(_PROMPT + 1, max_new_tokens=12, deadline_s=0.001)
        with pytest.raises(ServingDeadlineExceeded):
            s.result(timeout=60)
        for b in busy:
            b.result(timeout=60)
    finally:
        eng.close()


# --------------------------------------------------------------------------- #
# cooperative cancellation + refcount conservation                            #
# --------------------------------------------------------------------------- #

def test_cancel_frees_slot_and_conserves_refcounts(lc_model):
    """Cancel mid-decode: stream finishes truncated, the slot is
    reusable within one scheduler round, and the BlockPool returns to
    its idle free count — no leaked or double-released block."""
    eng = LMServingEngine(lc_model, enable_prefix_cache=False, **_ENG_KW)
    try:
        eng.warmup()
        eng.generate(_PROMPT, max_new_tokens=2, timeout=60)
        assert _wait(lambda: eng.stats()["active"] == 0)
        idle_free = eng.pool.free_count
        # the whole context as budget: a poller starved under a loaded
        # host must still find the stream mid-decode when it cancels
        s = eng.submit(_PROMPT, max_new_tokens=48)
        _wait(lambda: len(s.generated) >= 1)   # seated and decoding
        assert s.cancel() is True
        s.result(timeout=60)
        assert s.truncation is not None and \
            s.truncation.reason == "cancelled"
        assert _wait(lambda: eng.pool.free_count == idle_free)
        assert _wait(lambda: eng.stats()["active"] == 0)
        # the freed slot serves the next request immediately
        assert eng.generate(_PROMPT, max_new_tokens=2,
                            timeout=60).shape == (10,)
        assert eng.lifecycle_stats()["cancelled"] >= 1
    finally:
        eng.close()


def test_cancel_eos_same_round_race_conserves_pool(lc_model):
    """Regression (satellite): a cancel landing the same scheduler
    round as EOS/slot-recycle must not double-free or leak — hammer
    the race and assert pool conservation + radix retains released
    every cycle."""
    eng = LMServingEngine(lc_model, **_ENG_KW)   # prefix cache ON
    try:
        eng.warmup()
        full = eng.generate(_PROMPT, max_new_tokens=6, timeout=60)
        eos = int(full[len(_PROMPT)])   # EOS == the FIRST generated token
        assert _wait(lambda: eng.stats()["active"] == 0)
        idle_free = eng.pool.free_count
        for i in range(8):
            s = eng.submit(_PROMPT, max_new_tokens=6, eos_id=eos)
            if i % 2:
                time.sleep(0.001 * (i % 4))
            s.cancel()                  # races the EOS completion
            s.result(timeout=60)        # either outcome is clean
            assert _wait(lambda: eng.stats()["active"] == 0)
            # radix may retain cached chains, but retained blocks are
            # accounted: the free count must come back to idle exactly
            assert _wait(lambda: eng.pool.free_count == idle_free), \
                f"cycle {i}: pool leaked " \
                f"({eng.pool.free_count} != {idle_free})"
        # the engine still serves correctly after the hammering
        np.testing.assert_array_equal(
            eng.generate(_PROMPT, max_new_tokens=6, timeout=60), full)
    finally:
        eng.close()


def test_cancel_while_queued_never_prefills(lc_model):
    eng = LMServingEngine(lc_model, **_ENG_KW)
    try:
        eng.warmup()
        busy = [eng.submit(_PROMPT, max_new_tokens=48) for _ in range(2)]
        s = eng.submit(_PROMPT + 2, max_new_tokens=12)
        assert s.cancel() is True
        s.result(timeout=60)
        assert s.truncation is not None
        assert len(s.generated) == 0     # shed at the queue, no prefill
        for b in busy:
            b.result(timeout=60)
    finally:
        eng.close()


def test_cancel_hibernated_stream_without_resume(lc_model):
    """A hibernated stream is cancellable in place: no resume, no
    promote — the engine drops the host-tier entry and finishes the
    stream truncated."""
    from bigdl_tpu.serving import HostBlockStore
    eng = LMServingEngine(lc_model,
                          kvtier=HostBlockStore(host_bytes=64 << 20,
                                                name="lc-tier"),
                          **_ENG_KW)
    try:
        eng.warmup()
        s = eng.submit(_PROMPT, max_new_tokens=48)   # see the cancel test
        _wait(lambda: len(s.generated) >= 2)
        assert eng.hibernate(s, timeout=30.0)
        assert s.cancel() is True
        s.result(timeout=60)
        assert s.truncation is not None and \
            s.truncation.reason == "cancelled"
        assert eng.lifecycle_stats()["cancelled"] >= 1
    finally:
        eng.close()


# --------------------------------------------------------------------------- #
# batcher lifecycle                                                           #
# --------------------------------------------------------------------------- #

def test_batcher_deadline_preadmission_and_queued_expiry():
    release = threading.Event()

    def slow(x):
        release.wait(10)
        return x

    b = DynamicBatcher(slow, max_batch_size=1, max_wait_ms=1)
    try:
        with pytest.raises(ServingDeadlineExceeded):
            b.submit(np.ones((1, 2), np.float32), deadline_s=0.0)
        f1 = b.submit(np.ones((1, 2), np.float32))        # wedges worker
        f2 = b.submit(np.ones((1, 2), np.float32), deadline_s=0.01)
        time.sleep(0.05)
        release.set()
        assert f1.result(timeout=10).shape == (1, 2)
        with pytest.raises(ServingDeadlineExceeded):
            f2.result(timeout=10)    # expired waiting, never dispatched
    finally:
        release.set()
        b.close()


def test_batcher_close_drains_precancelled_future():
    """Regression (satellite): a future the CLIENT cancelled while it
    sat in the queue must not wedge close()'s drain — the sweep skips
    it cleanly and every other future still resolves."""
    release = threading.Event()

    def slow(x):
        release.wait(10)
        return x

    b = DynamicBatcher(slow, max_batch_size=1, max_wait_ms=1)
    f1 = b.submit(np.ones((1, 2), np.float32))   # occupies the worker
    f2 = b.submit(np.ones((1, 2), np.float32))
    f3 = b.submit(np.ones((1, 2), np.float32))
    assert f2.cancel()          # client walks away while queued
    release.set()
    b.close()
    assert f1.result(timeout=10).shape == (1, 2)
    assert f2.cancelled()
    # f3 either completed before close or was typed-resolved by it
    try:
        assert f3.result(timeout=10).shape == (1, 2)
    except Exception as e:  # noqa: BLE001
        assert type(e).__name__ == "ServingClosed"


def test_batcher_cancelled_future_skipped_at_assembly():
    """A cancelled future is shed at batch assembly: the run function
    never sees its payload."""
    seen = []
    b = DynamicBatcher(lambda x: (seen.append(int(x.shape[0])) or x),
                       max_batch_size=8, max_wait_ms=40)
    try:
        f = b.submit(np.ones((3, 2), np.float32))
        assert f.cancel()
        time.sleep(0.15)
        assert seen == []        # nothing dispatched for the dead future
        g = b.submit(np.ones((2, 2), np.float32))
        assert g.result(timeout=10).shape == (2, 2)
    finally:
        b.close()


# --------------------------------------------------------------------------- #
# hedge policy + routed lifecycle                                             #
# --------------------------------------------------------------------------- #

def test_hedge_policy_trigger_and_budget():
    pol = HedgePolicy(trigger_quantile=0.5, window=16,
                      min_observations=4, max_hedge_fraction=0.5)
    assert pol.trigger_s() is None           # no evidence yet
    for w in (0.1, 0.2, 0.3, 0.4):
        pol.observe(w)
    trig = pol.trigger_s()
    assert trig is not None and 0.1 <= trig <= 0.4
    for _ in range(4):
        pol.note_dispatch()
    assert pol.should_hedge(trig + 1.0)
    pol.note_fired()
    pol.note_outcome(True)
    # budget: 1 hedge fired out of 4 dispatches; a 2nd would be 2/4 =
    # 50% which is still <= max_hedge_fraction, a 3rd would not
    assert pol.should_hedge(trig + 1.0)
    pol.note_fired()
    assert not pol.should_hedge(trig + 1.0)
    st = pol.stats()
    assert st["hedges_fired"] == 2 and st["hedges_won"] == 1
    assert not pol.should_hedge(0.0)         # below trigger: never


def test_routed_deadline_and_cancel_propagation(lc_model):
    rs = LMReplicaSet(lc_model, 2, name="lc-rt", **_ENG_KW)
    try:
        rs.warmup()
        # generous budget completes; the deadline rode the dispatch
        s = rs.submit(_PROMPT, max_new_tokens=4, deadline_s=60.0)
        s.result(timeout=60)
        assert s.truncation is None
        # cancel propagates through the routed front to the member
        s2 = rs.submit(_PROMPT, max_new_tokens=12)
        s2.cancel()
        s2.result(timeout=60)
        assert s2.truncation is not None
        assert s2.truncation.reason == "cancelled"
        assert rs.lifecycle_stats()["cancelled"] >= 1
    finally:
        rs.close()


def test_hedged_dispatch_first_completion_wins(lc_model):
    """Saturate a 2-replica set so queue waits blow past the median
    trigger: hedges fire within budget, every result stays bit-exact,
    and the losers' cancels recycle their seats (lifecycle cancelled
    counter moves)."""
    pol = HedgePolicy(trigger_quantile=0.5, window=64,
                      min_observations=4, max_hedge_fraction=0.5,
                      min_trigger_s=0.0)
    rs = LMReplicaSet(lc_model, 2, hedge=pol, name="lc-hedge", **_ENG_KW)
    try:
        rs.warmup()
        ref = rs.submit(_PROMPT, max_new_tokens=6, temperature=0.7,
                        rng=3).result(timeout=60)
        # seed the wait-evidence window with sub-ms TTFTs so the p50
        # trigger sits below a real queued wait on this tiny model —
        # the e2e property under test is trigger-exceeded => hedge
        # fires within budget and results stay bit-exact, not the
        # organic window-fill (covered by the policy unit test above)
        for _ in range(8):
            pol.observe(0.0005)
        streams = [rs.submit(_PROMPT, max_new_tokens=6, temperature=0.7,
                             rng=3, hedgeable=True) for _ in range(10)]
        for s in streams:
            np.testing.assert_array_equal(s.result(timeout=120), ref)
        st = pol.stats()
        assert st["hedges_fired"] >= 1
        assert st["hedges_fired"] <= 1 + int(
            0.5 * st["dispatches"])          # budget respected
        assert st["hedges_won"] + st["hedges_lost"] == st["hedges_fired"]
    finally:
        rs.close()
