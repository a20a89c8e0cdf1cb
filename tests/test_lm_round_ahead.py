"""The decode round runs one ahead: ``LMServingEngine`` enqueues round n+1
(``_dispatch``) before it reads round n's ids (``_collect``) whenever nothing
needs the host in between (``_runs_ahead``), and drains otherwise.

Toy sizes, the CPU.  The streams of an engine that runs ahead are those of
the same engine made to drain every round (the predicate stood in for: the
engine has no option for it), greedy and sampled, for the GPT-2-shaped toy,
the toy Laguna and the toy Solar-Open2 (a recurrent state beside the pool);
a stream that ends on its eos while the next round is on the device emits
nothing past it and the row is counted; a finish by count leaves the slot out
of the round ahead; cancel, deadline, hibernate, adopt and close() each drain
or drop the round in flight; a speculating engine never runs ahead.  The
device's account follows it: a round that ran ahead has nothing starved, a
round after a drain its emit, sched and dispatch."""
import time

import numpy as np
import pytest

from bigdl_tpu.models.transformer import TransformerLM
from bigdl_tpu.obs import get_registry
from bigdl_tpu.serving import LMServingEngine, ServingClosed, lm_engine
from bigdl_tpu.serving.spec import SpecConfig

CASES = ["gpt2", "laguna", "solar2"]


def _gpt2(max_len=64):
    return TransformerLM(vocab_size=61, hidden_size=32, n_head=4, n_layers=2,
                         max_len=max_len).build(seed=7).evaluate()


def _engine(case="gpt2", **kw):
    if case != "gpt2":
        from benchmarks.drivers import serve_laguna, serve_solar2
        from benchmarks.tests import toy_laguna, toy_solar2
        driver, toy = ((serve_laguna, toy_laguna) if case == "laguna"
                       else (serve_solar2, toy_solar2))
        c = toy.config()
        c["engine"].update(kw)
        return driver.build_engine(c, 5)
    args = dict(slots=4, block_len=4, cache_len=64,
                prefill_buckets=(8, 16, 32), enable_prefix_cache=False)
    args.update(kw)
    return LMServingEngine(_gpt2(args["cache_len"]), **args)


def _drains_every_round(monkeypatch):
    """The test's seam: the predicate says no, so every round is collected
    before the next is enqueued -- the synchronous loop."""
    monkeypatch.setattr(LMServingEngine, "_runs_ahead", lambda self: False)


def _watch_dispatches(monkeypatch):
    """Every round the engine enqueues, as (ahead, its slots, rows that end by
    their count), in order."""
    seen, real = [], LMServingEngine._dispatch

    def watched(self, ahead):
        rnd = real(self, ahead)
        seen.append((rnd.ahead, [i for i, *_ in rnd.rows], rnd.n_last))
        return rnd

    monkeypatch.setattr(LMServingEngine, "_dispatch", watched)
    return seen


def _gen(stream, timeout=300):
    """The tokens a finished stream generated (``result`` leads with the
    prompt)."""
    stream.result(timeout=timeout)
    return list(map(int, stream.generated))


def _slow_rounds(monkeypatch, eng, seconds=0.006):
    """Every decode round takes ``seconds`` longer, so that a stream outlasts
    what the test does to it on any machine."""
    eng.warmup()
    own = eng._decode_exec
    monkeypatch.setattr(eng, "_decode_exec",
                        lambda *a: (time.sleep(seconds), own(*a))[1])


def _wait(what, timeout=60.0):
    deadline = time.perf_counter() + timeout
    while not what():
        assert time.perf_counter() < deadline, "timed out"
        time.sleep(0.001)


def _idle(eng):
    """The worker has nothing in flight, on the device or off it."""
    _wait(lambda: eng._flying is None and not eng._n_active)
    if eng.radix is None:       # (a prefix cache keeps the prompts' blocks)
        assert eng.pool.free_count == eng.pool.capacity


#: (prompt length, new tokens, temperature when sampled, rng seed): more
#: requests than the four slots, so some queue, finish and are replaced
MIX = [(5, 14, 0.7, 3), (11, 9, 1.3, 4), (19, 12, 0.7, 5), (7, 6, 1.0, 6),
       (13, 10, 0.9, 7), (9, 16, 1.1, 8)]


# -- (a) the same streams -----------------------------------------------------------
@pytest.mark.parametrize("sampling", ["greedy", "sampled"])
@pytest.mark.parametrize("case", CASES)
def test_streams_are_those_of_the_engine_that_drains_every_round(
        monkeypatch, case, sampling):
    eng = _engine(case)
    try:
        eng.warmup()
        rng = np.random.RandomState(6)
        prompts = [rng.randint(1, eng.model.vocab_size, size=n)
                   for n, *_ in MIX]

        def serve():
            before = (eng.metrics.decode_steps, eng.metrics.rounds_ahead)
            streams = [eng.submit(p, max_new_tokens=k,
                                  **(dict(temperature=t, rng=seed)
                                     if sampling == "sampled" else {}))
                       for p, (_, k, t, seed) in zip(prompts, MIX)]
            got = [_gen(s) for s in streams]
            # one alone: nobody to seat, every round but its first runs ahead
            got.append(_gen(eng.submit(prompts[0], max_new_tokens=12)))
            return got, (eng.metrics.decode_steps - before[0],
                         eng.metrics.rounds_ahead - before[1])

        ahead, (steps, n_ahead) = serve()
        assert n_ahead >= 10 and n_ahead < steps
        _drains_every_round(monkeypatch)
        drained, (steps, n_ahead) = serve()
        assert n_ahead == 0 and steps >= 25
        assert ahead == drained
        assert [len(g) for g in ahead] == [k for _, k, *_ in MIX] + [12]
        assert eng.metrics.rows_discarded == 0      # nobody sent an eos
        _idle(eng)
    finally:
        eng.close()


# -- (b) a stream that ends on its eos while the next round is in flight ------------
@pytest.mark.parametrize("case", ["gpt2", "solar2"])
def test_eos_with_a_round_in_flight_emits_nothing_past_it(monkeypatch, case):
    """One slot, so the request behind waits for the first one's slot AND
    blocks.  The first stream's eos cannot be known ahead, so the round after
    it is on the device when it arrives: that row is thrown away and counted,
    the stream ends at its eos, and the request seated into the freed slot
    and blocks (enqueued behind the discarded row's write) is served as it is
    served alone."""
    eng = _engine(case, slots=1, num_blocks=1 + 16)     # one request's blocks
    try:
        rng = np.random.RandomState(2)
        first, second = (rng.randint(1, eng.model.vocab_size, size=n)
                         for n in (9, 6))
        whole = _gen(eng.submit(first, max_new_tokens=20))
        alone = _gen(eng.submit(second, max_new_tokens=10))
        # an eos whose first occurrence is a few rounds in
        at = next(i for i in range(3, 19) if whole[i] not in whole[:i])
        seen = _watch_dispatches(monkeypatch)
        before = eng.metrics.decode_steps
        a = eng.submit(first, max_new_tokens=20, eos_id=whole[at])
        b = eng.submit(second, max_new_tokens=10)
        assert _gen(a) == whole[:at + 1]
        assert _gen(b) == alone
        _idle(eng)
        assert eng.metrics.rows_discarded == 1
        assert (get_registry().snapshot()["serving/lm/rows_discarded"]["value"]
                == eng.stats()["metrics"]["rows_discarded"] == 1)
        # the first stream's rounds: ``at`` that emitted and the one discarded;
        # the round after them is the second stream's first, after a drain
        assert eng.metrics.decode_steps - before == (at + 1) + 9
        assert [s for _, s, _ in seen] == [[0]] * (at + 1 + 9)
        assert seen[at][0] and not seen[at + 1][0]
    finally:
        eng.close()


def test_eos_of_the_only_stream_leaves_no_round_behind():
    """Nobody waits and nothing else decodes: the round that was on the
    device when the eos arrived is still collected (its one row discarded)
    before the worker idles."""
    eng = _engine("gpt2")
    try:
        prompt = np.random.RandomState(4).randint(1, 61, size=9)
        whole = _gen(eng.submit(prompt, max_new_tokens=20))
        at = next(i for i in range(3, 19) if whole[i] not in whole[:i])
        before = eng.metrics.decode_steps
        assert _gen(eng.submit(prompt, max_new_tokens=20,
                               eos_id=whole[at])) == whole[:at + 1]
        _idle(eng)
        assert eng.metrics.rows_discarded == 1
        assert eng.metrics.decode_steps - before == at + 1
        assert _gen(eng.submit(prompt, max_new_tokens=20)) == whole
    finally:
        eng.close()


# -- (c) a finish by count ----------------------------------------------------------
def test_finish_by_count_leaves_the_slot_out_of_the_round_ahead(monkeypatch):
    """Two slots, a short and a long request seated, a third waiting.  The
    short one's last row is known by its count: the round after it is not
    enqueued ahead (the third could be seated), does not hold the short one's
    slot, and holds the third after a drain."""
    eng = _engine("gpt2", slots=2)
    try:
        rng = np.random.RandomState(3)
        prompts = [rng.randint(1, 61, size=n) for n in (5, 7, 9)]
        news = (6, 30, 8)
        alone = [_gen(eng.submit(p, max_new_tokens=k))
                 for p, k in zip(prompts, news)]
        _idle(eng)
        seen = _watch_dispatches(monkeypatch)
        before = eng.metrics.snapshot()
        streams = [eng.submit(p, max_new_tokens=k)
                   for p, k in zip(prompts, news)]
        assert [_gen(s) for s in streams] == alone
        _idle(eng)
        after = eng.metrics.snapshot()
        assert after["rows_discarded"] == 0
        # a row a token after the first: nothing ran for a stream that had ended
        assert (after["tokens"] - before["tokens"]
                == sum(news))
        rows = sum(len(s) for _, s, _ in seen)
        assert rows == sum(k - 1 for k in news)
        ends = [i for i, (_, _, n_last) in enumerate(seen) if n_last]
        assert len(ends) == 3
        short = ends[0]
        assert len(seen[short][1]) == 2
        # while the third waited, the round behind the short one's last was
        # not ahead; it holds both slots again: the third was seated first
        assert not seen[short + 1][0] and len(seen[short + 1][1]) == 2
        assert any(a for a, *_ in seen[:short])        # before it: ahead
        assert any(a for a, *_ in seen[short + 2:])    # and after
        # the very last round of all ends every row it holds: nothing follows
        assert seen[-1][2] == len(seen[-1][1])
    finally:
        eng.close()


# -- (d) what needs the host drains the round in flight -----------------------------
def _running_ahead(eng, stream, tokens=3):
    """The stream is seated and its rounds run ahead."""
    _wait(lambda: len(stream.generated) >= tokens
          and eng.metrics.rounds_ahead > 0)


@pytest.mark.parametrize("how", ["cancel", "deadline"])
def test_cancel_and_deadline_are_honoured_with_a_round_in_flight(monkeypatch,
                                                                 how):
    eng = _engine("gpt2", cache_len=256, prefill_buckets=(8, 16))
    try:
        prompt = np.arange(1, 8)
        want = _gen(eng.submit(prompt, max_new_tokens=60))
        _slow_rounds(monkeypatch, eng)  # 240 of them outlast the deadline
        ahead = eng.metrics.rounds_ahead
        st = eng.submit(prompt, max_new_tokens=240,
                        deadline_s=1.0 if how == "deadline" else None)
        if how == "cancel":
            _wait(lambda: len(st.generated) >= 3
                  and eng.metrics.rounds_ahead > ahead)
            assert st.cancel()
        got = _gen(st, timeout=60)
        assert st.truncation is not None
        assert st.truncation.reason == ("cancelled" if how == "cancel"
                                        else "deadline")
        assert 3 <= len(got) < 240 and eng.metrics.rounds_ahead > ahead
        assert got[:60] == want[:len(got)]      # a prefix of the whole stream
        _idle(eng)
        # nothing of the dropped stream is left in the slots' books
        assert _gen(eng.submit(prompt, max_new_tokens=60)) == want
    finally:
        eng.close()


def test_hibernate_drains_the_round_in_flight_and_resume_is_exact(monkeypatch):
    from bigdl_tpu.serving.kvtier import HostBlockStore
    model = TransformerLM(vocab_size=31, hidden_size=16, n_head=2, n_layers=1,
                          max_len=64, pos_encoding="rope").build(seed=0)
    kw = dict(slots=2, cache_len=56, max_new_tokens=40, prefill_buckets=(8,),
              block_len=4)
    prompt = np.arange(1, 9, dtype=np.int32)
    ref = LMServingEngine(model, **kw)
    want = ref.generate(prompt, max_new_tokens=40)
    ref.close()
    eng = LMServingEngine(model, kvtier=HostBlockStore(
        host_bytes=64 << 20, name="t-ahead"), **kw)
    try:
        _slow_rounds(monkeypatch, eng, 0.01)
        st = eng.submit(prompt, max_new_tokens=40)
        _running_ahead(eng, st)
        assert eng.hibernate(st)
        assert eng._flying is None and len(eng._free) == eng.slots
        frozen = len(st.generated)
        time.sleep(0.05)
        assert len(st.generated) == frozen
        assert eng.resume(st)
        assert np.array_equal(st.result(timeout=120), want)
        assert eng.metrics.rows_discarded == 0
    finally:
        eng.close()


def test_adoption_is_seated_after_a_drain():
    """A decode replica whose one stream runs ahead is handed a second
    request's chain: it drains, seats it, and both streams are those of a
    co-located engine."""
    from bigdl_tpu.serving.disagg import DisaggCoordinator
    model = TransformerLM(vocab_size=31, hidden_size=16, n_head=2, n_layers=1,
                          max_len=64).build(seed=0)
    kw = dict(slots=2, cache_len=64, prefill_buckets=(4, 8, 16))
    rng = np.random.RandomState(7)
    prompts = [rng.randint(1, 31, size=n) for n in (5, 12)]
    ref = LMServingEngine(model, **kw)
    want = [ref.generate(p, max_new_tokens=k) for p, k in zip(prompts, (40, 10))]
    ref.close()
    with DisaggCoordinator(model, prefill_replicas=1, decode_replicas=1,
                           **kw) as co:
        dec = co.decode[0]
        long = co.submit(prompts[0], max_new_tokens=40)
        _running_ahead(dec, long)
        short = co.submit(prompts[1], max_new_tokens=10)
        assert np.array_equal(short.result(timeout=120), want[1])
        assert np.array_equal(long.result(timeout=120), want[0])
        assert dec.adopted == 2 and dec.metrics.rows_discarded == 0
        assert 0 < dec.metrics.rounds_ahead < dec.metrics.decode_steps


@pytest.mark.parametrize("how", ["drain", "abort"])
def test_close_with_a_round_in_flight_leaves_no_stream_hanging(monkeypatch,
                                                               how):
    eng = _engine("gpt2", cache_len=256, prefill_buckets=(8, 16))
    _slow_rounds(monkeypatch, eng, 0.003)
    prompt = np.arange(1, 8)
    st = eng.submit(prompt, max_new_tokens=60 if how == "drain" else 240)
    queued = [eng.submit(prompt, max_new_tokens=4) for _ in range(5)]
    _wait(lambda: len(st.generated) >= 3)
    if how == "drain":
        eng.close()             # finishes what is seated and queued
        assert len(_gen(st, timeout=1)) == 60
        assert all(len(_gen(q, timeout=1)) == 4 for q in queued)
    else:
        eng.close(timeout=0.0)  # the worker is told to stop where it stands
        with pytest.raises(ServingClosed):
            st.result(timeout=10)
        assert all(q.done() for q in queued)
    assert not eng._worker.is_alive()
    assert eng._flying is None and not eng._n_active
    assert eng.pool.free_count == eng.pool.capacity


# -- (e) the counter ----------------------------------------------------------------
def test_rounds_ahead_of_two_hundred_plain_rounds_and_of_a_speculating_engine():
    eng = _engine("gpt2", cache_len=256, prefill_buckets=(8, 16))
    try:
        assert len(_gen(eng.submit(np.arange(1, 8), max_new_tokens=201),
                        timeout=600)) == 201
        m = eng.stats()["metrics"]
        assert m["decode_steps"] == 200
        assert m["rounds_ahead"] / m["decode_steps"] > 0.9
        assert m["rounds_ahead"] == 199      # all but the one behind the admission
        assert (get_registry().snapshot()["serving/lm/rounds_ahead"]["value"]
                == 199)
    finally:
        eng.close()
    eng = _engine("gpt2", spec=SpecConfig(k=3))
    try:
        for s in [eng.submit(np.arange(1, n), max_new_tokens=12)
                  for n in (6, 9, 14)]:
            s.result(timeout=300)
        m = eng.stats()["metrics"]
        assert m["decode_steps"] >= 3
        assert m["rounds_ahead"] == 0 and m["rows_discarded"] == 0
        assert eng._flying is None
    finally:
        eng.close()


def test_operand_sentinel_is_what_the_round_ahead_hands_its_step(monkeypatch):
    """A round enqueued behind another hands its step ``TAKE_PREV`` in the
    place of every token that round picks, and the previous round's ids, still
    a device array, as ``prev_ids``; a round after a drain hands the host's
    tokens."""
    eng = _engine("gpt2")
    try:
        eng.warmup()
        own, calls = eng._decode_exec, []

        def recorded(params, operands, prev_ids, *kv):
            token, pos, *_ = lm_engine.split_decode_operands(
                np.asarray(operands), eng.slots)
            out = own(params, operands, prev_ids, *kv)
            slot = int(np.argmax(pos))          # the one that decodes
            calls.append((int(token[slot]), prev_ids, out[0], slot))
            return out

        monkeypatch.setattr(eng, "_decode_exec", recorded)
        out = _gen(eng.submit(np.arange(1, 8), max_new_tokens=9))
        _idle(eng)
        assert len(calls) == 8
        assert calls[0][0] == out[0] - 1        # the host's: the first token
        for (token, prev_ids, *_), (_, _, ids_before, _) in zip(calls[1:],
                                                               calls):
            assert token == lm_engine.TAKE_PREV
            assert prev_ids is ids_before       # no copy, no transfer
        assert [int(np.asarray(ids)[slot]) + 1
                for _, _, ids, slot in calls] == out[1:]
    finally:
        eng.close()


# -- (f) the device's account --------------------------------------------------------
@pytest.mark.parametrize("traced", [False, True], ids=["tracer_off", "tracer_on"])
@pytest.mark.parametrize("drains", [False, True], ids=["ahead", "drains"])
@pytest.mark.parametrize("case", ["gpt2", "solar2"])
def test_account_of_one_stream_round_by_round(monkeypatch, lm_round_records,
                                              case, drains, traced):
    """One request into an idle engine: the admission is starved from the
    wake-up to the prefill's enqueue and not for its insert (a recurrent
    model's state insert), its first token or what the host does while they
    are on the device; then every round runs ahead with nothing starved, until
    the last is drained and its emission is starved.  Made to drain every
    round, each round is starved for its emit, sched and dispatch and never for
    its wait.  The tracer changes none of it."""
    from bigdl_tpu.obs import get_tracer
    P = lm_engine
    if drains:
        _drains_every_round(monkeypatch)
    tracer = get_tracer()
    was, tracer.enabled = tracer.enabled, traced
    eng = _engine(case)
    try:
        eng.warmup()
        assert len(_gen(eng.submit(np.arange(1, 8), max_new_tokens=10))) == 10
        _idle(eng)
    finally:
        eng.close()
        tracer.enabled = was
        tracer.clear()
    records = [(r["split"], r["starved"], r["admitted"])
               for r in lm_round_records]
    assert len(records) == 10 and [a for *_, a in records] == [1] + [0] * 9
    split, starved, _ = records[0]
    assert starved[P.P_PREFILL] == split[P.P_PREFILL] > 0
    assert 0 < starved[P.P_ADMIT_HOST] < split[P.P_ADMIT_HOST]
    assert 0 < starved[P.P_SCHED] <= split[P.P_SCHED]
    assert (starved[P.P_IDLE] == starved[P.P_INSERT] == starved[P.P_STATE_INSERT]
            == starved[P.P_FIRST_TOKEN] == starved[P.P_WAIT] == 0)
    assert split[P.P_INSERT] > 0 and split[P.P_FIRST_TOKEN] > 0
    assert (split[P.P_STATE_INSERT] > 0) == (case == "solar2")
    if not drains:
        # eight rounds ran ahead; the ninth collects the last step and drains
        assert [s for _, s, _ in records[1:9]] == [None] * 8
        assert eng.metrics.rounds_ahead == 8
    for split, starved, _ in (records[1:] if drains else records[9:]):
        assert starved[P.P_EMIT] == split[P.P_EMIT] > 0
        assert starved[P.P_DISPATCH] == split[P.P_DISPATCH]
        assert 0 < starved[P.P_SCHED] <= split[P.P_SCHED]
        assert starved[P.P_WAIT] == 0 < split[P.P_WAIT]
        assert sum(starved) == pytest.approx(
            starved[P.P_EMIT] + starved[P.P_SCHED] + starved[P.P_DISPATCH])
    # the last round dispatches nothing; a drained round before it does
    assert records[-1][0][P.P_DISPATCH] == 0
    if drains:
        assert all(split[P.P_DISPATCH] > 0 for split, *_ in records[1:-1])
    rounds = eng.stats()["rounds"]
    assert rounds["starved_s"] == pytest.approx(
        sum(sum(s) for _, s, _ in records if s), rel=1e-9)
