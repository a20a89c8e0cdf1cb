"""The LM engine's round loop is tiled with phase stamps: with the tracer on,
the worker thread's leaf spans cover every ``lm/round`` without a gap or an
overlap; with it off, the same stamps fill ``stats()["rounds"]``, name the
longest round's phase and log a slow round once; a round that hangs fires the
``lm_round`` watchdog while it hangs.  The same stamps keep the device's
account: what passes between a wait that proved the device empty and the next
enqueue is starved (``starved_s`` / ``starved_phase_s``, tracer off or on; one
``lm/starved`` envelope an interval with it on)."""
import logging
import time

import numpy as np
import pytest

from bigdl_tpu.models.transformer import TransformerLM
from bigdl_tpu.obs import Tracer, get_tracer, shared_watchdog
from bigdl_tpu.serving import LMServingEngine, lm_engine
from bigdl_tpu.serving.lm_engine import ROUND_PHASES
from bigdl_tpu.serving.spec import SpecConfig

LEAVES = {"lm/" + p for p in ROUND_PHASES}
WAIT_LEAVES = {"lm/decode_wait", "lm/first_token"}
ENQUEUE_LEAVES = {"lm/" + ROUND_PHASES[p] for p in lm_engine._ENQUEUES}
ADMIT_LEAVES = {"lm/admit_host", "lm/prefill", "lm/insert", "lm/first_token"}
EPS_US = 1e-3       # stamps share one clock read; what is left is rounding

ENGINES = {
    "plain": {},
    "chunked": {"max_prefill_chunk_tokens": 8},
    "spec": {"spec": SpecConfig(k=3)},
    "spec_tree": {"spec": SpecConfig(k=3, tree=True, promote_above=0.5)},
}


@pytest.fixture(scope="module")
def model():
    return TransformerLM(vocab_size=31, hidden_size=16, n_head=2, n_layers=1,
                         max_len=48, pos_encoding="rope").build(seed=0)


@pytest.fixture
def tracer():
    tr = get_tracer()
    was = tr.enabled
    tr.clear()
    yield tr
    tr.enabled = was
    tr.clear()


def _engine(model, **kw):
    kw.setdefault("max_new_tokens", 8)
    eng = LMServingEngine(model, slots=3, cache_len=48, block_len=4,
                          prefill_buckets=(8, 16), **kw)
    eng.warmup()
    return eng


def _serve(eng, n=6, max_new=6, seed=1):
    """Staggered mixed-length requests, more than the slots hold, so that
    admissions interrupt decode rounds and some requests queue."""
    rng = np.random.RandomState(seed)
    streams = []
    for i in range(n):
        streams.append(eng.submit(
            rng.randint(1, 31, size=int(rng.choice([5, 9, 14]))),
            max_new_tokens=max_new))
        time.sleep(0.003 * (i % 3))
    for s in streams:
        s.result(timeout=120)
    return streams


def _close(eng):
    """Closed, the worker thread has ended its last round."""
    eng.close()
    assert not eng._worker.is_alive()


def _worker_events(tr, eng):
    return [e for e in tr.events()
            if e["ph"] == "X" and e["tid"] == eng._worker.ident]


def _inside(ev, outer):
    return (outer["ts"] - EPS_US <= ev["ts"]
            and ev["ts"] + ev["dur"] <= outer["ts"] + outer["dur"] + EPS_US)


@pytest.mark.parametrize("kind", sorted(ENGINES))
def test_leaf_spans_tile_every_round(model, tracer, kind):
    tracer.enable()
    eng = _engine(model, **ENGINES[kind])
    _serve(eng)
    _close(eng)
    evs = _worker_events(tracer, eng)
    leaves = sorted((e for e in evs if e["name"] in LEAVES),
                    key=lambda e: (e["ts"], e["dur"]))
    rounds = [e for e in evs if e["name"] == "lm/round"]
    assert len(rounds) >= 4 and leaves
    # exactly one leaf at a time: each starts where the one before ended
    for a, b in zip(leaves, leaves[1:]):
        assert a["ts"] + a["dur"] <= b["ts"] + EPS_US, (a, b)
    # rounds do not overlap either, and every leaf but lm/idle lies in the
    # round whose index it carries
    rounds.sort(key=lambda e: e["ts"])
    for a, b in zip(rounds, rounds[1:]):
        assert a["ts"] + a["dur"] <= b["ts"] + EPS_US
        assert b["args"]["round"] == a["args"]["round"] + 1
    by_index = {r["args"]["round"]: r for r in rounds}
    for r in rounds:
        inside = [e for e in leaves if e["name"] != "lm/idle"
                  and e["args"]["round"] == r["args"]["round"]]
        assert all(_inside(e, r) for e in inside)
        assert sum(e["dur"] for e in inside) >= 0.99 * r["dur"], (kind, r)
    for e in leaves:
        if e["name"] == "lm/idle":
            assert not any(_inside(e, r) and e["dur"] > EPS_US for r in rounds)
        else:
            assert e["args"]["round"] in by_index
    if kind.startswith("spec"):
        assert any(e["name"] == "lm/draft" for e in leaves)
        assert any(e["name"] == "lm/verify_step" for e in evs)


@pytest.mark.parametrize("kind", ["plain", "spec"])
def test_decoding_round_holds_one_step_with_one_dispatch_and_one_wait(
        model, tracer, kind):
    """A round of the loop collects ONE decode step: one ``lm/decode_wait``,
    ending where the step's span ends.  A speculating engine's round is
    synchronous: its dispatch and its wait fill the step.  A plain engine's
    round runs one ahead: it holds at most one dispatch, which enqueues the
    NEXT step before the wait (``ahead`` 1 on that step's span) or, after a
    drain, behind it; consecutive ``lm/decode_step`` spans never overlap, a
    step that ran ahead starts where its predecessor's span ended, a step
    after a drain where its own dispatch began."""
    tracer.enable()
    eng = _engine(model, **ENGINES[kind])
    _serve(eng)
    # one request alone: nobody to seat, so its rounds run ahead
    eng.submit(np.arange(1, 8), max_new_tokens=8).result(timeout=120)
    _close(eng)
    evs = _worker_events(tracer, eng)
    step_name = "lm/decode_step" if kind == "plain" else "lm/verify_step"
    decoded, all_steps = 0, []
    for r in (e for e in evs if e["name"] == "lm/round"):
        mine = [e for e in evs if e is not r and e.get("args", {}).get("round")
                == r["args"]["round"]]
        steps = [e for e in mine if e["name"] == step_name]
        found = {leaf: [e for e in mine if e["name"] == leaf]
                 for leaf in ("lm/decode_dispatch", "lm/decode_wait")}
        assert len(steps) == len(found["lm/decode_wait"]) <= 1
        assert len(found["lm/decode_dispatch"]) <= 1
        if not steps:
            continue
        decoded += 1
        step, wait = steps[0], found["lm/decode_wait"][0]
        all_steps.append(step)
        assert step["args"]["active"] == r["args"]["active"] > 0
        assert _inside(wait, step)
        assert wait["ts"] + wait["dur"] == pytest.approx(
            step["ts"] + step["dur"], abs=EPS_US)
        if kind == "spec":
            # dispatch then wait fill the step: nothing else happens inside
            assert "ahead" not in step["args"]
            assert _inside(found["lm/decode_dispatch"][0], step)
            assert sum(e["dur"] for e in mine if e["name"] in
                       ("lm/decode_dispatch", "lm/decode_wait")) \
                == pytest.approx(step["dur"], abs=2 * EPS_US)
    assert decoded >= 5
    assert decoded == eng.metrics.decode_steps
    if kind == "spec":
        assert eng.metrics.rounds_ahead == 0
        return
    all_steps.sort(key=lambda e: e["ts"])
    dispatches = sorted((e for e in evs if e["name"] == "lm/decode_dispatch"),
                        key=lambda e: e["ts"])
    assert len(dispatches) == len(all_steps)        # every step was enqueued once
    for i, (step, dispatch) in enumerate(zip(all_steps, dispatches)):
        assert step["args"]["ahead"] in (0, 1)
        if i:
            before = all_steps[i - 1]
            assert before["ts"] + before["dur"] <= step["ts"] + EPS_US
        if step["args"]["ahead"]:
            # enqueued while its predecessor was on the device: the device
            # could turn to it when that one's ids were out
            assert dispatch["ts"] < before["ts"] + before["dur"]
            assert step["ts"] == pytest.approx(before["ts"] + before["dur"],
                                               abs=EPS_US)
        else:
            assert step["ts"] == pytest.approx(dispatch["ts"], abs=EPS_US)
    ahead = sum(e["args"]["ahead"] for e in all_steps)
    assert ahead == eng.metrics.rounds_ahead >= 5


def test_admit_spans_count_the_prefills(model, tracer):
    tracer.enable()
    eng = _engine(model)
    before = eng.metrics.prefills
    streams = _serve(eng, n=7)
    _close(eng)
    evs = _worker_events(tracer, eng)
    admits = [e for e in evs if e["name"] == "lm/admit"]
    seated = [e for e in admits if not e["args"]["deferred"]]
    assert len(seated) == eng.metrics.prefills - before == 7
    assert sum(r["args"]["admitted"]
               for r in evs if r["name"] == "lm/round") == 7
    ids = {s.request_id for s in streams}
    for a in seated:
        assert a["args"]["kind"] == "submit" and a["args"]["request_id"] in ids
        assert a["args"]["bucket"] in (8, 16)
        assert a["args"]["prompt_len"] in (5, 9, 14)
        assert a["args"]["matched_tokens"] % 4 == 0
        # its leaves tile it and carry the request, so they nest in its tree
        inside = [e for e in evs if e["name"] in ADMIT_LEAVES and _inside(e, a)]
        assert {e["name"] for e in inside} == ADMIT_LEAVES
        assert all(e["args"]["request_id"] == a["args"]["request_id"]
                   for e in inside)
        assert sum(e["dur"] for e in inside) == pytest.approx(a["dur"],
                                                              abs=8 * EPS_US)
    # envelopes of a request are told apart from work of the thread
    by_cat = {e["name"]: e["cat"] for e in tracer.events()}
    assert by_cat["lm/queue_wait"] == by_cat["lm/request"] \
        == by_cat["lm/decode_round"] == "request"
    assert by_cat["lm/admit"] == by_cat["lm/round"] == "serve"
    tree = tracer.span_tree(streams[0].request_id)
    root = next(n for n in tree["spans"] if n["name"] == "lm/request")
    admit = next(c for c in root["children"] if c["name"] == "lm/admit")
    assert {c["name"] for c in admit["children"]} == ADMIT_LEAVES


def test_tracer_off_leaves_the_ring_empty_and_fills_the_round_record(
        model, tracer):
    tracer.disable()
    eng = _engine(model)
    eng.metrics.reset_rounds()
    _serve(eng)
    _close(eng)
    assert len(tracer) == 0 and tracer.dropped == 0
    rounds = eng.stats()["rounds"]
    assert rounds["count"] >= 6 and rounds["plain"] >= 1
    assert rounds["trace_dropped"] == 0 and rounds["slow"] == 0
    assert set(rounds["phase_s"]) == set(ROUND_PHASES)
    for phase in ("sched", "admit_host", "prefill", "insert", "first_token",
                  "decode_dispatch", "decode_wait", "emit"):
        assert rounds["phase_s"][phase] > 0, phase
    assert rounds["phase_s"]["draft"] == rounds["phase_s"]["tree_commit"] == 0
    assert 0 < rounds["median_plain_s"] <= rounds["longest"]["seconds"]
    longest = rounds["longest"]
    assert sum(longest["phase_s"].values()) == pytest.approx(
        longest["seconds"], rel=1e-6)
    assert longest["phase"] == max(longest["phase_s"],
                                   key=longest["phase_s"].get)
    assert 0 <= longest["at_s"] and abs(longest["at_unix"] - time.time()) < 600
    assert 0 <= longest["active"] <= 3 and 0 <= longest["admitted"] <= 3
    # the rounds' own time (all but idle) is what the rounds summed to
    assert eng.metrics.rounds == rounds["count"]


def test_slow_emission_is_named_by_the_longest_round_and_logged_once(
        model, tracer, monkeypatch, caplog):
    tracer.disable()
    monkeypatch.setattr(lm_engine, "SLOW_ROUND_S", 0.2)
    eng = _engine(model)
    _serve(eng, n=3)                    # plain rounds for the running median
    assert eng.metrics.plain_rounds >= 3
    eng.metrics.reset_rounds()
    _serve(eng, n=2, seed=3)
    real, calls = lm_engine.LMStream._emit, {"n": 0}

    def slow_emit(self, token_1b):
        # a decode round's emission (the step picked the token on the device;
        # a first token is emitted in lm/first_token)
        in_round = self.first_token_at is not None
        calls["n"] += in_round
        if in_round and calls["n"] == 5:
            time.sleep(0.3)
        return real(self, token_1b)

    monkeypatch.setattr(lm_engine.LMStream, "_emit", slow_emit)
    with caplog.at_level(logging.WARNING, logger="bigdl_tpu.serving"):
        _serve(eng, n=3, max_new=8, seed=5)
        _close(eng)
    rounds = eng.stats()["rounds"]
    longest = rounds["longest"]
    assert longest["phase"] == "emit"
    assert 0.3 <= longest["phase_s"]["emit"] <= longest["seconds"] < 3.0
    assert rounds["slow"] == 1
    lines = [r.getMessage() for r in caplog.records
             if "slow round" in r.getMessage()]
    assert len(lines) == 1
    assert f"slow round {longest['round']}:" in lines[0]
    assert f"'emit': {round(longest['phase_s']['emit'], 4)}" in lines[0]


def test_held_round_fires_the_lm_round_watchdog(model, tracer, monkeypatch):
    monkeypatch.setenv("BIGDL_TPU_WATCHDOG_K", "10")
    wd = shared_watchdog("lm_round")
    monkeypatch.setattr(wd, "_capture", {})     # no /proc scan in a test
    monkeypatch.setattr(wd, "poll_s", 0.05)
    wd.stop()
    tracer.disable()
    eng = _engine(model)
    assert eng.watchdog is wd
    _serve(eng, n=3)                    # completed rounds arm the median rule
    assert len(wd._durations) >= wd.min_samples
    real, calls, seen = lm_engine.LMStream._emit, {"n": 0}, {}

    def held_emit(self, token_1b):
        calls["n"] += 1
        if calls["n"] == 8:     # in a plain round, past both admissions
            # the round hangs until it is seen (a toy's admission round can
            # pass 10 medians by itself: only what fires DURING the hold counts)
            before, deadline = wd.last_event, time.perf_counter() + 20.0
            while wd.last_event is before and time.perf_counter() < deadline:
                time.sleep(0.01)
            seen["event"] = wd.last_event
        return real(self, token_1b)

    monkeypatch.setattr(lm_engine.LMStream, "_emit", held_emit)
    _serve(eng, n=2, seed=7)
    event = seen["event"]
    assert event["watchdog"] == "lm_round"
    assert event["inflight_s"] >= event["threshold_s"]
    # the dump names where the worker hung, while it hung
    assert "held_emit" in event["thread_stacks"][eng._worker.name]
    assert eng.stats()["rounds"]["watchdog"]["stalls"] == wd.stall_count >= 1
    _close(eng)
    # never across lm/idle, and disarmed at close()
    assert wd._inflight_since is None and not wd._durations
    assert wd._thread is None


def test_tracer_counts_what_a_full_ring_drops():
    tr = Tracer(capacity=8, enabled=True)
    for i in range(8):
        tr.instant(f"e{i}")
    assert tr.dropped == 0 and len(tr) == 8
    t0 = time.perf_counter()
    for i in range(5):
        tr.add_complete(f"s{i}", t0, 0.001)
    assert tr.dropped == 5 and len(tr) == 8
    assert {e["name"] for e in tr.events()} == {
        "e5", "e6", "e7", "s0", "s1", "s2", "s3", "s4"}
    assert tr.export_chrome()["otherData"]["dropped"] == 5
    tr.clear()
    assert tr.dropped == 0 and len(tr) == 0


# -- the device's account ------------------------------------------------------------
def _ends(ev):
    return ev["ts"] + ev["dur"]


@pytest.mark.parametrize("traced", [False, True], ids=["tracer_off", "tracer_on"])
@pytest.mark.parametrize("kind", sorted(ENGINES))
def test_starved_account_sums_and_envelopes(model, tracer, lm_round_records,
                                            kind, traced):
    """Waits and ``lm/idle`` are never starved, no phase is starved for longer
    than it lasted, the split sums to ``starved_s``; the account reads the same
    way with the tracer off.  With it on, the ``lm/starved`` envelopes sum to
    ``starved_s``, overlap no wait, start where a wait or ``lm/idle`` ended (a
    first token's: where a later leaf did, once the insert behind it was
    ready), end where an enqueue leaf ends (or where ``lm/idle`` starts or the
    worker leaves: the last drain), hold two leaves or more and are written
    after the last."""
    tracer.enabled = traced
    records = lm_round_records
    eng = _engine(model, **ENGINES[kind])
    _serve(eng)
    eng.submit(np.arange(1, 8), max_new_tokens=8).result(timeout=120)
    _close(eng)
    rounds = eng.stats()["rounds"]
    phase_s, starved = rounds["phase_s"], rounds["starved_phase_s"]
    assert set(starved) == set(ROUND_PHASES)
    assert starved["idle"] == starved["decode_wait"] == starved["first_token"] == 0
    for p in ROUND_PHASES:
        assert 0 <= starved[p] <= phase_s[p] + 1e-9, p
    assert sum(starved.values()) == pytest.approx(rounds["starved_s"], rel=1e-9)
    assert 0 < rounds["starved_s"] <= (
        sum(phase_s.values()) - phase_s["idle"] - phase_s["decode_wait"]
        - phase_s["first_token"] + 1e-9)
    # an admission into an idle engine, or after a drain: its prefill's enqueue
    assert starved["prefill"] > 0 and starved["sched"] > 0
    # round by round: the same account, folded
    assert sum(sum(r["starved"]) for r in records if r["starved"]) \
        == pytest.approx(rounds["starved_s"], rel=1e-9)
    for r in records:
        for p, v in enumerate(r["starved"] or ()):
            assert 0 <= v <= r["split"][p] + 1e-12
            assert not (v and ROUND_PHASES[p] in ("idle", "decode_wait",
                                                  "first_token"))
    if not traced:
        assert len(tracer) == 0
        return
    assert tracer.dropped == 0
    # (the ring's own order: events() sorts by start)
    evs = list(enumerate(e for e in list(tracer._events)
                         if e["ph"] == "X" and e["tid"] == eng._worker.ident))
    leaves = [(i, e) for i, e in evs if e["name"] in LEAVES]
    envelopes = [(i, e) for i, e in evs if e["name"] == "lm/starved"]
    assert len(envelopes) >= 3
    assert sum(e["dur"] for _, e in envelopes) * 1e-6 == pytest.approx(
        rounds["starved_s"], abs=len(envelopes) * EPS_US * 1e-6)
    leaf_ends = {}
    for _, e in leaves:
        leaf_ends.setdefault(e["name"], []).append(_ends(e))
    idle_starts = [e["ts"] for _, e in leaves if e["name"] == "lm/idle"]
    last_round_end = max(_ends(e) for _, e in evs if e["name"] == "lm/round")
    near = lambda t, ts: any(abs(t - x) <= EPS_US for x in ts)   # noqa: E731
    for i, env in envelopes:
        args = env["args"]
        assert env["cat"] == "serve" and set(args) == {
            "round", "after", "until", "admitted"}
        assert args["after"] in ("decode_wait", "first_token", "idle", "start")
        for _, w in leaves:
            if w["name"] in WAIT_LEAVES:
                assert (_ends(w) <= env["ts"] + EPS_US
                        or _ends(env) <= w["ts"] + EPS_US), (env, w)
        if args["until"] == "idle":     # the engine ran out of work, or closed
            assert near(_ends(env), idle_starts + [last_round_end])
            assert not args["admitted"]
        else:
            assert "lm/" + args["until"] in ENQUEUE_LEAVES
            assert near(_ends(env), leaf_ends["lm/" + args["until"]])
            # an admission's envelope ends in its prefill's enqueue (a
            # chunk is prefilled by the loop, outside any lm/admit)
            assert args["admitted"] == (args["until"] == "prefill"
                                        and kind != "chunked")
        if args["after"] in ("decode_wait", "idle"):
            assert near(env["ts"], leaf_ends["lm/" + args["after"]])
        elif args["after"] == "first_token":
            assert env["ts"] >= min(leaf_ends["lm/first_token"]) - EPS_US
            assert near(env["ts"], [t for ts in leaf_ends.values() for t in ts])
        inside = [(j, e) for j, e in leaves if _inside(e, env)]
        # never the shortest cover of an instant: two leaves or more (one
        # that ends in lm/idle may hold a speculating round's emit alone)
        assert len(inside) >= (1 if args["until"] == "idle" else 2), env
        assert all(e["dur"] < env["dur"] for _, e in inside) or len(inside) == 1
        assert sum(e["dur"] for _, e in inside) == pytest.approx(
            env["dur"], abs=len(inside) * EPS_US)
        assert i > max(j for j, _ in inside)        # written after its last leaf
        assert not {e["name"] for _, e in inside} & (WAIT_LEAVES | {"lm/idle"})
    # envelopes do not overlap each other
    ordered = sorted((e for _, e in envelopes), key=lambda e: e["ts"])
    for a, b in zip(ordered, ordered[1:]):
        assert _ends(a) <= b["ts"] + EPS_US
    # a request that woke the idle engine: starved from the wake-up to the
    # prefill's enqueue, inside its lm/admit
    woke = [e for _, e in envelopes if e["args"]["after"] == "idle"]
    assert woke and all(e["args"]["until"] == "prefill" for e in woke)


def test_speculating_rounds_are_starved_between_their_wait_and_their_draft(
        model, tracer, lm_round_records):
    """A verify round is synchronous: its wait proves the device empty, so its
    emission, the scheduling after it and the next round's draft (the first
    enqueue) pass starved; its dispatch and its wait do not."""
    tracer.disable()
    records = lm_round_records
    eng = _engine(model, spec=SpecConfig(k=3), max_new_tokens=24)
    _serve(eng)
    # one request alone, four tokens a round at most: six rounds or more
    eng.submit(np.arange(1, 8), max_new_tokens=24).result(timeout=120)
    _close(eng)
    P = lm_engine
    plain = [r for prev, r in zip(records, records[1:])
             if r["plain"] and prev["split"][P.P_WAIT] > 0]
    assert len(plain) >= 3
    for r in plain:
        split, starved = r["split"], r["starved"]
        for p in (P.P_SCHED, P.P_DRAFT, P.P_EMIT):
            assert starved[p] == pytest.approx(split[p], rel=1e-9) and split[p] > 0
        assert starved[P.P_DISPATCH] == starved[P.P_WAIT] == 0
        assert split[P.P_DISPATCH] > 0 and split[P.P_WAIT] > 0


@pytest.mark.parametrize("drains", [True, False], ids=["after_a_drain", "ahead"])
def test_slow_round_line_carries_the_rounds_starved_seconds(
        model, tracer, monkeypatch, caplog, drains):
    """A slow emission after a drain is the host's and the device waits for
    it: the line and ``longest`` say ``starved`` 0.3 s; in a round that ran
    ahead the device held the next round meanwhile: ``starved 0.000``."""
    tracer.disable()
    monkeypatch.setattr(lm_engine, "SLOW_ROUND_S", 0.2)
    if drains:
        monkeypatch.setattr(LMServingEngine, "_runs_ahead", lambda self: False)
    eng = _engine(model)
    _serve(eng, n=3)
    eng.metrics.reset_rounds()
    real, calls = lm_engine.LMStream._emit, {"n": 0}

    def slow_emit(self, token_1b):
        in_round = self.first_token_at is not None
        calls["n"] += in_round
        if in_round and calls["n"] == 4:
            time.sleep(0.3)
        return real(self, token_1b)

    monkeypatch.setattr(lm_engine.LMStream, "_emit", slow_emit)
    with caplog.at_level(logging.WARNING, logger="bigdl_tpu.serving"):
        # (a prompt no earlier one shares a block with: no suffix prefill)
        eng.submit(np.arange(20, 27), max_new_tokens=8).result(timeout=120)
        _close(eng)
    rounds = eng.stats()["rounds"]
    longest = rounds["longest"]
    assert longest["phase"] == "emit" and longest["phase_s"]["emit"] >= 0.3
    lines = [r.getMessage() for r in caplog.records
             if "slow round" in r.getMessage()]
    assert len(lines) == 1
    assert f"starved {longest['starved_s']:.3f} s" in lines[0]
    if drains:
        assert longest["phase_s"]["emit"] <= longest["starved_s"] \
            <= longest["seconds"]
        assert rounds["starved_phase_s"]["emit"] >= 0.3
    else:
        assert longest["starved_s"] == 0.0
        assert rounds["starved_phase_s"]["emit"] < 0.3
