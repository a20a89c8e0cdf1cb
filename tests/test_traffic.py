"""bigdl_tpu.traffic: the production-traffic harness on CPU.

Deterministic-trace and SLO-controller unit tests, the typed-shed
accounting contract (ServingOverloaded + ``serving/rejected_total``),
the incident-log loader both halves of the tooling share, and the
tier-1 CHAOS SOAK: staggered arrivals against a 2-replica set while a
replica dies mid-stream and a transfer chunk wobbles — every accepted
request must complete with the healthy set's exact answer, and the SLO
controller must shed new arrivals (typed, counted) instead of letting
the queue grow without bound.

Fault-marked tests ride the same fast resilience gate as
tests/test_resilience.py (``pytest -m faults``).
"""
import time

import numpy as np
import pytest

from bigdl_tpu.obs.registry import Histogram, percentile_from_counts
from bigdl_tpu.resilience import ServingOverloaded, classify_error, faults
from bigdl_tpu.traffic import (ChaosReplayer, TraceLoadGenerator,
                               SLOController, append_incident,
                               build_schedule, detect_knee,
                               inter_incident_gaps, load_incidents)


def _counter(name: str) -> float:
    from bigdl_tpu.obs import get_registry
    return get_registry().counter(name).value


@pytest.fixture
def inject(monkeypatch):
    """Arm the fault injector through the real activation path (env var
    + refresh), and guarantee it is disarmed afterwards."""
    def _inject(spec: str, seed: int = 0):
        monkeypatch.setenv(faults.ENV_SPEC, spec)
        monkeypatch.setenv(faults.ENV_SEED, str(seed))
        return faults.refresh_from_env()

    yield _inject
    monkeypatch.delenv(faults.ENV_SPEC, raising=False)
    monkeypatch.delenv(faults.ENV_SEED, raising=False)
    faults.refresh_from_env()


def _fake_clock():
    """(clock, sleep) pair over virtual time — trace replays run in
    microseconds of wall time."""
    t = [0.0]
    return (lambda: t[0]), (lambda s: t.__setitem__(0, t[0] + s))


# --------------------------------------------------------------------------- #
# deterministic traces                                                        #
# --------------------------------------------------------------------------- #

def test_trace_deterministic_and_seed_sensitive():
    mk = lambda seed: TraceLoadGenerator(  # noqa: E731
        kind="bursty", rate_rps=30, duration_s=4, seed=seed).trace()
    a, b = mk(7), mk(7)
    assert len(a) == len(b) > 0
    for x, y in zip(a, b):
        assert x.at_s == y.at_s and x.max_new == y.max_new
        assert np.array_equal(x.prompt, y.prompt)
    c = mk(8)
    assert [x.at_s for x in c] != [x.at_s for x in a]


def test_trace_kinds_shape():
    # mean offered rate stays ~rate_rps for every kind except diurnal,
    # whose PEAK is rate_rps (half-sine mean = floor + (1-floor)*2/pi)
    for kind, lo, hi in (("poisson", 0.6, 1.5), ("bursty", 0.6, 1.5),
                         ("diurnal", 0.3, 1.1)):
        n = len(TraceLoadGenerator(kind=kind, rate_rps=50, duration_s=6,
                                   seed=3).trace())
        assert lo <= n / (50 * 6) <= hi, (kind, n)
    # arrivals are sorted, in-window, with menu-drawn lengths
    g = TraceLoadGenerator(kind="diurnal", rate_rps=40, duration_s=3,
                           seed=1, prompt_lens=(4, 8), max_news=(2, 6))
    tr = g.trace()
    assert all(0 < a.at_s < 3 for a in tr)
    assert all(tr[i].at_s <= tr[i + 1].at_s for i in range(len(tr) - 1))
    assert {a.prompt_len for a in tr} <= {4, 8}
    assert {a.max_new for a in tr} <= {2, 6}
    with pytest.raises(ValueError):
        TraceLoadGenerator(kind="sawtooth")


def test_open_loop_arrivals_never_wait_on_completions():
    """The defining property: submit times track the SCHEDULE even when
    nothing ever completes (handles are never resolved)."""
    gen = TraceLoadGenerator(kind="poisson", rate_rps=100, duration_s=1,
                             seed=0)
    clock, sleep = _fake_clock()
    submitted = []
    report = gen.run(lambda a: submitted.append((a.index, clock())) or a,
                     clock=clock, sleep=sleep)
    sched = gen.trace()
    assert report.offered == len(sched) == len(submitted)
    for (idx, t), arr in zip(submitted, sched):
        assert idx == arr.index
        assert abs(t - arr.at_s) < 1e-9   # virtual clock: exact replay


def test_open_loop_shed_and_error_accounting():
    gen = TraceLoadGenerator(kind="poisson", rate_rps=50, duration_s=1,
                             seed=2)
    clock, sleep = _fake_clock()

    def submit(a):
        if a.index % 3 == 0:
            raise ServingOverloaded("full up")
        if a.index % 3 == 1:
            raise ValueError("not an overload")
        return a.index

    report = gen.run(submit, clock=clock, sleep=sleep)
    n = report.offered
    assert len(report.shed) == len([i for i in range(n) if i % 3 == 0])
    assert len(report.errors) == len([i for i in range(n) if i % 3 == 1])
    assert len(report.accepted) == n - len(report.shed) - len(report.errors)
    s = report.summary()
    assert s["offered"] == n and s["shed"] == len(report.shed)


# --------------------------------------------------------------------------- #
# typed shed + rejected counter                                               #
# --------------------------------------------------------------------------- #

def test_queue_full_is_typed_and_counted():
    from bigdl_tpu.serving import DynamicBatcher, ServingQueueFull

    ev = __import__("threading").Event()
    batcher = DynamicBatcher(lambda x: (ev.wait(10), x)[1],
                             max_batch_size=4, max_wait_ms=0.0,
                             max_queue=1, pool=None)
    try:
        before = _counter("serving/rejected_total")
        batcher.submit(np.zeros((1, 4), np.float32))  # dispatched
        sheds = 0
        for _ in range(8):
            try:
                batcher.submit(np.zeros((1, 4), np.float32))
            except ServingQueueFull as e:
                # the classification contract: overload is transient —
                # retryable after load drains, never a backend loss
                assert isinstance(e, ServingOverloaded)
                assert classify_error(e) == "transient"
                sheds += 1
        assert sheds > 0
        assert _counter("serving/rejected_total") - before == sheds
    finally:
        ev.set()
        batcher.close()


@pytest.mark.faults
def test_serving_enqueue_injection_converts_to_shed(inject):
    from bigdl_tpu.serving import DynamicBatcher

    inject("serving.enqueue:transient:count=2")
    batcher = DynamicBatcher(lambda x: x, max_batch_size=4,
                             max_wait_ms=0.0, max_queue=8, pool=None)
    try:
        before = _counter("serving/rejected_total")
        for _ in range(2):
            with pytest.raises(ServingOverloaded):
                batcher.submit(np.zeros((1, 4), np.float32))
        assert _counter("serving/rejected_total") - before == 2
        # spec exhausted (count=2): admission is open again
        fut = batcher.submit(np.ones((2, 4), np.float32))
        np.testing.assert_allclose(fut.result(timeout=30),
                                   np.ones((2, 4), np.float32))
    finally:
        batcher.close()


# --------------------------------------------------------------------------- #
# SLO controller                                                              #
# --------------------------------------------------------------------------- #

def test_windowed_percentile_from_counts():
    h = Histogram()
    for _ in range(100):
        h.observe(0.001)
    old = h.counts()
    for _ in range(100):
        h.observe(1.0)
    delta = [a - b for a, b in zip(h.counts(), old)]
    # the window only saw the slow observations
    assert percentile_from_counts(delta, 99) == pytest.approx(1.0, rel=0.2)
    assert percentile_from_counts([0] * len(delta), 99) is None
    # lifetime p99 mixes both — the reason windowing exists
    assert h.percentile(50) < 0.01


def test_slo_controller_scale_then_admission_ladder():
    h = Histogram()
    acts = []
    up_budget = [2]

    def scale_up():
        if up_budget[0] > 0:
            up_budget[0] -= 1
            acts.append("up")
            return True
        return False

    c = SLOController(histogram=h, target_p99_s=0.1, window_intervals=4,
                      scale_up=scale_up,
                      set_admission=lambda v: acts.append(("adm", v)),
                      admission_levels=[64, 16, 4],
                      hot_streak=2, cool_streak=3)
    for _ in range(10):
        h.observe(0.5)
        c.tick()
    # ladder order: capacity first (both scale-ups), then admission
    # tightening, then saturated
    assert acts == ["up", "up", ("adm", 16), ("adm", 4)]
    assert c.summary()["scaling_exhausted"]
    assert [a["action"] for a in c.actions] == \
        ["scale_up", "scale_up", "admission_tighten", "admission_tighten",
         "saturated"]
    # recovery: cool ticks relax admission back up the ladder
    for _ in range(12):
        h.observe(0.001)
        c.tick()
    assert ("adm", 16) in acts[4:] and ("adm", 64) in acts[4:]


def test_slo_controller_holds_relax_while_shedding():
    """A healthy accepted-request p99 while sheds are still happening
    means admission is WORKING, not that load dropped — the controller
    must hold the gate instead of relaxing into queue collapse."""
    h = Histogram()
    rejected = [0]
    adm = []
    c = SLOController(histogram=h, target_p99_s=0.1, window_intervals=2,
                      set_admission=adm.append, admission_levels=[64, 4],
                      hot_streak=1, cool_streak=2, start_level=1,
                      rejections=lambda: rejected[0])
    assert adm == [4]          # fail-closed start applied immediately
    # cool ticks, but the window keeps shedding: hold, never relax
    for _ in range(8):
        rejected[0] += 3
        h.observe(0.001)
        c.tick()
    assert adm == [4]
    assert all(a["action"] == "hold_shedding" for a in c.actions)
    # sheds stop; once the shed window drains, cool ticks relax
    for _ in range(8):
        h.observe(0.001)
        c.tick()
    assert adm == [4, 64]


def test_slo_controller_idle_window_is_not_hot():
    h = Histogram()
    fired = []
    c = SLOController(histogram=h, target_p99_s=0.01, window_intervals=2,
                      set_admission=fired.append, admission_levels=[8, 2],
                      hot_streak=1, cool_streak=1)
    for _ in range(5):
        assert c.tick()["p99_s"] is None
    assert fired == [] and c.actions == []
    # stale observations age out of the window and stop driving actions
    h.observe(5.0)
    c.tick()
    assert c.tick()["p99_s"] is not None
    for _ in range(3):
        c.tick()
    assert c.tick()["p99_s"] is None


def test_detect_knee():
    curve = [{"offered_rps": o, "goodput_rps": g}
             for o, g in ((4, 3.9), (8, 7.8), (16, 12.0), (32, 12.4))]
    k = detect_knee(curve)
    assert k["knee_rps"] == 8.0
    assert k["peak_goodput_rps"] == 12.4
    assert k["saturated"]
    # a sweep that never saturates reports its own inadequacy
    k2 = detect_knee([{"offered_rps": 4, "goodput_rps": 3.9},
                      {"offered_rps": 8, "goodput_rps": 7.9}])
    assert k2["knee_rps"] == 8.0 and not k2["saturated"]
    assert detect_knee([])["knee_rps"] is None


# --------------------------------------------------------------------------- #
# incident log + chaos schedule                                               #
# --------------------------------------------------------------------------- #

def test_incident_log_roundtrip(tmp_path):
    p = str(tmp_path / "INC.json")
    assert load_incidents(p) == []
    append_incident("bench", 124, p, now=100.0)
    append_incident("profile", 0, p, now=700.0)
    append_incident("lm", 124, p, now=1900.0)
    rows = load_incidents(p)
    assert [r["stage"] for r in rows] == ["bench", "profile", "lm"]
    assert inter_incident_gaps(rows) == [600.0, 1200.0]


def test_incident_log_tolerates_corruption(tmp_path):
    p = tmp_path / "INC.json"
    p.write_text("{ not json")
    assert load_incidents(str(p)) == []
    # appending over a corrupt file starts a fresh, valid log
    append_incident("bench", 124, str(p), now=5.0)
    assert len(load_incidents(str(p))) == 1
    # malformed rows are dropped individually, valid ones survive
    p.write_text('{"incidents": [{"ts_unix": 1.0, "stage": "a", "rc": 1},'
                 ' {"stage": "no-ts"}, "junk"]}')
    rows = load_incidents(str(p))
    assert len(rows) == 1 and rows[0]["stage"] == "a"


def test_build_schedule_deterministic_and_mapped(tmp_path):
    p = str(tmp_path / "INC.json")
    for i, (stage, rc) in enumerate((("bench", 124), ("lm", 124),
                                     ("profile", 0), ("attention", 124),
                                     ("probe", 124))):
        append_incident(stage, rc, p, now=600.0 * (i + 1) + 40.0 * i)
    a = build_schedule(6.0, path=p, seed=9)
    assert a == build_schedule(6.0, path=p, seed=9)
    assert a != build_schedule(6.0, path=p, seed=10)
    assert all(0 < e["at_s"] < 6.0 for e in a)
    assert all(e["spec"].endswith(":count=1") for e in a)
    sites = {e["site"] for e in a}
    assert sites <= {"transfer.chunk", "serving.dispatch",
                     "serving.enqueue", "engine.init"}
    # the stage->site mapping is what ties replay to what really died
    mapped = {e["source_stage"]: e["site"] for e in a}
    for stage, site in mapped.items():
        want = {"bench": "transfer.chunk", "attention": "transfer.chunk",
                "lm": "serving.dispatch", "profile": "serving.enqueue",
                "probe": "engine.init"}[stage]
        assert site == want
    # empty log still yields a schedule (default gap)
    b = build_schedule(4.0, path=str(tmp_path / "missing.json"), seed=0)
    assert len(b) >= 2 and all(0 < e["at_s"] < 4.0 for e in b)


@pytest.mark.faults
def test_chaos_replayer_arms_and_fires(monkeypatch):
    monkeypatch.delenv(faults.ENV_SPEC, raising=False)
    faults.refresh_from_env()
    sched = [{"at_s": 0.0, "site": "serving.enqueue", "kind": "transient",
              "spec": "serving.enqueue:transient:count=1"}]
    rep = ChaosReplayer(sched)
    with rep:
        deadline = time.perf_counter() + 5.0
        fired = False
        while time.perf_counter() < deadline and not fired:
            try:
                faults.fault_point("serving.enqueue", n=1)
            except Exception:
                fired = True
            time.sleep(0.01)
        assert fired
        s = rep.summary()
        assert s["armed"] == 1 and s["fired"] == 1
    # stop() disarms fully: site is a no-op again, env restored
    assert faults.active() is None
    assert faults.ENV_SPEC not in __import__("os").environ
    faults.fault_point("serving.enqueue", n=1)


def test_chaos_replayer_refuses_to_clobber_explicit_spec(monkeypatch, inject):
    inject("transfer.chunk:transient:count=1")
    with pytest.raises(RuntimeError):
        ChaosReplayer([]).start()


@pytest.mark.faults
def test_injector_stats_aggregate_identical_specs(inject):
    """A chaos schedule arms many events with IDENTICAL describe()
    strings (e.g. two transfer.chunk:transient:count=1 events); stats()
    must aggregate them — last-wins dict keying silently reported
    fired=0 for a schedule whose first event had fired."""
    spec = "transfer.chunk:transient:count=1"
    inj = inject(spec + ";" + spec)
    with pytest.raises(Exception):
        inj.check("transfer.chunk")
    st = inj.stats()
    assert list(st) == ["transfer.chunk:transient:count=1"]
    assert st["transfer.chunk:transient:count=1"]["fired"] == 1
    assert st["transfer.chunk:transient:count=1"]["seen"] >= 1


# --------------------------------------------------------------------------- #
# actuators: LM slot limit, ReplicaSet scale_to                               #
# --------------------------------------------------------------------------- #

def test_lm_slot_limit_caps_concurrency_token_exact():
    from bigdl_tpu.models.transformer import TransformerLM
    from bigdl_tpu.models.transformer.generate import generate
    from bigdl_tpu.serving import LMServingEngine

    model = TransformerLM(vocab_size=31, hidden_size=16, n_head=2,
                          n_layers=1, max_len=32,
                          pos_encoding="rope").build(seed=0)
    eng = LMServingEngine(model, slots=2, cache_len=24, max_new_tokens=6,
                          prefill_buckets=(4, 8))
    try:
        eng.warmup()
        assert eng.set_slot_limit(99) == 2    # clamped to physical slots
        assert eng.set_slot_limit(0) == 1     # floor keeps progress
        assert eng.set_slot_limit(1) == 1
        rng = np.random.RandomState(0)
        prompts = [rng.randint(1, 32, size=t).astype(np.int32)
                   for t in (4, 7, 5)]
        streams = [eng.submit(p, max_new_tokens=4) for p in prompts]
        outs = [s.result(timeout=60) for s in streams]
        for p, out in zip(prompts, outs):
            ref = np.asarray(generate(model, model.params, p[None], 4))
            np.testing.assert_array_equal(out, ref[0])
        snap = eng.metrics.snapshot()
        # the cap held: never more than 1 of the 2 slots active
        assert snap["slot_occupancy"] is not None
        assert snap["slot_occupancy"] <= 0.5 + 1e-9
        assert eng.stats()["slot_limit"] == 1
    finally:
        eng.close()


def test_replicaset_scale_to():
    from bigdl_tpu import nn
    from bigdl_tpu.resilience import ReplicaSet

    model = nn.Sequential(nn.Linear(8, 4), nn.LogSoftMax()).build(seed=0)
    x = np.linspace(-1, 1, 16, dtype=np.float32).reshape(2, 8)
    with ReplicaSet(model, n_replicas=1, input_shape=(8,),
                    max_batch_size=8) as rs:
        rs.warmup()
        ref = rs.predict(x, timeout=60)
        assert rs.scale_to(3) == 3
        assert len([r for r in rs.stats()["replicas"].values()
                    if r["state"] != "draining"]) == 3
        np.testing.assert_allclose(rs.predict(x, timeout=60), ref,
                                   atol=1e-6)
        assert rs.scale_to(1) == 1
        np.testing.assert_allclose(rs.predict(x, timeout=60), ref,
                                   atol=1e-6)
        assert _counter("resilience/scale_ups") >= 2
        assert _counter("resilience/scale_downs") >= 2


# --------------------------------------------------------------------------- #
# the chaos soak                                                              #
# --------------------------------------------------------------------------- #

@pytest.mark.faults
def test_chaos_soak_zero_accepted_loss(inject):
    """Staggered open-loop arrivals against a 2-replica set while r1
    dies mid-stream, a transfer chunk wobbles, and dispatches drag.
    Contract: every ACCEPTED request completes with the healthy set's
    exact answer; the live SLO controller tightens admission so excess
    arrivals become typed sheds, not unbounded queue growth."""
    from bigdl_tpu import nn
    from bigdl_tpu.resilience import ReplicaSet

    model = nn.Sequential(nn.Linear(8, 4), nn.LogSoftMax()).build(seed=0)

    def payload(idx: int) -> np.ndarray:
        return np.full((1, 8), (idx % 5) * 0.5 - 1.0, np.float32)

    rs = ReplicaSet(model, n_replicas=2, input_shape=(8,),
                    max_batch_size=8, max_queue=64,
                    failure_threshold=1, cooldown_s=60.0)
    try:
        rs.warmup()
        refs = {i: rs.predict(payload(i), timeout=60) for i in range(5)}

        # r1 dies for good on its 2nd dispatch; every dispatch drags
        # 25 ms (the die spec comes FIRST: check() stops at the first
        # firing spec per call); one staged chunk wobbles transiently
        inject("serving.dispatch:die:name=r1,after=2;"
               "serving.dispatch:latency:ms=25;"
               "transfer.chunk:transient:count=1")

        before = _counter("serving/rejected_total")
        ctrl = SLOController(
            histogram=rs.metrics.total_latency, target_p99_s=0.005,
            interval_s=0.05, window_intervals=4,
            set_admission=rs.batcher.set_max_queue,
            admission_levels=[64, 2, 1], hot_streak=2, cool_streak=50)
        gen = TraceLoadGenerator(kind="bursty", rate_rps=60,
                                 duration_s=2.0, seed=11)
        with ctrl:
            report = gen.run(lambda a: rs.submit(payload(a.index)))
            lost = []
            for a, fut in report.accepted:
                try:
                    y = fut.result(timeout=60)
                    if not np.allclose(y, refs[a.index % 5], atol=1e-5):
                        lost.append((a.index, "mismatch"))
                except Exception as e:  # noqa: BLE001
                    lost.append((a.index, repr(e)))

        assert report.offered > 40
        # ZERO accepted-request loss through replica death + wobble
        assert lost == []
        # the controller tightened admission and shed the excess —
        # typed, counted, and bounded-queue by construction
        assert any(a["action"] == "admission_tighten"
                   for a in ctrl.actions), ctrl.summary()
        assert len(report.shed) > 0
        assert _counter("serving/rejected_total") - before == \
            len(report.shed)
        assert report.errors == []
        # r1 really died: its circuit is open and the injector fired it
        st = faults.active().stats()
        assert any(k.startswith("serving.dispatch:backend_lost")
                   and v["fired"] >= 1 for k, v in st.items())
        r1 = rs.stats()["replicas"]["r1"]
        assert r1["state"] in ("open", "half_open")
    finally:
        rs.close()
