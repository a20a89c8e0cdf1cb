"""Flight recorder + telemetry time-series + request-scoped tracing.

The three observability pillars this file pins:

- :class:`TimeSeriesSampler` — gauge values, counter deltas, and
  windowed histogram percentiles sampled into a bounded ring;
- request-scoped span trees — a ``request_id`` minted at submit and
  propagated through batch assembly, prefill/decode rounds, and
  failover re-dispatch, reassembled per request from the flat ring;
- :class:`FlightRecorder` — exactly ONE schema-valid ``FLIGHT_*.json``
  bundle per distinct incident, cross-referenced from the
  incident ledger.

The chaos soak at the bottom is the acceptance test: replica death plus
an injected stall mid-load must yield a span tree for every accepted
request (including the failover hop) and one bundle per incident whose
time-series window covers it.
"""
import json
import os
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

from bigdl_tpu.obs import (MetricRegistry, TimeSeriesSampler, get_registry,
                           get_sampler, get_tracer, set_sampler)
from bigdl_tpu.obs import flight as flight_mod
from bigdl_tpu.obs.flight import FlightRecorder

REPO = os.path.join(os.path.dirname(__file__), os.pardir)
sys.path.insert(0, os.path.join(REPO, "scripts"))
from validate_artifact import validate as validate_artifact  # noqa: E402


def _wait(pred, timeout=30.0):
    deadline = time.perf_counter() + timeout
    while time.perf_counter() < deadline:
        if pred():
            return True
        time.sleep(0.005)
    return False


@pytest.fixture
def global_trace():
    """Process-wide tracer, enabled with a clean buffer and full
    request sampling; restored afterwards."""
    tr = get_tracer()
    was, rate = tr.enabled, tr.sample_rate
    tr.clear()
    tr.enable()
    tr.set_sample_rate(1.0)
    yield tr
    tr.enabled = was
    tr.set_sample_rate(rate)
    tr.clear()


@pytest.fixture
def recorder(tmp_path):
    """Process-wide flight recorder armed into a tmp dir (bundle files
    and the incident ledger both land there); restored afterwards."""
    old = flight_mod.get_flight_recorder()
    rec = flight_mod.configure(
        enabled=True, out_dir=str(tmp_path),
        incidents_path=str(tmp_path / "INCIDENTS.json"))
    yield rec
    flight_mod._GLOBAL = old


def _bundles(tmp_path):
    return sorted(tmp_path.glob("FLIGHT_*.json"))


# --------------------------------------------------------------------- #
# telemetry time-series
# --------------------------------------------------------------------- #

def test_sampler_counter_values_and_deltas():
    reg = MetricRegistry(max_metrics=64)
    reg.counter("app/requests").add(3)
    s = TimeSeriesSampler(reg, interval_s=0.01, capacity=16)
    row1 = s.sample_now()
    reg.counter("app/requests").add(2)
    row2 = s.sample_now()
    assert row1["metrics"]["app/requests"]["value"] == 3.0
    assert row2["metrics"]["app/requests"]["value"] == 5.0
    assert row2["metrics"]["app/requests"]["delta"] == 2.0
    assert row2["t_unix"] >= row1["t_unix"]


def test_sampler_windowed_histogram_percentiles():
    from bigdl_tpu.obs import Histogram
    reg = MetricRegistry(max_metrics=64)
    h = Histogram()
    reg.register("app/latency", h, replace=True)
    for _ in range(100):
        h.observe(0.001)
    s = TimeSeriesSampler(reg, capacity=16)
    s.sample_now()
    for _ in range(50):
        h.observe(1.0)  # only THIS interval's observations
    row = s.sample_now()
    m = row["metrics"]["app/latency"]
    assert m["count"] == 150 and m["count_delta"] == 50
    assert 0.9 <= m["p50_s"] <= 1.2  # windowed, not lifetime (~0.001)
    assert 0.9 <= m["p99_s"] <= 1.2


def test_sampler_ring_bounded_and_window_trim():
    reg = MetricRegistry(max_metrics=8)
    reg.gauge("g").set(1.0)
    s = TimeSeriesSampler(reg, capacity=5)
    for _ in range(9):
        s.sample_now()
    assert len(s) == 5  # bounded ring, oldest evicted
    assert len(s.window()) == 5
    assert s.window(last_s=0.0) in ([], [s.window()[-1]]) or \
        all(r["t_unix"] >= time.time() - 1.0 for r in s.window(last_s=1.0))
    pairs = s.series("g", "value")  # (t_unix, value) plot pairs
    assert [v for _, v in pairs] == [1.0] * 5
    assert [t for t, _ in pairs] == sorted(t for t, _ in pairs)


def test_sampler_background_thread():
    reg = MetricRegistry(max_metrics=8)
    reg.counter("ticks").add(1)
    s = TimeSeriesSampler(reg, interval_s=0.02, capacity=64)
    with s:
        assert _wait(lambda: len(s) >= 3, timeout=10.0)
    n = len(s)
    time.sleep(0.06)
    assert len(s) == n  # stopped: no more rows
    s.stop()  # idempotent


def test_sampler_reports_registry_cardinality():
    reg = MetricRegistry(max_metrics=16)
    reg.counter("a").add(1)
    reg.gauge("b").set(2.0)
    s = TimeSeriesSampler(reg, capacity=4)
    row = s.sample_now()
    assert row["metrics"]["obs/registry_cardinality"]["value"] == 2.0


def test_global_sampler_install_and_restore():
    s = TimeSeriesSampler(MetricRegistry(max_metrics=8), capacity=4)
    prev = set_sampler(s)
    try:
        assert get_sampler() is s
    finally:
        set_sampler(prev)
    assert get_sampler() is prev


# --------------------------------------------------------------------- #
# flight recorder: bundles, dedup, triggers
# --------------------------------------------------------------------- #

def test_recorder_disabled_by_default_records_nothing(tmp_path,
                                                      monkeypatch):
    monkeypatch.delenv("BIGDL_TPU_FLIGHT", raising=False)
    rec = FlightRecorder(out_dir=str(tmp_path))
    assert rec.enabled is False
    assert rec.record("stall", {"x": 1}) is None
    assert rec.note_shed() is None
    assert _bundles(tmp_path) == []


def test_bundle_schema_pointer_and_correlation(tmp_path, recorder,
                                               global_trace):
    reg = get_registry()
    sampler = TimeSeriesSampler(reg, capacity=32)
    prev = set_sampler(sampler)
    try:
        with global_trace.span("serve/device", cat="serve",
                               request_ids=["r1-1"]):
            pass
        sampler.sample_now()
        recorder.register_state("pool", lambda: {"free": 7})
        recorder.register_requests("eng", lambda: ["r1-1", "r1-2"])
        path = recorder.record("backend_lost",
                               {"reason": "no_replica_available"},
                               key="replicaset")
    finally:
        set_sampler(prev)
    assert path is not None and os.path.exists(path)
    assert os.path.basename(path).startswith("FLIGHT_")
    # schema-valid under the repo artifact linter
    assert validate_artifact(path) == []
    doc = json.loads(open(path).read())
    assert doc["flight"] == "backend_lost" and doc["complete"] is True
    assert doc["detail"]["reason"] == "no_replica_available"
    assert any(s.get("name") == "serve/device" for s in doc["spans"])
    assert doc["timeseries"], "time-series window missing"
    assert doc["state"]["pool"] == {"free": 7}
    assert doc["active_requests"]["eng"] == ["r1-1", "r1-2"]
    assert isinstance(doc["diagnose_tpu"], str)
    # ledger row cross-references the bundle: the pointer must resolve
    # to the bundle file (relative to cwd for in-tree flight/ dirs,
    # absolute for out-of-tree ones like this tmp dir)
    ledger = json.loads(open(recorder.incidents_path).read())
    (row,) = ledger["incidents"]
    assert os.path.abspath(row["flight"]) == os.path.abspath(path)
    assert row["stage"] == "flight/backend_lost" and row["rc"] == 0


def test_one_bundle_per_distinct_incident(tmp_path, recorder):
    p1 = recorder.record("fault_injected", {"site": "a"}, key="a")
    p2 = recorder.record("fault_injected", {"site": "a"}, key="a")
    p3 = recorder.record("fault_injected", {"site": "b"}, key="b")
    p4 = recorder.record("stall", {"watchdog": "serve"}, key="serve")
    assert p1 is not None and p2 is None  # deduped within the window
    assert p3 is not None and p4 is not None  # distinct incidents
    assert len(_bundles(tmp_path)) == 3
    assert recorder.bundles_written == 3


def test_dedup_window_expiry_rearms(tmp_path, recorder):
    # (a window of 0.05 s was shorter than the first bundle's write under six
    # loaded test workers: the second record then re-armed and the test failed)
    recorder.dedup_window_s = 0.5
    assert recorder.record("stall", key="w") is not None
    assert recorder.record("stall", key="w") is None
    time.sleep(0.6)
    assert recorder.record("stall", key="w") is not None


def test_provider_failure_is_captured_not_fatal(tmp_path, recorder):
    recorder.register_state("bad", lambda: 1 / 0)
    path = recorder.record("stall", key="x")
    doc = json.loads(open(path).read())
    assert "capture failed" in doc["state"]["bad"]


def test_shed_burst_threshold_fires_once(tmp_path, recorder):
    recorder.shed_burst_threshold = 5
    for _ in range(4):
        assert recorder.note_shed() is None
    assert recorder.note_shed() is not None  # 5th shed in the window
    for _ in range(10):
        assert recorder.note_shed() is None  # deduped burst
    (bundle,) = _bundles(tmp_path)
    doc = json.loads(open(bundle).read())
    assert doc["flight"] == "shed_burst"
    assert doc["detail"]["sheds_in_window"] >= 5


def test_batcher_shed_reaches_recorder(tmp_path, recorder):
    """count_rejection() (every typed queue-full/oversize shed) feeds
    the burst detector without any serving engine running."""
    from bigdl_tpu.serving.batcher import count_rejection
    recorder.shed_burst_threshold = 3
    for _ in range(3):
        count_rejection()
    assert len(_bundles(tmp_path)) == 1


def test_watchdog_stall_dumps_bundle(tmp_path, recorder):
    from bigdl_tpu.obs import StallWatchdog, Tracer
    wd = StallWatchdog("flighttest", deadline_s=0.01, poll_s=30.0,
                       tracer=Tracer(enabled=False),
                       capture={"diagnose_tpu": lambda: "dummy"})
    wd.step_started()
    try:
        time.sleep(0.02)
        event = wd.check_now()
    finally:
        wd.step_finished()
        wd.stop()
    assert event is not None
    (bundle,) = _bundles(tmp_path)
    doc = json.loads(open(bundle).read())
    assert doc["flight"] == "stall"
    assert doc["detail"]["watchdog"] == "flighttest"
    assert "thread_stacks" not in doc["detail"]  # bundles stay bounded


def test_cli_dump_writes_bundle_and_ledger_row(tmp_path):
    env = dict(os.environ, PYTHONPATH=REPO, JAX_PLATFORMS="cpu")
    env.pop("BIGDL_TPU_FLIGHT", None)  # CLI arms itself
    proc = subprocess.run(
        [sys.executable, "-m", "bigdl_tpu.obs.flight", "dump",
         "probe", "1", "--dir", str(tmp_path)],
        cwd=str(tmp_path), env=env, capture_output=True, text=True,
        timeout=120)
    assert proc.returncode == 0, proc.stderr[-2000:]
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    assert out["flight"] == "probe_death"
    assert validate_artifact(out["path"]) == []
    # ledger row looks like the old bare append PLUS the pointer
    ledger = json.loads((tmp_path / "INCIDENTS.json").read_text())
    (row,) = ledger["incidents"]
    assert row["stage"] == "probe" and row["rc"] == 1
    assert row["flight"] == os.path.basename(out["path"])


@pytest.mark.parametrize("name, doc, problem", [
    # families the linter no longer knows are reported, not passed
    ("BENCH_SPEC.json", {"rows": [], "complete": True, "platform": "cpu"},
     "unknown artifact family"),
    ("PROFILE_MEM.json", {"rows": [], "complete": True, "platform": "cpu"},
     "unknown artifact family"),
    ("TUNE_ATTN.json", {"rows": [{}], "complete": True, "platform": "cpu"},
     "unknown artifact family"),
    ("FLIGHT_x.json", {"flight": "probe_death"}, "flight bundle lacks"),
])
def test_validate_artifact_knows_two_families(tmp_path, name, doc,
                                                problem):
    path = tmp_path / name
    path.write_text(json.dumps(doc))
    probs = validate_artifact(str(path))
    assert probs and problem in probs[0]


# --------------------------------------------------------------------- #
# request-scoped tracing: span trees across the serving stack
# --------------------------------------------------------------------- #

def test_batch_serving_request_span_trees(global_trace, tmp_path):
    from bigdl_tpu import nn
    from bigdl_tpu.serving import ServingEngine

    model = nn.Sequential(nn.Linear(8, 4), nn.LogSoftMax()).build(seed=1)
    rng = np.random.RandomState(0)
    with ServingEngine(model, input_shape=(8,), max_batch_size=8,
                       max_wait_ms=2.0) as eng:
        eng.warmup()
        futs = [eng.submit(rng.randn(n, 8).astype(np.float32))
                for n in (1, 3, 2)]
        for f in futs:
            f.result(timeout=30)
    rids = [f.request_id for f in futs]
    assert len(set(rids)) == 3 and all(rids)
    for rid in rids:
        tree = global_trace.span_tree(rid)
        assert tree["span_count"] > 0
        roots = [n["name"] for n in tree["spans"]]
        assert "serve/request" in roots, roots
        root = next(n for n in tree["spans"]
                    if n["name"] == "serve/request")
        child_names = {c["name"] for c in root["children"]}
        # queue-wait and the batch phases nest under the request root
        assert "serve/queue_wait" in child_names
        assert {"serve/assemble", "serve/device"} & child_names
    # per-request Chrome export round-trips and is filtered
    path = str(tmp_path / "TRACE_REQ.json")
    doc = global_trace.export_request(rids[0], path)
    assert doc["otherData"]["request_id"] == rids[0]
    loaded = json.loads(open(path).read())
    for e in loaded["traceEvents"]:
        if e["ph"] == "M":
            continue
        args = e.get("args", {})
        assert (args.get("request_id") == rids[0]
                or rids[0] in args.get("request_ids", []))


def test_request_ids_minted_even_when_tracing_off():
    from bigdl_tpu import nn
    from bigdl_tpu.serving import ServingEngine

    tr = get_tracer()
    was = tr.enabled
    tr.enabled = False
    try:
        model = nn.Sequential(nn.Linear(8, 4),
                              nn.LogSoftMax()).build(seed=1)
        with ServingEngine(model, input_shape=(8,), max_batch_size=4,
                           max_wait_ms=1.0) as eng:
            fut = eng.submit(np.zeros((1, 8), np.float32))
            fut.result(timeout=30)
        # forensics needs the id regardless of the sampling verdict
        assert fut.request_id
    finally:
        tr.enabled = was


def test_lm_serving_request_span_trees(global_trace):
    from bigdl_tpu.models.transformer import TransformerLM
    from bigdl_tpu.serving import LMServingEngine

    model = TransformerLM(vocab_size=31, hidden_size=16, n_head=2,
                          n_layers=1, max_len=32,
                          pos_encoding="rope").build(seed=0)
    eng = LMServingEngine(model, slots=2, cache_len=24, block_len=4,
                          max_new_tokens=6, prefill_buckets=(4, 8, 16))
    try:
        eng.warmup()
        rng = np.random.RandomState(1)
        streams = [eng.submit(
            rng.randint(1, 31, size=n).astype(np.int32) + 1,
            max_new_tokens=4) for n in (4, 7)]
        for s in streams:
            s.result(timeout=60)
        assert _wait(lambda: eng.metrics.completed == 2)
    finally:
        eng.close()
    for s in streams:
        assert s.request_id
        tree = global_trace.span_tree(s.request_id)
        root = next((n for n in tree["spans"]
                     if n["name"] == "lm/request"), None)
        assert root is not None, [n["name"] for n in tree["spans"]]
        names = {c["name"] for c in root["children"]}
        assert "lm/queue_wait" in names
        assert "lm/decode_round" in names or "lm/verify_round" in names
        # the admission is one envelope whose leaves tile it
        admit = next(c for c in root["children"] if c["name"] == "lm/admit")
        assert admit["args"]["deferred"] is False
        assert {"lm/admit_host", "lm/prefill", "lm/insert",
                "lm/first_token"} <= {c["name"] for c in admit["children"]}
        assert root["args"]["emitted"] >= 1
    # the enqueue instant precedes the root (recorded pre-admission)
    enq = [e for e in global_trace.events()
           if e.get("name") == "lm/enqueue"]
    assert len(enq) == 2


def test_sample_rate_zero_keeps_serving_untraced(global_trace):
    from bigdl_tpu import nn
    from bigdl_tpu.serving import ServingEngine

    global_trace.set_sample_rate(0.0)
    model = nn.Sequential(nn.Linear(8, 4), nn.LogSoftMax()).build(seed=1)
    with ServingEngine(model, input_shape=(8,), max_batch_size=4,
                       max_wait_ms=1.0) as eng:
        fut = eng.submit(np.zeros((2, 8), np.float32))
        fut.result(timeout=30)
    assert fut.request_id
    # request-scoped events are sampled out; batch-level spans remain
    assert global_trace.request_events(fut.request_id) == []
    assert global_trace.span_tree(fut.request_id)["span_count"] == 0


# --------------------------------------------------------------------- #
# acceptance: chaos soak — replica death + injected stall mid-load
# --------------------------------------------------------------------- #

@pytest.mark.faults
def test_chaos_soak_span_trees_and_bundles(tmp_path, recorder,
                                           global_trace, monkeypatch):
    """Replica r1 dies mid-load while a watchdog stall fires: every
    accepted request still yields a span tree (including the failover
    hop for re-dispatched requests), and the recorder writes exactly
    one schema-valid bundle per distinct incident, each carrying a
    time-series window that covers the incident instant."""
    from bigdl_tpu import nn
    from bigdl_tpu.obs import StallWatchdog, Tracer
    from bigdl_tpu.resilience import ReplicaSet, faults

    monkeypatch.setenv(faults.ENV_SPEC,
                       "serving.dispatch:die:name=r1,after=3")
    monkeypatch.setenv(faults.ENV_SEED, "0")
    faults.refresh_from_env()
    sampler = TimeSeriesSampler(get_registry(), interval_s=0.02,
                                capacity=512)
    prev = set_sampler(sampler)
    model = nn.Sequential(nn.Linear(8, 4), nn.LogSoftMax()).build(seed=0)
    rng = np.random.RandomState(3)
    t_start = time.time()
    try:
        sampler.start()
        rs = ReplicaSet(model, n_replicas=2, input_shape=(8,),
                        max_batch_size=4, max_wait_ms=1.0,
                        failure_threshold=2, cooldown_s=300.0)
        try:
            # one request per batch (the resilience-test idiom) so r1
            # accumulates enough dispatches to die and trip its breaker
            futs, outs = [], []
            for i in range(12):
                if i == 6:
                    # the injected stall, mid-load: a held-open step
                    # past its deadline (the hung-backend signature)
                    wd = StallWatchdog(
                        "soak", deadline_s=0.01, poll_s=30.0,
                        tracer=Tracer(enabled=False),
                        capture={"diagnose_tpu": lambda: "dummy"})
                    wd.step_started()
                    time.sleep(0.02)
                    assert wd.check_now() is not None
                    wd.step_finished()
                    wd.stop()
                futs.append(rs.submit(rng.randn(1, 8).astype(np.float32)))
                outs.append(futs[-1].result(timeout=60))
            assert all(o.shape == (1, 4) for o in outs)
            st = rs.stats()
            assert st["replicas"]["r1"]["state"] == "open"
        finally:
            rs.close()
    finally:
        sampler.stop()
        set_sampler(prev)
        monkeypatch.delenv(faults.ENV_SPEC, raising=False)
        monkeypatch.delenv(faults.ENV_SEED, raising=False)
        faults.refresh_from_env()

    # -- >= 99% of accepted requests have a span tree ------------------- #
    rids = [f.request_id for f in futs]
    assert all(rids) and len(set(rids)) == 12
    with_tree = 0
    failover_rids = []
    for rid in rids:
        tree = global_trace.span_tree(rid)
        roots = [n["name"] for n in tree["spans"]]
        if "serve/request" in roots:
            with_tree += 1
        for ev in global_trace.request_events(rid):
            if ev.get("name") == "resilience/failover":
                failover_rids.append(rid)
                break
    assert with_tree == len(rids)  # 100%, bar is >= 99%
    # the failover hop is part of the re-dispatched requests' trees
    assert failover_rids, "no request recorded its failover hop"
    fail_tree = global_trace.span_tree(failover_rids[0])
    flat = json.dumps(fail_tree)
    assert "resilience/failover" in flat
    assert "resilience/dispatch" in flat

    # -- exactly one bundle per distinct incident ----------------------- #
    bundles = _bundles(tmp_path)
    by_kind = {}
    for b in bundles:
        doc = json.loads(open(b).read())
        assert validate_artifact(str(b)) == []
        by_kind.setdefault(doc["flight"], []).append(doc)
    # two distinct incidents: the fault-injector fire (replica death)
    # and the watchdog stall — one bundle each, dedup ate the repeats
    assert set(by_kind) == {"fault_injected", "stall"}, set(by_kind)
    assert [len(v) for v in by_kind.values()] == [1, 1]
    for kind, (doc,) in by_kind.items():
        # the time-series window covers the incident instant
        assert doc["timeseries"], kind
        ts = [r["t_unix"] for r in doc["timeseries"]]
        assert min(ts) >= t_start - 1.0
        assert min(ts) <= doc["ts_unix"] + 0.1
    # and the ledger cross-references both
    ledger = json.loads(open(recorder.incidents_path).read())
    assert len(ledger["incidents"]) == 2
    assert all(r.get("flight") for r in ledger["incidents"])
