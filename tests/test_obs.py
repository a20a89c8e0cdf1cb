"""bigdl_tpu.obs: tracer, metric registry, stall watchdog — and the
end-to-end acceptance paths: a traced 3-step DistriOptimizer run and a
traced mixed-batch serving smoke must each export a loadable Chrome
trace containing every instrumented phase, and a deliberately stalled
step must produce a diagnostics event carrying ``diagnose_tpu`` output.
"""
import json
import os
import sys
import threading
import time

import numpy as np
import pytest

from bigdl_tpu.obs import (Counter, FnGauge, Gauge, Histogram,
                           MetricRegistry, StallWatchdog, Tracer,
                           get_registry, get_tracer, shared_watchdog,
                           thread_stacks)

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "scripts"))
from validate_trace import validate_trace  # noqa: E402


# --------------------------------------------------------------------- #
# tracer
# --------------------------------------------------------------------- #

def test_disabled_tracer_records_nothing_and_allocates_nothing():
    tr = Tracer(enabled=False)
    with tr.span("a", cat="t", k=1):
        pass
    tr.instant("b")
    tr.add_complete("c", time.perf_counter(), 0.1)
    assert len(tr) == 0
    # the disabled path returns one shared no-op object, not a fresh
    # context manager per call — that is the near-zero-overhead contract
    assert tr.span("x") is tr.span("y")


def test_span_nesting_and_threads():
    tr = Tracer(enabled=True)
    # the OS reuses the ident of a thread that has ended: all three record
    # while all three are alive
    barrier = threading.Barrier(3)

    def work(label):
        with tr.span(f"outer/{label}", cat="t"):
            with tr.span(f"inner/{label}", cat="t"):
                time.sleep(0.002)
        barrier.wait(timeout=30)

    threads = [threading.Thread(target=work, args=(i,)) for i in range(3)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=60)
    assert not any(t.is_alive() for t in threads)
    events = tr.events()
    assert len(events) == 6
    by_tid = {}
    for e in events:
        by_tid.setdefault(e["tid"], []).append(e)
    assert len(by_tid) == 3  # one lane per thread
    for tid, evs in by_tid.items():
        inner = next(e for e in evs if e["name"].startswith("inner/"))
        outer = next(e for e in evs if e["name"].startswith("outer/"))
        assert inner["name"][6:] == outer["name"][6:]
        # inner span is contained in its outer span on the same thread
        assert outer["ts"] <= inner["ts"]
        assert inner["ts"] + inner["dur"] <= outer["ts"] + outer["dur"] + 1


def test_span_records_error_on_exception():
    tr = Tracer(enabled=True)
    with pytest.raises(ValueError):
        with tr.span("boom"):
            raise ValueError("no")
    (ev,) = tr.events()
    assert ev["args"]["error"] == "ValueError: no"


def test_traced_decorator_and_ring_capacity():
    """A ring-capacity test (the ``traced`` decorator it once used is
    gone; the name stays so that the count of tests does)."""
    tr = Tracer(capacity=4, enabled=True)
    for i in range(10):
        with tr.span(f"f/{i}", cat="t"):
            pass
    events = tr.events()
    assert len(events) == 4  # ring buffer: oldest evicted
    assert [e["name"] for e in events] == ["f/6", "f/7", "f/8", "f/9"]
    assert tr.dropped == 6


def test_export_chrome_round_trips_and_validates(tmp_path):
    tr = Tracer(enabled=True)
    t0 = time.perf_counter()  # retroactive start, after the epoch
    with tr.span("phase/a", cat="t", rows=3):
        tr.instant("marker", cat="t")
        time.sleep(0.002)
    tr.add_complete("phase/b", t0, time.perf_counter() - t0, cat="t")
    path = str(tmp_path / "trace.json")
    doc = tr.export_chrome(path)

    loaded = json.loads(open(path).read())
    assert loaded == json.loads(json.dumps(doc))
    events = loaded["traceEvents"]
    phases = {e["ph"] for e in events}
    assert phases == {"X", "i", "M"}
    for e in events:
        if e["ph"] == "X":
            assert e["ts"] >= 0 and e["dur"] >= 0
        if e["ph"] == "i":
            assert e["s"] == "t"
    # thread_name metadata present for the recording thread
    meta = [e for e in events if e["ph"] == "M"]
    assert meta and all(e["name"] == "thread_name" for e in meta)
    assert validate_trace(path) == []


def test_validate_trace_cli(tmp_path):
    """The scripts/validate_trace.py CLI: exit 0 on a real export,
    exit 1 on a broken file (no jax import — stays fast)."""
    import subprocess

    tr = Tracer(enabled=True)
    with tr.span("a"):
        pass
    good = str(tmp_path / "TRACE_GOOD.json")
    tr.export_chrome(good)
    bad = str(tmp_path / "TRACE_BAD.json")
    with open(bad, "w") as f:
        json.dump({"traceEvents": [{"name": "x", "ph": "X", "ts": 0,
                                    "pid": 1, "tid": 1}]}, f)
    script = os.path.join(os.path.dirname(__file__), "..", "scripts",
                          "validate_trace.py")
    # -S skips site imports: the validator is stdlib-only and the test
    # must stay subsecond
    ok = subprocess.run([sys.executable, "-S", script, good],
                        capture_output=True, text=True)
    assert ok.returncode == 0 and "OK" in ok.stdout
    fail = subprocess.run([sys.executable, "-S", script, good, bad],
                          capture_output=True, text=True)
    assert fail.returncode == 1 and "bad dur" in fail.stdout
    assert subprocess.run([sys.executable, "-S", script],
                          capture_output=True).returncode == 2


def test_validate_trace_flags_malformed_files(tmp_path):
    bad = tmp_path / "bad.json"
    bad.write_text(json.dumps({"traceEvents": [
        {"name": "x", "ph": "X", "ts": 1.0, "pid": 1, "tid": 1},  # no dur
        {"name": "", "ph": "i", "ts": -5, "pid": 1, "tid": 1, "s": "z"},
        {"ph": "?", "pid": "one", "tid": 1},
    ]}))
    problems = validate_trace(str(bad))
    text = "\n".join(problems)
    assert "bad dur" in text
    assert "scope" in text and "bad ts" in text
    assert "unknown phase" in text
    assert validate_trace(str(tmp_path / "missing.json"))
    empty = tmp_path / "empty.json"
    empty.write_text('{"traceEvents": []}')
    assert any("empty trace" in p for p in validate_trace(str(empty)))


# --------------------------------------------------------------------- #
# registry
# --------------------------------------------------------------------- #

def test_registry_get_or_create_and_type_guard():
    reg = MetricRegistry()
    c = reg.counter("train/steps", unit="steps")
    assert reg.counter("train/steps") is c
    c.add(2)
    assert reg.snapshot()["train/steps"]["value"] == 2.0
    with pytest.raises(TypeError):
        reg.gauge("train/steps")
    with pytest.raises(ValueError):
        reg.register("train/steps", Gauge())
    g = Gauge(unit="x")
    assert reg.register("train/steps", g, replace=True) is g
    assert reg.get("train/steps") is g


def test_registry_snapshot_mixes_metric_kinds():
    reg = MetricRegistry()
    reg.counter("c", unit="s").set(4.0, n=2)
    reg.gauge("g").set(7.5)
    reg.register("fn", FnGauge(lambda: 3.0))
    h = reg.histogram("h")
    for v in (0.001, 0.002, 0.003):
        h.observe(v)
    snap = reg.snapshot()
    assert snap["c"] == {"value": 4.0, "n": 2, "unit": "s"}
    assert snap["g"]["value"] == 7.5
    assert snap["fn"]["value"] == 3.0
    assert snap["h"]["count"] == 3 and snap["h"]["p50_s"] > 0
    assert reg.names() == ["c", "fn", "g", "h"]


def test_registry_export_through_visualization(tmp_path):
    from bigdl_tpu.visualization import ObsSummary

    reg = MetricRegistry()
    reg.counter("train/loss_sum").set(1.5)
    h = reg.histogram("serving/latency")
    h.observe(0.01)
    s = ObsSummary(str(tmp_path), "app")
    wrote = reg.export_to_summary(s, step=3)
    assert wrote >= 3  # the counter + histogram p50/p99/mean/count
    vals = s.read_scalar("Obs/train/loss_sum")
    assert vals and vals[0][:2] == (3, 1.5)
    lat = s.read_scalar("Obs/serving/latency/p50_s")
    assert lat and lat[0][1] > 0
    s.close()


def test_fn_gauge_swallows_capture_errors():
    def boom():
        raise RuntimeError("x")
    assert FnGauge(boom).snapshot() == {"value": None}


# --------------------------------------------------------------------- #
# optim.Metrics satellites: unit-aware summary + single-process aggregate
# --------------------------------------------------------------------- #

def test_metrics_summary_units():
    from bigdl_tpu.optim.metrics import Metrics

    m = Metrics()
    m.set("computing time", 3.0, parallel=2)          # default unit "s"
    m.set("batches", 6.0, parallel=2, unit="batches")
    m.add("records", 10.0, unit="")
    out = m.summary()
    assert "computing time : 1.5 s" in out
    # a batch count must not be stamped as seconds
    assert "batches : 3.0 batches" in out
    assert "batches : 3.0 s" not in out
    assert "records : 10.0" in out and "records : 10.0 s" not in out
    # unit_scale only rescales the seconds counters
    scaled = m.summary(unit_scale=1e-3)
    assert "computing time : 1500.0 s" in scaled
    assert "batches : 3.0 batches" in scaled


def test_metrics_aggregate_single_process_noop():
    from bigdl_tpu.optim.metrics import Metrics

    m = Metrics()
    m.set("shard data time", 2.0, parallel=4)
    out = m.aggregate()
    assert out is m  # jax.process_count() == 1 -> no collective, no copy
    assert m.get("shard data time") == (2.0, 4)


def test_metrics_publish_to_registry_live():
    from bigdl_tpu.optim.metrics import Metrics

    reg = MetricRegistry()
    m = Metrics().publish_to(reg)
    m.set("computing time", 1.0)
    assert reg.snapshot()["train/computing time"]["value"] == 1.0
    m.add("computing time", 0.5)  # live object: no re-publish needed
    assert reg.snapshot()["train/computing time"]["value"] == 1.5
    # latest publisher wins the process-wide names
    m2 = Metrics().publish_to(reg)
    m2.set("computing time", 9.0)
    assert reg.snapshot()["train/computing time"]["value"] == 9.0


# --------------------------------------------------------------------- #
# serving metrics satellite: sliding-window throughput
# --------------------------------------------------------------------- #

def test_serving_throughput_uses_sliding_window():
    from bigdl_tpu.serving.metrics import ServingMetrics

    sm = ServingMetrics(throughput_window_s=0.2)
    sm.record_batch(100, 128, [0.001], 0.002)
    snap = sm.snapshot()
    assert snap["throughput_eps"] > 0
    assert snap["throughput_window_s"] == 0.2
    time.sleep(0.3)  # the burst ages out of the window
    snap2 = sm.snapshot()
    assert snap2["throughput_eps"] == 0.0
    # lifetime number keeps the old semantics: examples since start
    assert 0 < snap2["throughput_eps_lifetime"] < snap["throughput_eps_lifetime"]
    sm.record_batch(50, 64, [0.001], 0.002)
    # traffic resumed: the rate reflects only the windowed burst
    # (50 examples over the 0.2s window), not the idle history
    snap3 = sm.snapshot()
    assert snap3["throughput_eps"] == pytest.approx(50 / 0.2, rel=0.2)


def test_serving_metrics_publish_to_registry():
    from bigdl_tpu.serving.metrics import ServingMetrics

    reg = MetricRegistry()
    sm = ServingMetrics().publish_to(reg)
    sm.record_submit()
    sm.record_batch(4, 8, [0.001, 0.002], 0.003)
    snap = reg.snapshot()
    assert snap["serving/requests"]["value"] == 1
    assert snap["serving/examples"]["value"] == 4
    assert snap["serving/device_time"]["count"] == 1
    assert snap["serving/throughput_eps"]["value"] > 0


# --------------------------------------------------------------------- #
# watchdog
# --------------------------------------------------------------------- #

def test_watchdog_stalled_step_produces_diagnose_tpu_event():
    """Acceptance: a deliberately stalled step fires ONE diagnostics
    event containing ``diagnose_tpu`` output and all-thread stacks."""
    tr = Tracer(enabled=False)  # firing must force the event in anyway
    wd = StallWatchdog("test_stall", deadline_s=0.05, min_samples=5,
                       poll_s=30.0, tracer=tr)  # poll thread stays quiet
    try:
        wd.step_started()
        time.sleep(0.08)  # the "stall": in-flight past the deadline
        ev = wd.check_now()
        assert ev is not None and ev["kind"] == "stall"
        assert ev["watchdog"] == "test_stall"
        assert ev["inflight_s"] >= 0.05
        # the capture ran the real /proc scan (safe while wedged)
        assert isinstance(ev["diagnose_tpu"], str) and ev["diagnose_tpu"]
        # stack dumps name this very function as the blocked site
        stacks = "\n".join(ev["thread_stacks"].values())
        assert "test_watchdog_stalled_step" in stacks
        # fires once per stall, not once per poll
        assert wd.check_now() is None
        assert wd.stall_count == 1 and wd.last_event is ev
        # the instant event landed in the trace despite enabled=False
        (trace_ev,) = tr.events()
        assert trace_ev["name"] == "stall:test_stall"
        assert trace_ev["args"]["diagnose_tpu"] == ev["diagnose_tpu"]
        assert not tr.enabled  # force-enable was scoped to the event
        # completing the step re-arms the detector
        wd.step_finished()
        wd.step_started()
        time.sleep(0.08)
        assert wd.check_now() is not None
        wd.step_finished()
    finally:
        wd.stop()


def test_watchdog_median_rule_needs_min_samples():
    wd = StallWatchdog("t", k=2.0, min_samples=3, poll_s=30.0,
                       tracer=Tracer(enabled=False))
    try:
        for _ in range(2):
            with wd.step():
                time.sleep(0.005)
        wd.step_started()
        time.sleep(0.03)  # > 2 x ~5ms median, but only 2 samples
        assert wd.check_now() is None  # < min_samples: unarmed
        wd.step_finished()  # the probe step itself lands a 3rd sample
        assert wd.median() is not None
        wd.step_started()
        time.sleep(0.05)  # >> 2 x median: armed now, fires
        ev = wd.check_now()
        assert ev is not None and ev["steps_observed"] == 3
        wd.step_finished()
    finally:
        wd.stop()


def test_watchdog_background_thread_fires():
    fired = []
    wd = StallWatchdog("bg", deadline_s=0.05, poll_s=0.02,
                       tracer=Tracer(enabled=False),
                       on_stall=fired.append,
                       capture={"diagnose_tpu": lambda: "probe-ok"})
    try:
        wd.step_started()  # starts the poll thread; never finishes
        deadline = time.perf_counter() + 2.0
        while not fired and time.perf_counter() < deadline:
            time.sleep(0.01)
        assert fired and fired[0]["diagnose_tpu"] == "probe-ok"
        wd.step_finished()
    finally:
        wd.stop()


def test_watchdog_reset_and_shared_instances():
    wd = shared_watchdog("test_shared")
    assert shared_watchdog("test_shared") is wd
    with wd.step():
        pass
    assert wd.median() is not None
    wd.reset(k=3.0, deadline_s=1.5)
    assert wd.median() is None and wd.k == 3.0 and wd.deadline_s == 1.5
    wd.stop()


def test_watchdog_env_knobs(monkeypatch):
    from bigdl_tpu.obs import env_watchdog_enabled, env_watchdog_kwargs

    monkeypatch.delenv("BIGDL_TPU_WATCHDOG", raising=False)
    assert env_watchdog_enabled()  # default on
    monkeypatch.setenv("BIGDL_TPU_WATCHDOG", "0")
    assert not env_watchdog_enabled()
    monkeypatch.setenv("BIGDL_TPU_WATCHDOG_K", "4.5")
    monkeypatch.setenv("BIGDL_TPU_WATCHDOG_DEADLINE_S", "12")
    kw = env_watchdog_kwargs()
    assert kw == {"k": 4.5, "deadline_s": 12.0}
    monkeypatch.setenv("BIGDL_TPU_WATCHDOG_K", "junk")
    assert "k" not in env_watchdog_kwargs()


def test_thread_stacks_names_live_threads():
    stacks = thread_stacks()
    assert any("MainThread" in k for k in stacks)
    assert "test_thread_stacks_names_live_threads" in \
        stacks.get("MainThread", "")


# --------------------------------------------------------------------- #
# acceptance: instrumented training + serving produce loadable traces
# --------------------------------------------------------------------- #

@pytest.fixture
def global_trace(tmp_path):
    """Enable the process-wide tracer (the instrumented modules bound it
    at import) with a clean buffer; restore afterwards."""
    tr = get_tracer()
    was = tr.enabled
    tr.clear()
    tr.enable()
    yield tr
    tr.enabled = was
    tr.clear()


def _span_names(events):
    return {e["name"] for e in events if e["ph"] == "X"}


def test_training_run_emits_full_phase_trace(global_trace, tmp_path, nprng):
    import jax
    from bigdl_tpu import nn
    from bigdl_tpu.dataset import DataSet, Sample
    from bigdl_tpu.dataset.transformer import SampleToBatch
    from bigdl_tpu.optim import SGD, Trigger
    from bigdl_tpu.parallel import DistriOptimizer, create_mesh
    from bigdl_tpu.parallel.mesh import DATA_AXIS

    samples = [Sample(nprng.randn(4).astype(np.float32),
                      np.asarray(float(i % 2) + 1, np.float32))
               for i in range(24)]
    ds = DataSet.array(samples) >> SampleToBatch(8, drop_last=True)
    mesh = create_mesh({DATA_AXIS: 2}, devices=jax.devices()[:2])
    m = nn.Sequential(nn.Linear(4, 8), nn.Tanh(), nn.Linear(8, 2),
                      nn.LogSoftMax())
    opt = DistriOptimizer(m, ds, nn.ClassNLLCriterion(), mesh=mesh)
    opt.set_optim_method(SGD(learning_rate=0.1)) \
       .set_end_when(Trigger.max_iteration(3))
    opt.optimize()

    path = str(tmp_path / "TRACE_TRAIN.json")
    global_trace.export_chrome(path)
    assert validate_trace(path) == []
    events = json.loads(open(path).read())["traceEvents"]
    names = _span_names(events)
    # every instrumented training phase shows up
    for phase in ("train/fetch", "train/h2d", "train/step",
                  "train/publish"):
        assert phase in names, (phase, sorted(names))
    steps = [e for e in events if e["name"] == "train/step"]
    assert len(steps) == 3
    assert {e["args"]["iteration"] for e in steps} == {1, 2, 3}
    assert all("loss" in e["args"] for e in steps)


def test_serving_smoke_emits_full_phase_trace(global_trace, tmp_path,
                                              nprng):
    from bigdl_tpu import nn
    from bigdl_tpu.serving import ServingEngine

    model = nn.Sequential(nn.Linear(8, 4), nn.LogSoftMax()).build(seed=1)
    with ServingEngine(model, input_shape=(8,), max_batch_size=8,
                       max_wait_ms=2.0) as eng:
        eng.warmup()
        futs = [eng.submit(nprng.randn(n, 8).astype(np.float32))
                for n in (1, 3, 2, 5, 1)]  # mixed batch sizes
        outs = [f.result(timeout=30) for f in futs]
    assert [o.shape[0] for o in outs] == [1, 3, 2, 5, 1]

    path = str(tmp_path / "TRACE_SERVE.json")
    global_trace.export_chrome(path)
    assert validate_trace(path) == []
    events = json.loads(open(path).read())["traceEvents"]
    names = _span_names(events)
    for phase in ("serve/queue_wait", "serve/assemble", "serve/device",
                  "serve/h2d", "serve/slice_back"):
        assert phase in names, (phase, sorted(names))
    instants = {e["name"] for e in events if e["ph"] == "i"}
    assert "serve/enqueue" in instants
    # warmup pre-compiled every bucket: traffic is all cache hits
    assert "serve/cache_hit" in instants
    enq = [e for e in events if e["name"] == "serve/enqueue"]
    assert len(enq) == 5 and all("queue_depth" in e["args"] for e in enq)


def test_transfer_chunks_are_traced(global_trace):
    import jax.numpy as jnp
    from bigdl_tpu.utils.transfer import chunked_device_put

    x = np.zeros((64, 1024), np.float32)  # 256 KB
    out = chunked_device_put(x, jnp.float32, chunk_bytes=64 * 1024)
    assert out.shape == x.shape
    names = _span_names(global_trace.events())
    assert "h2d/chunk" in names
    chunks = [e for e in global_trace.events()
              if e["name"] == "h2d/chunk"]
    assert len(chunks) >= 4  # 256KB / 64KB
    assert all(e["args"]["bytes"] <= 64 * 1024 for e in chunks)


# --------------------------------------------------------------------- #
# percentile_from_counts edge cases (pinned: empty window, single
# bucket, overflow-bucket mass, torn negative deltas)
# --------------------------------------------------------------------- #

def test_percentile_from_counts_empty_window_is_none():
    from bigdl_tpu.obs.registry import _EDGES, percentile_from_counts
    assert percentile_from_counts([], 99) is None
    assert percentile_from_counts([0] * (len(_EDGES) + 1), 50) is None


def test_percentile_from_counts_single_bucket():
    from bigdl_tpu.obs.registry import _EDGES, percentile_from_counts
    counts = [0] * (len(_EDGES) + 1)
    counts[7] = 42  # all mass in one in-range bucket
    for p in (1, 50, 99, 100):
        assert percentile_from_counts(counts, p) == _EDGES[7]


def test_percentile_from_counts_overflow_bucket_mass():
    from bigdl_tpu.obs.registry import (_EDGES, OVERFLOW_EDGE,
                                        percentile_from_counts)
    counts = [0] * (len(_EDGES) + 1)
    counts[-1] = 5  # everything past the last edge (stalled window)
    got = percentile_from_counts(counts, 99)
    assert got == OVERFLOW_EDGE
    # strictly greater than every real edge: overflow mass can never
    # make the window look healthier than the instrumented range
    assert got > _EDGES[-1]
    # finite, so it survives strict-JSON artifact writers
    assert got == pytest.approx(got) and got != float("inf")
    # caller-supplied ceiling is honored
    assert percentile_from_counts(counts, 99, overflow=123.0) == 123.0


def test_percentile_from_counts_mixed_and_negative_deltas():
    from bigdl_tpu.obs.registry import _EDGES, OVERFLOW_EDGE, \
        percentile_from_counts
    counts = [0] * (len(_EDGES) + 1)
    counts[3] = 90
    counts[-1] = 10
    assert percentile_from_counts(counts, 50) == _EDGES[3]
    assert percentile_from_counts(counts, 99) == OVERFLOW_EDGE
    # a torn counts-delta (negative entry) is clamped, not corrupting
    torn = list(counts)
    torn[0] = -7
    assert percentile_from_counts(torn, 50) == _EDGES[3]


def test_histogram_windowed_percentile_via_counts_delta():
    from bigdl_tpu.obs.registry import percentile_from_counts
    h = Histogram()
    for _ in range(100):
        h.observe(0.001)
    before = h.counts()
    for _ in range(100):
        h.observe(1.0)  # the window being measured
    delta = [c - p for c, p in zip(h.counts(), before)]
    p50 = percentile_from_counts(delta, 50)
    assert p50 is not None and 0.9 <= p50 <= 1.2  # window only


# --------------------------------------------------------------------- #
# tracer: concurrent writers, stable export, request sampling
# --------------------------------------------------------------------- #

def test_tracer_export_stable_under_concurrent_writers(tmp_path):
    tr = Tracer(capacity=4096, enabled=True)
    stop = threading.Event()

    def writer(k):
        i = 0
        while not stop.is_set():
            with tr.span(f"w{k}/span", cat="t", i=i):
                pass
            tr.instant(f"w{k}/mark", cat="t")
            i += 1

    threads = [threading.Thread(target=writer, args=(k,))
               for k in range(4)]
    for t in threads:
        t.start()
    try:
        time.sleep(0.05)
        evs1 = tr.events()
        evs2 = tr.events()
    finally:
        stop.set()
        for t in threads:
            t.join()
    for evs in (evs1, evs2):
        # stable ordering: sorted by timestamp even though writers
        # interleave arbitrarily in the ring
        ts = [e["ts"] for e in evs]
        assert ts == sorted(ts)
        # no torn spans: every complete event carries its full shape
        for e in evs:
            assert "name" in e and "ph" in e and "ts" in e
            if e["ph"] == "X":
                assert "dur" in e and e["dur"] >= 0
    # export under load parses and validates
    path = str(tmp_path / "TRACE_CONC.json")
    tr.export_chrome(path)
    assert validate_trace(path) == []


def test_mint_request_id_unique_and_mine():
    from bigdl_tpu.obs import mint_request_id
    ids = {mint_request_id() for _ in range(100)}
    assert len(ids) == 100
    assert all(i.startswith("r%d-" % os.getpid()) for i in ids)


def test_request_sampling_deterministic_and_rate_bounds():
    tr = Tracer(enabled=True, sample_rate=1.0)
    assert tr.sampled("r1-1") and tr.sampled("r1-2")
    tr.set_sample_rate(0.0)
    assert not tr.sampled("r1-1")
    tr.set_sample_rate(0.5)
    rids = ["r1-%d" % i for i in range(400)]
    picks = [tr.sampled(r) for r in rids]
    # deterministic: same rid -> same verdict, every time
    assert picks == [tr.sampled(r) for r in rids]
    frac = sum(picks) / len(picks)
    assert 0.3 < frac < 0.7  # hash-split, not all-or-nothing
    # disabled tracer samples nothing regardless of rate
    off = Tracer(enabled=False, sample_rate=1.0)
    assert not off.sampled("r1-1")


def test_request_context_roundtrip_and_clear():
    from bigdl_tpu.obs import (clear_request_context, get_request_context,
                               set_request_context)
    assert get_request_context() == ()
    set_request_context(["r1-1", "r1-2"])
    assert get_request_context() == ("r1-1", "r1-2")
    # other threads see their own (empty) context
    seen = {}
    t = threading.Thread(
        target=lambda: seen.setdefault("ctx", get_request_context()))
    t.start()
    t.join()
    assert seen["ctx"] == ()
    clear_request_context()
    assert get_request_context() == ()


# --------------------------------------------------------------------- #
# registry cardinality cap
# --------------------------------------------------------------------- #

def test_registry_caps_cardinality_and_reports_it():
    reg = MetricRegistry(max_metrics=10)
    for i in range(10):
        reg.counter("ok/%d" % i).add(1)
    assert reg.cardinality() == 10
    # past the cap: callers still get a LIVE metric (hot paths never
    # crash or None-check), but the name is not registered
    extra = reg.counter("over/0")
    extra.add(5)
    assert extra.get()[0] == 5.0
    assert "over/0" not in reg.names()
    assert reg.cardinality() == 10
    assert reg.overflow_total() == 1
    # register() of a new name at cap is likewise refused
    reg.register("over/1", Counter(), replace=True)
    assert "over/1" not in reg.names()
    assert reg.overflow_total() == 2
    # existing names keep working at cap
    reg.counter("ok/3").add(1)
    assert reg.overflow_total() == 2
    snap = reg.snapshot()
    assert snap["obs/registry_cardinality"]["value"] == 10.0
    assert snap["obs/registry_overflow_total"]["value"] == 2.0
    # the synthetic gauges do not occupy registry slots
    assert "obs/registry_cardinality" not in reg.names()
    reg.clear()
    assert reg.cardinality() == 0 and reg.overflow_total() == 0


def test_registry_cap_env_knob(monkeypatch):
    monkeypatch.setenv("BIGDL_TPU_REGISTRY_MAX", "12")
    assert MetricRegistry().max_metrics == 12
    monkeypatch.setenv("BIGDL_TPU_REGISTRY_MAX", "1")  # floor of 8
    assert MetricRegistry().max_metrics == 8
    monkeypatch.delenv("BIGDL_TPU_REGISTRY_MAX")
    assert MetricRegistry().max_metrics == \
        MetricRegistry.DEFAULT_MAX_METRICS


def test_quant_per_path_gauges_bounded_by_cap():
    """The one unbounded per-key family the sweep found
    (quant/max_abs_dequant_error/<path>) is held by the cap instead of
    growing without limit."""
    reg = MetricRegistry(max_metrics=8)
    for i in range(50):
        reg.gauge("quant/max_abs_dequant_error/layer%d" % i).set(0.1)
    assert reg.cardinality() == 8
    assert reg.overflow_total() == 42
