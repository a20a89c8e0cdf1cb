"""bigdl_tpu.resilience: the tier-1 CPU fault matrix.

Every test here replays a failure mode of a wedged or lost backend
deterministically on CPU via the
``BIGDL_TPU_FAULTS`` injector: backend wobble mid-transfer (retry +
chunk downshift), backend death mid-transfer (classified BackendLostError
instead of an indefinite hang), a training run dying mid-epoch
(emergency checkpoint -> resume_from -> same trajectory), a serving
replica dying mid-stream (failover, zero lost requests), and the
circuit breaker's open/half-open/close lifecycle.

All tests carry the ``faults`` marker so CI can run the matrix alone
(`pytest -m faults`) as a fast resilience gate.
"""
import time

import numpy as np
import pytest

import jax.numpy as jnp

from bigdl_tpu import nn
from bigdl_tpu.dataset import DataSet, Sample
from bigdl_tpu.dataset.transformer import SampleToBatch
from bigdl_tpu.optim import SGD, Trigger, LocalOptimizer
from bigdl_tpu.resilience import (BackendLostError, TransientBackendError,
                                  classify_error, with_backoff)
from bigdl_tpu.resilience import faults
from bigdl_tpu.utils import file_io
from bigdl_tpu.utils.transfer import chunked_device_put

pytestmark = pytest.mark.faults


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


# --------------------------------------------------------------------------- #
# error classification + backoff policy (no jax involved)                           #
# --------------------------------------------------------------------------- #

def test_classify_error_classification():
    assert classify_error(TransientBackendError("wobble")) == "transient"
    assert classify_error(RuntimeError("UNAVAILABLE: Socket closed")) == \
        "transient"
    assert classify_error(RuntimeError("DEADLINE_EXCEEDED: 30s")) == \
        "transient"
    assert classify_error(BackendLostError("gone")) == "backend_lost"
    assert classify_error(
        RuntimeError("Unable to initialize backend 'tpu'")) == "backend_lost"
    # programming errors must never be retried
    assert classify_error(ValueError("bad shape")) == "fatal"
    assert classify_error(KeyError("velocity")) == "fatal"
    # unknown exceptions fail safe: surface, don't spin
    assert classify_error(RuntimeError("something else entirely")) == "fatal"


def test_with_backoff_retries_then_escalates():
    calls = {"n": 0}

    def flaky():
        calls["n"] += 1
        if calls["n"] < 3:
            raise TransientBackendError("UNAVAILABLE: backend wobble")
        return "ok"

    assert with_backoff(flaky, retries=4, sleep=lambda s: None) == "ok"
    assert calls["n"] == 3

    def always():
        raise TransientBackendError("UNAVAILABLE: forever")

    with pytest.raises(BackendLostError):
        with_backoff(always, retries=2, sleep=lambda s: None)

    def broken():
        raise ValueError("a bug, not a backend")

    with pytest.raises(ValueError):  # fatal passes straight through
        with_backoff(broken, retries=5, sleep=lambda s: None)


# --------------------------------------------------------------------------- #
# injector gating + determinism                                               #
# --------------------------------------------------------------------------- #

def test_injector_refuses_activation_without_env(monkeypatch):
    monkeypatch.delenv(faults.ENV_SPEC, raising=False)
    faults.refresh_from_env()
    assert faults.active() is None
    with pytest.raises(RuntimeError, match="refusing"):
        faults.install(faults.FaultInjector("transfer.chunk:transient"))
    faults.fault_point("transfer.chunk")  # inactive: must be a no-op


def test_malformed_spec_raises_loudly():
    with pytest.raises(ValueError):
        faults.parse_spec("transfer.chunk")  # no kind
    with pytest.raises(ValueError):
        faults.parse_spec("transfer.chunk:explode")  # unknown kind
    with pytest.raises(ValueError):
        faults.parse_spec("transfer.chunk:transient:count")  # not k=v
    with pytest.raises(ValueError):
        faults.parse_spec("transfer.chunk:transient:frequency=2")  # bad key


def test_probabilistic_specs_are_seed_deterministic():
    def pattern(seed):
        inj = faults.FaultInjector("s:transient:p=0.5", seed=seed)
        out = []
        for _ in range(32):
            try:
                inj.check("s")
                out.append(0)
            except TransientBackendError:
                out.append(1)
        return out

    assert pattern(7) == pattern(7)
    assert pattern(7) != pattern(8)
    assert 0 < sum(pattern(7)) < 32  # actually probabilistic


# --------------------------------------------------------------------------- #
# transfers: retry + downshift, classified backend loss (no hang)             #
# --------------------------------------------------------------------------- #

def test_transfer_retries_and_downshifts(inject):
    """A flaky backend mid-transfer: the slice retries with backoff AND
    halves the working chunk size toward the floor; the assembled array
    is still exact."""
    inject("transfer.chunk:transient:count=3")
    retries0 = _counter("resilience/retries")
    downs0 = _counter("resilience/transfer_downshifts")
    x = np.random.RandomState(0).randn(64, 256).astype(np.float32)
    out = chunked_device_put(x, chunk_bytes=16 << 10,    # 16 rows/slice
                             min_chunk_bytes=4 << 10)    # 4-row floor
    np.testing.assert_array_equal(np.asarray(out), x)
    assert _counter("resilience/retries") - retries0 == 3
    # 16K -> 8K -> 4K, then pinned at the floor (no further downshift)
    assert _counter("resilience/transfer_downshifts") - downs0 == 2
    st = faults.active().stats()
    assert st["transfer.chunk:transient:count=3"]["fired"] == 3


def test_transfer_relay_death_is_classified_not_hung(inject):
    """The mid-transfer failure: the backend dies mid-chunked_device_put.  The
    acceptance contract is a classified BackendLostError after bounded
    attempts — never an indefinite hang."""
    inject("transfer.chunk:backend_lost:after=2")
    lost0 = _counter("resilience/backend_lost")
    x = np.zeros((64, 256), np.float32)
    t0 = time.perf_counter()
    with pytest.raises(BackendLostError):
        chunked_device_put(x, chunk_bytes=16 << 10)
    assert time.perf_counter() - t0 < 30.0
    assert _counter("resilience/backend_lost") - lost0 >= 1


def test_transfer_exhausted_retries_escalate(inject):
    """A permanently flaky backend exhausts the retry budget and
    escalates to BackendLostError (chained to the last transient)."""
    inject("transfer.chunk:transient")
    x = np.zeros((8, 256), np.float32)
    with pytest.raises(BackendLostError) as ei:
        chunked_device_put(x, chunk_bytes=16 << 10, max_retries=2)
    assert isinstance(ei.value.__cause__, TransientBackendError)


def test_engine_init_backend_loss_surfaces(inject):
    """The classic bring-up failure: the backend never answers the first
    devices() touch.  Engine.init surfaces it as BackendLostError."""
    from bigdl_tpu.utils.engine import Engine
    inject("engine.init:backend_lost:count=1")
    with pytest.raises(BackendLostError):
        Engine.init()
    Engine.reset()
    Engine.init()  # count exhausted: next init succeeds


# --------------------------------------------------------------------------- #
# training: emergency checkpoint + auto-resume equivalence                    #
# --------------------------------------------------------------------------- #

def _regression_dataset(n=96, batch=16, seed=0):
    rng = np.random.RandomState(seed)
    W = np.array([[2.0, -1.0], [0.5, 1.5]], dtype=np.float32)
    samples = []
    for _ in range(n):
        x = rng.randn(2).astype(np.float32)
        samples.append(Sample(x, (W @ x).astype(np.float32)))
    return DataSet.array(samples, seed=seed) >> SampleToBatch(batch)


class _DyingDataSet:
    """Delegates to a real dataset but raises a transient backend error
    on the k-th training-batch fetch (1-based) — the CPU stand-in for a
    backend death mid-epoch."""

    def __init__(self, inner, fail_at_fetch):
        self.inner = inner
        self.fail_at_fetch = fail_at_fetch
        self.fetches = 0

    def size(self):
        return self.inner.size()

    def shuffle(self):
        self.inner.shuffle()

    def data(self, train=True):
        it = self.inner.data(train=train)
        if not train:
            return it

        def gen():
            while True:
                self.fetches += 1
                if self.fetches == self.fail_at_fetch:
                    raise TransientBackendError(
                        "UNAVAILABLE: backend died mid-epoch (injected)")
                yield next(it)
        return gen()


def _make_opt(model, ds, end_iter=6):
    opt = LocalOptimizer(model, ds, nn.MSECriterion())
    opt.set_optim_method(SGD(learning_rate=0.1, momentum=0.9, dampening=0.0))
    opt.set_end_when(Trigger.max_iteration(end_iter))
    return opt


def test_mid_epoch_crash_resume_matches_uninterrupted(tmp_path, monkeypatch):
    """THE acceptance test for training resilience: run A trains 6
    iterations uninterrupted; run B dies fetching iteration 4's batch,
    writes an emergency checkpoint of the last COMPLETED step (3),
    resumes from disk, and finishes.  Final weights must match — the
    optimizer state, LR-schedule position, epoch counters, and the
    mid-epoch data position (shuffle-replay + record fast-forward) all
    have to line up for that to hold."""
    # prefetch would pull iteration 4's batch during iteration 3; keep
    # the fetch at the crash iteration so exactly 3 steps complete
    monkeypatch.setenv("BIGDL_TPU_PREFETCH_OVERLAP", "0")

    # run A: uninterrupted
    model_a = nn.Linear(2, 2, with_bias=False)
    _make_opt(model_a, _regression_dataset()).optimize()
    w_a = np.asarray(model_a.params["weight"])

    # run B part 1: dies at iteration 4's fetch
    emerg0 = _counter("resilience/emergency_checkpoints")
    model_b = nn.Linear(2, 2, with_bias=False)
    dying = _DyingDataSet(_regression_dataset(), fail_at_fetch=4)
    opt_b = _make_opt(model_b, dying)
    opt_b.set_checkpoint(str(tmp_path), Trigger.several_iteration(1000))
    with pytest.raises(TransientBackendError):
        opt_b.optimize()
    assert _counter("resilience/emergency_checkpoints") - emerg0 == 1
    found = file_io.latest_checkpoint(str(tmp_path))
    assert found is not None
    assert found[2] == 3  # last completed step: at most one step lost
    snap = file_io.load(found[1])
    assert snap["driver_state"]["records_processed"] == 48  # 3 batches in

    # run B part 2: fresh process state, resume, finish
    resumes0 = _counter("resilience/resumes")
    model_b2 = nn.Linear(2, 2, with_bias=False)
    opt_b2 = _make_opt(model_b2, _regression_dataset())
    opt_b2.resume_from(str(tmp_path))
    assert _counter("resilience/resumes") - resumes0 == 1
    assert opt_b2.state["neval"] == 4
    opt_b2.optimize()

    w_b = np.asarray(model_b2.params["weight"])
    np.testing.assert_allclose(w_b, w_a, rtol=1e-6, atol=1e-7)


def test_resume_from_empty_dir_is_cold_start(tmp_path):
    model = nn.Linear(2, 2, with_bias=False)
    opt = _make_opt(model, _regression_dataset(), end_iter=2)
    opt.resume_from(str(tmp_path))  # nothing there: not an error
    assert opt.state.get("neval", 1) == 1
    opt.optimize()
    assert opt.state["neval"] == 3


class _FlagMidRun:
    """Sets the optimizer's stall-escalation flag during the k-th batch
    fetch — standing in for the watchdog thread firing mid-run."""

    def __init__(self, inner, at_fetch):
        self.inner = inner
        self.at_fetch = at_fetch
        self.opt = None
        self.fetches = 0

    def size(self):
        return self.inner.size()

    def shuffle(self):
        self.inner.shuffle()

    def data(self, train=True):
        it = self.inner.data(train=train)
        if not train:
            return it

        def gen():
            while True:
                self.fetches += 1
                if self.fetches == self.at_fetch:
                    self.opt._stall_ckpt_requested = True
                yield next(it)
        return gen()


def test_stall_escalation_checkpoints_at_next_iteration(tmp_path, monkeypatch):
    """StallWatchdog escalation: arming wires on_stall to the request
    flag, and a flag raised mid-run produces an emergency checkpoint at
    the next COMPLETED iteration even though the scheduled trigger
    never fires."""
    monkeypatch.setenv("BIGDL_TPU_PREFETCH_OVERLAP", "0")
    model = nn.Linear(2, 2, with_bias=False)
    ds = _FlagMidRun(_regression_dataset(), at_fetch=2)
    opt = _make_opt(model, ds, end_iter=3)
    ds.opt = opt
    opt.set_checkpoint(str(tmp_path), Trigger.several_iteration(1000))

    # the arming contract the training loop uses on its real watchdog
    class _Watchdog:
        on_stall = None
    wd = _Watchdog()
    opt._arm_stall_checkpoint(wd)
    assert callable(wd.on_stall) and opt._stall_ckpt_requested is False
    wd.on_stall({"kind": "stall", "seconds": 12.0})
    assert opt._stall_ckpt_requested is True
    opt._stall_ckpt_requested = False

    emerg0 = _counter("resilience/emergency_checkpoints")
    opt.optimize()
    assert _counter("resilience/emergency_checkpoints") - emerg0 == 1
    found = file_io.latest_checkpoint(str(tmp_path))
    assert found is not None and found[2] == 2  # after iteration 2


# --------------------------------------------------------------------------- #
# serving: replica death mid-stream, circuit breaker lifecycle                #
# --------------------------------------------------------------------------- #

def _serving_model():
    return nn.Sequential(nn.Linear(8, 4), nn.LogSoftMax()).build(seed=0)


def test_replica_death_failover_loses_no_requests(inject):
    """THE acceptance test for serving resilience: one of two replicas
    dies mid-stream; every accepted request still resolves, outputs
    agree exactly with a single engine's, the batch fails over, and the
    dead replica's circuit opens."""
    from bigdl_tpu.resilience import ReplicaSet
    from bigdl_tpu.serving import ServingEngine

    model = _serving_model()
    xs = np.random.RandomState(3).randn(12, 8).astype(np.float32)

    with ServingEngine(model, input_shape=(8,), max_batch_size=4,
                       max_wait_ms=1.0) as single:
        expected = [single.predict(xs[i:i + 1], timeout=60)
                    for i in range(len(xs))]

    # r1 dies from its 3rd dispatched batch onwards
    inject("serving.dispatch:die:name=r1,after=3")
    failovers0 = _counter("resilience/failovers")
    rs = ReplicaSet(model, n_replicas=2, input_shape=(8,),
                    max_batch_size=4, max_wait_ms=1.0,
                    failure_threshold=2, cooldown_s=300.0)
    try:
        got = [rs.predict(xs[i:i + 1], timeout=60) for i in range(len(xs))]
        for g, e in zip(got, expected):
            np.testing.assert_array_equal(g, e)  # exact, not approximate
        st = rs.stats()
        assert st["replicas"]["r1"]["state"] == "open"
        assert st["replicas"]["r0"]["state"] == "healthy"
        assert _counter("resilience/failovers") - failovers0 >= 1
        # both replicas actually served traffic before the death
        assert st["replicas"]["r1"]["dispatched"] >= 2
    finally:
        rs.close()


def test_circuit_breaker_open_halfopen_close(inject):
    """Breaker lifecycle on an injectable clock: consecutive failures
    OPEN the circuit; after the cooldown one half-open probe runs; a
    failed probe re-opens, a successful probe closes the circuit."""
    from bigdl_tpu.resilience import ReplicaSet

    clk = {"t": 0.0}
    # r0 fails its first 3 dispatches, then recovers for good
    inject("serving.dispatch:die:name=r0,count=3")
    model = _serving_model()
    rs = ReplicaSet(model, n_replicas=2, input_shape=(8,),
                    max_batch_size=4, max_wait_ms=1.0,
                    failure_threshold=2, cooldown_s=5.0,
                    clock=lambda: clk["t"])
    x = np.ones((1, 8), np.float32)
    try:
        rs.predict(x, timeout=60)   # r0 dies (1 consecutive), r1 serves
        rs.predict(x, timeout=60)   # r0 dies again -> circuit OPEN
        assert rs.stats()["replicas"]["r0"]["state"] == "open"
        rs.predict(x, timeout=60)   # cooldown not passed: r1 only
        assert rs.stats()["replicas"]["r0"]["dispatched"] == 2

        clk["t"] = 6.0              # past the 5s cooldown
        rs.predict(x, timeout=60)   # half-open probe fails -> re-OPEN
        assert rs.stats()["replicas"]["r0"]["state"] == "open"

        clk["t"] = 8.0              # 2s since re-open: still cooling
        rs.predict(x, timeout=60)
        assert rs.stats()["replicas"]["r0"]["dispatched"] == 3

        clk["t"] = 12.0             # cooled again; fault budget spent
        rs.predict(x, timeout=60)   # probe SUCCEEDS -> circuit closes
        assert rs.stats()["replicas"]["r0"]["state"] == "healthy"

        rs.predict(x, timeout=60)   # healthy replica takes traffic again
        assert rs.stats()["replicas"]["r0"]["dispatched"] == 5
        assert faults.active().stats()[
            "serving.dispatch:backend_lost:count=3,name=r0"]["fired"] == 3
    finally:
        rs.close()


def test_replica_set_matches_engine_without_faults():
    """No faults armed: the replica set is behaviorally a serving
    engine (same outputs, both replicas share the load)."""
    from bigdl_tpu.resilience import ReplicaSet

    model = _serving_model()
    xs = np.random.RandomState(5).randn(6, 8).astype(np.float32)
    ref = np.asarray(model.evaluate().forward(xs))
    with ReplicaSet(model, n_replicas=2, input_shape=(8,),
                    max_batch_size=8, max_wait_ms=1.0) as rs:
        y = rs.predict(xs, timeout=60)
        np.testing.assert_allclose(y, ref, atol=1e-5)
        one = rs.predict_one(xs[0], timeout=60)
        np.testing.assert_allclose(one, ref[0], atol=1e-5)
        st = rs.stats()
        assert set(st["replicas"]) == {"r0", "r1"}
    # closed set rejects new work
    from bigdl_tpu.serving import ServingClosed
    with pytest.raises(ServingClosed):
        rs.submit(xs)


def test_all_replicas_dead_is_bounded_backend_lost(inject):
    """When EVERY replica is gone the batch fails with a classified
    BackendLostError after the bounded re-dispatch budget — accepted
    requests resolve (with the error), nothing hangs."""
    from bigdl_tpu.resilience import ReplicaSet

    inject("serving.dispatch:die")  # everyone, always
    model = _serving_model()
    rs = ReplicaSet(model, n_replicas=2, input_shape=(8,),
                    max_batch_size=4, max_wait_ms=1.0,
                    failure_threshold=1, cooldown_s=300.0)
    try:
        fut = rs.submit(np.ones((1, 8), np.float32))
        with pytest.raises(BackendLostError):
            fut.result(timeout=60)
    finally:
        rs.close()


def test_quantized_replica_coexists_and_fails_over_exactly(inject):
    """Heterogeneous replica set: a Module.quantize() int8 clone serves
    next to its f32 original behind ONE batcher (the compile cache keys
    them apart by params dtype).  The failover contract is per-replica
    exactness — and once the f32 replica dies, every answer is exactly
    what the int8 engine produces alone."""
    from bigdl_tpu.resilience import ReplicaSet
    from bigdl_tpu.serving import ServingEngine

    # weights must clear QuantPolicy's min_size=128 floor or the clone
    # silently stays f32 and the test is vacuous
    model = nn.Sequential(nn.Linear(8, 32), nn.LogSoftMax()).build(seed=0)
    qmodel = model.quantize()
    xs = np.random.RandomState(7).randn(10, 8).astype(np.float32)

    kw = dict(input_shape=(8,), max_batch_size=4, max_wait_ms=1.0)
    with ServingEngine(model, **kw) as e32:
        exp32 = [e32.predict(xs[i:i + 1], timeout=60)
                 for i in range(len(xs))]
    with ServingEngine(qmodel, **kw) as e8:
        assert e8.quant_dtype == "int8"  # quantization really engaged
        exp8 = [e8.predict(xs[i:i + 1], timeout=60)
                for i in range(len(xs))]
    assert any(not np.array_equal(a, b) for a, b in zip(exp32, exp8))

    # the f32 replica dies from its 3rd dispatched batch onwards
    inject("serving.dispatch:die:name=r0,after=2")
    failovers0 = _counter("resilience/failovers")
    rs = ReplicaSet([model, qmodel], failure_threshold=2,
                    cooldown_s=300.0, **kw)
    try:
        assert rs._replicas[0].engine.quant_dtype == "f32"
        assert rs._replicas[1].engine.quant_dtype == "int8"
        got = [rs.predict(xs[i:i + 1], timeout=60)
               for i in range(len(xs))]
        # per-replica exactness: every answer matches the single-engine
        # output of whichever replica served it, bit for bit
        for g, a, b in zip(got, exp32, exp8):
            assert (np.array_equal(g, a) or np.array_equal(g, b))
        st = rs.stats()
        assert st["replicas"]["r0"]["state"] == "open"
        assert st["replicas"]["r1"]["state"] == "healthy"
        assert _counter("resilience/failovers") - failovers0 >= 1
        # with r0 open (cooldown 300s), the tail is all-int8 exact
        assert np.array_equal(got[-1], exp8[-1])
    finally:
        rs.close()
