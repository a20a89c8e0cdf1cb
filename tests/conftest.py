"""Test configuration: force an 8-virtual-device CPU platform so multi-chip
sharding paths are exercised without TPU pods (the analog of the reference's
simulated-multinode trick: DistriOptimizerSpec runs 4 "nodes" as 4
partitions in one local[1] JVM, optim/DistriOptimizerSpec.scala:39-43).

Tests are CPU-only: ``JAX_PLATFORMS`` is forced to ``cpu`` here, before jax
is imported, so subprocesses the tests start inherit it too.  The
persistent compilation cache stays off under pytest: six xdist workers
would otherwise race on the checkout's ``.jax_cache``.
"""
import os

os.environ["JAX_PLATFORMS"] = "cpu"

flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (flags + " --xla_force_host_platform_device_count=8").strip()

import jax  # noqa: E402

jax.config.update("jax_enable_compilation_cache", False)
jax.config.update("jax_enable_x64", False)

import numpy as np  # noqa: E402
import pytest  # noqa: E402


def pytest_addoption(parser):
    parser.addoption(
        "--full", action="store_true", default=False,
        help="CI-full mode: run the slow tests too (multihost subprocess "
             "jobs, exhaustive torch oracles)")


def pytest_configure(config):
    config.addinivalue_line(
        "markers", "slow: long-running test, skipped unless --full or "
        "BIGDL_TPU_FULL_TESTS=1 (driver windows need the default run "
        "under ~8 minutes; full coverage stays one flag away)")
    config.addinivalue_line(
        "markers", "faults: deterministic fault-injection matrix "
        "(bigdl_tpu.resilience) — fast, tier-1, CPU-only; selectable "
        "alone via -m faults as the CI resilience gate")


def pytest_collection_modifyitems(config, items):
    full = (config.getoption("--full")
            or os.environ.get("BIGDL_TPU_FULL_TESTS") == "1"
            or (config.getoption("-m") and "slow" in config.getoption("-m")))
    if full:
        return
    skip = pytest.mark.skip(
        reason="slow: run with --full or BIGDL_TPU_FULL_TESTS=1")
    for item in items:
        if "slow" in item.keywords:
            item.add_marker(skip)


@pytest.fixture(autouse=True)
def _reset_engine():
    from bigdl_tpu.utils.engine import Engine
    Engine.reset()
    os.environ["BIGDL_TPU_CHECK_SINGLETON"] = "0"
    yield


@pytest.fixture(scope="session")
def fake_mesh():
    """The 8-virtual-device CPU mesh this conftest forces via XLA_FLAGS
    — the shared fixture for every multi-chip test (placement, tensor
    parallel, grad accum).  Returns the device tuple; skips (instead of
    silently passing on one device) when the flag did not take, e.g.
    when a backend was initialized before conftest ran."""
    devs = jax.devices()
    if len(devs) < 8:
        pytest.skip(f"needs the 8-device CPU mesh, got {len(devs)} "
                    "device(s) (XLA_FLAGS applied too late?)")
    return tuple(devs[:8])


@pytest.fixture
def rng():
    return jax.random.PRNGKey(42)


@pytest.fixture
def nprng():
    return np.random.RandomState(42)


def corrupt_variants(good: bytes, n_trials: int, seed: int = 0):
    """Yield (trial, corrupted_bytes) for reader fuzz tests: truncations,
    header-region bit flips, and garbage tails — one shared mutation
    schedule so the t7 and seqfile fuzz tests cannot drift."""
    rng = np.random.RandomState(seed)
    for trial in range(n_trials):
        data = bytearray(good)
        mode = trial % 3
        if mode == 0:
            data = data[: rng.randint(1, len(data))]
        elif mode == 1:
            data[rng.randint(0, min(64, len(data)))] ^= 0xFF
        else:
            data = data[: rng.randint(8, len(data))] + bytes(
                rng.randint(0, 256, size=16, dtype=np.uint8))
        yield trial, bytes(data)


@pytest.fixture
def lm_round_records(monkeypatch):
    """Every round of every LM engine as ``LMMetrics.record_round`` is handed
    it, in order: its phase split and its starved split (None: nothing of it
    passed with the device proven empty), both indexed as ``ROUND_PHASES``."""
    from bigdl_tpu.serving import lm_engine
    seen, real = [], lm_engine.LMMetrics.record_round

    def watched(self, t0, dur_s, split, index, active, admitted, plain,
                starved=None):
        seen.append({"index": index, "admitted": admitted, "plain": plain,
                     "split": list(split),
                     "starved": None if starved is None else list(starved)})
        return real(self, t0, dur_s, split, index, active, admitted, plain,
                    starved)

    monkeypatch.setattr(lm_engine.LMMetrics, "record_round", watched)
    return seen
