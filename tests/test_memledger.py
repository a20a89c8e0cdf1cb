"""MemoryLedger: byte attribution, executable costs, OOM-risk plumbing.

What this file pins:

- register/release semantics across all three provider forms (static
  int, computed callable, live array held by weakref — a dead weakref
  reports stale-at-0 instead of silently vanishing);
- ``headroom()``/``over_watermark()`` against an injected byte budget
  (the CPU test box has no backend allocator to read);
- :class:`CompileCache` filing a REAL lowered executable's
  ``memory_analysis()``/``cost_analysis()`` roofline row with the
  ledger, and keeping the table in step with LRU eviction;
- reconciliation degrading gracefully on CPU: ``verdict: degraded``
  with drift pinned at a NUMERIC 0 (the artifact schema rejects null);
- exactly ONE schema-valid ``mem_pressure`` flight bundle per
  incident, carrying the full attribution table;
- the SLO controller refusing slot scale-up below the watermark (fake
  ledger injection — no real memory is filled);
- ``diagnose_tpu()`` growing a backend-free memory section.
"""
import os
import sys

import numpy as np
import pytest

from bigdl_tpu.obs import MetricRegistry
from bigdl_tpu.obs import flight as flight_mod
from bigdl_tpu.obs.ledger import MemoryLedger, get_ledger, set_ledger
from bigdl_tpu.obs.registry import Histogram
from bigdl_tpu.traffic import SLOController

REPO = os.path.join(os.path.dirname(__file__), os.pardir)
sys.path.insert(0, os.path.join(REPO, "scripts"))
from validate_artifact import validate as validate_artifact  # noqa: E402


@pytest.fixture
def ledger():
    """Fresh process-wide ledger over a private registry; the old one
    is restored afterwards so engine registrations elsewhere in the
    suite keep their owner."""
    led = MemoryLedger(registry=MetricRegistry(), budget_bytes=None)
    old = set_ledger(led)
    yield led
    set_ledger(old)


@pytest.fixture
def recorder(tmp_path):
    old = flight_mod.get_flight_recorder()
    rec = flight_mod.configure(
        enabled=True, out_dir=str(tmp_path),
        incidents_path=str(tmp_path / "INCIDENTS.json"))
    yield rec
    flight_mod._GLOBAL = old


# --------------------------------------------------------------------- #
# registration / attribution
# --------------------------------------------------------------------- #


def test_register_release_and_attribution(ledger):
    ledger.register("params", "m/staged", 1000, note="quant=f32")
    ledger.register("kvcache", "m/kv_arena", lambda: 2048,
                    shape=(2, 4, 8), dtype="float32")
    assert ledger.attribution() == {"params": 1000, "kvcache": 2048}
    assert ledger.total_bytes() == 3048
    rows = ledger.entries()
    assert [r["name"] for r in rows] == ["m/kv_arena", "m/staged"]
    kv = rows[0]
    assert kv["nbytes"] == 2048 and kv["shape"] == [2, 4, 8]
    assert not kv["stale"]
    assert ledger.release("params", "m/staged")
    assert not ledger.release("params", "m/staged")  # already gone
    assert ledger.attribution() == {"kvcache": 2048}


def test_reregister_replaces_latest_owner_wins(ledger):
    ledger.register("params", "m/staged", 100)
    ledger.register("params", "m/staged", 900)
    assert ledger.attribution() == {"params": 900}
    assert len(ledger.entries()) == 1


def test_live_array_weakref_goes_stale(ledger):
    import jax.numpy as jnp

    arr = jnp.zeros((16, 16), jnp.float32)
    ledger.register("kvcache", "pool", arr)
    row = ledger.entries()[0]
    assert row["nbytes"] == 16 * 16 * 4
    assert row["shape"] == [16, 16] and not row["stale"]
    del arr
    import gc
    gc.collect()
    row = ledger.entries()[0]
    # a released arena must read 0/stale, never the old bytes
    assert row["stale"] and row["nbytes"] == 0
    assert ledger.attribution() == {"kvcache": 0}


def test_non_weakrefable_falls_back_to_static(ledger):
    # an nbytes-carrier that cannot be weakref'd (slots, no __weakref__)
    # degrades to a static count rather than pinning the object
    class Buf:
        __slots__ = ("nbytes", "shape", "dtype")

        def __init__(self):
            self.nbytes = 8 * 8 * 4
            self.shape = (8, 8)
            self.dtype = "float32"

    ledger.register("host_stager", "buf", Buf())
    row = ledger.entries()[0]
    assert row["nbytes"] == 8 * 8 * 4 and not row["stale"]


def test_raising_provider_reports_stale(ledger):
    def boom():
        raise RuntimeError("backend gone")

    ledger.register("spec", "draft", boom)
    row = ledger.entries()[0]
    assert row["stale"] and row["nbytes"] == 0


# --------------------------------------------------------------------- #
# headroom / watermark (injected budget: CPU has no allocator stats)
# --------------------------------------------------------------------- #


def test_headroom_against_injected_budget():
    led = MemoryLedger(registry=MetricRegistry(), budget_bytes=1000,
                       watermark=0.9)
    led.register("params", "m", 500)
    assert led.used_fraction() == 0.5
    assert led.headroom() == 0.5
    assert not led.over_watermark()
    led.register("kvcache", "arena", 450)
    assert led.over_watermark()
    assert led.headroom() == pytest.approx(0.05)


def test_unknown_budget_is_permissive(ledger, monkeypatch):
    monkeypatch.delenv("BIGDL_TPU_MEM_BUDGET", raising=False)
    ledger.register("params", "m", 10**12)
    # no budget, no backend stats on CPU: callers must not invent
    # pressure they cannot see
    assert ledger.headroom() is None
    assert not ledger.over_watermark()


def test_env_budget_and_watermark(monkeypatch):
    monkeypatch.setenv("BIGDL_TPU_MEM_BUDGET", "1000")
    monkeypatch.setenv("BIGDL_TPU_MEM_WATERMARK", "0.5")
    led = MemoryLedger(registry=MetricRegistry())
    led.register("params", "m", 600)
    assert led.capacity_bytes() == 1000
    assert led.watermark == 0.5
    assert led.over_watermark()


# --------------------------------------------------------------------- #
# executable cost rows from a real lowered executable
# --------------------------------------------------------------------- #


def test_compile_cache_files_cost_rows(ledger):
    import jax.numpy as jnp
    from bigdl_tpu.serving.compile_cache import CompileCache

    def infer(params, buffers, x):
        return x @ params["w"]

    params = {"w": jnp.ones((8, 4), jnp.float32)}
    cache = CompileCache(infer, name="unit")
    assert cache.stats()["ledger_tag"] == "unit"
    y = cache(params, {}, jnp.ones((2, 8), jnp.float32))
    assert y.shape == (2, 4)
    rows = ledger.executables()
    assert len(rows) == 1
    row = rows[0]
    assert row["tag"] == "unit"
    # the roofline halves must be present on CPU, not degraded: the
    # committed PROFILE_MEM.json is produced by exactly this path
    mem, cost = row["memory"], row["cost"]
    assert set(mem) == {"temp_bytes", "argument_bytes", "output_bytes",
                        "alias_bytes", "code_bytes"}
    assert all(isinstance(v, int) for v in mem.values())
    assert cost["flops"] >= 0 and cost["bytes_accessed"] >= 0
    # generated code shows up as the synthetic executables subsystem
    if mem["code_bytes"]:
        assert ledger.attribution()["executables"] == mem["code_bytes"]


def test_compile_cache_eviction_releases_ledger_rows(ledger):
    import jax.numpy as jnp
    from bigdl_tpu.serving.compile_cache import CompileCache

    def infer(params, buffers, x):
        return x * 2.0

    cache = CompileCache(infer, max_entries=1, name="evict")
    cache({}, {}, jnp.ones((2,), jnp.float32))
    assert len(ledger.executables()) == 1
    first_key = ledger.executables()[0]["key"]
    cache({}, {}, jnp.ones((4,), jnp.float32))
    rows = ledger.executables()
    # the LRU evicted the (2,) executable; its ledger row went with it
    assert len(rows) == 1 and rows[0]["key"] != first_key
    assert cache.stats()["evictions"] == 1


def test_serving_stack_attributes_every_subsystem(ledger):
    """A batch engine plus an LM engine with a model drafter and a host
    KV tier report in under five subsystems, and their AOT executables
    file cost rows: the whole stack is attributed, not just the part a
    unit test registers by hand."""
    from bigdl_tpu import nn
    from bigdl_tpu.models.transformer import TransformerLM
    from bigdl_tpu.serving import (HostBlockStore, LMServingEngine,
                                   ServingEngine, SpecConfig)

    lm_model = TransformerLM(vocab_size=31, hidden_size=16, n_head=2,
                             n_layers=1, max_len=32,
                             pos_encoding="rope").build(seed=0)
    eng = ServingEngine(
        nn.Sequential(nn.Linear(8, 4), nn.LogSoftMax()).build(seed=0),
        input_shape=(8,), max_batch_size=4, name="stack")
    lm = LMServingEngine(lm_model, slots=2, cache_len=24,
                         prefill_buckets=(8,), spec=SpecConfig(k=2),
                         kvtier=HostBlockStore(host_bytes=1 << 20,
                                               name="stack"),
                         name="stack-lm")
    try:
        eng.warmup()
        lm.warmup()
        attr = ledger.attribution()
        assert {"params", "host_stager", "kvcache", "spec",
                "kvtier"} <= set(attr)
        assert attr["params"] > 0 and attr["kvcache"] > 0 \
            and attr["spec"] > 0
        rows = ledger.executables()
        assert rows and all(r["memory"]["code_bytes"] >= 0 for r in rows)
    finally:
        lm.close()
        eng.close()
    assert ledger.attribution().get("kvcache", 0) == 0


# --------------------------------------------------------------------- #
# reconciliation: CPU degrade path
# --------------------------------------------------------------------- #


def test_reconcile_degrades_on_cpu(ledger):
    import jax

    ledger.register("params", "m", 4096)
    rec = ledger.reconcile(jax.devices("cpu")[0])
    assert rec["verdict"] == "degraded"
    assert rec["backend_bytes_in_use"] is None
    # drift must stay NUMERIC on the degrade path — the artifact
    # schema (and the obs/ledger/drift_bytes gauge) reject null
    assert rec["drift_bytes"] == 0 and isinstance(rec["drift_bytes"], int)
    assert rec["ledger_bytes"] == 4096
    # summary() reuses the cached verdict without a fresh backend read
    assert ledger.summary()["last_reconcile"]["verdict"] == "degraded"


def test_reconcile_against_fake_backend(ledger, monkeypatch):
    ledger.register("params", "m", 1000)
    monkeypatch.setattr(
        MemoryLedger, "backend_stats",
        staticmethod(lambda device=None: {"bytes_in_use": 1500,
                                          "bytes_limit": 4000}))
    rec = ledger.reconcile()
    assert rec["verdict"] == "reconciled"
    assert rec["drift_bytes"] == 500
    assert ledger.capacity_bytes() == 4000
    assert ledger.used_fraction() == 1500 / 4000


# --------------------------------------------------------------------- #
# mem_pressure flight bundle: schema + one-per-incident
# --------------------------------------------------------------------- #


def test_mem_pressure_fires_one_schema_valid_bundle(tmp_path, recorder):
    led = MemoryLedger(registry=MetricRegistry(), budget_bytes=1000,
                       watermark=0.9)
    old = set_ledger(led)
    try:
        led.register("kvcache", "arena", 950, shape=(2, 4),
                     dtype="float32")
        path = led.check_pressure(context={"site": "unit"})
        assert path is not None and os.path.exists(path)
        assert validate_artifact(path) == []
        import json
        bundle = json.load(open(path))
        assert bundle["flight"] == "mem_pressure"
        detail = bundle["detail"]
        assert detail["site"] == "unit"
        assert detail["attribution"] == {"kvcache": 950}
        assert detail["table"][0]["name"] == "arena"
        assert detail["used_fraction"] >= 0.9
        # same condition re-checked inside the dedup window: ONE bundle
        assert led.check_pressure() is None
        assert recorder.bundles_written == 1
        # under the watermark: no bundle at all
        led.release("kvcache", "arena")
        led.register("kvcache", "arena", 100)
        assert led.check_pressure() is None
    finally:
        set_ledger(old)


def test_check_pressure_noop_without_budget(ledger, recorder,
                                            monkeypatch):
    monkeypatch.delenv("BIGDL_TPU_MEM_BUDGET", raising=False)
    ledger.register("kvcache", "arena", 10**12)
    assert ledger.check_pressure() is None
    assert recorder.bundles_written == 0


# --------------------------------------------------------------------- #
# SLO scale-up consults the ledger
# --------------------------------------------------------------------- #


class _FakeLedger:
    def __init__(self, over):
        self.over = over
        self.calls = 0

    def over_watermark(self, device=None):
        self.calls += 1
        return self.over


def test_slo_scale_up_refused_below_watermark():
    h = Histogram()
    fake = _FakeLedger(over=True)
    ups = []
    adm = []
    c = SLOController(histogram=h, target_p99_s=0.1, window_intervals=2,
                      scale_up=lambda: ups.append(1) or True,
                      set_admission=adm.append, admission_levels=[64, 4],
                      ledger=fake, hot_streak=1, cool_streak=2)
    for _ in range(4):
        h.observe(0.5)
        c.tick()
    # slots were never added; the ladder fell through to admission
    assert ups == []
    assert fake.calls >= 1
    assert adm == [4]
    assert c.summary()["scaling_exhausted"]
    acts = [a["action"] for a in c.actions]
    assert "scale_up" not in acts and "admission_tighten" in acts
    # pressure clears + cool window: rearm, then scale-up works again
    fake.over = False
    for _ in range(10):
        h.observe(0.001)
        c.tick()
    for _ in range(4):
        h.observe(0.5)
        c.tick()
    assert ups  # rearmed: slots grow again once pressure clears


def test_slo_without_ledger_scales_as_before():
    h = Histogram()
    ups = []
    c = SLOController(histogram=h, target_p99_s=0.1, window_intervals=2,
                      scale_up=lambda: ups.append(1) or True,
                      hot_streak=1, cool_streak=2)
    for _ in range(3):
        h.observe(0.5)
        c.tick()
    assert ups  # no ledger injected -> no byte gate


# --------------------------------------------------------------------- #
# diagnose_tpu memory section
# --------------------------------------------------------------------- #


def test_diagnose_tpu_memory_note(ledger):
    from bigdl_tpu.utils.engine import Engine

    # empty ledger: no memory note (diagnose stays noise-free)
    assert Engine._diagnose_memory() == []
    ledger.register("params", "m", 2048)
    notes = Engine._diagnose_memory()
    assert len(notes) == 1 and notes[0].startswith("memory: ")
    assert "2048" in notes[0] and "1 subsystems" in notes[0]
    # and it rides the full diagnose output
    assert "memory: " in Engine.diagnose_tpu()
