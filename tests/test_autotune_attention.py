"""Block-size autotuner + crossover dispatch.

The tuning cache (TUNE_ATTN.json) is a resumable measurement artifact:
row flushed after every candidate, ``complete`` false until the final
flush, reuse strictly identity-matched (platform, device_kind,
candidate key, batch/heads/iters).  The dispatch side: ``"auto"``
attention consults the cache winners — ``use_flash=False`` reroutes to
the naive-XLA core, tuned blocks replace the 128x128 default, explicit
blocks pin the Pallas kernel regardless, and a cache tuned on another
device kind is ignored entirely.
"""
import json
import os
import subprocess
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from bigdl_tpu.ops import autotune, flash_attention, resolve_attention_plan

REPO = os.path.join(os.path.dirname(__file__), os.pardir)

#: tiny CPU sweep: interpret-mode flash at t=32 is milliseconds
TINY = dict(head_dim=8, dtype="float32", causal=True, batch=1, heads=2,
            grid=((8, 8), (8, 16)), log=lambda *_: None)


@pytest.fixture(autouse=True)
def _fresh_memo():
    autotune.clear_cache()
    yield
    autotune.clear_cache()


# --------------------------------------------------------------------------- #
# sweep + cache determinism                                                   #
# --------------------------------------------------------------------------- #

def test_sweep_writes_winners_and_lookup_roundtrip(tmp_path):
    path = str(tmp_path / "tune.json")
    doc = autotune.autotune_attention([32], iters=1, path=path, **TINY)
    assert doc["complete"] is True
    assert doc["platform"] == "cpu"
    # 2 grid candidates + 1 naive baseline, every row measured
    assert len(doc["rows"]) == 3
    assert all("step_s" in r for r in doc["rows"])
    key = autotune.attention_key(32, 8, "float32", True)
    w = doc["winners"][key]
    assert w["use_flash"] in (True, False)
    assert (w["block_q"], w["block_k"]) in TINY["grid"]
    e = autotune.lookup(32, 8, "float32", True, path=path)
    assert e is not None and e.use_flash == w["use_flash"]
    assert (e.block_q, e.block_k) == (w["block_q"], w["block_k"])
    # no verdict for a config never swept
    assert autotune.lookup(64, 8, "float32", True, path=path) is None


def test_resume_reuses_only_identity_matched_rows(tmp_path):
    path = str(tmp_path / "tune.json")
    autotune.autotune_attention([32], iters=1, path=path, **TINY)
    # same config: every row reused, winners identical
    doc2 = autotune.autotune_attention([32], iters=1, path=path, **TINY)
    assert all(r.get("reused_from_previous_run") for r in doc2["rows"])
    # iters mismatch: the quick smoke must not stand in for the real
    # sample — everything re-measured
    doc3 = autotune.autotune_attention([32], iters=2, path=path, **TINY)
    assert not any(r.get("reused_from_previous_run") for r in doc3["rows"])


def test_certified_doc_survives_allreuse_and_killed_reruns(tmp_path,
                                                           monkeypatch):
    """A complete:true doc must not be rewritten by a rerun until a
    candidate genuinely re-measures — an all-reuse pass, or one killed
    mid-measurement of its first new candidate (a run's time
    limit), leaves the certified artifact byte-identical."""
    path = str(tmp_path / "tune.json")
    doc = autotune.autotune_attention([32], iters=1, path=path, **TINY)
    assert doc["complete"] is True
    certified = open(path, "rb").read()
    # all-reuse rerun: reported, but the file is untouched
    doc2 = autotune.autotune_attention([32], iters=1, path=path, **TINY)
    assert doc2["complete"] is True
    assert all(r.get("reused_from_previous_run") for r in doc2["rows"])
    assert open(path, "rb").read() == certified
    # wider grid whose first NEW candidate dies mid-measure (simulated
    # kill): the interim flush must not have regressed complete:true
    fa_mod = sys.modules["bigdl_tpu.ops.flash_attention"]

    def _killed(*a, **k):
        raise KeyboardInterrupt

    monkeypatch.setattr(fa_mod, "flash_attention", _killed)
    wider = dict(TINY, grid=((8, 8), (8, 16), (16, 16)))
    with pytest.raises(KeyboardInterrupt):
        autotune.autotune_attention([32], iters=1, path=path, **wider)
    assert open(path, "rb").read() == certified


def test_other_config_rows_accumulate_across_sweeps(tmp_path):
    path = str(tmp_path / "tune.json")
    autotune.autotune_attention([32], iters=1, path=path, **TINY)
    autotune.autotune_paged_decode(slots=2, heads=2, head_dim=8,
                                   cache_len=16, block_len=4,
                                   dtype="float32", iters=1, path=path,
                                   log=lambda *_: None)
    doc = json.load(open(path))
    kinds = {r["kind"] for r in doc["rows"]}
    assert kinds == {"train_step", "paged_decode"}  # nothing dropped
    assert autotune.attention_key(32, 8, "float32", True) in doc["winners"]
    pk = autotune.paged_key(8, 4, "float32")
    assert doc["winners"][pk]["use_kernel"] in (True, False)
    e = autotune.lookup_paged(8, 4, "float32", path=path)
    assert e is not None and e.use_kernel == doc["winners"][pk]["use_kernel"]


def test_lookup_ignores_other_device_kind(tmp_path):
    path = str(tmp_path / "tune.json")
    key = autotune.attention_key(64, 8, "float32", True)
    with open(path, "w") as f:
        json.dump({"device_kind": "TPU v99",
                   "winners": {key: {"use_flash": False}}}, f)
    assert autotune.lookup(64, 8, "float32", True, path=path) is None


# --------------------------------------------------------------------------- #
# crossover dispatch                                                          #
# --------------------------------------------------------------------------- #

def _fake_cache(tmp_path, monkeypatch, winners):
    path = tmp_path / "tune.json"
    path.write_text(json.dumps(
        {"device_kind": jax.devices()[0].device_kind, "winners": winners}))
    monkeypatch.setenv("BIGDL_TPU_TUNE_CACHE", str(path))
    autotune.clear_cache()


def test_plan_tuned_xla_reroute(tmp_path, monkeypatch):
    key = autotune.attention_key(64, 8, "float32", True)
    _fake_cache(tmp_path, monkeypatch, {key: {"use_flash": False}})
    plan = resolve_attention_plan(64, 8, jnp.float32, True)
    assert (plan.impl, plan.source) == ("xla", "tuned")


def test_plan_tuned_blocks(tmp_path, monkeypatch):
    key = autotune.attention_key(64, 8, "float32", True)
    _fake_cache(tmp_path, monkeypatch,
                {key: {"use_flash": True, "block_q": 16, "block_k": 32}})
    plan = resolve_attention_plan(64, 8, jnp.float32, True)
    assert plan == ("flash", 16, 32, "tuned")


def test_plan_explicit_blocks_pin_the_kernel(tmp_path, monkeypatch):
    """The tuner itself (and every test passing small blocks) must
    never be rerouted by the verdict it is measuring for."""
    key = autotune.attention_key(64, 8, "float32", True)
    _fake_cache(tmp_path, monkeypatch, {key: {"use_flash": False}})
    plan = resolve_attention_plan(64, 8, jnp.float32, True,
                                  block_q=8, block_k=8)
    assert plan == ("flash", 8, 8, "pinned")


def test_plan_default_without_cache(tmp_path, monkeypatch):
    monkeypatch.setenv("BIGDL_TPU_TUNE_CACHE",
                       str(tmp_path / "missing.json"))
    autotune.clear_cache()
    plan = resolve_attention_plan(64, 8, jnp.float32, True)
    assert plan == ("flash", 128, 128, "default")


def test_flash_attention_tuned_reroute_matches_xla_core(tmp_path,
                                                        monkeypatch):
    """With use_flash=False tuned, flash_attention() IS the naive-XLA
    attention — the acceptance property "never slower than naive"
    becomes "identical to naive"."""
    from bigdl_tpu.nn.attention import dot_product_attention
    key = autotune.attention_key(32, 8, "float32", True)
    _fake_cache(tmp_path, monkeypatch, {key: {"use_flash": False}})
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q, k, v = (jax.random.normal(kk, (1, 2, 32, 8)) for kk in ks)
    out = flash_attention(q, k, v, causal=True)
    ref = dot_product_attention(q, k, v, causal=True)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))


# --------------------------------------------------------------------------- #
# acceptance: the committed cache                                             #
# --------------------------------------------------------------------------- #

def test_repo_cache_has_cpu_crossover_verdict(monkeypatch):
    """ACCEPTANCE: the repo ships TUNE_ATTN.json from a real CPU run;
    at (seq 2048, bf16, head_dim 128) the verdict is use_flash=False
    (interpret-mode flash loses to fused XLA by >10x), so with the
    crossover live flash_attention() can never be slower than naive
    XLA there — it IS naive XLA."""
    path = os.path.join(REPO, "TUNE_ATTN.json")
    assert os.path.exists(path), "committed tuning cache missing"
    doc = json.load(open(path))
    assert doc["platform"] == "cpu" and doc["complete"] is True
    w = doc["winners"][autotune.attention_key(2048, 128, "bfloat16", True)]
    assert w["use_flash"] is False
    assert w["flash_step_s"] > w["xla_step_s"]
    if doc["device_kind"] == jax.devices()[0].device_kind:
        monkeypatch.setenv("BIGDL_TPU_TUNE_CACHE", path)
        autotune.clear_cache()
        plan = resolve_attention_plan(2048, 128, jnp.bfloat16, True)
        assert (plan.impl, plan.source) == ("xla", "tuned")


# --------------------------------------------------------------------------- #
# CLI: attention_bench --autotune (subprocess, resumable)                     #
# --------------------------------------------------------------------------- #

def test_bench_attn_cli_resume(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               BIGDL_TPU_TUNE_CACHE=str(tmp_path / "tune.json"))
    bench_json = str(tmp_path / "attn.json")
    argv = [sys.executable, "-m", "bigdl_tpu.models.utils.attention_bench",
            "--autotune", "--sweep", "32", "--headDim", "8", "--dtype",
            "float32", "--heads", "2", "--iters", "1",
            "--grid", "8:8,8:16", "--naive", "--useTuned",
            "--json", bench_json]
    passes = []
    for _ in range(2):
        r = subprocess.run(argv, env=env, cwd=REPO, capture_output=True,
                           text=True, timeout=560)
        assert r.returncode == 0, r.stderr[-2000:]
        passes.append((r.stdout, json.load(open(tmp_path / "tune.json"))))
    tune = passes[1][1]
    assert tune["complete"] is True
    # second pass re-used every tuning measurement: it logs each row as
    # reused and leaves the certified cache as the first pass wrote it
    logged = [ln for ln in passes[1][0].splitlines()
              if ln.startswith("tune: ")]
    assert len(logged) == len(tune["rows"]) == 3
    assert all("'reused_from_previous_run': True" in ln for ln in logged)
    assert tune == passes[0][1]
    bench = json.load(open(bench_json))
    assert bench["complete"] is True
    impls = {r["impl"] for r in bench["rows"]}
    assert {"flash", "naive_xla"} <= impls
    # the regeneration measured the TUNED blocks (--useTuned)
    w = tune["winners"][autotune.attention_key(32, 8, "float32", True)]
    f = next(r for r in bench["rows"] if r["impl"] == "flash")
    assert (f["block_q"], f["block_k"]) == (w["block_q"], w["block_k"])
    s = next(s for s in bench["summary"] if s["seq_len"] == 32)
    assert (s["block_q"], s["block_k"]) == (w["block_q"], w["block_k"])
