"""Resume across runs for the measurement sweeps.

A chip call has a time limit; the sweep
CLIs therefore rewrite their artifact after every row and, on restart,
reuse successful same-configuration rows.  These tests lock the resume
matching: reuse must hit only when the full configuration matches, and
error rows must be retried, not reused.
"""
import json
import os
import subprocess
import sys

import pytest

REPO = os.path.join(os.path.dirname(__file__), os.pardir)


def _run(mod, *argv, timeout=600):
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    return subprocess.run(
        [sys.executable, "-m", mod, *map(str, argv)], cwd=REPO, env=env,
        capture_output=True, text=True, timeout=timeout)


ATTN_ARGS = ("--sweep", "64,128", "--naive", "--iters", "1", "-b", "1",
             "--heads", "2", "--headDim", "64")


@pytest.mark.slow
def test_attention_sweep_resumes_same_config(tmp_path):
    art = tmp_path / "attn.json"
    p = _run("bigdl_tpu.models.utils.attention_bench", *ATTN_ARGS,
             "--json", art)
    assert p.returncode == 0, p.stderr[-800:]
    d = json.loads(art.read_text())
    assert d["complete"] and len(d["rows"]) == 4
    assert not any(r.get("reused_from_previous_run") for r in d["rows"])

    # same config again: every row must be reused, nothing re-measured
    p = _run("bigdl_tpu.models.utils.attention_bench", *ATTN_ARGS,
             "--json", art)
    assert p.returncode == 0, p.stderr[-800:]
    d = json.loads(art.read_text())
    assert d["complete"]
    assert all(r.get("reused_from_previous_run") for r in d["rows"])

    # different config (head_dim changes): nothing may be reused
    p = _run("bigdl_tpu.models.utils.attention_bench", "--sweep", "64,128",
             "--naive", "--iters", "1", "-b", "1", "--heads", "2",
             "--headDim", "32", "--json", art)
    assert p.returncode == 0, p.stderr[-800:]
    d = json.loads(art.read_text())
    assert not any(r.get("reused_from_previous_run") for r in d["rows"])

    # rows recorded on another platform (e.g. a real-TPU artifact being
    # extended after a CPU debug run, or vice versa): never reused
    d["platform"] = "tpu"
    art.write_text(json.dumps(d))
    p = _run("bigdl_tpu.models.utils.attention_bench", "--sweep", "64,128",
             "--naive", "--iters", "1", "-b", "1", "--heads", "2",
             "--headDim", "32", "--json", art)
    assert p.returncode == 0, p.stderr[-800:]
    d = json.loads(art.read_text())
    assert d["platform"] == "cpu"
    assert not any(r.get("reused_from_previous_run") for r in d["rows"])


@pytest.mark.slow
def test_attention_partial_artifact_extends(tmp_path):
    """A partial artifact (window closed mid-sweep) keeps its measured
    rows and the next run fills only the gap."""
    art = tmp_path / "attn.json"
    p = _run("bigdl_tpu.models.utils.attention_bench", "--sweep", "64",
             "--naive", "--iters", "1", "-b", "1", "--heads", "2",
             "--headDim", "64", "--json", art)
    assert p.returncode == 0, p.stderr[-800:]
    # simulate the kill: mark incomplete (rows stay)
    d = json.loads(art.read_text())
    d["complete"] = False
    art.write_text(json.dumps(d))

    p = _run("bigdl_tpu.models.utils.attention_bench", *ATTN_ARGS,
             "--json", art)
    assert p.returncode == 0, p.stderr[-800:]
    d = json.loads(art.read_text())
    assert d["complete"] and len(d["rows"]) == 4
    reused = {(r["seq_len"], r["impl"])
              for r in d["rows"] if r.get("reused_from_previous_run")}
    assert reused == {(64, "flash"), (64, "naive_xla")}


@pytest.mark.slow
def test_lm_sweep_resumes_and_error_rows_retry(tmp_path):
    art = tmp_path / "lm.json"
    args = ("--sweep", "32,64", "-b", "2", "-t", "32", "--vocab", "64",
            "--hidden", "16", "--heads", "2", "--layers", "1", "-i", "1")
    p = _run("bigdl_tpu.models.utils.lm_perf", *args, "--json", art)
    assert p.returncode == 0, p.stderr[-800:]
    d = json.loads(art.read_text())
    assert d["complete"] and len(d["rows"]) == 4

    # poison one row into an error: it must be re-measured, others reused
    d["rows"][0] = {"seq_len": d["rows"][0]["seq_len"],
                    "flash": d["rows"][0]["flash"], "error": "backend died"}
    d["complete"] = False
    art.write_text(json.dumps(d))
    p = _run("bigdl_tpu.models.utils.lm_perf", *args, "--json", art)
    assert p.returncode == 0, p.stderr[-800:]
    d = json.loads(art.read_text())
    assert d["complete"]
    assert sum(1 for r in d["rows"] if r.get("reused_from_previous_run")) == 3
    assert all("tokens_per_s" in r for r in d["rows"])


# --------------------------------------------------------------------------- #
# corrupted resumable artifacts (resilience): treated as absent, loudly       #
# --------------------------------------------------------------------------- #

@pytest.mark.faults
def test_corrupt_artifact_treated_as_absent_with_warning(tmp_path, caplog):
    """A truncated/garbage artifact (kill mid-flush, disk corruption)
    must restart the sweep with a warning — never crash the round on a
    JSONDecodeError, never resume from half a document."""
    import logging
    from bigdl_tpu.utils.artifacts import load_artifact, write_artifact

    art = tmp_path / "sweep.json"
    # missing file: silent cold start
    with caplog.at_level(logging.WARNING, logger="bigdl_tpu.artifacts"):
        assert load_artifact(str(art)) is None
    assert not caplog.records

    art.write_text('{"complete": true, "rows": [')  # truncated mid-flush
    with caplog.at_level(logging.WARNING, logger="bigdl_tpu.artifacts"):
        assert load_artifact(str(art)) is None
    assert any("unreadable" in r.message for r in caplog.records)

    # a good artifact still round-trips
    write_artifact(str(art), {"complete": True, "rows": [{"n": 1}]})
    assert load_artifact(str(art))["rows"] == [{"n": 1}]
