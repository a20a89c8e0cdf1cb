"""The GLM-4.7-Flash driver end to end at toy size on the CPU, through the
function the command calls (``run.run_cell``), in a temporary tree that holds
the toy cell and a copy of ``benchmarks/``: the closed loop's pre-roll computes
the shared prefixes and the rest match them, every round of the window is a
self-drafting round, the result line holds ``out_tokens_per_s``, every new
per-layer metric reads a number from the program's own spans and counters (the
two device-trace shares from a recorded toy trace's modules), and the controls
of its ``correct`` come out not correct: an altered token, and -- while the
served tokens stay right -- a drafter whose ``W_eh`` is zeroed, one fed the
hidden state of the wrong position, one without ``eh_proj``'s hidden half, and
latent rows cached without their rotated lanes or rounded to float8."""
import json

import pytest

from benchmarks import run
from benchmarks.tests import toy, toy_glm47, toy_ling3


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return toy_glm47.make_root(str(tmp_path_factory.mktemp("bench")))


def _line(root, seed=2 ** 31 + 40, seconds=2.0, trace=False, **kw):
    line = run.run_cell(root, "toy_glm47.agentloop", seed, seconds, trace,
                        require_accelerator=False, **kw)
    json.dumps(line)
    return line


def test_toy_cell_self_drafts_over_shared_prefixes(root, capsys):
    line = _line(root)
    assert line["correct"] and line["failed"] == 0 and line["attempted"] >= 8
    assert {"out_tokens_per_s", "setup_s"} == set(line["metrics"])
    assert line["metrics"]["out_tokens_per_s"]["value"] > 0
    notes = [json.loads(l) for l in capsys.readouterr().out.splitlines()
             if l.startswith("{")]
    compared = {n["compared"]: n for n in notes if "compared" in n}
    assert {"served_gap_max", "served_gap_mean", "draft_gap_mean", "accept_gap",
            "latent_row_gap", "compiles_in_window"} <= set(compared)
    assert 0 < compared["latent_row_gap"]["value"] < 1e-5
    assert compared["accept_gap"]["value"] <= 0.02
    assert compared["compiles_in_window"]["value"] == 0
    setup = next(n for n in notes if "setup_phases_s" in n)
    assert setup["kv_pool_row"] == "one latent row a position"
    assert setup["latent_layers_in_arena"] == 5         # 4 main + the module's
    assert setup["prefix_cache"] == "on"
    assert setup["spec"] == {"drafter": "prediction module",
                             "shares_pool": True, "k": 1}
    # the pre-roll computed the two prefixes; the rest matched 16 of 20-24
    pre = setup["preroll_counters"]
    assert pre["lm.prefix_matched_tokens"] >= 16 * (pre["lm.prefills"] - 4)
    window = next(n for n in notes if "window_counters" in n)["window_counters"]
    assert window["lm.prefix_hit_share"] > 0.6
    assert 0 < window["lm.spec_accept_share"] < 1
    assert 1 < window["lm.tokens_per_slot_round"] < 2
    assert window["lm.logit_rows_to_host"] <= window["lm.prefills"] + 4  # a row an admission
    check = next(n for n in notes if "check" in n)
    assert check["requests"] >= 6 and check["drafts"] > 40
    assert check["acceptance"]["agree_pct"] > 98
    assert check["latent_rows"]["read"] > 5 * 10


def test_an_altered_token_comes_out_not_correct(root, monkeypatch):
    """The timed path broken underneath: every eighth token is altered where
    the engine emits it."""
    from bigdl_tpu.serving import lm_engine
    real, n = lm_engine.LMStream._emit, {"n": 0}

    def emit(self, token_1b):
        n["n"] += 1
        real(self, token_1b % 90 + 1 if n["n"] % 8 == 0 else token_1b)

    monkeypatch.setattr(lm_engine.LMStream, "_emit", emit)
    line = _line(root)
    assert line["correct"] is False
    gap = line["compared"]["served_gap_max"]
    assert gap["value"] > 100 * gap["limit"], gap


def _eh_zeroed():
    """``W_eh`` zeroed: the module sees nothing of its pair."""
    import jax.numpy as jnp
    from bigdl_tpu.models.transformer import TransformerLM
    return toy_glm47._patched(
        TransformerLM, "mtp_embed", lambda real: (
            lambda self, params, h, ids: jnp.zeros_like(real(self, params, h, ids))))


@pytest.mark.parametrize("control", [
    _eh_zeroed, toy_glm47.hidden_off_by_one, toy_glm47.eh_hidden_dropped],
    ids=["eh_zeroed", "hidden_off_by_one", "eh_hidden_dropped"])
def test_a_lesser_drafter_comes_out_not_correct_while_the_tokens_stay_right(
        root, control):
    with control():
        line = _line(root)
    assert line["correct"] is False and line["failed"] == 0
    compared = line["compared"]
    # drafts are verified: the served tokens are the main model's own
    for name in ("served_gap_max", "served_gap_mean"):
        assert compared[name]["value"] <= compared[name]["limit"], name
    # ... and the drafts are not the reference module's
    gap = compared["draft_gap_mean"]
    assert gap["value"] > 100 * gap["limit"], gap


@pytest.mark.parametrize("control", [
    toy_glm47.rope_dropped, toy_ling3.latent_rounded],
    ids=["rope_dropped", "latent_f8"])
def test_lesser_latent_rows_come_out_not_correct(root, control):
    """Rows cached without their rotated lanes, and rows rounded to float8
    (the main layers' and the module's alike)."""
    with control():
        line = _line(root)
    assert line["correct"] is False
    gap = line["compared"]["latent_row_gap"]
    assert gap["value"] > 100 * gap["limit"], gap


def test_the_drafter_off_is_the_same_cell_with_plain_rounds(root, capsys):
    line = _line(root, config_update={"engine": {"self_draft_k": 0}})
    assert line["correct"] and line["failed"] == 0
    assert "draft_gap_mean" not in line["compared"]
    notes = [json.loads(l) for l in capsys.readouterr().out.splitlines()
             if l.startswith("{")]
    setup = next(n for n in notes if "setup_phases_s" in n)
    assert setup["latent_layers_in_arena"] == 4 and setup["spec"]["drafter"] is None
    window = next(n for n in notes if "window_counters" in n)["window_counters"]
    assert window["lm.tokens_per_slot_round"] == 1


def test_toy_traced_run_reports_every_new_metric(root, monkeypatch):
    """``--trace 1`` through the function the command calls.  The CPU has no
    device plane, so the profiler is left out and its reduction is a recorded
    toy trace's modules with a device time: the span and counter metrics are
    the program's real ones, and the device-trace shares read their operations
    and bytes from the program's own counters over that time."""
    from benchmarks.harness import peaks
    toy.without_profiler(monkeypatch)
    monkeypatch.setitem(peaks.PEAKS, "cpu", peaks.PEAKS["TPU v5 lite"])
    monkeypatch.setattr(run.Run, "reduce_trace", lambda self, spans: {
        "chips": 1, "window_s": 1.0, "busy_s": 0.5, "device_ops": [],
        "idle_gaps": [], "modules": {
            "jit__selfdraft_fn(7)": {"calls": 40.0, "device_s": 0.4},
            "jit__prefix_prefill_fn(8)": {"calls": 4.0, "device_s": 0.05}}})
    line = _line(root, seed=2 ** 31 + 41, trace=True)
    assert line["correct"] and line["failed"] == 0
    assert set(line["metrics"]) == {
        "verify_round_ms.glm47", "spec_accept_pct.glm47",
        "tokens_per_slot_round.glm47", "prefix_hit_pct.glm47",
        "round_host_ms.glm47", "slot_occupancy.glm47",
        "moe_experts_hit_pct.glm47", "prefill_interrupt_ms.glm47",
        "latent_bytes_pct.glm47", "glm47_round_hbm_roofline",
        "glm47_prefill_mfu"}
    assert all(m["value"] > 0 for m in line["metrics"].values())
    assert 0 < line["metrics"]["spec_accept_pct.glm47"]["value"] < 100
    assert 1 < line["metrics"]["tokens_per_slot_round.glm47"]["value"] < 2
    assert line["metrics"]["prefix_hit_pct.glm47"]["value"] > 60
    assert 0 < line["metrics"]["latent_bytes_pct.glm47"]["value"] < 100
