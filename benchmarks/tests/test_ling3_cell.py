"""The Ling-3.0 driver end to end at toy size on the CPU, through the function
the command calls (``run.run_cell``), in a temporary tree that holds the toy
cell and a copy of ``benchmarks/``: the closed loop runs, the window opens
once every stream has its n-th token, the result line holds
``out_tokens_per_s``, every new per-layer metric reads a number from the
program's own spans and counters (the two device-trace shares from a recorded
toy trace's module), and two controls of its ``correct`` (an altered token,
latent rows rounded to float8: by the check's reading of the latent arena
itself, ``latent_row_gap``) come out not correct.  The third control, a
recurrent state kept in bfloat16, is held to a stated tolerance on the logits
by ``tests/test_ling3.py`` and read on the chip at the cell's size
(``readings_ling3.py``)."""
import json

import pytest

from benchmarks import run
from benchmarks.tests import toy, toy_ling3


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return toy_ling3.make_root(str(tmp_path_factory.mktemp("bench")))


def _line(root, seed=2 ** 31 + 35, seconds=1.0, trace=False, **kw):
    line = run.run_cell(root, "toy_ling3.longdecode", seed, seconds, trace,
                        require_accelerator=False, **kw)
    json.dumps(line)
    return line


def test_toy_cell_opens_its_window_at_a_token_count(root, capsys):
    line = _line(root)
    assert line["correct"] and line["failed"] == 0 and line["attempted"] >= 4
    assert {"out_tokens_per_s", "setup_s"} == set(line["metrics"])
    assert line["metrics"]["out_tokens_per_s"]["value"] > 0
    notes = [json.loads(l) for l in capsys.readouterr().out.splitlines()
             if l.startswith("{")]
    compared = {n["compared"]: n for n in notes if "compared" in n}
    assert {"served_gap_max", "served_gap_mean", "latent_row_gap",
            "compiles_in_window"} <= set(compared)
    # the latent arena itself was read: float32 rows, a rounding or two off
    assert 0 < compared["latent_row_gap"]["value"] < 1e-5
    assert compared["compiles_in_window"]["value"] == 0
    setup = next(n for n in notes if "setup_phases_s" in n)
    assert setup["kv_pool_row"] == "one latent row a position"
    assert setup["latent_row_bytes"] == 128 * 4             # 32 lanes -> 128
    assert setup["kv_arena_bytes"] == 641 * 4 * 128 * 4     # ONE arena
    assert setup["state_arena_bytes"] == 7 * 4 * (4 * 16 * 16 * 4 + 3 * 192 * 4)
    assert setup["prefix_cache"].startswith("off: the model has recurrent")
    # every prefill, and every stream's first six tokens, lie before the window
    fired = next(n for n in notes if "tokens_before_window" in n)
    # (as in the cell, no stream ends inside the run: the four that were sent
    # first are the four the close cancels)
    assert fired["fired"] == 4 and fired["finished"] == 0
    assert fired["tokens_before_window"] >= 4 * 6
    moe = next(n for n in notes if "moe" in n)
    assert moe["moe"]["lm.moe_expert_layer_rounds"] % 6 == 0    # 6 routed layers
    assert 0 < moe["moe"]["lm.moe_experts_hit_share"] <= 1
    assert moe["moe"]["lm.moe_groups_hit"] > 0
    assert moe["state"]["row_steps"] > 0 and moe["state"]["row_steps"] % 7 == 0
    assert moe["latent"]["rows_read"] > 0
    assert moe["latent"]["bytes_read"] == moe["latent"]["rows_read"] * 512
    check = next(n for n in notes if "check" in n)
    assert check["requests"] == 4 and sorted(check["prompt_lens"]) == [16, 16, 40, 40]
    assert check["tokens"] > 4 * 6
    # the prefills' rows and the decode step's, of all four streams
    assert check["latent_rows"]["read"] > 4 * 6
    # what a wrong id would read: most of them over the limit, not all
    assert check["altered_token_gap"]["share_over_limit_pct"] > 50


def test_an_altered_token_comes_out_not_correct(root, monkeypatch):
    """The timed path broken underneath: every eighth token is altered where
    the engine emits it."""
    from bigdl_tpu.serving import lm_engine
    real, n = lm_engine.LMStream._emit, {"n": 0}

    def emit(self, token_1b):
        n["n"] += 1
        real(self, token_1b % 90 + 1 if n["n"] % 8 == 0 else token_1b)

    monkeypatch.setattr(lm_engine.LMStream, "_emit", emit)
    assert _line(root)["correct"] is False


@pytest.mark.parametrize("control", [
    lambda: toy_ling3.latent_rounded("float8_e4m3fn"), toy_ling3.rope_dropped],
    ids=["float8", "rope_dropped"])
def test_a_lesser_latent_row_comes_out_not_correct(root, control):
    """The cached row rounded to float8, or cached without its rotated lanes."""
    with control():
        line = _line(root)
    assert line["correct"] is False
    # ... by the reading of the latent arena itself, whatever the tokens did
    gap = line["compared"]["latent_row_gap"]
    assert gap["value"] > 100 * gap["limit"], gap


def test_toy_traced_run_reports_every_new_metric(root, monkeypatch):
    """``--trace 1`` through the function the command calls.  The CPU has no
    device plane, so the profiler is left out and its reduction is a recorded
    toy trace's decode module with a device time: the span and counter metrics
    are the program's real ones, and the two device-trace shares read their
    operations and bytes from the program's own counters over that time."""
    from benchmarks.harness import peaks
    toy.without_profiler(monkeypatch)
    monkeypatch.setitem(peaks.PEAKS, "cpu", peaks.PEAKS["TPU v5 lite"])
    monkeypatch.setattr(run.Run, "reduce_trace", lambda self, spans: {
        "chips": 1, "window_s": 1.0, "busy_s": 0.5, "device_ops": [],
        "idle_gaps": [], "modules": {
            "jit__decode_fn(7)": {"calls": 40.0, "device_s": 0.4}}})
    line = _line(root, seed=2 ** 31 + 36, trace=True)
    assert line["correct"] and line["failed"] == 0
    assert set(line["metrics"]) == {
        "decode_round_ms.ling3", "round_host_ms.ling3", "slot_occupancy.ling3",
        "moe_experts_hit_pct.ling3", "latent_bytes_pct.ling3",
        "state_bytes_pct.ling3", "ling3_decode_hbm_roofline", "ling3_decode_mfu",
        "round_max_ms.ling3", "round_max_host_ms.ling3"}
    assert all(m["value"] > 0 for m in line["metrics"].values())
    assert 0 < line["metrics"]["latent_bytes_pct.ling3"]["value"] < 100
    assert 0 < line["metrics"]["state_bytes_pct.ling3"]["value"] < 100
    assert line["metrics"]["slot_occupancy.ling3"]["value"] == 100
    # the window's longest round, and the host's part of it
    assert (line["metrics"]["round_max_ms.ling3"]["value"]
            > line["metrics"]["round_max_host_ms.ling3"]["value"])
