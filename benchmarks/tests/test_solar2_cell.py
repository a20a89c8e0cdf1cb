"""The Solar-Open2 driver end to end at toy size on the CPU, through the
function the command calls (``run.run_cell``), in a temporary tree that holds
the toy cell and a copy of ``benchmarks/``: the closed loop runs, the result
line holds ``out_tokens_per_s``, every new per-layer metric reads a number
from the program's own spans and counters (the two device-trace shares from
a recorded toy trace's modules), and two controls of its ``correct`` (an
altered token, int8 K/V blocks) come out not correct.  The third control, a
recurrent state kept in bfloat16, is held to a stated tolerance on the logits
by ``tests/test_solar2.py`` and read on the chip at the cell's size
(``readings_solar2.py``): a toy window's 80 served tokens of at most 44
positions are too few and too short for it to flip one."""
import json

import pytest

from benchmarks import run
from benchmarks.tests import toy, toy_solar2


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return toy_solar2.make_root(str(tmp_path_factory.mktemp("bench")))


def _line(root, seed=2 ** 31 + 33, seconds=2.0, trace=False, **kw):
    line = run.run_cell(root, "toy_solar2.backlog", seed, seconds, trace,
                        require_accelerator=False, **kw)
    json.dumps(line)
    return line


def test_toy_cell_runs_the_closed_loop_and_reports_tokens_a_second(root, capsys):
    line = _line(root)
    assert line["correct"] and line["failed"] == 0 and line["attempted"] >= 6
    assert {"out_tokens_per_s", "setup_s"} == set(line["metrics"])
    assert line["metrics"]["out_tokens_per_s"]["value"] > 0
    notes = [json.loads(l) for l in capsys.readouterr().out.splitlines()
             if l.startswith("{")]
    compared = {n["compared"]: n for n in notes if "compared" in n}
    assert {"served_gap_max", "served_gap_mean",
            "compiles_in_window"} <= set(compared)
    assert compared["compiles_in_window"]["value"] == 0
    setup = next(n for n in notes if "setup_phases_s" in n)
    assert setup["state_arena_bytes"] == 6 * 4 * (4 * 16 * 16 * 4 + 3 * 192 * 4)
    assert setup["prefix_cache"].startswith("off: the model has recurrent")
    moe = next(n for n in notes if "moe" in n)
    assert moe["moe"]["lm.moe_expert_layer_rounds"] % 8 == 0    # 8 routed layers
    assert 0 < moe["moe"]["lm.moe_experts_hit_share"] <= 1
    assert moe["state"]["row_steps"] > 0 and moe["state"]["row_steps"] % 6 == 0
    check = next(n for n in notes if "check" in n)
    assert check["requests"] >= 2 and check["tokens"] > 10


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


def test_int8_kv_control_comes_out_not_correct(root):
    assert _line(root, config_update=toy_solar2.KV8)["correct"] is False


def test_toy_traced_run_reports_every_new_metric(root, monkeypatch):
    """``--trace 1`` through the function the command calls.  The CPU has no
    device plane, so the profiler is left out and its reduction is a recorded
    toy trace's modules (a decode module and a prefill module with device
    times): the span and counter metrics are the program's real ones, and the
    two device-trace shares read their operations and bytes from the
    program's own counters over those times."""
    from benchmarks.harness import peaks
    toy.without_profiler(monkeypatch)
    # (the recording names the device it ran on; for this one reading the
    # toy's "cpu" borrows the v5e's published peaks: the values read mean
    # nothing, that the readers find their counters does)
    monkeypatch.setitem(peaks.PEAKS, "cpu", peaks.PEAKS["TPU v5 lite"])
    monkeypatch.setattr(run.Run, "reduce_trace", lambda self, spans: {
        "chips": 1, "window_s": 1.0, "busy_s": 0.5, "device_ops": [],
        "idle_gaps": [], "modules": {
            "jit__decode_fn(7)": {"calls": 40.0, "device_s": 0.4},
            "jit__prefill_fn(3)": {"calls": 3.0, "device_s": 0.01}}})
    line = _line(root, seed=2 ** 31 + 35, trace=True)
    assert line["correct"] and line["failed"] == 0
    assert set(line["metrics"]) == {
        "decode_round_ms.solar2", "round_host_ms.solar2",
        "prefill_interrupt_ms.solar2", "slot_occupancy.solar2",
        "moe_experts_hit_pct.solar2", "state_bytes_pct.solar2",
        "solar2_decode_hbm_roofline", "solar2_prefill_mfu"}
    assert all(m["value"] > 0 for m in line["metrics"].values())
    assert 0 < line["metrics"]["state_bytes_pct.solar2"]["value"] < 100
    assert 0 < line["metrics"]["moe_experts_hit_pct.solar2"]["value"] <= 100
    assert 50 < line["metrics"]["slot_occupancy.solar2"]["value"] <= 100
