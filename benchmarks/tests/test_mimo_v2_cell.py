"""The MiMo-V2-Flash driver end to end at toy size on the CPU, through the
function the command calls (``run.run_cell``), in a temporary tree that holds
the toy cell and a copy of ``benchmarks/``: the two closed loops feed one
queue, the window opens once every long stream has its n-th token and the
pre-roll is over, the result line holds ``out_tokens_per_s``, every new
per-layer metric reads a number from the program's own spans and counters (the
two device-trace shares from a recorded toy trace's modules), and the controls
of its ``correct`` come out not correct: the sink left out and K/V rounded to
int8 by the served tokens' gaps, the window's release one block early by the
gaps too (a sliding layer reads the scratch block)."""
import json

import pytest

from benchmarks import run
from benchmarks.tests import toy, toy_mimo_v2


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return toy_mimo_v2.make_root(str(tmp_path_factory.mktemp("bench")))


def _line(root, seed=2 ** 31 + 42, seconds=1.0, trace=False, **kw):
    line = run.run_cell(root, "toy_mimo_v2.mixedqueue", seed, seconds, trace,
                        require_accelerator=False, **kw)
    json.dumps(line)
    return line


def test_toy_cell_runs_two_loops_over_one_queue(root, capsys):
    line = _line(root)
    assert line["correct"] and line["failed"] == 0 and line["attempted"] >= 6
    assert {"out_tokens_per_s", "setup_s"} == set(line["metrics"])
    assert line["metrics"]["out_tokens_per_s"]["value"] > 0
    notes = [json.loads(l) for l in capsys.readouterr().out.splitlines()
             if l.startswith("{")]
    compared = {n["compared"]: n for n in notes if "compared" in n}
    assert {"served_gap_max", "served_gap_mean", "window_blocks_held_max",
            "compiles_in_window"} <= set(compared)
    # the allocator's promise, read from the pool: ceil(8 / 4) + 1
    held = compared["window_blocks_held_max"]
    assert 0 < held["value"] <= held["limit"] == 3
    assert compared["compiles_in_window"]["value"] == 0
    setup = next(n for n in notes if "setup_phases_s" in n)
    assert [k["window"] for k in setup["kv_classes"]] == [None, 8]
    assert [k["layers"] for k in setup["kv_classes"]] == [[0, 5], [1, 2, 3, 4, 6]]
    fired = next(n for n in notes if "tokens_before_window" in n)
    assert fired["fired_long"] == 2 and fired["fired"] > 6
    assert fired["short_admissions_in_window"] > 0
    check = next(n for n in notes if "check" in n)
    assert sorted(check["prompt_lens"], reverse=True)[:2] == [56, 40]
    assert check["requests"] == 8 and check["window_blocks_released"] > 0
    counters = next(n for n in notes if "window_counters" in n)["window_counters"]
    assert counters["lm.window_blocks_released"] > 0
    assert 0 < counters["lm.window_blocks_held_share"] < 0.6
    assert counters["lm.decode_context_tokens"] > counters["lm.decode_window_tokens"]


@pytest.mark.parametrize("control", ["sink_dropped", "release_early", "kv8"])
def test_a_control_comes_out_not_correct(root, control):
    with toy_mimo_v2.CONTROLS[control]():
        line = _line(root)
    assert line["correct"] is False
    gap = line["compared"]["served_gap_mean"]
    assert gap["value"] > 10 * gap["limit"], gap


def test_an_allocator_that_lets_go_of_nothing_comes_out_not_correct(
        root, monkeypatch):
    """``window_blocks_held_max`` against its limit: a windowed class that
    only allots reads as the blocks of a whole context."""
    from bigdl_tpu.serving.kvcache.blocks import BlockPool
    plain = BlockPool.advance
    monkeypatch.setattr(BlockPool, "advance", lambda self, chain, marks, pos,
                        upto: plain(self, chain, marks, 0, upto))
    # (a windowed class large enough to hold whole contexts, so that the run
    # reaches its end)
    line = _line(root, config_update={"engine": {"num_blocks": [700, 500]}})
    assert line["correct"] is False
    held = line["compared"]["window_blocks_held_max"]
    assert held["value"] > held["limit"]
    assert (line["compared"]["served_gap_mean"]["value"]
            < line["compared"]["served_gap_mean"]["limit"])     # the logits are sound


def test_toy_traced_run_reports_every_new_metric(root, monkeypatch):
    """``--trace 1`` through the function the command calls.  The CPU has no
    device plane, so the profiler is left out and its reduction is a recorded
    toy trace's modules with device times: the span and counter metrics are
    the program's real ones, and the two device-trace shares read their
    operations and bytes from the program's own counters over those times."""
    from benchmarks.harness import peaks
    toy.without_profiler(monkeypatch)
    monkeypatch.setitem(peaks.PEAKS, "cpu", peaks.PEAKS["TPU v5 lite"])
    monkeypatch.setattr(run.Run, "reduce_trace", lambda self, spans: {
        "chips": 1, "window_s": 1.0, "busy_s": 0.5, "device_ops": [],
        "idle_gaps": [], "modules": {
            "jit__decode_fn(7)": {"calls": 40.0, "device_s": 0.4},
            "jit__prefill_fn(3)": {"calls": 4.0, "device_s": 0.05}}})
    line = _line(root, seed=2 ** 31 + 43, seconds=2.0, trace=True)
    assert line["correct"] and line["failed"] == 0
    want = {"decode_round_ms.mimo_v2", "round_host_ms.mimo_v2",
            "window_release_ms.mimo_v2", "prefill_interrupt_ms.mimo_v2",
            "slot_occupancy.mimo_v2", "moe_experts_hit_pct.mimo_v2",
            "full_kv_bytes_pct.mimo_v2", "window_kv_bytes_pct.mimo_v2",
            "window_blocks_held_pct.mimo_v2", "mimo_v2_round_hbm_roofline",
            "mimo_v2_prefill_mfu"}
    assert want <= set(line["metrics"]), want - set(line["metrics"])
    assert all(line["metrics"][m]["value"] > 0 for m in want)
    assert (line["metrics"]["full_kv_bytes_pct.mimo_v2"]["value"]
            + line["metrics"]["window_kv_bytes_pct.mimo_v2"]["value"]) < 100
    assert line["metrics"]["window_blocks_held_pct.mimo_v2"]["value"] < 60
