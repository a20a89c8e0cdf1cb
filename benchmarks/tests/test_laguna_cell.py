"""The Laguna driver end to end at toy size on the CPU, through the function
the command calls (``run.run_cell``), in a temporary tree that holds the toy
cell and a copy of ``benchmarks/``; and the two controls of its ``correct``:
int8 KV blocks, and expert matmuls in a lower precision than the
configuration states, both come out not correct."""
import json

import pytest

from benchmarks import run
from benchmarks.tests import toy, toy_laguna


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return toy_laguna.make_root(str(tmp_path_factory.mktemp("bench")))


def _line(root, seed=2 ** 31 + 29, seconds=2.0, **kw):
    line = run.run_cell(root, "toy_laguna.steady", seed, seconds, False,
                        require_accelerator=False, **kw)
    json.dumps(line)
    return line


def test_toy_cell_reports_the_tail_and_every_counter(root, capsys):
    line = _line(root)
    assert line["correct"] and line["failed"] == 0 and line["attempted"] == 16
    assert {"itl_p95_ms", "setup_s"} == set(line["metrics"])
    notes = [json.loads(l) for l in capsys.readouterr().out.splitlines()
             if l.startswith("{")]
    compared = {n["compared"]: n for n in notes if "compared" in n}
    assert {"served_gap_max", "served_gap_mean",
            "compiles_in_window"} <= set(compared)
    assert compared["compiles_in_window"]["value"] == 0
    moe = next(n for n in notes if "moe" in n)["moe"]
    assert moe["lm.moe_expert_layer_rounds"] % 8 == 0       # 8 sparse layers
    assert 0 < moe["lm.moe_experts_hit"] <= moe["lm.moe_assignments"]
    assert 0 < moe["lm.moe_experts_hit_share"] <= 1
    check = next(n for n in notes if "check" in n)
    assert check["requests"] == 10 and check["tokens"] > 50


def test_int8_kv_control_comes_out_not_correct(root):
    assert _line(root, config_update=toy_laguna.KV8)["correct"] is False


def test_expert_matmuls_in_a_lower_precision_come_out_not_correct(root):
    with toy_laguna.experts_rounded("bfloat16"):
        assert _line(root)["correct"] is False


def test_toy_traced_run_reports_every_metric_the_cpu_can_read(root, monkeypatch):
    """``--trace 1`` through the function the command calls.  The CPU has no
    device plane, so the profiler is left out and its reduction canned: the
    two device-trace shares find nothing to read and are left out of the
    line; the span and counter metrics are the program's real ones."""
    toy.without_profiler(monkeypatch)
    line = run.run_cell(root, "toy_laguna.steady", 2 ** 31 + 31, 2.0, True,
                        require_accelerator=False)
    json.dumps(line)
    assert line["correct"] and line["failed"] == 0
    assert set(line["metrics"]) == {
        "decode_round_ms.laguna_s", "prefill_interrupt_ms.laguna_s",
        "round_host_ms.laguna_s", "first_token_wait_ms.laguna_s",
        "queue_wait_p95_ms.laguna_s", "loadgen_late_p95_ms.laguna_s",
        "moe_experts_hit_pct"}
    assert all(m["value"] > 0 for m in line["metrics"].values())
    assert 0 < line["metrics"]["moe_experts_hit_pct"]["value"] <= 100
