"""The driver end to end at toy size on the CPU, through the function the
command calls (``run.run_cell``), in a temporary tree that ADDS its
configuration, mixes and a per-layer metric to a copy of ``benchmarks/`` and
edits nothing -- the way a later PR adds a cell.  Also the two tests the
contract asks of ``correct``: the lower-precision control (the program's own
int8 paths, switched on) comes out not correct, and a run whose timed path is
broken underneath does too."""
import json
import os

import numpy as np
import pytest

from benchmarks import run
from benchmarks.tests import readings, toy


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return toy.make_root(str(tmp_path_factory.mktemp("bench")))


def _line(root, cell, seed=2 ** 31 + 17, seconds=2.0, **kw):
    line = run.run_cell(root, cell, seed, seconds, False,
                        require_accelerator=False, **kw)
    json.dumps(line)                    # the last line has to serialise
    assert set(line) >= {"correct", "attempted", "failed", "metrics", "device"}
    assert list(line)[-1] == "compared"
    assert set(line["compared"]) == {"served_gap_max", "served_gap_mean",
                                     "compiles_in_window"}
    return line


def test_the_command_refuses_to_run_without_an_accelerator(root):
    with pytest.raises(SystemExit, match="accelerator"):
        run.run_cell(root, "toy.steady", 1, 1.0, False)
    with pytest.raises(SystemExit, match="no workload"):
        run.run_cell(root, "toy.absent", 1, 1.0, False)


def test_the_command_ends_both_streams_with_the_numbers_compared(
        monkeypatch, capsys):
    line = {"correct": False, "attempted": 1, "failed": 0, "metrics": {},
            "device": {}, "compared": {"served_gap_max": {"value": 0.5,
                                                          "limit": 0.15}}}
    monkeypatch.setattr(run, "run_cell", lambda *a, **kw: dict(line))
    assert run.main(["--workload", "x", "--seed", "1", "--seconds", "1"]) == 0
    out, err = capsys.readouterr()
    assert json.loads(out.splitlines()[-1]) == line
    assert err.splitlines()[-1] == "compared served_gap_max: 0.5 (limit 0.15)"


def test_steady_cell_reports_tails_from_the_due_time(root, capsys):
    line = _line(root, "toy.steady")
    assert line["correct"] and line["failed"] == 0
    assert line["attempted"] == 20                      # 10 a second for 2 s
    assert {"itl_p95_ms", "setup_s"} <= set(line["metrics"])
    assert all(m["value"] > 0 for m in line["metrics"].values())
    notes = [json.loads(l) for l in capsys.readouterr().out.splitlines()
             if l.startswith("{")]
    compared = {n["compared"]: n for n in notes if "compared" in n}
    assert {"served_gap_max", "served_gap_mean",
            "compiles_in_window"} <= set(compared)
    assert compared["compiles_in_window"]["value"] == 0
    # the committed configuration holds a limit for every number compared
    with open(os.path.join(os.path.dirname(toy.BENCH), "benchmarks", "configs",
                           "gpt2-xl.json")) as f:
        assert set(json.load(f)["check"]) == set(compared) - {"compiles_in_window"}
    sizes = next(n for n in notes if "ttft_ms" in n)
    assert sizes["ttft_ms"]["n"] == 20 and sizes["fire_late_ms"]["n"] == 20
    # TTFT counts from the due instant: never shorter than the lateness
    assert sizes["ttft_ms"]["median"] > sizes["fire_late_ms"]["median"]


def test_backlog_cell_counts_window_tokens_and_cancels_the_rest(root):
    line = _line(root, "toy.backlog")
    assert line["correct"] and line["failed"] == 0
    assert set(line["metrics"]) == {"out_tokens_per_s", "setup_s"}
    assert line["metrics"]["out_tokens_per_s"]["value"] > 0


@pytest.mark.parametrize("cell,control", [
    *(("toy.steady", c) for c in sorted(readings.CONTROLS)),
    ("toy.backlog", "kv8")])
def test_int8_control_comes_out_not_correct(root, cell, control):
    assert _line(root, cell,
                 config_update=readings.CONTROLS[control])["correct"] is False


@pytest.mark.parametrize("cell", ["toy.steady", "toy.backlog"])
def test_altered_token_comes_out_not_correct(root, monkeypatch, cell):
    """The timed path broken underneath: every eighth token is altered where
    the engine emits it."""
    from bigdl_tpu.serving import lm_engine
    real, n = lm_engine.LMStream._emit, {"n": 0}

    def emit(self, token_1b):
        n["n"] += 1
        real(self, token_1b % 500 + 1 if n["n"] % 8 == 0 else token_1b)

    monkeypatch.setattr(lm_engine.LMStream, "_emit", emit)
    assert _line(root, cell)["correct"] is False


def test_a_later_pr_adds_a_metric_by_adding_files(root):
    """A new per-layer metric is a reader file and an entry; read through the
    same function the traced run uses, on a recording like a driver's."""
    bench_dir = os.path.join(root, "benchmarks")
    with open(os.path.join(bench_dir, "layer_metrics", "prefill_ms.json"), "w") as f:
        json.dump({"reader": {"span": "lm/prefill", "stat": "mean",
                              "scale": 1000}}, f)
    rec = {"window": (10.0, 20.0), "counters": {"lm.slot_occupancy": 0.5},
           "spans": [("lm/prefill", 11.0, 0.010), ("lm/prefill", 12.0, 0.030),
                     ("lm/prefill", 25.0, 9.0), ("lm/decode_step", 11.0, 0.02)]}
    assert run.read_layer_metric(bench_dir, "prefill_ms", rec) == pytest.approx(20.0)
    assert run.read_layer_metric(bench_dir, "slot_occupancy", rec) == 50.0
    assert run.read_layer_metric(bench_dir, "queue_wait_p95_ms", rec) is None


def test_roofline_readers_on_a_recording():
    bench_dir = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(bench_dir, "configs", "gpt2-xl.json")) as f:
        gpt2 = json.load(f)
    rec = {"config": gpt2, "device_kind": "TPU v5 lite", "chips": 1,
           "counters": {"lm.decode_context_tokens": 100 * 16 * 300},
           "trace": {"modules": {"jit__decode_fn": {"calls": 100.0,
                                                    "device_s": 3.0}}}}
    # 100 rounds x 3.112 GB + 480,000 positions x 307,200 B = 458.7 GB;
    # / 819 GB/s = 0.560 s least; over 3.0 s on the device = 18.7%
    assert run.read_layer_metric(bench_dir, "decode_hbm_roofline", rec) \
        == pytest.approx(18.67, abs=0.02)
