"""The per-layer metrics that read the LM engine's round spans (PR 24): each
reader on a hand-built recording, nothing to read on a program without the
spans (the parent), and a toy traced run whose result line holds them."""
import json
import os

import pytest

from benchmarks import run
from benchmarks.tests import toy

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
STEADY = ("prefill_interrupt_ms.steady", "first_token_wait_ms.steady",
          "round_host_ms.steady")
SATURATED = ("round_host_ms.saturated", "round_max_ms.saturated",
             "round_max_host_ms.saturated")


def _round(start, wait, host_before=0.002, host_after=0.003, admit=None):
    """One lm/round as the engine writes it: [sched][admit ...][dispatch]
    [wait][emit]; ``admit`` is (admit_host_s, first_token_s)."""
    spans, t = [], start
    spans.append(("lm/sched", t, host_before / 2))
    t += host_before / 2
    if admit:
        host_s, first_s = admit
        spans += [("lm/admit", t, host_s + first_s),
                  ("lm/admit_host", t, host_s),
                  ("lm/first_token", t + host_s, first_s)]
        t += host_s + first_s
    spans.append(("lm/decode_dispatch", t, host_before / 2))
    t += host_before / 2
    spans += [("lm/decode_step", t - host_before / 2, host_before / 2 + wait),
              ("lm/decode_wait", t, wait)]
    t += wait
    spans.append(("lm/emit", t, host_after))
    t += host_after
    spans.append(("lm/round", start, t - start))
    return spans, t


@pytest.fixture(scope="module")
def recording():
    """A window of 10 s from t=100: a round before it, three plain rounds
    (host 5, 5 and 9 ms), one with an admission (host 4 + 5 ms, first token
    60 ms), one stalled in the emit phase for 4 s, a request's envelopes."""
    spans, t = [], 99.0
    for kw in ({"wait": 0.5},                                    # before
               {"wait": 0.240},
               {"wait": 0.242},
               {"wait": 0.241, "admit": (0.004, 0.060)},
               {"wait": 0.243, "host_after": 4.0},               # the stall
               {"wait": 0.244, "host_after": 0.007}):
        if t < 100.0 < t + 1:
            t = 100.5
        more, t = _round(t, **kw)
        spans += more
    spans += [("lm/queue_wait", 98.0, 9.0), ("lm/request", 98.0, 12.0)]
    return {"window": (100.0, 110.0), "spans": spans, "counters": {}}


def _read(name, rec):
    return run.read_layer_metric(BENCH_DIR, name, rec)


def test_readers_on_a_hand_built_recording(recording):
    assert _read("prefill_interrupt_ms.steady", recording) == pytest.approx(64.0)
    assert _read("first_token_wait_ms.steady", recording) == pytest.approx(60.0)
    # plain rounds: hosts 5, 5 and 9 ms and the stalled one's 4002; the round
    # with the admission and the round before the window are left out
    for name in ("round_host_ms.steady", "round_host_ms.saturated"):
        assert _read(name, recording) == pytest.approx((5.0 + 9.0) / 2)
    assert _read("round_max_ms.saturated", recording) == pytest.approx(4245.0)
    assert _read("round_max_host_ms.saturated", recording) == pytest.approx(4002.0)


def test_longest_round_with_an_admission_leaves_both_device_waits_out(recording):
    """Without the stall the longest round is the one an admission
    interrupted: 2 + 4 + 60 + 241 + 3 ms, of which the host's are 9."""
    spans = [s for s in recording["spans"] if not 101.2 < s[1] < 105.5]
    rec = dict(recording, spans=spans)
    assert _read("round_max_ms.saturated", rec) == pytest.approx(310.0)
    assert _read("round_max_host_ms.saturated", rec) == pytest.approx(9.0)


def test_nothing_to_read_where_the_program_has_no_round_spans(recording):
    """The parent commit: the old spans only."""
    old = [s for s in recording["spans"]
           if s[0] in ("lm/decode_step", "lm/queue_wait", "lm/request")]
    for rec in (dict(recording, spans=old), dict(recording, spans=[])):
        for name in STEADY + SATURATED:
            assert _read(name, rec) is None, name
    # rounds but none that decoded uninterrupted: still nothing, not a zero
    admit_only = [s for s in recording["spans"] if 100.9 < s[1] < 101.3]
    assert _read("round_host_ms.steady",
                 dict(recording, spans=admit_only)) is None


def test_every_new_entry_names_its_cell_and_has_a_reader():
    with open(os.path.join(os.path.dirname(BENCH_DIR), "BENCHMARK.json")) as f:
        bench = json.load(f)
    entries = {m["name"]: m for m in bench["per_layer"]}
    for names, cell, moves in ((STEADY, "gpt2xl.steady", "itl_p95_ms"),
                               (SATURATED, "gpt2xl.backlog",
                                "out_tokens_per_s")):
        for name in names:
            assert entries[name]["workloads"] == [cell]
            assert entries[name]["moves"] == moves
            assert entries[name]["source"] == "program_span"
            assert os.path.exists(os.path.join(BENCH_DIR, "layer_metrics",
                                               name + ".json"))
    # in the order PR 24 appended them, whatever later PRs append behind
    assert [n for n in entries if n in STEADY + SATURATED] \
        == list(STEADY + SATURATED)


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return toy.make_root(str(tmp_path_factory.mktemp("bench_rounds")))


@pytest.mark.parametrize("cell,names", [("toy.steady", STEADY),
                                        ("toy.backlog", SATURATED)])
def test_toy_traced_run_reports_the_cells_round_metrics(root, monkeypatch,
                                                        cell, names):
    """``--trace 1`` through the function the command calls.  The CPU has no
    device plane, so the profiler is left out and its reduction canned; the
    program's spans are the real ones."""
    toy.without_profiler(monkeypatch)
    line = run.run_cell(root, cell, 2 ** 31 + 24, 2.0, True,
                        require_accelerator=False)
    json.dumps(line)
    assert line["correct"] and line["failed"] == 0
    assert set(names) <= set(line["metrics"]), sorted(line["metrics"])
    for name in names:
        assert line["metrics"][name]["unit"] == "ms"
        assert line["metrics"][name]["value"] > 0
    # the other cell's are not in this one's line
    other = set(STEADY + SATURATED) - set(names)
    assert not other & set(line["metrics"])
    if cell == "toy.backlog":
        m = line["metrics"]
        assert m["round_max_host_ms.saturated"]["value"] \
            <= m["round_max_ms.saturated"]["value"]
        assert m["round_host_ms.saturated"]["value"] \
            <= m["round_max_ms.saturated"]["value"]
