"""The two readers of the engine's device account (PR 37):
``device_starved_pct.*`` (the ``lm/starved`` envelopes over the part of the
measured window before the profiled sub-window) and
``starved_account_error_pct.*`` (the account against the device trace's idle
time inside that sub-window), on made-up recordings; the trace reduction's gap
labels with and without the envelope; and every new reader file on a toy
cell's real recording."""
import json
import os

import pytest

from benchmarks import run
from benchmarks.harness import trace_reduce as tr
from benchmarks.tests import toy

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SUFFIXES = {"steady": ("gpt2xl.steady", "itl_p95_ms"),
            "saturated": ("gpt2xl.backlog", "out_tokens_per_s"),
            "laguna_s": ("laguna_s.steady", "itl_p95_ms"),
            "solar2": ("solar2.backlog", "out_tokens_per_s"),
            "ling3": ("ling3.longdecode", "out_tokens_per_s")}
NAMES = [f"{m}.{s}" for m in ("device_starved_pct", "starved_account_error_pct")
         for s in SUFFIXES]
TRACE = os.path.join(os.path.dirname(__file__), "data", "tiny_trace.xplane.pb")


def _read(name, rec):
    return run.read_layer_metric(BENCH_DIR, name, rec)


def _margin():
    return run._load_py(os.path.join(BENCH_DIR, "layer_metrics",
                                     "device_starved_pct.py")).MARGIN_BEFORE_S


@pytest.fixture()
def recording():
    """A window of 40 s from t=100, profiled from 112 to 116.  Envelopes: one
    across the window's opening edge (0.5 s, 0.2 of it inside), two in the
    part before the profile (0.3 and 0.1), one across the start of the
    stretch left out before it (0.4, of which 0.1 lies before it), one inside
    the profile (1.0), two after it (0.6, 0.8) and one after the window."""
    cut = 112.0 - _margin()
    spans = [("lm/round", 99.5, 1.0), ("lm/round", 101.0, 0.5),
             ("lm/idle", 113.0, 0.75),
             ("lm/starved", 99.7, 0.5), ("lm/starved", 103.0, 0.3),
             ("lm/starved", 105.0, 0.1), ("lm/starved", cut - 0.1, 0.4),
             ("lm/starved", 114.0, 1.0), ("lm/starved", 118.0, 0.6),
             ("lm/starved", 139.7, 0.8), ("lm/starved", 141.0, 0.2),
             ("lm/decode_wait", 103.0, 0.3)]
    return {"window": (100.0, 140.0), "traced_window": (112.0, 116.0),
            "spans": spans, "counters": {},
            "trace": {"window_s": 4.0, "busy_s": 2.0}}


@pytest.mark.parametrize("suffix", sorted(SUFFIXES))
def test_device_starved_pct_reads_the_window_before_the_profile(recording,
                                                                suffix):
    clean = 12.0 - _margin()
    assert _read(f"device_starved_pct.{suffix}", recording) == pytest.approx(
        (0.2 + 0.3 + 0.1 + 0.1) / clean * 100.0)
    # rounds traced and nothing starved: a zero, not nothing
    none = dict(recording, spans=[s for s in recording["spans"]
                                  if s[0] != "lm/starved"])
    assert _read(f"device_starved_pct.{suffix}", none) == 0.0
    # the tracer off (no lm/round), or no profiled sub-window: nothing
    assert _read(f"device_starved_pct.{suffix}",
                 dict(recording, spans=[s for s in recording["spans"]
                                        if s[0] != "lm/round"])) is None
    for traced in (None, (112.0, None)):
        assert _read(f"device_starved_pct.{suffix}",
                     dict(recording, traced_window=traced)) is None
    # a full ring dropped the window's first seconds: the share is taken over
    # what the rounds that are left cover (from the first one's start)
    lost = dict(recording, spans=[s for s in recording["spans"] if s[1] >= 104.0]
                + [("lm/round", 104.5, 0.5)])
    assert _read(f"device_starved_pct.{suffix}", lost) == pytest.approx(
        (0.1 + 0.1) / (clean - 4.5) * 100.0)
    # a later profile: more of the window is read; one that starts with the
    # window leaves nothing to read
    late = dict(recording, traced_window=(137.0, 140.0))
    assert _read(f"device_starved_pct.{suffix}", late) == pytest.approx(
        (0.2 + 0.3 + 0.1 + 0.4 + 1.0 + 0.6) / (37.0 - _margin()) * 100.0)
    assert _read(f"device_starved_pct.{suffix}",
                 dict(recording, traced_window=(100.2, 104.2))) is None


@pytest.mark.parametrize("suffix", sorted(SUFFIXES))
def test_starved_account_error_pct_is_the_account_less_the_devices_idle_time(
        recording, suffix):
    name = f"starved_account_error_pct.{suffix}"
    # inside the profile: 1.0 s starved + 0.75 s idle against 4.0 - 2.0
    assert _read(name, recording) == pytest.approx(0.25 / 4.0 * 100.0)
    # an account that claims more than the device idled errs as far
    busy = dict(recording, trace={"window_s": 4.0, "busy_s": 3.0})
    assert _read(name, busy) == pytest.approx(0.75 / 4.0 * 100.0)
    # envelopes are clipped to the profile's edges
    edge = dict(recording, spans=recording["spans"]
                + [("lm/starved", 111.5, 0.6), ("lm/idle", 115.9, 5.0)])
    assert _read(name, edge) == pytest.approx(
        abs(1.75 + 0.1 + 0.1 - 2.0) / 4.0 * 100.0)
    for missing in ({"trace": None}, {"traced_window": None},
                    {"traced_window": (112.0, None)},
                    {"spans": [s for s in recording["spans"]
                               if s[0] != "lm/round"]}):
        assert _read(name, dict(recording, **missing)) is None


def test_gap_labels_are_the_same_with_and_without_the_envelope():
    """``lm/starved`` holds two leaves or more and is never shorter than one
    of them, so it is never the shortest cover of a gap's middle."""
    leaves = [("lm/emit", 66.5e6, 70.0e6), ("lm/sched", 70.0e6, 78.0e6),
              ("lm/decode_dispatch", 78.0e6, 87.0e6),
              ("lm/round", 60.0e6, 90.0e6)]
    with_envelope = leaves + [("lm/starved", 66.5e6, 87.0e6)]
    a = tr.reduce_trace(TRACE, window=(66.0e6, 88.0e6), host_spans=leaves)
    b = tr.reduce_trace(TRACE, window=(66.0e6, 88.0e6),
                        host_spans=with_envelope)
    assert a["idle_gaps"] == b["idle_gaps"]
    assert "lm/starved" not in dict(b["idle_gaps"])
    assert dict(a["idle_gaps"])["lm/sched"] > 0.02
    # an envelope of ONE leaf (a speculating engine's last emission before
    # lm/idle) ties with it, and the leaf, written first, keeps the label
    one = [("lm/emit", 66.5e6, 87.0e6), ("lm/starved", 66.5e6, 87.0e6)]
    assert "lm/starved" not in dict(tr.reduce_trace(
        TRACE, window=(66.0e6, 88.0e6), host_spans=one)["idle_gaps"])


def test_every_new_entry_names_its_cell_and_has_a_reader():
    with open(os.path.join(os.path.dirname(BENCH_DIR), "BENCHMARK.json")) as f:
        bench = json.load(f)
    entries = {m["name"]: m for m in bench["per_layer"]}
    for name in NAMES:
        metric, suffix = name.split(".")
        cell, moves = SUFFIXES[suffix]
        assert entries[name] == {
            "name": name, "unit": "%", "better": "lower", "layer": "server",
            "source": ("program_span" if metric == "device_starved_pct"
                       else "device_trace"),
            "moves": moves, "workloads": [cell]}
        with open(os.path.join(BENCH_DIR, "layer_metrics", name + ".json")) as f:
            spec = json.load(f)
        assert spec["reader"] == {"py": metric + ".py"} and spec["doc"]
    # appended behind everything PR 36 left
    assert list(entries)[-len(NAMES):] == NAMES


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return toy.make_root(str(tmp_path_factory.mktemp("bench_starved")))


def _with_a_profiled_sub_window(monkeypatch, seen):
    """``--trace 1`` on the CPU: no profiler and a canned reduction, as
    ``toy.without_profiler``, but the sub-window is stamped as ``trace_tick``
    stamps it (three tenths into the window, a fifth of it long) and the
    recording the readers get is kept."""
    import time
    toy.without_profiler(monkeypatch)

    def tick(self):
        now = time.perf_counter()
        if not self.trace or not self._window_is_open:
            return
        if self.traced_window is None and now >= self.t_open + 0.3 * self.seconds:
            self.traced_window = (now, None)
        elif (self.traced_window is not None and self.traced_window[1] is None
              and now >= self.traced_window[0] + 0.2 * self.seconds):
            self.traced_window = (self.traced_window[0], now)

    monkeypatch.setattr(run.Run, "trace_tick", tick)
    real = run.read_layer_metric

    def kept(bench_dir, name, recording):
        seen["recording"] = recording
        return real(bench_dir, name, recording)

    monkeypatch.setattr(run, "read_layer_metric", kept)


@pytest.mark.parametrize("cell,suffix", [("toy.steady", "steady"),
                                         ("toy.backlog", "saturated")])
def test_toy_traced_run_reports_both_and_every_reader_file_loads(
        root, monkeypatch, cell, suffix):
    seen = {}
    _with_a_profiled_sub_window(monkeypatch, seen)
    line = run.run_cell(root, cell, 2 ** 31 + 37, 5.0, True,
                        require_accelerator=False)
    json.dumps(line)
    assert line["correct"] and line["failed"] == 0
    rec = seen["recording"]
    assert rec["traced_window"][1] is not None
    names = {n for n, _, _ in rec["spans"]}
    assert {"lm/starved", "lm/round", "lm/decode_wait"} <= names
    for metric in ("device_starved_pct", "starved_account_error_pct"):
        got = line["metrics"][f"{metric}.{suffix}"]
        assert got["unit"] == "%" and 0.0 <= got["value"] <= 100.0
    # every new reader file, on this recording, reads what the cell's own did
    for name in NAMES:
        value = run.read_layer_metric(os.path.join(root, "benchmarks"), name, rec)
        assert value == line["metrics"][name.split(".")[0] + "." + suffix]["value"]
    # the other cells' entries are not in this one's line
    assert not {n for n in NAMES if not n.endswith("." + suffix)} \
        & set(line["metrics"])
    # the envelopes lie outside the waits, on the recording's own clock
    waits = [(s, s + d) for n, s, d in rec["spans"]
             if n in ("lm/decode_wait", "lm/first_token")]
    for n, s, d in rec["spans"]:
        if n == "lm/starved":
            assert all(b <= s + 1e-9 or s + d <= a + 1e-9 for a, b in waits)
