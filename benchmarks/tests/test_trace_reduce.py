"""The reduction from a profiler trace to numbers, on a recording made on the
chip (``data/tiny_trace.xplane.pb``: four rounds of a three-matmul program and
a row reduction, 20 ms of sleep between; a by-hand probe made it, PR 23).
Hand-checked against the events the probe printed."""
import os

import pytest

from benchmarks.harness import trace_reduce as tr

TRACE = os.path.join(os.path.dirname(__file__), "data", "tiny_trace.xplane.pb")
#: device durations of the eight XLA Modules events, ns, as printed
MATMUL = [42248, 42062, 42038, 42047]
REDUCE = [4036, 4141, 4272, 4002]


def test_module_time_and_calls():
    r = tr.reduce_trace(TRACE)
    assert r["chips"] == 1
    assert set(r["modules"]) == {"jit_matmul_chain", "jit_reduce_rows"}
    assert r["modules"]["jit_matmul_chain"]["calls"] == 4
    assert r["modules"]["jit_matmul_chain"]["device_s"] == pytest.approx(
        sum(MATMUL) * 1e-9, rel=1e-3)
    assert r["modules"]["jit_reduce_rows"]["device_s"] == pytest.approx(
        sum(REDUCE) * 1e-9, rel=1e-3)


def test_busy_union_and_idle_share():
    r = tr.reduce_trace(TRACE)
    # first op starts at 44,480,060 ns, the last ends at 131,615,328 ns
    assert r["window_s"] == pytest.approx(0.087135, rel=1e-4)
    # the operations fill their modules but for a few ns at the edges
    assert r["busy_s"] == pytest.approx(sum(MATMUL + REDUCE) * 1e-9, rel=2e-3)
    idle = 1.0 - r["busy_s"] / r["window_s"]
    assert idle == pytest.approx(0.99788, abs=1e-4)
    assert r["device_ops"][0][0].startswith("convolution_tanh_fusion")
    assert sum(s for _, s in r["device_ops"]) == pytest.approx(r["busy_s"],
                                                               rel=1e-6)


def test_window_clips_and_gaps_take_the_host_spans_name():
    # a window over the second round only: module events at 66.2 and 87.3 ms
    r = tr.reduce_trace(TRACE, window=(66.0e6, 88.0e6),
                        host_spans=[("sleeping", 66.5e6, 87.0e6),
                                    ("outer", 60.0e6, 90.0e6)])
    assert r["modules"]["jit_matmul_chain"]["calls"] == 1
    assert r["modules"]["jit_reduce_rows"]["calls"] == 1
    assert r["busy_s"] == pytest.approx((42062 + 4141) * 1e-9, rel=2e-3)
    gaps = dict(r["idle_gaps"])
    # the long gap between the two programs lies inside "sleeping" (the
    # innermost span that covers its middle); the edges only inside "outer"
    assert gaps["sleeping"] == pytest.approx(0.0210, abs=2e-4)
    assert gaps["outer"] < 0.001
    assert sum(gaps.values()) == pytest.approx(r["window_s"] - r["busy_s"],
                                               rel=1e-6)
    # the same window and spans given on a host clock 2 s ahead of the trace's
    # (the marker the probe did not record is looked for, and missed)
    with pytest.raises(ValueError, match="clock_sync"):
        tr.reduce_trace(TRACE, window=(2.066, 2.088), sync_perf=2.0)


def test_interval_union():
    assert tr.merge([(0, 2), (1, 3), (5, 6), (5.5, 5.8)]) == [(0, 3), (5, 6)]
    assert tr.module_name("jit__decode_fn(123)") == "jit__decode_fn"
    assert tr.op_name("%fusion.3 = bf16[8]{0} fusion(...)") == "fusion.3"
