"""By hand, on the chip: what the LM engine's round stamps see in whole runs
of a benchmark cell.  Not part of the benchmark: it calls
``benchmarks.run.run_cell`` and reads the engine from outside.

    python3 scripts/lm_round_hunt.py --cell gpt2xl.backlog --seeds <n> [<n> ...]
        one untraced run a seed, in this process; after each, one JSON line
        with the result line's metrics and the engine's stats()["rounds"]
        over the measured window (reset where the window opens, read where it
        closes): seconds by phase and, of them, the seconds that passed
        with the device proven empty and work to do ("starved_s",
        "starved_phase_s": the engine's own account, no trace needed), the
        running median, the longest round with its own phase split and its
        own starved seconds -- the stall hunt; and, under "lists", what the
        decode rounds' live lists held over the window (blocks listed,
        blocks gathered, the entries of whole tables) and, beside them,
        "logit_rows_to_host": the rows of V float32 logits the window
        copied to the host, against "prefills", its admissions' first
        tokens (equal when no plain decode round moves a row), and
        "rounds_ahead", the decode rounds enqueued while their predecessor
        was still on the device, with "ahead_share", their share of
        "decode_steps", and "rows_discarded", the rows of streams that had
        ended on their eos a round before; for a model
        with recurrent layers "state_row_steps" (active slots x recurrent
        layers, summed over the rounds), "state_rows_in_use", the state's
        read-and-write bytes a round and "state_step_path", the form its
        decode step takes ("kernel": ops.kda_step, or "xla")
    ... --tracer-on     the same with the span tracer enabled for the whole run
                        (not the profiler): what tracing costs end to end
    ... --trace         runs with --trace 1 instead; beside each result line,
                        the interval that bounds the device clock's offset
                        from the host's (device module inside the program's
                        lm/decode_dispatch .. lm/decode_wait annotations of
                        the same round, on the profiler's one clock), how
                        much of the window the worker's leaf spans cover,
                        and, under "starved_gaps", every device idle gap over
                        0.5 ms of the xplane against the lm/starved / lm/idle
                        envelope that covers it once that offset is applied
                        (seconds covered by each; what no envelope covers by
                        the leaf phase under it: the account's blind side; the
                        gaps one by one in chiprun_out/starved_gaps.jsonl), and
                        under "profiler_edges" what start_trace and stop_trace
                        cost the rounds around the profiled sub-window
    ... --blocks <n>    the pool probe: the cell with a KV pool of n blocks
                        laid over its configuration (does a round grow with
                        the pool?)
    ... --stamp-cost    no cell: times the stamps of one plain round on an
                        idle toy engine, tracer off and on: a round that ran
                        ahead (the device's account has nothing to do) and a
                        round after a drain (its emit, sched and dispatch are
                        starved, one lm/starved envelope with the tracer on)

On a tree without the stamps (the parent) the rounds read null.
"""
import argparse
import json
import os
import statistics
import sys
import time
import weakref

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def _watch_engines():
    """Reset every live LM engine's round record where a window opens and
    read it where the window closes; returns the dict the readings land in."""
    from benchmarks import run
    from bigdl_tpu.serving import lm_engine
    engines, seen = [], {}
    init = lm_engine.LMServingEngine.__init__
    open_window, close_window = run.Run.open_window, run.Run.close_window

    def watched_init(self, *a, **kw):
        init(self, *a, **kw)
        engines.append(weakref.ref(self))

    def live():
        return [e for e in (r() for r in engines)
                if e is not None and hasattr(e, "rounds_stats")]

    def lists(e):
        m = e.metrics
        # (the parent's tree has no logit_rows_to_host, and the one before
        # the run-ahead round no rounds_ahead: null there)
        return [m.decode_steps, getattr(m, "live_blocks", None),
                getattr(m, "gathered_blocks", None), m.prefills,
                getattr(m, "logit_rows_to_host", None),
                getattr(m, "state_row_steps", None),
                getattr(m, "rounds_ahead", None),
                getattr(m, "rows_discarded", None)]

    def watched_open(self, at=None):
        for e in live():
            e.metrics.reset_rounds()
        seen["_lists"] = [lists(e) for e in live()]
        return open_window(self, at)

    def watched_close(self, at=None):
        t = close_window(self, at)
        seen["rounds"] = [e.rounds_stats() for e in live()]
        # the decode rounds' live lists over the window: blocks listed,
        # blocks gathered (the rungs), and what whole tables would hold
        seen["lists"] = []
        for e, before in zip(live(), seen.pop("_lists", [])):
            (steps, listed, gathered, prefills, logit_rows, state_rows,
             ahead, discarded) = (None if b is None else a - b
                                  for a, b in zip(lists(e), before))
            # a recurrent model's state arena (null on a model without, and
            # on the parent's tree): (slot, layer) rows the rounds moved, the
            # rows that hold a request's state now, and the bytes a round
            # reads and writes of them on average
            arena = getattr(e, "state", None)
            seen["lists"].append({
                "decode_steps": steps, "live_blocks": listed,
                "gathered_blocks": gathered,
                "table_entries": steps * e.slots * e.table_width,
                "prefills": prefills, "logit_rows_to_host": logit_rows,
                "rounds_ahead": ahead,
                "ahead_share": (None if ahead is None or not steps
                                else ahead / steps),
                "rows_discarded": discarded,
                "state_row_steps": state_rows,
                "state_rows_in_use": getattr(e.metrics, "state_rows_in_use",
                                             None),
                "state_bytes_a_round": (
                    None if arena is None or not steps
                    else 2 * arena.row_bytes * state_rows / steps),
                # which form the recurrent layers' decode step takes (null
                # on a model without, and on a tree before PR 43)
                "state_step_path": getattr(e, "_state_step_path", None),
                # which grouped matmul each step program's routed layers
                # take, and the row windows the kernel visited so far (null
                # on a tree before PR 41)
                "expert_matmul": getattr(e, "_expert_matmul_paths",
                                         lambda: None)(),
                "moe_row_tiles": getattr(e.metrics, "moe_row_tiles", None)})
        del engines[:]
        return t

    lm_engine.LMServingEngine.__init__ = watched_init
    run.Run.open_window, run.Run.close_window = watched_open, watched_close
    return seen


def _clock_offset(xplane: str):
    """[lo, hi] ms for (device clock - host clock) in one profile: a round's
    decode module starts after its lm/decode_dispatch was entered and ends
    before its lm/decode_wait was left.  Since the round runs one ahead the
    NEXT round's dispatch is entered while a module runs and the previous
    round's wait is left just after it starts, so a module is paired with the
    last dispatch entered before it STARTED and the first wait left after it
    ENDED: the wait's side stays tight (a module's end to its ids on the
    host); the dispatch's side is tight only for a round after a drain (one
    enqueued ahead waits for the device, not for the host)."""
    from jax.profiler import ProfileData
    dispatch, wait, modules = [], [], []
    for plane in ProfileData.from_file(xplane).planes:
        for line in plane.lines:
            for ev in line.events:
                if plane.name.startswith("/host:"):
                    if ev.name == "lm/decode_dispatch":
                        dispatch.append(ev.start_ns)
                    elif ev.name == "lm/decode_wait":
                        wait.append((ev.start_ns, ev.start_ns + ev.duration_ns))
                elif (plane.name.startswith("/device:TPU:")
                      and line.name == "XLA Modules" and "decode_fn" in ev.name):
                    modules.append((ev.start_ns, ev.start_ns + ev.duration_ns))
    dispatch.sort(), wait.sort(), modules.sort()
    lows, highs = [], []
    for m0, m1 in modules:
        # (offsets are under a millisecond or two, rounds 7 to 34 ms)
        d = [t for t in dispatch if t < m0]
        w = [b for a, b in wait if b > m1]
        if d and w and w[0] - d[-1] < 3 * (m1 - m0):
            highs.append((m0 - d[-1]) * 1e-6)
            lows.append((m1 - w[0]) * 1e-6)
    if not lows:
        return None
    return {"rounds": len(lows), "device_minus_host_ms": [max(lows), min(highs)],
            "module_start_after_dispatch_ms": statistics.median(highs),
            "wait_end_after_module_end_ms": -statistics.median(lows)}


def gap_cover(gaps, envelopes, leaves):
    """Each device idle gap ``(a, b)`` against the engine's account, all on
    one clock: the seconds of it under an ``lm/starved`` or ``lm/idle``
    envelope (``envelopes``: ``(name, a, b)``), and what no envelope covers
    by the leaf phase under it (``leaves``: ``(name, a, b)``; ``no leaf``
    where none is).  Returns one dict a gap."""
    from benchmarks.harness.trace_reduce import merge
    out = []
    for a, b in gaps:
        row = {"at": a, "gap": b - a, "covered": {}, "uncovered": {}}
        inside = [(n, max(x, a), min(y, b)) for n, x, y in envelopes
                  if x < b and y > a]
        for n, x, y in inside:
            row["covered"][n] = row["covered"].get(n, 0.0) + (y - x)
        # what is left of the gap, piece by piece
        edges = [a] + [t for iv in merge((x, y) for _, x, y in inside)
                       for t in iv] + [b]
        for lo, hi in zip(edges[0::2], edges[1::2]):
            left = hi - lo
            if left <= 0:
                continue
            for n, x, y in leaves:
                part = min(y, hi) - max(x, lo)
                if part > 0:
                    row["uncovered"][n] = row["uncovered"].get(n, 0.0) + part
                    left -= part
            if left > 1e-9:
                row["uncovered"]["no leaf"] = (
                    row["uncovered"].get("no leaf", 0.0) + left)
        out.append(row)
    return out


def _overlap_s(a, b) -> float:
    """Seconds two sorted lists of disjoint intervals have in common."""
    total, i, j = 0.0, 0, 0
    while i < len(a) and j < len(b):
        total += max(0.0, min(a[i][1], b[j][1]) - max(a[i][0], b[j][0]))
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return total


def _starved_gaps(xplane: str, spans, traced_window, sync_perf, offset_ms,
                  min_gap_s: float = 0.5e-3, dump: str = None):
    """Gap by gap: every device idle gap over ``min_gap_s`` of the traced
    window against the ``lm/starved`` / ``lm/idle`` envelope that covers it
    once the measured clock offset is applied; what no envelope covers is
    listed by the leaf under it -- the account's blind side.  Returns the
    summary and the gaps one by one."""
    from jax.profiler import ProfileData
    from benchmarks.harness import trace_reduce
    from bigdl_tpu.serving.lm_engine import ROUND_PHASES
    busy, sync_ns = [], None
    for plane in ProfileData.from_file(xplane).planes:
        for line in plane.lines:
            if plane.name.startswith("/host:"):
                for ev in line.events:
                    if ev.name == trace_reduce.SYNC:
                        sync_ns = ev.start_ns
            elif (plane.name.startswith("/device:TPU:")
                  and line.name == "XLA Ops"):
                busy += [(ev.start_ns, ev.start_ns + ev.duration_ns)
                         for ev in line.events]
    if sync_ns is None or not busy:
        return None
    # everything in seconds from the sync marker, on the device's clock
    on_device = lambda t: t - sync_perf + offset_ms * 1e-3      # noqa: E731
    lo, hi = (on_device(t) for t in traced_window)
    busy = trace_reduce.merge(((a - sync_ns) * 1e-9, (b - sync_ns) * 1e-9)
                              for a, b in busy)
    edges = [lo] + [t for a, b in busy if b > lo and a < hi
                    for t in (max(a, lo), min(b, hi))] + [hi]
    gaps = [(a, b) for a, b in zip(edges[0::2], edges[1::2]) if b > a]
    long = [g for g in gaps if g[1] - g[0] >= min_gap_s]
    leaf_names = {"lm/" + p for p in ROUND_PHASES} - {"lm/idle"}
    envelopes = [(n, on_device(s), on_device(s + d)) for n, s, d in spans
                 if n in ("lm/starved", "lm/idle") and d > 0]
    leaves = [(n, on_device(s), on_device(s + d)) for n, s, d in spans
              if n in leaf_names and d > 0]
    if dump:    # what the comparison was made of, to make it again by hand
        with open(dump, "w") as f:
            json.dump({"offset_ms": offset_ms, "window": [lo, hi],
                       "gaps": long, "envelopes": envelopes,
                       "leaves": [l for l in leaves
                                  if l[2] > lo - 0.05 and l[1] < hi + 0.05]}, f)
    rows = gap_cover(long, envelopes, leaves)
    total = lambda key: {                                       # noqa: E731
        n: sum(r[key].get(n, 0.0) for r in rows)
        for n in sorted({n for r in rows for n in r[key]})}
    claimed_busy = _overlap_s(
        sorted((max(x, lo), min(y, hi)) for _, x, y in envelopes
               if x < hi and y > lo), busy)
    worst = sorted(rows, key=lambda r: -sum(r["uncovered"].values()))[:12]
    return {"offset_ms": offset_ms, "window_s": hi - lo,
            "idle_s": sum(b - a for a, b in gaps),
            "gaps_over_half_ms": {"n": len(long),
                                  "s": sum(b - a for a, b in long)},
            "gaps_under_half_ms": {"n": len(gaps) - len(long),
                                   "s": sum(b - a for a, b in gaps
                                            if b - a < min_gap_s)},
            "covered_s": total("covered"), "uncovered_s": total("uncovered"),
            "claimed_while_busy_s": claimed_busy,
            "worst_uncovered": [
                {"at_s": r["at"] - lo, "gap_ms": r["gap"] * 1e3,
                 "uncovered_ms": {n: v * 1e3 for n, v in r["uncovered"].items()}}
                for r in worst if r["uncovered"]]}, rows


def _starved_by_part(spans, window, traced_window):
    """The starved share of the measured window's parts apart: before the
    profiled sub-window (what ``device_starved_pct.*`` reads), inside it, and
    after it from past ``stop_trace``'s 0.45-s stop to the window's close,
    while the trace is still being written out on its caller's thread."""
    from benchmarks import run
    mod = run._load_py(os.path.join(ROOT, "benchmarks", "layer_metrics",
                                    "device_starved_pct.py"))
    (lo, hi), (t0, t1) = window, traced_window
    parts = {"before": (lo, t0 - mod.MARGIN_BEFORE_S), "profiled": (t0, t1),
             "after": (t1 + 1.5, hi)}
    return {k: [mod.overlap(spans, ("lm/starved",), [(a, b)]) / (b - a) * 100,
                b - a] for k, (a, b) in parts.items() if b > a}


def _profiler_edges(spans, window, traced_window, calls):
    """What ``start_trace`` and ``stop_trace`` cost the worker: when each call
    began and returned (seconds from the profiled sub-window's start and from
    its end), the starved share of the window's parts, and the median and
    longest ``lm/round`` by quarter second from 2 s before the sub-window to
    4 s after it."""
    lo, hi = traced_window
    bins = {}
    for n, s, d in spans:
        if n == "lm/round" and lo - 2.0 <= s < hi + 4.0:
            key = ("%+.2f" % ((s - lo) // 0.25 * 0.25) if s < hi
                   else "end%+.2f" % ((s - hi) // 0.25 * 0.25))
            bins.setdefault(key, []).append(d)
    return {"start_trace": [t - lo for t in calls["start"]],
            "stop_trace": [t - hi for t in calls["stop"]],
            "starved_pct_by_part": _starved_by_part(spans, window,
                                                    traced_window),
            "round_ms_by_quarter_s": {
                k: [round(statistics.median(v) * 1e3, 3),
                    round(max(v) * 1e3, 1), len(v)] for k, v in bins.items()}}


def _watch_trace(seen: dict):
    """Before the harness reduces (and deletes) a trace: the clock offset
    from its planes, the leaf spans' cover of the traced window, the device's
    idle gaps against the engine's starved account, and what the profiler's
    start and stop cost."""
    import jax
    from benchmarks import run
    from benchmarks.harness import trace_reduce
    reduce_trace = run.Run.reduce_trace
    calls = {}

    def timed(fn, key):
        def call(*a, **kw):
            t0 = time.perf_counter()
            try:
                return fn(*a, **kw)
            finally:
                calls[key] = (t0, time.perf_counter())
        return call

    jax.profiler.start_trace = timed(jax.profiler.start_trace, "start")
    jax.profiler.stop_trace = timed(jax.profiler.stop_trace, "stop")

    def watched(self, spans):
        try:
            xplane = trace_reduce.find_xplane(self._trace_dir)
            seen["clock_offset"] = offset = _clock_offset(xplane)
            seen["profiler_edges"] = _profiler_edges(
                spans, (self.t_open, self.t_close), self.traced_window, calls)
            if offset is not None:
                # the wait's side of the interval is the tight one (a module's
                # end to its ids on the host); the dispatch's side is loose
                # where few rounds follow a drain (7-14 ms at solar2.backlog)
                lo, hi = offset["device_minus_host_ms"]
                found = _starved_gaps(
                    xplane, spans, self.traced_window, self._sync_perf,
                    lo + min(0.5, (hi - lo) / 2),
                    dump=os.path.join(ROOT, "chiprun_out", "starved_raw_%s_%d.json"
                                      % (self.cell["name"], self.seed)))
                if found:
                    seen["starved_gaps"], seen["_gap_rows"] = found
        except Exception as e:  # noqa: BLE001 -- a reading, not the run
            seen.setdefault("clock_offset", repr(e))
            seen["starved_gaps"] = repr(e)
        seen["cover"] = _cover(spans, (self.t_open, self.t_close))
        seen["round_shapes"] = _round_shapes(spans, (self.t_open, self.t_close))
        return reduce_trace(self, spans)

    run.Run.reduce_trace = watched


def _cover(spans, window):
    """Share of the window that the worker thread's leaf spans cover, and the
    share inside lm/round spans."""
    try:
        from bigdl_tpu.serving.lm_engine import ROUND_PHASES
    except ImportError:
        return None
    leaves = {"lm/" + p for p in ROUND_PHASES}
    lo, hi = window
    clip = lambda s, d: max(0.0, min(s + d, hi) - max(s, lo))   # noqa: E731
    by_name = {}
    for n, s, d in spans:
        if n in leaves or n == "lm/round":
            by_name[n] = by_name.get(n, 0.0) + clip(s, d)
    leaf_s = sum(v for n, v in by_name.items() if n != "lm/round")
    return {"window_s": hi - lo, "leaf_share": leaf_s / (hi - lo),
            "round_share": by_name.get("lm/round", 0.0) / (hi - lo),
            "seconds_by_leaf": dict(sorted(by_name.items()))}


def _round_shapes(spans, window):
    """What an admission costs the rounds, from the spans of a traced run:
    rounds by how many lm/admit they hold, with the medians of the round, of
    its lm/first_token time and of its lm/decode_wait -- a prefill's insert
    program is waited for by the NEXT decode step, not by the first token --
    and the 95th percentile of all rounds (what a gap between tokens is)."""
    from benchmarks.harness import stats
    lo, hi = window
    rounds = sorted((s, s + d) for n, s, d in spans
                    if n == "lm/round" and lo <= s < hi)
    if not rounds:
        return None
    by_admits = {}
    for a, b in rounds:
        inside = [(n, d) for n, s, d in spans if a <= s < b and n in
                  ("lm/admit", "lm/first_token", "lm/decode_wait")]
        k = sum(n == "lm/admit" for n, _ in inside)
        by_admits.setdefault(k, []).append(
            (b - a, sum(d for n, d in inside if n == "lm/first_token"),
             sum(d for n, d in inside if n == "lm/decode_wait")))
    ms = lambda v: stats.median(v) * 1e3                        # noqa: E731
    return {"rounds": len(rounds),
            "round_p95_ms": stats.percentile([b - a for a, b in rounds], 95) * 1e3,
            "by_admits": {str(k): {"n": len(v), "round_ms": ms([x[0] for x in v]),
                                   "first_token_ms": ms([x[1] for x in v]),
                                   "decode_wait_ms": ms([x[2] for x in v])}
                          for k, v in sorted(by_admits.items())}}


def _stalls() -> int:
    from bigdl_tpu.obs import shared_watchdog
    return shared_watchdog("lm_round").stall_count


def _new_stall(before: int):
    """The round watchdog's last event, if it fired in this run: when it
    saw the round (``inflight_s`` against ``threshold_s`` says whether its
    own thread ran on time) and where the worker stood."""
    from bigdl_tpu.obs import shared_watchdog
    wd = shared_watchdog("lm_round")
    if wd.stall_count == before or wd.last_event is None:
        return None
    ev = dict(wd.last_event)
    stacks = ev.pop("thread_stacks", {})
    ev["fired"] = wd.stall_count - before
    ev["worker_stack"] = {k: v[-1500:] for k, v in stacks.items()
                          if k.startswith("lm-serve")}
    return ev


def stamp_cost() -> dict:
    """Seconds of host time the stamps of ONE plain round cost, on an engine
    whose worker sleeps in lm/idle (so this thread may drive its stamps)."""
    from bigdl_tpu.models.transformer import TransformerLM
    from bigdl_tpu.obs import get_tracer
    from bigdl_tpu.serving import LMServingEngine, lm_engine as le
    model = TransformerLM(vocab_size=31, hidden_size=16, n_head=2, n_layers=1,
                          max_len=32).build(seed=0)
    eng = LMServingEngine(model, slots=2, cache_len=24, prefill_buckets=(8,))
    time.sleep(0.2)                                 # the worker reaches idle
    tracer, out = get_tracer(), {}

    def one_round():
        # a round that ran ahead: the device holds work throughout
        eng.watchdog.step_started()
        eng._stamp(le.P_DISPATCH)
        eng._stamp(le.P_WAIT)
        eng._stamp(le.P_EMIT)
        eng._round_end(0)

    def drained_round():
        # a round after a drain: its wait proves the device empty, so its
        # emit, sched and dispatch are starved (one lm/starved, tracer on)
        eng.watchdog.step_started()
        eng._stamp(le.P_DISPATCH)
        eng._stamp(le.P_WAIT)
        eng._proved_empty(eng._stamp(le.P_EMIT), "decode_wait")
        eng._round_end(0)

    for label, on, n, a_round in (
            ("tracer_off", False, 200000, one_round),
            ("tracer_on", True, 50000, one_round),
            ("drained_tracer_off", False, 200000, drained_round),
            ("drained_tracer_on", True, 50000, drained_round)):
        tracer.enabled = on
        for _ in range(2000):
            a_round()
        samples = []
        for _ in range(5):
            t0 = time.perf_counter()
            for _ in range(n // 5):
                a_round()
            samples.append((time.perf_counter() - t0) / (n // 5))
        out[f"round_stamps_us_{label}"] = {
            "median": statistics.median(samples) * 1e6,
            "min": min(samples) * 1e6, "max": max(samples) * 1e6, "rounds": n}
    tracer.enabled = False
    tracer.clear()
    eng.close()
    return out


def main(argv) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--cell")
    ap.add_argument("--seconds", type=float, default=40.0)
    ap.add_argument("--seeds", type=int, nargs="*", default=[])
    ap.add_argument("--trace", action="store_true")
    ap.add_argument("--tracer-on", action="store_true")
    ap.add_argument("--blocks", type=int)
    ap.add_argument("--stamp-cost", action="store_true")
    ap.add_argument("--cpu", action="store_true",
                    help="a rehearsal: do not insist on an accelerator")
    ap.add_argument("--root", default=ROOT,
                    help="where BENCHMARK.json lies (a toy tree, to rehearse)")
    ap.add_argument("--tag", default="")
    a = ap.parse_args(argv)
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    log = open(os.path.join(ROOT, "chiprun_out", "round_hunt.jsonl"), "a")

    def out(row):
        print(json.dumps(row), flush=True)
        log.write(json.dumps(row) + "\n")
        log.flush()

    if a.stamp_cost:
        out({"tag": a.tag, **stamp_cost()})
    if not a.seeds:
        return 0
    from benchmarks import run
    from bigdl_tpu.obs import get_tracer
    seen = _watch_engines()
    if a.trace:
        _watch_trace(seen)
    tracer = get_tracer()
    for seed in a.seeds:
        seen.clear()
        if a.tracer_on:
            tracer.clear()
            tracer.enable()
        stalls_before = _stalls()
        t0 = time.perf_counter()
        line = run.run_cell(
            a.root, a.cell, seed, a.seconds, a.trace,
            require_accelerator=not a.cpu,
            config_update=(a.blocks and {"engine": {"num_blocks": a.blocks}}))
        row = {"tag": a.tag, "cell": a.cell, "seed": seed, "blocks": a.blocks,
               "trace": int(a.trace), "tracer_on": a.tracer_on,
               "run_s": time.perf_counter() - t0, "line": line,
               "rounds": seen.get("rounds"), "lists": seen.get("lists")}
        stall = _new_stall(stalls_before)
        if stall is not None:
            row["watchdog_event"] = stall
        if a.tracer_on:
            tracer.disable()
            row["ring"] = {"events": len(tracer),
                           "dropped": getattr(tracer, "dropped", None)}
        if a.trace:
            row["clock_offset"] = seen.get("clock_offset")
            row["cover"] = seen.get("cover")
            row["round_shapes"] = seen.get("round_shapes")
            row["profiler_edges"] = seen.get("profiler_edges")
            row["starved_gaps"] = seen.get("starved_gaps")
            # every gap over 0.5 ms, one line each, beside the summary
            with open(os.path.join(ROOT, "chiprun_out", "starved_gaps.jsonl"),
                      "a") as f:
                for r in seen.get("_gap_rows") or ():
                    f.write(json.dumps(dict(r, cell=a.cell, seed=seed)) + "\n")
        out(row)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
