"""The benchmark's one command::

    python3 benchmarks/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Everything that belongs to one cell is data the harness finds by name:
``BENCHMARK.json`` names the cell's configuration (``configs/<config>.json``,
whose ``"driver"`` names ``drivers/<driver>.py``), its mix
(``traffic/<cell>.json``) and its metrics (``layer_metrics/<metric>.json``
holds each per-layer metric's reader).  A later PR adds files and one
``workloads`` entry; nothing here is edited.

The last line of standard output is the result; earlier lines are JSON notes
(sample counts and medians, compilations inside the window).  Every number the
check compared stands beside its limit under the result's last key,
``compared``, and on the last lines of standard error.
"""
_T0 = __import__("time").perf_counter()     # process start, for setup_s

import argparse                             # noqa: E402
import importlib.util                       # noqa: E402
import json                                 # noqa: E402
import os                                   # noqa: E402
import shutil                               # noqa: E402
import sys                                  # noqa: E402
import time                                 # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

#: seconds of the window that a --trace 1 run records with the profiler
TRACE_SECONDS = 4.0


def _load_json(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def _load_py(path: str):
    spec = importlib.util.spec_from_file_location(
        "bench_" + os.path.basename(path)[:-3].replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class Run:
    """What a driver gets: the cell's data and the window's hooks."""

    def __init__(self, bench_dir, cell, config, mix, seed, seconds, trace,
                 devices):
        self.bench_dir, self.cell, self.config, self.mix = (
            bench_dir, cell, config, mix)
        self.seed, self.seconds, self.trace = int(seed), float(seconds), trace
        self.devices = devices
        self.t_open = self.t_close = None
        self.compiles_in_window = 0
        self._window_is_open = False
        self._trace_state, self.traced_window, self._sync_perf = 0, None, None
        self._trace_dir = os.path.join(bench_dir, ".trace")
        from jax import monitoring
        monitoring.register_event_duration_secs_listener(self._on_duration)

    def _on_duration(self, event, secs, **_):
        if (self._window_is_open
                and event == "/jax/core/compile/backend_compile_duration"):
            self.compiles_in_window += 1

    @staticmethod
    def out(note: dict) -> None:
        print(json.dumps(note), flush=True)

    # -- the measured window ------------------------------------------------
    def open_window(self, at: float = None) -> float:
        self._window_is_open = True
        self.t_open = time.perf_counter() if at is None else at
        return self.t_open

    def close_window(self, at: float = None) -> float:
        self.t_close = time.perf_counter() if at is None else at
        self._window_is_open = False
        if self._trace_state == 1:
            self._stop_trace()
        return self.t_close

    def sleep_until(self, t: float, until=None) -> None:
        """Sleep to ``t`` on the perf_counter clock, or until ``until()``."""
        while True:
            self.trace_tick()
            left = t - time.perf_counter()
            if left <= 0 or (until is not None and until()):
                return
            time.sleep(min(left, 0.02))

    def trace_tick(self) -> None:
        """Call between steps (or every few tens of ms): starts the profiler
        three tenths into the window and stops it TRACE_SECONDS later."""
        if not self.trace or not self._window_is_open:
            return
        now = time.perf_counter()
        if (self._trace_state == 0
                and now >= self.t_open + 0.3 * self.seconds):
            import jax
            shutil.rmtree(self._trace_dir, ignore_errors=True)
            jax.profiler.start_trace(self._trace_dir)
            self._sync_perf = time.perf_counter()
            with jax.profiler.TraceAnnotation("bench/clock_sync"):
                pass
            self._trace_state = 1
            self.traced_window = (time.perf_counter(), None)
        elif (self._trace_state == 1
              and now >= self.traced_window[0] + TRACE_SECONDS):
            self._stop_trace()

    def _stop_trace(self) -> None:
        import jax
        self.traced_window = (self.traced_window[0], time.perf_counter())
        self._trace_state = 2
        jax.profiler.stop_trace()

    def memory_peak_bytes(self, program_temp_bytes: int) -> int:
        """The peak on the fullest chip: the allocator's high-water mark plus
        the temporaries of the largest program the window ran.  The TPU
        allocator's ``peak_bytes_in_use`` leaves a running program's
        temporaries out (a probe on the chip, PR 23: a program with 2.1 GB
        of them moved the peak by 1 MB), and they are most of what a step
        holds.  The two terms are printed apart, so the sum can be audited;
        the allocator's peak is the process's, so it is a run's own only
        in a process that makes one run, as the command does."""
        peak = max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
                   for d in self.devices)
        self.out({"allocator_peak_bytes_in_use": peak,
                  "largest_program_temp_bytes": int(program_temp_bytes),
                  "memory_peak_bytes": peak + int(program_temp_bytes)})
        return peak + int(program_temp_bytes)

    def reduce_trace(self, spans) -> dict:
        """The traced sub-window, reduced; the host spans label its gaps."""
        from benchmarks.harness import trace_reduce
        reduced = trace_reduce.reduce_trace(
            trace_reduce.find_xplane(self._trace_dir), self.traced_window,
            [(n, s, s + d) for n, s, d in spans if d > 0],
            sync_perf=self._sync_perf)
        shutil.rmtree(self._trace_dir, ignore_errors=True)
        return reduced


def read_layer_metric(bench_dir: str, name: str, recording: dict):
    """One per-layer metric through its reader file; None when the reader
    finds nothing to read."""
    from benchmarks.harness import stats
    spec = _load_json(bench_dir, "layer_metrics", name + ".json")["reader"]
    scale = float(spec.get("scale", 1.0))
    if "span" in spec:
        lo, hi = recording["window"]
        durs = [d for n, s, d in recording["spans"]
                if n == spec["span"] and lo <= s < hi]
        if not durs:
            return None
        stat = spec["stat"]
        value = (stats.percentile(durs, float(stat[1:])) if stat.startswith("p")
                 else {"median": stats.median, "mean": lambda v: sum(v) / len(v),
                       "sum": sum, "count": len}[stat](durs))
        return value * scale
    if "counter" in spec:
        value = recording["counters"].get(spec["counter"])
        return None if value is None else value * scale
    if "py" in spec:
        value = _load_py(os.path.join(bench_dir, "layer_metrics",
                                      spec["py"])).read(recording)
        return None if value is None else value * scale
    raise ValueError(f"layer metric {name}: no reader in {sorted(spec)}")


def _cell_metrics(entries: list, cell: str) -> list:
    return [m for m in entries if cell in m.get("workloads", [cell])]


def run_cell(root: str, workload: str, seed: int, seconds: float, trace: bool,
             require_accelerator: bool = True, mix_update: dict = None,
             config_update: dict = None) -> dict:
    """One run of one cell; returns the result line as a dict (and prints the
    notes).  ``root`` holds BENCHMARK.json; tests pass a temporary one.  The
    two updates (keys laid over the mix's, and over the configuration's, one
    level deep) are for tests/readings.py: the knee sweep's rates and the
    check's lower-precision control.  The command has no option for them."""
    bench = _load_json(root, "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise SystemExit(f"run.py: no workload {workload!r} in BENCHMARK.json "
                         f"(has {sorted(cells)})")
    cell = cells[workload]
    bench_dir = os.path.join(root, bench["paths"][0])
    config_entry = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    config = _load_json(root, config_entry["file"])
    for key, value in (config_update or {}).items():
        config[key] = (dict(config[key], **value)
                       if isinstance(value, dict) else value)
    mix = dict(_load_json(bench_dir, "traffic", workload + ".json"),
               **(mix_update or {}))

    import jax
    devices = jax.devices()
    if require_accelerator and (devices[0].platform == "cpu"
                                or len(devices) < cell["chips"]):
        raise SystemExit(
            f"run.py: cell {workload} needs {cell['chips']} accelerator chip(s); "
            f"JAX reports {len(devices)} x {devices[0].platform}")
    devices = devices[:cell["chips"]]
    # everything this process compiles goes to the one persistent cache, the
    # sub-second programs too, so that only a checkout's first run compiles
    from bigdl_tpu.utils.engine import configure_compile_cache
    cache_dir = configure_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)

    run = Run(bench_dir, cell, config, mix, seed, seconds, trace, devices)
    run.out({"workload": workload, "seed": seed, "seconds": seconds,
             "trace": int(trace), "platform": devices[0].platform,
             "device_kind": devices[0].device_kind, "devices": len(devices),
             "compile_cache_dir": cache_dir,
             "imports_and_backend_s": time.perf_counter() - _T0})
    driver = _load_py(os.path.join(bench_dir, "drivers",
                                   config["driver"] + ".py"))
    result = driver.run(run)

    checks = list(result["checks"])
    checks.append({"name": "compiles_in_window", "limit": 0,
                   "value": run.compiles_in_window,
                   "ok": run.compiles_in_window == 0})
    for c in checks:
        run.out({"compared": c["name"], "value": c["value"],
                 "limit": c["limit"], "ok": c["ok"]})
    device = {"platform": devices[0].platform, "kind": devices[0].device_kind,
              "count": len(jax.devices()),
              "memory_peak_bytes": result["memory_peak_bytes"]}
    values = dict(result["end_to_end"])
    values["setup_s"] = run.t_open - _T0
    line = {"correct": all(c["ok"] for c in checks),
            "attempted": result["attempted"], "failed": result["failed"]}
    if not trace:
        wanted = _cell_metrics(bench["end_to_end"], workload)
    else:
        reduced = run.reduce_trace(result["spans"])
        device["busy_s"], device["window_s"] = (reduced["busy_s"],
                                                reduced["window_s"])
        line["breakdown"] = {"device_ops": reduced["device_ops"],
                             "idle_gaps": reduced["idle_gaps"]}
        recording = dict(result, trace=reduced, config=config, mix=mix,
                         traced_window=run.traced_window,
                         seconds=seconds, device_kind=devices[0].device_kind,
                         chips=len(devices))
        wanted = _cell_metrics(bench["per_layer"], workload)
        values = {m["name"]: read_layer_metric(bench_dir, m["name"], recording)
                  for m in wanted}
    line["metrics"] = {m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
                       for m in wanted if values.get(m["name"]) is not None}
    line["device"] = device
    # every number the check compared beside its limit: the line's last key
    line["compared"] = {c["name"]: {"value": c["value"], "limit": c["limit"]}
                        for c in checks}
    return line


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    line = run_cell(ROOT, args.workload, args.seed, args.seconds,
                    bool(args.trace))
    sys.stdout.flush()
    print(json.dumps(line), flush=True)
    # and as the last lines of standard error
    for name, c in line["compared"].items():
        print(f"compared {name}: {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr)
    sys.stderr.flush()
    return 0


if __name__ == "__main__":
    sys.exit(main())
