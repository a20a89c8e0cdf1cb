"""By hand, on the chip: the two sets of runs a cell's bounds are set from.
Each run is the benchmark's own command in a process of its own (this parent
never touches JAX, so the chip is free for each child); set B repeats set A's
seeds.  Prints every run's last line, then for each metric the two sets'
medians and quartile spreads.

    python3 benchmarks/tests/full_sets.py <cell> <seconds> <seed> [<seed> ...]
"""
import json
import os
import statistics
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmarks.harness.stats import quartile_spread    # noqa: E402  (no JAX)


def main(argv) -> int:
    cell, seconds, seeds = argv[0], argv[1], argv[2:]
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    log = open(os.path.join(ROOT, "chiprun_out", f"full_{cell}.jsonl"), "a")
    sets = {"A": [], "B": []}
    for name in ("A", "B"):
        for seed in seeds:
            done = subprocess.run(
                [sys.executable, os.path.join(ROOT, "benchmarks", "run.py"),
                 "--workload", cell, "--seed", seed, "--seconds", seconds,
                 "--trace", "0"], cwd=ROOT, capture_output=True, text=True)
            lines = [l for l in done.stdout.splitlines() if l.startswith("{")]
            if done.returncode or not lines:
                print(json.dumps({"set": name, "seed": seed,
                                  "rc": done.returncode,
                                  "stderr": done.stderr[-2000:]}), flush=True)
                continue
            for note in lines[:-1]:
                log.write(note + "\n")
            last = json.loads(lines[-1])
            row = {"set": name, "seed": seed, **last}
            print(json.dumps(row), flush=True)
            log.write(json.dumps(row) + "\n")
            log.flush()
            sets[name].append(last)
    names = sorted({m for rows in sets.values() for r in rows
                    for m in r["metrics"]})
    for m in names:
        out = {"metric": m}
        for name, rows in sets.items():
            # the first run of the call compiles: its set-up is apart
            vals = [r["metrics"][m]["value"] for r in rows if m in r["metrics"]]
            if m == "setup_s" and name == "A":
                vals = vals[1:]
            if len(vals) >= 2:
                out[name] = {"n": len(vals), "median": statistics.median(vals),
                             "spread": quartile_spread(vals), "min": min(vals),
                             "max": max(vals)}
        print(json.dumps(out), flush=True)
    print(json.dumps({"all_correct": all(r["correct"] for rows in sets.values()
                                         for r in rows),
                      "runs": sum(len(r) for r in sets.values())}))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
