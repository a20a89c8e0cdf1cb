"""By hand, on the chip: the readings a serving cell's knee and its check's
limits are set from.  One process; each run is a whole run of the cell (own
seed, own weights, own engine).

    python3 benchmarks/tests/readings.py <cell> <seconds> --seeds <n> [<n> ...]
    ... --control kv8 | w8 | kv8w8   the program's own int8 paths switched on
                                     (the runs have to come out not correct)
    ... --rates <r> [<r> ...]        the knee sweep: the mix's rate, run by run
    ... --trace                      a last run (first seed) with the profiler on
"""
import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmarks import run      # noqa: E402

#: what the check's control lays over a configuration: ``kv_quant="int8"``
#: (int8 KV blocks, a scale per position and head; it needs the gather decode)
#: and ``quantize("int8")`` (int8 block matrices, a scale per output column)
CONTROLS = {
    "kv8": {"engine": {"kv_quant": "int8", "decode_attn": "gather"}},
    "w8": {"quantize": "int8"},
    "kv8w8": {"engine": {"kv_quant": "int8", "decode_attn": "gather"},
              "quantize": "int8"},
}


def main(argv) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("cell")
    ap.add_argument("seconds", type=float)
    ap.add_argument("--seeds", type=int, nargs="*", default=[])
    ap.add_argument("--rates", type=float, nargs="*", default=[])
    ap.add_argument("--control", choices=sorted(CONTROLS))
    ap.add_argument("--trace", action="store_true")
    a = ap.parse_args(argv)
    config = CONTROLS[a.control] if a.control else None
    runs = ([(1000003 * (i + 1) + 2 ** 31, {"rate_rps": r})
             for i, r in enumerate(a.rates)] + [(s, None) for s in a.seeds])
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    with open(os.path.join(ROOT, "chiprun_out", "readings.jsonl"), "a") as log:
        for seed, mix in runs:
            line = run.run_cell(ROOT, a.cell, seed, a.seconds, False,
                                mix_update=mix, config_update=config)
            row = {"seed": seed, "mix": mix, "control": a.control, **line}
            print(json.dumps(row), flush=True)
            log.write(json.dumps(row) + "\n")
        if a.trace:
            line = run.run_cell(ROOT, a.cell, runs[0][0], a.seconds, True,
                                mix_update=runs[0][1], config_update=config)
            print(json.dumps({"traced": True, **line}), flush=True)
            log.write(json.dumps(line) + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
