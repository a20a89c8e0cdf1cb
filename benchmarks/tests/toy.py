"""A toy benchmark tree for CPU rehearsals: the real ``benchmarks/`` copied
into a temporary root, plus toy-sized configurations, mixes and a
BENCHMARK.json that names them.  Nothing of the real files is edited -- the
same way a later PR adds a cell."""
import json
import os
import shutil

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)

TOY_GPT2 = {
    "driver": "serve_lm", "source": "toy", "reduced": [],
    "n_embd": 64, "n_head": 4, "n_layer": 2, "vocab_size": 512,
    "n_positions": 128,
    # float32 at toy size: a sound run reads gaps of exactly 0 (the dead
    # channels' outliers are invisible to floating point), the int8 control
    # 0.1 and more
    "assumed": {"serve_dtype": "float32"},
    "engine": {"slots": 4, "block_len": 8, "cache_len": 128,
               "prefill_buckets": [16, 32], "num_blocks": 64, "max_queue": 512},
    "check": {"served_gap_max": 1e-3, "served_gap_mean": 1e-5},
}
TOY_STEADY = {"kind": "poisson", "rate_rps": 10.0, "follow_s": 5,
              "prompt_lens": [8, 16, 24], "prompt_weights": [0.3, 0.4, 0.3],
              "output_lens": [4, 8], "output_weights": [0.5, 0.5]}
TOY_SATURATED = dict(TOY_STEADY, kind="bursty", rate_rps=60.0, order_seed=7,
                     follow_s=0, preroll_s=0.5, burst_factor=2, burst_period_s=1.0,
                     burst_duty=0.4)

def make_root(tmp: str) -> str:
    """``tmp/BENCHMARK.json`` + ``tmp/benchmarks/`` with the toy cells added."""
    dst = os.path.join(tmp, "benchmarks")
    shutil.copytree(BENCH, dst, ignore=shutil.ignore_patterns(
        "__pycache__", ".trace", "data"))
    with open(os.path.join(os.path.dirname(BENCH), "BENCHMARK.json")) as f:
        bench = json.load(f)

    def write(rel, obj):
        with open(os.path.join(dst, rel), "w") as f:
            json.dump(obj, f)

    write("configs/toy-gpt2.json", TOY_GPT2)
    write("traffic/toy.steady.json", TOY_STEADY)
    write("traffic/toy.saturated.json", TOY_SATURATED)
    bench["configs"] = [
        {"name": "toy-gpt2", "source": "toy", "reduced": [], "why": "toy",
         "file": "benchmarks/configs/toy-gpt2.json"}]
    bench["workloads"] = [
        {"name": "toy.steady", "config": "toy-gpt2", "traffic": "steady",
         "chips": 1, "why": "toy"},
        {"name": "toy.saturated", "config": "toy-gpt2", "traffic": "saturated",
         "chips": 1, "why": "toy"}]
    rename = {"gpt2xl.steady": "toy.steady", "gpt2xl.saturated": "toy.saturated"}
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "workloads" in m:
            m["workloads"] = [rename[w] for w in m["workloads"]]
    with open(os.path.join(tmp, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    return tmp
