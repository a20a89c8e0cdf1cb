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
TOY_BACKLOG = {"kind": "closed", "clients": 12, "poll_s": 0.0005,
               "sequence_len": 60,
               "order_seed": 7, "follow_s": 0, "preroll_s": 0.5,
               **{k: TOY_STEADY[k] for k in ("prompt_lens", "prompt_weights",
                                             "output_lens", "output_weights")}}
#: the real cells the toy ones stand for; a metric that lists none of them
#: (a later configuration's) is left out of the toy tree
RENAME = {"gpt2xl.steady": "toy.steady", "gpt2xl.backlog": "toy.backlog"}


def without_profiler(monkeypatch) -> None:
    """``--trace 1`` on the CPU, which has no device plane: the profiler is
    left out and its reduction canned; the program's spans are the real ones."""
    from benchmarks import run
    monkeypatch.setattr(run.Run, "trace_tick", lambda self: None)
    monkeypatch.setattr(run.Run, "reduce_trace", lambda self, spans: {
        "chips": 1, "window_s": 1.0, "busy_s": 0.5, "modules": {},
        "device_ops": [], "idle_gaps": []})


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
    write("traffic/toy.backlog.json", TOY_BACKLOG)
    bench["configs"] = [
        {"name": "toy-gpt2", "source": "toy", "reduced": [], "why": "toy",
         "file": "benchmarks/configs/toy-gpt2.json"}]
    bench["workloads"] = [
        {"name": "toy.steady", "config": "toy-gpt2", "traffic": "steady",
         "chips": 1, "why": "toy"},
        {"name": "toy.backlog", "config": "toy-gpt2", "traffic": "backlog",
         "chips": 1, "why": "toy"}]
    for key in ("end_to_end", "per_layer"):
        for m in bench[key]:
            if "workloads" in m:
                m["workloads"] = [RENAME[w] for w in m["workloads"]
                                  if w in RENAME]
        bench[key] = [m for m in bench[key] if m.get("workloads", True)]
    with open(os.path.join(tmp, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    return tmp
