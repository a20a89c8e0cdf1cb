"""Share of the chip's bf16 peak the prefill module reached in the traced
window (the module that runs the windowed, grouped-head flash kernel and the
grouped expert matmuls): the operations its prefills needed
(``costs_laguna.prefill_flops``, by the prompts' lengths and the program's own
count of the assignments that landed on held experts) over the published peak
and the module's device time."""
from benchmarks.harness import costs_laguna, peaks

MODULE = "jit__prefill_fn"  # LMServingEngine's whole-prompt prefill


def read(rec: dict):
    found = [m for name, m in rec["trace"]["modules"].items()
             if name.startswith(MODULE)]
    tokens = rec["counters"].get("lm.traced_prefill_tokens")
    if not found or not tokens:
        return None
    landed = rec["counters"].get("lm.traced_prefill_moe_assignments", 0)
    flops = (sum(costs_laguna.prefill_flops(rec["config"], t, 0) for t in tokens)
             + costs_laguna.prefill_flops(rec["config"], 0, landed)
             - costs_laguna.prefill_flops(rec["config"], 0, 0))
    peak = peaks.peaks(rec["device_kind"])["bf16_flops"]
    return flops / peak / sum(m["device_s"] for m in found) * 100.0
