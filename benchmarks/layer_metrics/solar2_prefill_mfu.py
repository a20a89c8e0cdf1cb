"""Share of the chip's bf16 peak Solar-Open2's prefill modules reached in the
traced window (the whole-prompt prefill: the flash kernel of the one softmax
layer, the chunked KDA scan, the grouped expert matmuls): the operations its
prefills needed (``costs_solar2.prefill_flops``, by the prompts' TRUE lengths
and the program's own count of the assignments that landed on held experts)
over the published peak and the modules' device time."""
from benchmarks.harness import costs_solar2, peaks

MODULE = "jit__prefill_fn"  # LMServingEngine's whole-prompt prefill


def read(rec: dict):
    found = [m for name, m in rec["trace"]["modules"].items()
             if name.startswith(MODULE)]
    tokens = rec["counters"].get("lm.traced_prefill_tokens")
    if not found or not tokens:
        return None
    c = rec["config"]
    landed = rec["counters"].get("lm.traced_prefill_moe_assignments", 0)
    flops = (sum(costs_solar2.prefill_flops(c, t, 0) for t in tokens)
             + costs_solar2.prefill_flops(c, 0, landed)
             - costs_solar2.prefill_flops(c, 0, 0))
    peak = peaks.peaks(rec["device_kind"])["bf16_flops"]
    return flops / peak / sum(m["device_s"] for m in found) * 100.0
