"""Share of the chip's bf16 peak GLM-4.7-Flash's prefill modules reached in the
traced window (whole-prompt and suffix prefills, a radix hit's one-token pass
among them): the operations its traced prefills needed
(``costs_glm47.prefill_flops``, by the TRUE lengths of what was computed --
matched prefixes are not -- from the args of the program's ``lm/prefill``
spans) over the published peak and the modules' device time."""
from benchmarks.harness import costs_glm47, peaks


def read(rec: dict):
    counters = rec["counters"]
    tokens = counters.get("lm.traced_prefill_tokens")
    device_s = costs_glm47.modules_device_s(rec, costs_glm47.PREFILL_MODULES)
    if not tokens or not device_s:
        return None
    flops = costs_glm47.prefill_flops(
        rec["config"], tokens, counters.get("lm.traced_prefill_pairs", 0),
        counters.get("lm.traced_prefill_chunks", 0))
    return flops / peaks.peaks(rec["device_kind"])["bf16_flops"] / device_s * 100.0
