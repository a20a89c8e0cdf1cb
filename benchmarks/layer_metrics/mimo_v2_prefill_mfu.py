"""Share of the chip's bf16 peak MiMo-V2-Flash's prefill modules reached in
the traced window: the operations its traced prefills needed
(``costs_mimo_v2.prefill_flops``, by the TRUE lengths of what was computed,
from the args of the program's ``lm/prefill`` spans and the assignments of its
``lm/first_token`` spans; a sliding layer's scores counted inside the window
only) over the published peak and the modules' device time."""
from benchmarks.harness import costs_mimo_v2, peaks


def read(rec: dict):
    chunks = rec["counters"].get("lm.traced_prefill_chunks")
    device_s = costs_mimo_v2.modules_device_s(rec, costs_mimo_v2.PREFILL_MODULES)
    if not chunks or not device_s:
        return None
    flops = costs_mimo_v2.prefill_flops(
        rec["config"], chunks,
        rec["counters"].get("lm.traced_prefill_assignments", 0))
    return flops / peaks.peaks(rec["device_kind"])["bf16_flops"] / device_s * 100.0
