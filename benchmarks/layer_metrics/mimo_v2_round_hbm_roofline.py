"""Share of the HBM roofline MiMo-V2-Flash's decode module reached in the
traced window: the least bytes its traced rounds had to move
(``costs_mimo_v2.decode_parts_bytes``: the fixed weights once a round, every
expert the program's own counter says a round hit, a full layer's K/V of every
position a slot may see and a sliding layer's inside its window, from the
``ctx_tokens`` and ``window_tokens`` args of the traced ``lm/decode_step``
spans) over the published bandwidth and the module's device time."""
from benchmarks.harness import costs_mimo_v2, peaks


def read(rec: dict):
    parts = costs_mimo_v2.traced_rounds(rec)
    device_s = costs_mimo_v2.modules_device_s(rec, costs_mimo_v2.ROUND_MODULES)
    if parts is None or not device_s:
        return None
    peak = peaks.peaks(rec["device_kind"])["hbm_bytes_per_s"]
    return sum(parts.values()) / peak / device_s * 100.0
