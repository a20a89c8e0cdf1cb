"""Share of the chip's bf16 peak Ling-3.0's WHOLE decode module reached in the
traced window: the least arithmetic its rounds needed
(``costs_ling3.decode_least_flops``, by the program's own counts of decoded
tokens, assignments landed and live latent positions) over the published peak
and the module's device time."""
from benchmarks.harness import costs_ling3, peaks


def read(rec: dict):
    found = costs_ling3.traced_module(rec)
    if found is None:
        return None
    (_, flops), device_s = found
    return flops / peaks.peaks(rec["device_kind"])["bf16_flops"] / device_s * 100.0
