"""Share of the HBM roofline Ling-3.0's WHOLE decode module reached in the
traced window: the least bytes its rounds had to move (``costs_ling3``: the
fixed weights once a round, every expert the program's own counter says a
round hit, one latent row a live position, and for every ACTIVE slot and KDA
layer the state and convolution tail, read and written) over the published
bandwidth and the module's device time."""
from benchmarks.harness import costs_ling3, peaks


def read(rec: dict):
    found = costs_ling3.traced_module(rec)
    if found is None:
        return None
    (parts, _), device_s = found
    peak = peaks.peaks(rec["device_kind"])["hbm_bytes_per_s"]
    return sum(parts.values()) / peak / device_s * 100.0
