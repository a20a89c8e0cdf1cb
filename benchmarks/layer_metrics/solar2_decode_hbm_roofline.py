"""Share of the HBM roofline Solar-Open2's decode module reached in the traced
window: the least bytes its rounds had to move (``costs_solar2``: the fixed
weights once a round, every expert the program's own counter says a round hit,
the K/V a decoded token may see on the one softmax layer, and for every
ACTIVE slot and KDA layer the state and convolution tail, read and written)
over the published bandwidth and the module's device time."""
from benchmarks.harness import costs_solar2, peaks

MODULE = "decode_fn"        # LMServingEngine's decode step: jit__decode_fn


def read(rec: dict):
    found = [m for name, m in rec["trace"]["modules"].items() if MODULE in name]
    least = costs_solar2.traced_decode_least_bytes(
        rec, sum(m["calls"] for m in found))
    if least is None:
        return None
    peak = peaks.peaks(rec["device_kind"])["hbm_bytes_per_s"]
    return least / peak / sum(m["device_s"] for m in found) * 100.0
