"""Share of the HBM roofline Laguna's decode module reached in the traced
window: the least bytes its rounds had to read (``costs_laguna``: the fixed
weights once a round, every expert the program's own counter says a round hit,
the K/V a decoded token may see) over the published bandwidth and the
module's device time."""
import jax.numpy as jnp

from benchmarks.harness import costs_laguna, peaks

MODULE = "decode_fn"        # LMServingEngine's decode step: jit__decode_fn


def read(rec: dict):
    found = [m for name, m in rec["trace"]["modules"].items() if MODULE in name]
    counters = rec["counters"]
    hit = counters.get("lm.traced_moe_experts_hit")
    if not found or not hit or not counters.get("lm.decode_context_tokens"):
        return None
    dtype_bytes = jnp.dtype(rec["config"]["assumed"]["serve_dtype"]).itemsize
    least = costs_laguna.decode_least_bytes(
        rec["config"], dtype_bytes, sum(m["calls"] for m in found), hit,
        counters["lm.decode_context_tokens"], counters["lm.decode_window_tokens"])
    peak = peaks.peaks(rec["device_kind"])["hbm_bytes_per_s"]
    return least / peak / sum(m["device_s"] for m in found) * 100.0
