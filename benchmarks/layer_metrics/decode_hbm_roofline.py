"""Share of the HBM roofline the decode module reached in the traced window."""
import jax.numpy as jnp

from benchmarks.harness import costs, peaks

MODULE = "decode_fn"        # LMServingEngine's decode step: jit__decode_fn


def read(rec: dict):
    found = [m for name, m in rec["trace"]["modules"].items() if MODULE in name]
    tokens = rec["counters"].get("lm.decode_context_tokens")
    if not found or not tokens:
        return None
    calls = sum(m["calls"] for m in found)
    device_s = sum(m["device_s"] for m in found)
    dtype_bytes = jnp.dtype(rec["config"]["assumed"]["serve_dtype"]).itemsize
    least_bytes = (calls * costs.gpt2_decode_weight_bytes(rec["config"], dtype_bytes)
                   + tokens * costs.gpt2_kv_bytes_per_position(rec["config"],
                                                               dtype_bytes))
    peak = peaks.peaks(rec["device_kind"])["hbm_bytes_per_s"]
    return least_bytes / peak / device_s * 100.0
