"""Operations and bytes the algorithms need, from shapes alone.  Hand-worked
lines are in PERF.md section 3; ``tests/test_costs.py`` holds them
to those numbers."""


def gpt2_decode_weight_bytes(c: dict, dtype_bytes: int) -> int:
    """Bytes of weights one decode round has to read whatever the batch: every
    block matrix and vector, the final LayerNorm, and the token embedding once
    as the tied head.  Not the position table (one row a slot)."""
    h = c["n_embd"]
    per_layer = 12 * h * h + 13 * h     # q k v o, fc, proj; biases; two norms
    return (c["n_layer"] * per_layer + 2 * h + c["vocab_size"] * h) * dtype_bytes


def gpt2_kv_bytes_per_position(c: dict, dtype_bytes: int) -> int:
    """Bytes of keys and values one cached position holds over all layers; a
    decoded token reads as many for every position of its context."""
    return c["n_layer"] * 2 * c["n_embd"] * dtype_bytes
