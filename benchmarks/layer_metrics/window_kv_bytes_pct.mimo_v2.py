"""The windowed class's share of the bytes the traced decode rounds had to
move (``costs_mimo_v2.decode_parts_bytes``, from the ``ctx_tokens`` and
``window_tokens`` args of the traced ``lm/decode_step`` spans)."""
from benchmarks.harness import costs_mimo_v2


def read(rec: dict):
    parts = costs_mimo_v2.traced_rounds(rec)
    if parts is None:
        return None
    return parts["window_kv"] / sum(parts.values()) * 100.0
