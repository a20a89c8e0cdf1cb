"""The recurrent state's share of the bytes the traced decode rounds had to
move: active slots x KDA layers a round (the ``state_rows`` arg of the traced
``lm/decode_step`` spans), each row read and written, over the rounds' least
bytes (``costs_ling3.decode_parts_bytes``)."""
from benchmarks.harness import costs_ling3


def read(rec: dict):
    return costs_ling3.part_share_pct(rec, "state")
