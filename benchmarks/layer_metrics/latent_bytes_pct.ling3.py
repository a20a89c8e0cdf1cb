"""The latent cache's share of the bytes the traced decode rounds had to move:
one row of 576 lanes for every live position of every active slot on the MLA
layer (the ``latent_positions`` arg of the traced ``lm/decode_step`` spans),
over the rounds' least bytes (``costs_ling3.decode_parts_bytes``)."""
from benchmarks.harness import costs_ling3


def read(rec: dict):
    return costs_ling3.part_share_pct(rec, "latent")
