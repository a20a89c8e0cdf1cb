"""The latent cache's share of the bytes the traced rounds had to move: one row
of 576 lanes for every live position of every arena layer read, the prediction
module's layer too (the ``latent_positions`` arg of the traced
``lm/verify_step`` spans), over the rounds' least bytes
(``costs_glm47.round_parts_bytes``)."""
from benchmarks.harness import costs_glm47


def read(rec: dict):
    parts = costs_glm47.traced_rounds(rec)
    if parts is None:
        return None
    return parts["latent"] / sum(parts.values()) * 100.0
