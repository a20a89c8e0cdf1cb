"""Share of the HBM roofline GLM-4.7-Flash's ROUND modules reached in the
traced window (the self-drafting round: verify at W = 2, pick, the prediction
module's pairs, one program; or, drafter off, the plain decode step): the least
bytes its traced rounds had to move (``costs_glm47.round_parts_bytes``: the
weights outside the experts once a round, the head once and once more where the
module ran, every expert the program's own counter says a round hit, one latent
row a live position and arena layer) over the published bandwidth and the
modules' device time."""
from benchmarks.harness import costs_glm47, peaks


def read(rec: dict):
    parts = costs_glm47.traced_rounds(rec)
    device_s = costs_glm47.modules_device_s(rec, costs_glm47.ROUND_MODULES)
    if parts is None or not device_s:
        return None
    peak = peaks.peaks(rec["device_kind"])["hbm_bytes_per_s"]
    return sum(parts.values()) / peak / device_s * 100.0
