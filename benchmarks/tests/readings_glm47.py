"""By hand, on the chip: ``readings.py`` with the GLM-4.7-Flash cell's
controls, none of which is an option of the program: latent rows cached without
their rotated lanes (``toy_glm47.rope_dropped``), the prediction module fed the
hidden state of the position before (``hidden_off_by_one``), ``eh_proj``'s
hidden half dropped (``eh_hidden_dropped``), latent rows cached in float8, the
nearest precision below the bfloat16 the configuration states (``latent_f8``:
``toy_ling3.latent_rounded``, the main layers' rows and the module's); and two
readings that are laid over the configuration: the same cell with the drafter
off (``plain``: plain run-ahead rounds) and another next-token lean of the
seeded head (``gain=<x>``: the one tuning of ``bigram_gain``).  Same arguments as
``readings.py``:

    python3 benchmarks/tests/readings_glm47.py glm47.agentloop 40 --control hidden_off_by_one --seeds <n> ...
    python3 benchmarks/tests/readings_glm47.py glm47.agentloop 40 --control plain --seeds <n>
    python3 benchmarks/tests/readings_glm47.py glm47.agentloop 40 --control gain=2.5 --seeds <n>
"""
import contextlib
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmarks.tests import readings, toy_glm47, toy_ling3    # noqa: E402

#: control -> the context it runs under (none is an option of the program)
CONTROLS = dict(toy_glm47.CONTROLS,
                latent_f8=lambda: toy_ling3.latent_rounded("float8_e4m3fn"))
readings.CONTROLS.update({name: {} for name in CONTROLS})
readings.CONTROLS["plain"] = {"engine": {"self_draft_k": 0}}


if __name__ == "__main__":
    for a in sys.argv[1:]:
        if a.startswith("gain="):
            readings.CONTROLS[a] = {"bigram_gain": float(a[5:])}
    named = [a for a in sys.argv[1:] if a in CONTROLS]
    with CONTROLS[named[0]]() if named else contextlib.nullcontext():
        sys.exit(readings.main(sys.argv[1:]))
