"""By hand, on the chip: ``readings.py`` with the Ling-3.0 cell's controls
that the program has no option for: the recurrent state kept in bfloat16 under
the float32 the configuration states (``toy_solar2.state_rounded``), the
latent rows rounded to a float8 under the bfloat16 it states
(``toy_ling3.latent_rounded``: e4m3, or the coarser e5m2), and the latent rows
cached without their rotated lanes (``toy_ling3.rope_dropped``).  Same
arguments as ``readings.py``:

    python3 benchmarks/tests/readings_ling3.py ling3.longdecode 40 --control state_bf16 --seeds <n> ...
    python3 benchmarks/tests/readings_ling3.py ling3.longdecode 40 --control latent_f8 --seeds <n> ...
    python3 benchmarks/tests/readings_ling3.py ling3.longdecode 40 --control latent_f8e5m2 --seeds <n> ...
    python3 benchmarks/tests/readings_ling3.py ling3.longdecode 40 --control rope_dropped --seeds <n> ...
"""
import contextlib
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmarks.tests import readings, toy_ling3, toy_solar2    # noqa: E402

#: control -> the context it runs under (none is an option of the program)
CONTROLS = {
    "state_bf16": lambda: toy_solar2.state_rounded("bfloat16"),
    "latent_f8": lambda: toy_ling3.latent_rounded("float8_e4m3fn"),
    "latent_f8e5m2": lambda: toy_ling3.latent_rounded("float8_e5m2"),
    "rope_dropped": toy_ling3.rope_dropped,
}
readings.CONTROLS.update({name: {} for name in CONTROLS})


if __name__ == "__main__":
    named = [a for a in sys.argv[1:] if a in CONTROLS]
    with CONTROLS[named[0]]() if named else contextlib.nullcontext():
        sys.exit(readings.main(sys.argv[1:]))
