"""By hand, on the chip: ``readings.py`` with the MiMo-V2-Flash cell's
controls, none of which the program has an option for
(``toy_mimo_v2.CONTROLS``): the sink left out of every softmax, the windowed
class's release one block early (a sliding layer reads the scratch block where
a key should be), the values unscaled, K and V rounded to int8 a (position,
head) row.  Same arguments as ``readings.py``:

    python3 benchmarks/tests/readings_mimo_v2.py mimo_v2.mixedqueue 40 --control sink_dropped --seeds <n> ...
    python3 benchmarks/tests/readings_mimo_v2.py mimo_v2.mixedqueue 40 --control release_early --seeds <n> ...
    python3 benchmarks/tests/readings_mimo_v2.py mimo_v2.mixedqueue 40 --control values_unscaled --seeds <n> ...
    python3 benchmarks/tests/readings_mimo_v2.py mimo_v2.mixedqueue 40 --control kv8 --seeds <n> ...
"""
import contextlib
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmarks.tests import readings, toy_mimo_v2      # noqa: E402

CONTROLS = toy_mimo_v2.CONTROLS
readings.CONTROLS.update({name: {} for name in CONTROLS})


if __name__ == "__main__":
    named = [a for a in sys.argv[1:] if a in CONTROLS]
    with CONTROLS[named[0]]() if named else contextlib.nullcontext():
        sys.exit(readings.main(sys.argv[1:]))
