"""By hand, on the chip: ``readings.py`` with the Laguna cell's second control,
the expert matmuls' activations rounded to float8 (e4m3) under the bfloat16
the configuration states (``toy_laguna.experts_rounded``: the program has no
such option).  Same arguments as ``readings.py``:

    python3 benchmarks/tests/readings_laguna.py laguna_s.steady 40 --control experts_f8 --seeds <n> ...
"""
import contextlib
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmarks.tests import readings, toy_laguna      # noqa: E402

readings.CONTROLS["experts_f8"] = {}

if __name__ == "__main__":
    with (toy_laguna.experts_rounded("float8_e4m3fn")
          if "experts_f8" in sys.argv else contextlib.nullcontext()):
        sys.exit(readings.main(sys.argv[1:]))
