"""By hand, on the chip: ``readings.py`` with the Solar-Open2 cell's controls
that the program has no option for: the recurrent state kept in bfloat16
under the float32 the configuration states (``toy_solar2.state_rounded``),
and every eighth served token altered where the engine emits it.  Same
arguments as ``readings.py`` (``--control kv8`` is the program's own int8 K/V):

    python3 benchmarks/tests/readings_solar2.py solar2.backlog 40 --control state_bf16 --seeds <n> ...
    python3 benchmarks/tests/readings_solar2.py solar2.backlog 40 --control altered --seeds <n> ...
"""
import contextlib
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmarks.tests import readings, toy_solar2      # noqa: E402

readings.CONTROLS["state_bf16"] = {}
readings.CONTROLS["altered"] = {}


@contextlib.contextmanager
def every_eighth_token_altered(vocab: int = 24576):
    from bigdl_tpu.serving import lm_engine
    real, n = lm_engine.LMStream._emit, {"n": 0}

    def emit(self, token_1b):
        n["n"] += 1
        real(self, (token_1b + 6) % vocab + 1 if n["n"] % 8 == 0 else token_1b)

    lm_engine.LMStream._emit = emit
    try:
        yield
    finally:
        lm_engine.LMStream._emit = real


if __name__ == "__main__":
    control = (toy_solar2.state_rounded("bfloat16") if "state_bf16" in sys.argv
               else every_eighth_token_altered() if "altered" in sys.argv
               else contextlib.nullcontext())
    with control:
        sys.exit(readings.main(sys.argv[1:]))
