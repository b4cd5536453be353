"""Device time (ms) per tick of the execute program (``execute_access``):
the tick's program with the most device time, in the traced stretch."""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import _ticks  # noqa: E402


def read(rec):
    return _ticks.per_tick_ms(rec, "exec")
