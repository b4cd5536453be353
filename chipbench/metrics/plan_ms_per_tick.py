"""Device time (ms) per tick of the planning programs: those that run
once a tick beside the execute program (``plan_access``), in the traced
stretch."""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import _ticks  # noqa: E402


def read(rec):
    return _ticks.per_tick_ms(rec, "plan")
