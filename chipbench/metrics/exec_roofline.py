"""Share (%) of the HBM roofline reached by the execute programs: the
least bytes the served work must move, over the chip's HBM bandwidth,
divided by the execute programs' device time, both per tick of the traced
stretch.

Least bytes per tick, from the plane counters' change over the stretch's
ticks, whatever implements them: each served record read and its output
row written (2 x row bytes), each page-in and dirty page-out read and
written once (2 x page bytes), each object-in read and written once
(2 x row bytes).  Bandwidth-bound: the executor does no arithmetic worth
counting."""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import _ticks  # noqa: E402


def least_bytes_per_tick(s: dict, ticks: int, row_bytes: int,
                         page_bytes: int) -> float:
    b = (2 * row_bytes * (s["hits"] + s["misses"])
         + 2 * page_bytes * (s["page_ins"] + s["dirty_page_outs"])
         + 2 * row_bytes * s["obj_ins"])
    return b / ticks


def read(rec):
    ms = _ticks.per_tick_ms(rec, "exec")
    if not ms or rec.stretch is None or not rec.stretch[1]:
        return None
    stats, ticks = rec.stretch
    floor_s = least_bytes_per_tick(stats, ticks, rec.row_bytes,
                                   rec.page_bytes) / rec.peaks["hbm_bytes_per_s"]
    return 100.0 * floor_s / (ms * 1e-3)
