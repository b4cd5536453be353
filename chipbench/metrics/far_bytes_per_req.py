"""Far-tier bytes moved by the window's ticks per request: page-ins x page
bytes + object-ins x row bytes + write-backs (dirty page-outs, object-outs),
from the plane's counters summed over shards.  The arithmetic of
``benchmarks/common.py`` ``traffic_bytes``."""


def far_bytes(rec) -> int:
    s = rec.stats
    return (s["page_ins"] * rec.page_bytes + s["obj_ins"] * rec.row_bytes
            + s["dirty_page_outs"] * rec.page_bytes
            + s["obj_outs"] * rec.row_bytes)


def read(rec):
    return far_bytes(rec) / rec.requests if rec.requests else None
