"""99th percentile latency (ms) of every request of the window, each timed
from its own due time (open loop) or send time (closed loop) to the host
seeing its last row ready."""
import numpy as np


def read(rec):
    return float(np.percentile(rec.lat_ms, 99)) if len(rec.lat_ms) else None
