"""Median latency (ms) of every request of the window (as ``p99_ms``)."""
import numpy as np


def read(rec):
    return float(np.percentile(rec.lat_ms, 50)) if len(rec.lat_ms) else None
